"""The must-fail readings for a deployment whose pods carry a required
podAffinity term over zones and a preferred podAntiAffinity term over
hostnames, beside benchmark/controls.py's stale wave.

    python3 benchmark/control_podaffinity.py .bench_out/<cell>-<seed>-<trace>

Such a deployment guarantees that a service's pods lie in one zone and
states the arithmetic of InterPodAffinityPriority. Each control is its
reference put in the daemon's place with one thing broken, read on the
cluster the run's window left and on the run's own check batch, from
the load generator's record:

  required_ignored   the required podAffinity terms left out of
                     MatchInterPodAffinity, as a scheduler that never
                     read them; with it `required_ignored_nodes_astray`,
                     the nodes that then hold a pod of a service outside
                     the service's zone
  preferred_ignored  the preferred terms, the pod's own and those of
                     bound pods, left out of InterPodAffinityPriority
  symmetric_weight_0 hardPodAffinitySymmetricWeight 0 where the default
                     is 1: a bound pod's required term adds nothing to
                     the nodes of its zone
  float32_normal     InterPodAffinityPriority normalised in float32
                     where upstream divides in float64
  zone_unheld        the first and the third together: on a deployment
                     whose required term selects the pod's own service
                     each of the two keeps the service in its zone
                     alone (the bound pods' symmetric weight gives the
                     zone's nodes hundreds of points), so each may read
                     0 where both read thousands; with it
                     `zone_unheld_nodes_astray`

Each prints what `picks_off_reference` would have shown. A cell on
which one reads 0 cannot see that guarantee, whatever its runs read.
Not part of a run: the builder reads them beside the sound runs'
number.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, controls, deploy  # noqa: E402

#: control -> the reference cluster's switches and what they are set to
BROKEN = {"required_ignored": {"required_holds": False},
          "preferred_ignored": {"preferred_holds": False},
          "symmetric_weight_0": {"hard_weight": 0},
          "float32_normal": {"normal": np.float32},
          "zone_unheld": {"required_holds": False, "hard_weight": 0}}


def broken(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, and each control
    of BROKEN, the two `*_nodes_astray`}; only "sound" where the
    deployment's reference keeps no such switch."""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    read = {"sound": sound["mismatches"]}
    if not hasattr(start, "required_holds"):
        return read
    for name, switches in BROKEN.items():
        blind = copy.deepcopy(start)
        for switch, value in switches.items():
            setattr(blind, switch, value)
        made = reference.decide(blind, backlog, sound["counter"][0])
        read[name] = reference.verify(copy.deepcopy(start), backlog,
                                      made)["mismatches"]
        if "required_holds" in switches:
            read[name + "_nodes_astray"] = blind.over_allocatable()
    return read


def main(argv=None) -> None:
    out_dir = (argv or sys.argv[1:])[0]
    record = deploy.load_json(os.path.join(out_dir, "loadgen.json"))
    cfg = deploy.load_json(os.path.join(out_dir, "config.json"))
    print(json.dumps({"run": os.path.basename(os.path.normpath(out_dir)),
                      **controls.stale_wave(record, cfg),
                      **broken(record, cfg)}))


if __name__ == "__main__":
    main()
