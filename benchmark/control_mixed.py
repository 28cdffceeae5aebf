"""The must-fail readings for a deployment whose templates differ in
kind (plain pods beside pods with a required podAffinity, a required
podAntiAffinity, a preferred podAffinity or a preferred podAntiAffinity
term, all with stated cpu and memory requests), beside
benchmark/controls.py's stale wave.

    python3 benchmark/control_mixed.py .bench_out/<cell>-<seed>-<trace>

Such a deployment guarantees that no node holds two pods of an
anti-affine service and states the arithmetic of MatchInterPodAffinity,
InterPodAffinityPriority and the two resource priorities. Each control
is its reference put in the daemon's place with one thing broken, read
on the cluster the run's window left and on the run's own check batch,
from the load generator's record:

  affinity_ignored       the required podAffinity terms left out, of the
                         predicate and (the bound pods' symmetric
                         weight) of the priority, as a scheduler that
                         never read them
  anti_ignored           the required podAntiAffinity terms left out of
                         MatchInterPodAffinity, both directions; with it
                         `anti_ignored_nodes_with_two`, the nodes that
                         then hold two pods of an anti-affine service
  pref_affinity_ignored  the preferred podAffinity terms, the pod's own
                         and those of bound pods, left out of
                         InterPodAffinityPriority
  pref_anti_ignored      the same for the preferred podAntiAffinity terms
  preferred_ignored      both preferred kinds together
  default_memory         the two resource priorities counting upstream's
                         non-zero default of 200Mi a pod where the pods
                         state 500Mi (a scorer that dropped the stated
                         memory, as both term deployments' pods have none
                         to drop)

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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, controls, deploy  # noqa: E402

#: priorities.go:55-60: what a container that states no memory counts
DEFAULT_MEMORY = 200 * 1024 * 1024
#: control -> the reference cluster's switches and what they are set to
BROKEN = {"affinity_ignored": {"affinity_holds": False},
          "anti_ignored": {"anti_holds": False},
          "pref_affinity_ignored": {"pref_affinity_holds": False},
          "pref_anti_ignored": {"pref_anti_holds": False},
          "preferred_ignored": {"pref_affinity_holds": False,
                                "pref_anti_holds": False},
          "default_memory": {"memory_scored": DEFAULT_MEMORY}}


def broken(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, each control of
    BROKEN, `anti_ignored_nodes_with_two`}; only "sound" where the
    deployment's reference keeps no such switches."""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    read = {"sound": sound["mismatches"]}
    if not hasattr(start, "pref_affinity_holds"):
        return read
    for name, switches in BROKEN.items():
        blind = copy.deepcopy(start)
        for switch, value in switches.items():
            setattr(blind, switch, value)
        made = reference.decide(blind, backlog, sound["counter"][0])
        read[name] = reference.verify(copy.deepcopy(start), backlog,
                                      made)["mismatches"]
        if "anti_holds" in switches:
            read[name + "_nodes_with_two"] = int(
                blind.nodes_with_two().sum())
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
