"""The must-fail readings for a deployment whose pods carry a required
hostname anti-affinity term, beside benchmark/controls.py's stale wave.

    python3 benchmark/control_antiaffinity.py .bench_out/<cell>-<seed>-<trace>

Such a deployment guarantees that no pod goes where a term excludes it
and states which requests the resource priorities count. Each control
is its reference put in the daemon's place with one of the two broken,
read on the cluster the run's window left and on the run's own check
batch, from the load generator's record:

  term_ignored     MatchInterPodAffinity left out of the predicates, as
                   a scheduler that never looked at the annotation; with
                   it `term_ignored_nodes_with_two`, the nodes that then
                   hold two live pods of one group
  stated_requests  LeastRequestedPriority and BalancedResourceAllocation
                   on the stated requests (memory 0) where upstream's
                   non-zero defaults (100m, 200Mi) belong

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


def broken(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, "term_ignored",
    "term_ignored_nodes_with_two", "stated_requests"}; only "sound"
    where the deployment's reference keeps no term."""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    read = {"sound": sound["mismatches"]}
    if not hasattr(start, "term_holds"):
        return read
    for name, switch in (("term_ignored", "term_holds"),
                         ("stated_requests", "nonzero_defaults")):
        blind = copy.deepcopy(start)
        setattr(blind, switch, False)
        made = reference.decide(blind, backlog, sound["counter"][0])
        read[name] = reference.verify(copy.deepcopy(start), backlog,
                                      made)["mismatches"]
        if switch == "term_holds":
            read[name + "_nodes_with_two"] = blind.over_allocatable()
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
