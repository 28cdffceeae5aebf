"""The must-fail reading of a run that was made: the control.

    python3 benchmark/controls.py .bench_out/<cell>-<seed>-<trace>

The deployment guarantees that the daemon's decisions are the serial
scheduler's. The control is the deployment's reference put in the
daemon's place with that guarantee broken: the commitments of
STALE_WAVE pods are folded together, so no pick inside such a wave
sees the picks before it (the step that tempts a faster batch). It is
read on the cluster the run's window left and on the run's own check
batch, from the load generator's record, and prints what
`picks_off_reference` would have shown. Not part of a run: the
builder reads it beside the sound runs' number, to set the limit.
"""

from __future__ import annotations

import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, deploy  # noqa: E402

#: the daemon's wave floor, the smallest wave it gathers
STALE_WAVE = 1024


def stale_wave(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, "stale_wave":
    the control's}"""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    made = reference.decide(copy.deepcopy(start), backlog,
                            sound["counter"][0], stale=STALE_WAVE)
    held = reference.verify(start, backlog, made)
    return {"sound": sound["mismatches"], "stale_wave": held["mismatches"]}


def main(argv=None) -> None:
    out_dir = (argv or sys.argv[1:])[0]
    record = deploy.load_json(os.path.join(out_dir, "loadgen.json"))
    cfg = deploy.load_json(os.path.join(out_dir, "config.json"))
    print(json.dumps({"run": os.path.basename(os.path.normpath(out_dir)),
                      **stale_wave(record, cfg)}))


if __name__ == "__main__":
    main()
