"""The second must-fail reading of a run that was made: the precision
control, beside benchmark/controls.py's stale wave.

    python3 benchmark/control_precision.py .bench_out/<cell>-<seed>-<trace>

A deployment whose file states the precision of its arithmetic
(`guarantees.arithmetic`) names a reference that computes in it
(`Cluster.real`). The control is that reference put in the daemon's
place with the guarantee broken: the same scorer in the nearest
precision below. It is read on the cluster the run's window left and on
the run's own check batch, from the load generator's record, and prints
what `picks_off_reference` would have shown, with the stale wave's
number beside it. A cell on which it reads 0 cannot tell the stated
precision from the one below, whatever its runs read. Not part of a
run: the builder reads it beside the sound runs' number.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, controls, deploy  # noqa: E402

#: the nearest precision below
BELOW = {"float64": np.float32, "float32": np.float16}


def lower_precision(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, "<precision>":
    the control's}; only "sound" where the deployment's reference
    states no precision."""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    read = {"sound": sound["mismatches"]}
    stated = getattr(start, "real", None)
    if stated is None:
        return read
    low = copy.deepcopy(start)
    low.real = BELOW[np.dtype(stated).name]
    made = reference.decide(low, backlog, sound["counter"][0])
    held = reference.verify(start, backlog, made)
    read[np.dtype(low.real).name] = held["mismatches"]
    return read


def main(argv=None) -> None:
    out_dir = (argv or sys.argv[1:])[0]
    record = deploy.load_json(os.path.join(out_dir, "loadgen.json"))
    cfg = deploy.load_json(os.path.join(out_dir, "config.json"))
    print(json.dumps({"run": os.path.basename(os.path.normpath(out_dir)),
                      **controls.stale_wave(record, cfg),
                      **lower_precision(record, cfg)}))


if __name__ == "__main__":
    main()
