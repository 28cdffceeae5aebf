"""The must-fail reading for a deployment whose templates ask for
different resources, beside benchmark/controls.py's stale wave and
benchmark/control_precision.py's lower precision.

    python3 benchmark/control_shapes.py .bench_out/<cell>-<seed>-<trace>

Such a deployment guarantees that each pod is scored with its own
template's requests. The control is its reference put in the daemon's
place with that guarantee broken: every template scored (fitted and
ranked) with the mean of the shapes, as a scheduler that kept one
request vector for a whole wave would, while each bound pod still
commits what it asked for. It is read on the cluster the run's window
left and on the run's own check batch, from the load generator's
record, and prints what `picks_off_reference` would have shown, with
the other two controls' numbers beside it. A cell on which it reads 0
cannot see that the requests differ, whatever its runs read. Not part
of a run: the builder reads it beside the sound runs' number.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, control_precision, controls, deploy  # noqa: E402


def scored_with_the_mean(cluster):
    """A copy of `cluster` that fits and ranks every template with the
    mean shape (in the whole milli-CPUs and bytes the scorers count in)
    and binds what each template asks for."""
    blind = copy.deepcopy(cluster)
    n = len(cluster.shape_cpu)
    blind.scored_cpu = np.full(n, int(cluster.shape_cpu.sum()) // n)
    blind.scored_mem = np.full(n, int(cluster.shape_mem.sum()) // n)
    return blind


def mean_shape(record: dict, cfg: dict) -> dict:
    """-> {"sound": the run's own picks_off_reference, "mean_shape":
    the control's}; only "sound" where the deployment's reference keeps
    no shape per template."""
    reference = check.load_reference(cfg)
    chk = record["check"]
    backlog = chk["backlog"]
    templates = dict(zip(chk["names"], backlog))
    start, _ = check.cluster(reference, cfg, chk["before"], templates)
    picks = [start.index.get(chk["after"].get(name, ""))
             for name in chk["names"]]
    sound = reference.verify(copy.deepcopy(start), backlog, picks)
    read = {"sound": sound["mismatches"]}
    if not hasattr(start, "shape_cpu"):
        return read
    made = reference.decide(scored_with_the_mean(start), backlog,
                            sound["counter"][0])
    read["mean_shape"] = reference.verify(start, backlog,
                                          made)["mismatches"]
    return read


def main(argv=None) -> None:
    out_dir = (argv or sys.argv[1:])[0]
    record = deploy.load_json(os.path.join(out_dir, "loadgen.json"))
    cfg = deploy.load_json(os.path.join(out_dir, "config.json"))
    print(json.dumps({"run": os.path.basename(os.path.normpath(out_dir)),
                      **controls.stale_wave(record, cfg),
                      **control_precision.lower_precision(record, cfg),
                      **mean_shape(record, cfg)}))


if __name__ == "__main__":
    main()
