"""Single-chip driver: microseconds of the `replay` phase per pod bound
in the window, from exclusive_totals(). `replay` is the host's pick
sequence for a run or a group of runs (models/wave.host_group_replay:
the tables of models/hosttab and the picks of models/replay), the
enqueue of a commit fold, and on a zoned cluster the wait for the
device replay from its dispatch to the read of its picks: host time
always, device time never. It ranks fourth among the phases (after
`encode`, `probe` and `score`), so a watch thread's `wire` or a `bind`
open at the same instant is counted here while a replay runs."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    if not bound:
        return None
    return 1e6 * _phases.spent(run, "replay_us_per_pod", ("replay",)) / bound
