"""Scheduler host side: microseconds of the `encode` phase per pod
bound in the window, from exclusive_totals(). `encode` ranks first
among the phases, so its exclusive time is its own: the pending pods'
dedup and rows plus the cache deltas of the wave before (the assumes,
the churn's deletes) going into the snapshot arrays."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    if not bound:
        return None
    return 1e6 * _phases.spent(run, "encode_us_per_pod", ("encode",)) / bound
