"""Scheduler host side: the share of the window no phase timer
covered: the scheduler had nothing to do, or was outside every timer."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    took = _phases.spent(run, "sched_unattributed_share")
    return 100.0 * (1.0 - took / run["window"]["seconds"])
