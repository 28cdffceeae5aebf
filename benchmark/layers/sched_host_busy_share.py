"""Scheduler host side: the share of the window its host-only phases
took (encode, transfer, wire, bind), from exclusive_totals()."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    took = _phases.spent(run, "sched_host_busy_share",
                         ("encode", "transfer", "wire", "bind"))
    return 100.0 * took / run["window"]["seconds"]
