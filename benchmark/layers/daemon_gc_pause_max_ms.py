"""Scheduler host side: the longest single pause of the garbage
collector in the process that holds the daemon, inside the window.
Every thread of the daemon stands still for it, so a long one is a
wave that comes late: it is what the tails (bind_latency_tail_ms)
read."""


def snapshot(ctx):
    return ctx["gc_pauses"]


def read(run):
    before, after = run["snapshots"]["daemon_gc_pause_max_ms"]
    return 1000.0 * max((s for _, _, s in after[len(before):]), default=0.0)
