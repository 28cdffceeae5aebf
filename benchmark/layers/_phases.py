"""The scheduler's own partition of its host wall clock
(trace/profile.exclusive_totals): seconds per phase, cumulative.
`probe`, `score` and `replay` are host time around a dispatch, never
device time."""


def snapshot(ctx):
    from kubernetes_tpu.trace import profile

    return profile.exclusive_totals()


def spent(run, reader, phases=None):
    """Seconds of the window the named phases took (all, if None)."""
    before, after = run["snapshots"][reader]
    return sum(after[p] - before[p] for p in (phases or after))
