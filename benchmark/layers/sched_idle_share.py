"""Scheduler host side: the share of the window the daemon waited and
did not work: blocked in next_pod() or asleep to let a wave fill
(trace/profile.idle_totals()). sched_unattributed_share, the window
outside every working phase, holds this and the time inside no timer at
all: the second is the difference of the two."""


def snapshot(ctx):
    from kubernetes_tpu.trace import profile

    if not hasattr(profile, "idle_totals"):
        return None
    return profile.idle_totals()


def read(run):
    before, after = run["snapshots"]["sched_idle_share"]
    if before is None or after is None:
        return None
    waited = sum(after[s] - before[s] for s in after)
    return 100.0 * waited / run["window"]["seconds"]
