"""Single-chip driver: pods bound in the window over the waves the
driver ran in it (WaveScheduler.stats["waves"], cumulative)."""


def snapshot(ctx):
    algorithm = ctx["sched"].scheduler.config.algorithm
    return {"waves": algorithm._wave.stats["waves"]}


def read(run):
    before, after = run["snapshots"]["pods_per_wave"]
    waves = after["waves"] - before["waves"]
    return run["loadgen"]["bound_in_window"] / waves if waves else None
