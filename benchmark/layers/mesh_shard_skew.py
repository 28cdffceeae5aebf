"""Mesh driver: the fullest shard's picks in the window over the mean
shard's, from the driver's own tally (MeshWaveScheduler.stats
["picks_by_shard"], cumulative: the picks that landed on each shard's
nodes); 1.0 is even. A driver that keeps no such tally (one chip, or a
program from before it) gives nothing to read."""


def snapshot(ctx):
    algorithm = ctx["sched"].scheduler.config.algorithm
    stats = getattr(getattr(algorithm, "_wave", None), "stats", None) or {}
    return list(stats.get("picks_by_shard") or [])


def read(run):
    before, after = run["snapshots"]["mesh_shard_skew"]
    picks = [a - b for a, b in zip(after, before or [0] * len(after))]
    if not picks or not sum(picks):
        return None
    return max(picks) / (sum(picks) / len(picks))
