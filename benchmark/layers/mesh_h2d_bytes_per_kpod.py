"""Mesh driver: bytes the resident state and the driver shipped host to
device per 1,000 pods bound in the window (MeshWaveScheduler.stats
["h2d_bytes_total"], cumulative, brought up to date at the end of each
wave: table rows that changed, the pending pods' rows, the commit
counts). Beside the single-chip driver's h2d_bytes_per_kpod it says
what shipping deltas buys. A driver without the tally gives nothing to
read."""


def snapshot(ctx):
    algorithm = ctx["sched"].scheduler.config.algorithm
    stats = getattr(getattr(algorithm, "_wave", None), "stats", None) or {}
    return {"bytes": stats.get("h2d_bytes_total")}


def read(run):
    before, after = run["snapshots"]["mesh_h2d_bytes_per_kpod"]
    bound = run["loadgen"]["bound_in_window"]
    if after["bytes"] is None or before["bytes"] is None or not bound:
        return None
    return (after["bytes"] - before["bytes"]) / (bound / 1000.0)
