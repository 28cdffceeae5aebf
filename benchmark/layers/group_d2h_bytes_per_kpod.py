"""Single-chip driver: bytes the grouped header probes fetched from the
device per 1,000 pods bound in the window
(WaveScheduler.stats["group_d2h_bytes"], cumulative): every run slot's
header rows and the carry's resource block, one transfer a group. A
program that keeps no such counter gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "group_d2h_bytes" not in stats:
        return {}
    return {"group_d2h_bytes": int(stats["group_d2h_bytes"])}


def read(run):
    before, after = run["snapshots"]["group_d2h_bytes_per_kpod"]
    bound = run["loadgen"]["bound_in_window"]
    if not bound or not after:
        return None
    fetched = after["group_d2h_bytes"] - before["group_d2h_bytes"]
    return fetched / (bound / 1000.0)
