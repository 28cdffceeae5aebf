"""Single-chip driver: the pods one dispatch of the serial scan decided,
averaged over the window (WaveScheduler.stats["pods_by_path"]["scan"]
over stats["scan_flushes"], the calls of models/waveloop.flush that
found pods pending; both cumulative). The loop hands the scan what is
pending, in FIFO order, before every step that is not the scan's, and
waits for its picks: one dispatch of `jit_batch_scan` in the pod bucket
above the count and one blocking read a flush. Where every pod takes the
scan it is a wave's pods; where the kinds alternate it is the stretch
between two runs of another kind. A program that keeps no such counter
gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "scan_flushes" not in stats:
        return {}
    return {"pods": int(stats["pods_by_path"]["scan"]),
            "flushes": int(stats["scan_flushes"])}


def read(run):
    before, after = run["snapshots"]["scan_pods_per_flush"]
    if not after:
        return None
    flushes = after["flushes"] - before["flushes"]
    if not flushes:
        return None
    return (after["pods"] - before["pods"]) / flushes
