"""Single-chip driver: the share of the window's decided pods that went
through one path of the wave driver, from its own tally
(WaveScheduler.stats["pods_by_path"], cumulative; the paths are
models/wave.PATHS). The path is the metric's second name:

  wave_path_share.scan.<traffic>     the serial scan program
                                     (`jit_batch_scan`, one pick a step)
  wave_path_share.grouped.<traffic>  a grouped header probe replayed on
                                     the host, or a grouped device
                                     replay (`group_host` + `group_device`)

What is left of 100 went run by run (`single`). A program that keeps no
such tally gives nothing to read."""

GROUPS = {"scan": ("scan",), "grouped": ("group_host", "group_device")}


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    return dict(stats.get("pods_by_path") or {})


def read(run):
    before, after = run["snapshots"]["wave_path_share"]
    decided = {k: n - before.get(k, 0) for k, n in after.items()}
    total = sum(decided.values())
    if not total:
        return None
    paths = GROUPS[run["metric"].split(".")[1]]
    return 100.0 * sum(decided.get(p, 0) for p in paths) / total
