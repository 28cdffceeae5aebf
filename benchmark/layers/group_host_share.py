"""Single-chip driver: the share of the window's decided pods that a
grouped header probe replayed on the host decided (`group_host` of
WaveScheduler.stats["pods_by_path"], cumulative; the paths are
models/wave.PATHS): one `jit_group_probe` dispatch for a group of runs
of several templates, each run's table rebuilt on the host against what
the runs before it committed (models/hosttab), the picks replayed by
models/replay. wave_path_share.grouped counts it together with the
grouped device replay; here it stands alone. A program that keeps no
such tally gives nothing to read."""

from benchmark.layers import wave_path_share

snapshot = wave_path_share.snapshot


def read(run):
    before, after = run["snapshots"]["group_host_share"]
    decided = {k: n - before.get(k, 0) for k, n in after.items()}
    total = sum(decided.values())
    if not total or "group_host" not in decided:
        return None
    return 100.0 * decided["group_host"] / total
