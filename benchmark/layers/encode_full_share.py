"""Scheduler host side: the share of the window's waves whose snapshot
the from-scratch encoder made (WaveScheduler.stats["waves_by_encoder"],
cumulative: `full` over `full` + `incremental`, counted where
TPUScheduleAlgorithm chooses). A daemon keeps its snapshot from wave to
wave (snapshot/incremental.py) unless a scope gate sends the wave to
`SnapshotEncoder(state, reps)`, which walks every node and bound pod and
has every device table shipped again; stats["encoder_fallbacks"] says
by which gate. A program that keeps no such tally gives nothing to
read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    return dict(stats.get("waves_by_encoder") or {})


def read(run):
    before, after = run["snapshots"]["encode_full_share"]
    made = {k: n - before.get(k, 0) for k, n in after.items()}
    total = sum(made.values())
    if not total:
        return None
    return 100.0 * made.get("full", 0) / total
