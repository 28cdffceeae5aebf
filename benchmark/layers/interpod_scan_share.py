"""Single-chip driver: the share of the window's decided pods that the
wave driver sent to the serial scan because of an inter-pod term of the
pod's own (WaveScheduler.stats["scan_reasons"], cumulative: the pods of
runs of at least `min_run` that `run_verdict` refused, by reason; here
`hard_affinity` + `self_preferred` + `zone_anti` over the sum of
stats["pods_by_path"]). Such a run is `jit_batch_scan`'s, one pick a
step with `match_interpod`, `interpod_priority` and `interpod_commit`
live in the step, until the run tables learn its terms. A program that
keeps no such tally gives nothing to read."""

TERM_REASONS = ("hard_affinity", "self_preferred", "zone_anti")


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "scan_reasons" not in stats:
        return {}
    return {"by_term": sum(int(stats["scan_reasons"].get(r, 0))
                           for r in TERM_REASONS),
            "decided": sum(stats["pods_by_path"].values())}


def read(run):
    before, after = run["snapshots"]["interpod_scan_share"]
    if not after:
        return None
    decided = after["decided"] - before["decided"]
    if not decided:
        return None
    return 100.0 * (after["by_term"] - before["by_term"]) / decided
