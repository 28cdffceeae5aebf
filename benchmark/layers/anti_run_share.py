"""Single-chip driver: the share of the window's decided pods that a
run carrying the self-anti veto placed (WaveScheduler.stats
["anti_picks"] over the sum of stats["pods_by_path"], both cumulative):
runs whose pods own a required hostname anti-affinity term that selects
their own labels, each decided by `run_single` with one `probe_fused`
whose tables drop a node once the run has picked it. A program that
keeps no such counter gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "anti_picks" not in stats:
        return {}
    return {"anti_picks": int(stats["anti_picks"]),
            "decided": sum(stats["pods_by_path"].values())}


def read(run):
    before, after = run["snapshots"]["anti_run_share"]
    if not after:
        return None
    decided = after["decided"] - before["decided"]
    if not decided:
        return None
    return 100.0 * (after["anti_picks"] - before["anti_picks"]) / decided
