"""Single-chip driver: the share of the window's decided pods in runs of
`min_run` pods and more that the wave driver left to the serial scan
because a PREFERRED term of the pod selects the pod's own copies
(WaveScheduler.stats["scan_reasons"]["self_preferred"], cumulative: the
second refusal of models/wave.run_verdict, tested after
`hard_affinity`; over the sum of stats["pods_by_path"]). Each commit of
such a run moves the score of the next copy on the picked node's
domain, a slope in the copy number that the run tables do not hold; it
is what teaching them that slope would take off the scan.
`interpod_scan_share` reads the three term reasons together. A program
that keeps no such tally gives nothing to read (the parent of PR 49
keeps it, but no cell listed this reader there)."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "scan_reasons" not in stats:
        return {}
    return {"self_preferred": int(stats["scan_reasons"].get(
                "self_preferred", 0)),
            "decided": sum(stats["pods_by_path"].values())}


def read(run):
    before, after = run["snapshots"]["self_preferred_scan_share"]
    if not after:
        return None
    decided = after["decided"] - before["decided"]
    if not decided:
        return None
    return 100.0 * (after["self_preferred"] - before["self_preferred"]) \
        / decided
