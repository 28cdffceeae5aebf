"""Single-chip driver: of the pick steps the device replay ran in the
window (`jit_zreplay_group`'s and, where the program counts them,
`jit_zreplay_run`'s), the share, in %, that evaluated the carried score
again, from the programs' own counters (WaveScheduler.stats
["zreplay_rescores"] over ["zreplay_steps"], both cumulative). A step
rescores when its epoch was spent: a node picked twice since the last
evaluation, or one that left the fit set (since PR 50: holding an
extreme that a normaliser reads). 0 where every run is one evaluation;
near 50 where half the device replay's pods are runs with the self-anti
veto and every pick of such a run ends its epoch. A window in which the
device replay ran no step, or a program that keeps no such counters,
gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "zreplay_rescores" not in stats:
        return {}
    return {"steps": stats["zreplay_steps"],
            "rescores": stats["zreplay_rescores"]}


def read(run):
    before, after = run["snapshots"]["zreplay_rescore_share"]
    if not after:
        return None
    steps = after["steps"] - before["steps"]
    if not steps:
        return None
    return 100.0 * (after["rescores"] - before["rescores"]) / steps
