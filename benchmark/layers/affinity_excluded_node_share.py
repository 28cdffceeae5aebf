"""Single-chip driver: of the deployment's nodes, the share that a run
whose pods own a required podAffinity term could not go to when its
wave began, averaged over the window's such runs
(WaveScheduler.stats["affinity_nodes_excluded"] over
stats["affinity_runs"] times the deployment's nodes, both cumulative;
the driver counts, on the inter-pod tables the wave's snapshot holds
and with no device read of its own, the nodes with allocatable that lie
in no domain where some bound pod matches the term). With every service
in one of three zones it reads two thirds; it reads near 0 if the churn
ever empties the services (the first pod of a collection goes anywhere)
or the term stops deciding. A program that keeps no such counters gives
nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "affinity_nodes_excluded" not in stats:
        return {}
    return {"excluded": int(stats["affinity_nodes_excluded"]),
            "runs": int(stats["affinity_runs"])}


def read(run):
    before, after = run["snapshots"]["affinity_excluded_node_share"]
    if not after:
        return None
    runs = after["runs"] - before["runs"]
    nodes = int(run["config"]["nodes"]["count"])
    if not runs or not nodes:
        return None
    return 100.0 * (after["excluded"] - before["excluded"]) / (runs * nodes)
