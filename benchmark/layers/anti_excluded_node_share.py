"""Single-chip driver: of the deployment's nodes, the share a run with
the self-anti veto found unfit at its first probe, averaged over the
window's such runs (WaveScheduler.stats["anti_nodes_excluded"] over
stats["anti_runs"] times the deployment's nodes, both cumulative; the
driver counts them on the tables the probe shipped, with no device read
of its own). Where nothing but the term excludes a node it is the share
of the cluster the terms of bound pods have taken from a run before it
starts; it reads near 0 if the churn ever empties the cluster. A
program that keeps no such counters gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "anti_nodes_excluded" not in stats:
        return {}
    return {"excluded": int(stats["anti_nodes_excluded"]),
            "runs": int(stats["anti_runs"])}


def read(run):
    before, after = run["snapshots"]["anti_excluded_node_share"]
    if not after:
        return None
    runs = after["runs"] - before["runs"]
    nodes = int(run["config"]["nodes"]["count"])
    if not runs or not nodes:
        return None
    return 100.0 * (after["excluded"] - before["excluded"]) / (runs * nodes)
