"""Door / store / fan-out: the share of the window the apiserver's
process stood still in its garbage collector, from its own /metrics
(process_gc_pause_seconds_total, summed over generations)."""

COUNTER = "process_gc_pause_seconds_total"


def snapshot(ctx):
    return ctx["api_metrics"].get(COUNTER)


def read(run):
    before, after = run["snapshots"]["apiserver_gc_pause_share"]
    if before is None or after is None:
        return None
    return 100.0 * (after - before) / run["window"]["seconds"]
