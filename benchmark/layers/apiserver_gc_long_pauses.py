"""Door / store / fan-out: collections that stopped the apiserver's
process for 100 ms or more inside the window, from its own /metrics
(process_gc_long_pauses_total, summed over generations): what a stall
of the door of seconds would show up as."""

COUNTER = "process_gc_long_pauses_total"


def snapshot(ctx):
    return ctx["api_metrics"].get(COUNTER)


def read(run):
    before, after = run["snapshots"]["apiserver_gc_long_pauses"]
    if before is None or after is None:
        return None
    return after - before
