"""Scheduler host side, read from the generator's clock:
created->bound from the tick a pod was due at, at the percentile the
metric's name carries (`bind_latency_tail_ms.p95.<traffic>`). The
tails stand here without a bound, beside the median that has one,
because they read how many long pauses of the collector in the
daemon's process (daemon_gc_pause_max_ms) fell into the window."""


def read(run):
    percentile = run["metric"].split(".")[1]
    return run["loadgen"].get(f"bind_latency_{percentile}_ms")
