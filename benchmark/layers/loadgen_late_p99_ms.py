"""Load generator: how late the open loop's ticks left, sent minus
due, 99th percentile over the window's ticks. A guard: a starved
generator must not read as a fast server."""


def read(run):
    return run["loadgen"].get("loadgen_late_p99_ms")
