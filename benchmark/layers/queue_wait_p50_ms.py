"""Scheduler host side: how long the median pod sat in the daemon's
queue before its wave started, from the window's diff of the histogram
scheduler_pod_queue_wait_seconds (buckets of 10 ms up to 0.5 s),
interpolated inside the bucket the median falls into."""


def snapshot(ctx):
    from kubernetes_tpu import metrics

    hist = getattr(metrics, "scheduler_pod_queue_wait_seconds", None)
    if hist is None:
        return None
    return {"buckets": list(hist.buckets), "counts": hist.bucket_counts()}


def read(run):
    before, after = run["snapshots"]["queue_wait_p50_ms"]
    if before is None or after is None:
        return None
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    half = sum(counts) / 2.0
    if not half:
        return None
    seen, low = 0, 0.0
    for count, high in zip(counts, after["buckets"]):
        if count and seen + count >= half:
            return 1000.0 * (low + (high - low) * (half - seen) / count)
        seen, low = seen + count, high
    return 1000.0 * low  # in the overflow bucket: its lower edge
