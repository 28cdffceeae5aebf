"""Door / store / fan-out: apiserver requests per 1,000 pods bound in
the window, from its /metrics before and after."""


def snapshot(ctx):
    return {"requests": ctx["api_metrics"].get("apiserver_requests_total")}


def read(run):
    before, after = run["snapshots"]["api_requests_per_kpod"]
    bound = run["loadgen"]["bound_in_window"]
    if before["requests"] is None or after["requests"] is None or not bound:
        return None
    return (after["requests"] - before["requests"]) / (bound / 1000.0)
