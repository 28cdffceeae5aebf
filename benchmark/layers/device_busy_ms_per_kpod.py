"""Device (kernels): device busy time per 1,000 pods bound in the
traced slice."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["chips"] or not trace["bound_in_slice"]:
        return None
    return trace["busy_s"] * 1000.0 / (trace["bound_in_slice"] / 1000.0)
