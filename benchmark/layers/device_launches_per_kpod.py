"""Device (kernels): XLA programs launched per 1,000 pods bound in the
traced slice."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["chips"] or not trace["bound_in_slice"]:
        return None
    return trace["launches"] / (trace["bound_in_slice"] / 1000.0)
