"""Device: the share of the traced slice in which no operation ran on
the chip: 1 - union of device-op intervals over the slice."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["chips"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
