"""Single-chip driver (and the mesh's, on the same thread): microseconds
per pod bound in the window that the loop's thread stood in a host read
that waits for the device (trace/profile.device_wait: the picks of the
scan and of the device replay, the probes' tables, a donated fold's
drain), from thread_totals()'s role "loop". Wall time of one thread:
the device's own time plus the way back (device_sync_return_lag_ms)."""

from benchmark.layers import loop_host_us_per_pod as _ledger

snapshot = _ledger.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    loop = _ledger.loop_of(run, "loop_device_wait_us_per_pod")
    if loop is None or not bound:
        return None
    return 1e6 * loop[_ledger.WAIT]["wall"] / bound
