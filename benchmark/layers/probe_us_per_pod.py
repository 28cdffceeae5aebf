"""Single-chip driver: microseconds of the `probe` phase per pod bound
in the window, from exclusive_totals(). `probe` brackets a run's device
round trip (models/wave.run_single: the dispatch of `jit_probe_fused_*`
with the deferred fold riding it, and `device_get` of its packed
tables; run_group_host: the same for `jit_group_probe`), so it is host
time that ends at a device sync, never device time alone. It ranks
second among the phases (after `encode`), so a watch thread's `wire`
or a `bind` open at the same instant is counted here while the loop
waits for the tables."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    if not bound:
        return None
    return 1e6 * _phases.spent(run, "probe_us_per_pod", ("probe",)) / bound
