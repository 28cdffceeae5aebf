"""Single-chip driver: microseconds of the `score` phase per pod bound
in the window, from exclusive_totals(). `score` brackets the serial
scan program (`jit_batch_scan`) from its dispatch to the read of its
picks (models/wave.flush: `np.asarray(chosen)`), so it is host time
that ends at a device sync: the device's run of the program, the
enqueue before it and the copy of the picks after it, and never device
time alone. It ranks third among the phases (after `encode` and
`probe`), so a watch thread's `wire` or a `bind` open at the same
instant is counted here while the scan runs."""

from benchmark.layers import _phases

snapshot = _phases.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    if not bound:
        return None
    return 1e6 * _phases.spent(run, "score_us_per_pod", ("score",)) / bound
