"""Door / store / fan-out, as the daemon pays for it: CPU microseconds
per pod bound in the window on the threads that share the loop's
interpreter lock, by role (thread_totals()): `informer` is the
reflectors' and informers' threads (the watch frames' `wire` decode and
the `ingest` into store, queue and scheduler cache), `binder` the bind
pool's (`bind`, and the `wire` decode of its answers). Thread CPU
inside the working phases, so whatever the loop does meanwhile: a
shorter device wait uncovers this work on the one timeline and does not
add to it here. One name a role:
`side_thread_cpu_us_per_pod.<role>.<traffic>`."""

from benchmark.layers import loop_host_us_per_pod as _ledger

snapshot = _ledger.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    moved = _ledger.table(run, "side_thread_cpu_us_per_pod")
    if moved is None or not bound:
        return None
    role = run["metric"].split(".")[1]
    return 1e6 * _ledger.over(moved.get(role, {}), _ledger.profile.PHASES,
                              "cpu") / bound
