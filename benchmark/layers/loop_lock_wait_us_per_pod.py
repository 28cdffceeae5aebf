"""Scheduler host side: microseconds per pod bound in the window that
the loop's thread was runnable and not running: over its working
phases, wall less thread CPU (thread_totals(), role "loop"), less the
same difference inside its waits for the device. On that thread nothing
else blocks by design (`transfer` ends at an enqueue, `assume` hands
over to the pool), so this is the wait for the interpreter lock the
informers' and the binder's threads hold, plus what the OS took."""

from benchmark.layers import loop_host_us_per_pod as _ledger

snapshot = _ledger.snapshot


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    loop = _ledger.loop_of(run, "loop_lock_wait_us_per_pod")
    if loop is None or not bound:
        return None
    working = _ledger.profile.PHASES
    off_cpu = _ledger.over(loop, working, "wall") \
        - _ledger.over(loop, working, "cpu")
    wait = loop[_ledger.WAIT]
    return 1e6 * (off_cpu - (wait["wall"] - wait["cpu"])) / bound
