"""Scheduler host side: the share of the time between the two reads
that the loop's thread spent inside no timer at all, neither a working
phase nor an idle state (thread_totals(), role "loop"): the code
between the timers. A timer open at a read is booked whole when it
ends, so the share is off by up to one phase's length over the window
either way."""

from benchmark.layers import loop_host_us_per_pod as _ledger

snapshot = _ledger.snapshot


def read(run):
    loop = _ledger.loop_of(run, "loop_uncovered_share")
    if loop is None:
        return None
    timed = _ledger.profile.PHASES + _ledger.profile.IDLE_STATES
    seconds = _ledger.seconds_between_reads(run)
    return 100.0 * (1.0 - _ledger.over(loop, timed, "wall") / seconds)
