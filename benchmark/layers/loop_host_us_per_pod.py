"""Scheduler host side: the loop thread's own work per pod bound in the
window, in microseconds: its wall seconds inside the working phases
less its waits for the device (trace/profile.thread_totals(), role
"loop": scheduler/core.Scheduler._loop, the one thread whose steps one
after the other are a wave's period). Unlike exclusive_totals()'s one
timeline this is that thread's time alone, whatever the informers' and
the binder's threads did meanwhile:

    loop_device_wait + loop_host + (idle + uncovered) a pod = the period

It also holds what the five readers of the per-thread ledger share: the
snapshot, the difference of the two reads as a table, and the line on
stderr that shows the whole table once a run.
"""

import json
import sys
import time

from kubernetes_tpu.trace import profile

WAIT = "device_wait"
FIELDS = ("wall", "cpu", "count")


def snapshot(ctx):
    """None where the program keeps no per-thread ledger (a parent's
    line then leaves the names out)."""
    if not hasattr(profile, "thread_totals"):
        return None
    if "threads" not in ctx:  # one read serves the five readers
        ctx["threads"] = {"threads": profile.thread_totals(),
                          "exclusive": profile.exclusive_totals(),
                          "process_cpu": time.process_time()}
    return ctx["threads"]


def table(run, reader):
    """{role: {key: {"wall", "cpu", "count"}}} between the two reads,
    or None without a ledger."""
    before, after = run["snapshots"][reader]
    if before is None or after is None:
        return None
    _say(run, before, after)
    return _moved(before["threads"], after["threads"])


def _moved(before, after):
    zero = dict.fromkeys(FIELDS, 0)
    return {role: {key: {f: cell[f] - before.get(role, {}).get(key, zero)[f]
                         for f in FIELDS}
                   for key, cell in cells.items()}
            for role, cells in after.items()}


def over(cells, keys, field):
    return sum(cells[k][field] for k in keys if k in cells)


def seconds_between_reads(run):
    """The ledger is cumulative, so what it gained lies between the two
    reads, which are not quite the window's ends."""
    window = run["window"]
    first, second = window.get("reads", (0.0, 0.0))
    return window["seconds"] + second - first


def loop_of(run, reader):
    """The loop role's cells between the reads, or None."""
    moved = table(run, reader)
    return None if moved is None else moved.get("loop")


def _say(run, before, after):
    """Every thread's every phase, once a run, for the one who reads
    its stderr; with it how the loop's wall in the phases only it opens
    compares with the one timeline's, and what share of the process's
    CPU time lay inside timers."""
    if run.get("thread_table_said"):
        return
    run["thread_table_said"] = True
    moved = _moved(before["threads"], after["threads"])
    shown = {role: {key: [round(c["wall"], 4), round(c["cpu"], 4),
                          c["count"]]
                    for key, c in cells.items() if c["count"]}
             for role, cells in moved.items()}
    loop = moved.get("loop", {})
    own = ("probe", "score", "replay")
    timeline = sum(after["exclusive"][p] - before["exclusive"][p]
                   for p in own)
    inside = sum(over(cells, profile.PHASES, "cpu")
                 for cells in moved.values())
    process = after["process_cpu"] - before["process_cpu"]
    print("thread ledger between the reads, role: phase: [wall s, cpu s, "
          f"entries]: {json.dumps(shown)}; loop's wall in probe + score "
          f"+ replay {over(loop, own, 'wall'):.4f} s against the timeline's "
          f"{timeline:.4f} s; cpu inside working phases, all roles "
          f"{inside:.4f} s of the process's {process:.4f} s "
          f"({100.0 * inside / process if process else 0.0:.1f}%)",
          file=sys.stderr)


def read(run):
    bound = run["loadgen"]["bound_in_window"]
    loop = loop_of(run, "loop_host_us_per_pod")
    if loop is None or not bound:
        return None
    return 1e6 * (over(loop, profile.PHASES, "wall")
                  - loop[WAIT]["wall"]) / bound
