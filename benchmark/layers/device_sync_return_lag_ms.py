"""Scheduler host side, on the profiler's clock: how long after the
device is done the loop runs again. The daemon marks every host read
that waits for the device (trace/profile.device_wait) with a
TraceAnnotation("sched/device_wait") that ends when the host has the
value; a wait's lag is from the end of the last program the device
finished before that ("XLA Modules" line of the same .xplane.pb) to the
annotation's end: the picks' copy to the host and the interpreter lock
regained, which a side thread in pure Python may hold for a whole
switch interval. The median over the slice's waits, in ms.

The pass over the trace is this reader's own: it keeps the host planes'
`sched/` events and the device planes' "XLA Modules" line, never the
"XLA Ops" line (millions of events in a slice). Imported before the
daemon starts, and only in a traced run: importing it switches the
annotations on. A program that marks no wait leaves no
`sched/device_wait` in the trace, and the name reads nothing.
"""

import bisect
import statistics
import sys

from benchmark import trace_reduce
from benchmark.layers import device_idle_by_host as _by_host
from kubernetes_tpu.trace import profile

WAIT = _by_host.PREFIX + "device_wait"

if hasattr(profile, "set_annotations"):
    profile.set_annotations(True)


def load(path: str) -> dict:
    """trace_reduce.load_xplane's plain form, of the lines named above."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = trace_reduce.is_device(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name != trace_reduce.MODULES_LINE:
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(_by_host.PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def lags(trace: dict) -> list:
    """[(lag ns, whether the program was still running when the wait
    began)] a `sched/device_wait` annotation that has a program ending
    before its own end; in the order of the annotations' ends."""
    done = sorted(start + dur for plane in trace["planes"]
                  if trace_reduce.is_device(plane["name"])
                  for _n, start, dur in trace_reduce._line(
                      plane, trace_reduce.MODULES_LINE))
    waits = sorted((start + dur, start) for plane in trace["planes"]
                   if not trace_reduce.is_device(plane["name"])
                   for line in plane["lines"]
                   for name, start, dur in line["events"] if name == WAIT)
    out = []
    for end, start in waits:
        k = bisect.bisect_right(done, end) - 1
        if k >= 0:
            out.append((end - done[k], done[k] >= start))
    return out


def read(run):
    me = "device_sync_return_lag_ms"
    try:
        trace = load(_by_host.newest_trace(run["cell"]["name"]))
    except FileNotFoundError as e:
        print(f"{me}: {e}", file=sys.stderr)
        return None
    found = lags(trace)
    if not found:
        print(f"{me}: no sched/device_wait annotation or no program on the "
              "device's line in the trace", file=sys.stderr)
        return None
    blocked = [lag for lag, waited in found if waited]
    ready = [lag for lag, waited in found if not waited]
    print(f"{me}: {len(found)} waits; the device still ran when "
          f"{len(blocked)} began (median lag "
          f"{statistics.median(blocked) / 1e6 if blocked else None} ms, max "
          f"{max(blocked, default=0) / 1e6} ms), was done before "
          f"{len(ready)} (median "
          f"{statistics.median(ready) / 1e6 if ready else None} ms); clock "
          f"check: {_by_host.clock_check(trace)}", file=sys.stderr)
    return statistics.median(lag for lag, _w in found) / 1e6
