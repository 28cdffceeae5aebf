"""Scheduler host side, on the profiler's clock: what the host was
doing while the chip was idle. The share of the traced slice's
device-idle time under each host state, from the host plane of the same
.xplane.pb as the device's "XLA Ops" line: the daemon's phase timers
open a TraceAnnotation("sched/<phase>") each (trace/profile.py), and at
each instant the top-ranked one that is open counts (the order of
profile.PHASES, then the idle states). One name per state:
`device_idle_by_host.<state>.<traffic>` with <state> one of encode,
bind, wire, waiting (queue_wait + gather: the daemon had nothing to do,
or slept to fill a wave) and uncovered (inside no phase timer). The
other phases' shares go to stderr, so the five sum to 100 or less.

The profiler keeps an annotation only if it began and ended inside the
slice. A bind of a full wave takes seconds when the door is the
bottleneck, so most `sched/bind` annotations of such a slice are lost;
the wave traces of the span ring (`wave.bind`: handed to the pool ->
acknowledged, on the wall clock, which is the profiler's: the trace
gives its own start in "Task Environment") stand in for them.

Imported before the daemon starts, and only in a traced run: importing
it switches the annotations on. A program without them (before PR 25)
leaves no `sched/` event in the trace, and every name reads nothing.
"""

import bisect
import glob
import os
import sys

from benchmark import trace_reduce
from benchmark.layers import _waves
from kubernetes_tpu.trace import profile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIX = "sched/"
#: the timers that end in the launch of a device program
DISPATCHERS = ("transfer", "probe", "score", "replay")

if hasattr(profile, "set_annotations"):
    profile.set_annotations(True)

snapshot = _waves.snapshot


def newest_trace(cell: str) -> str:
    """The .xplane.pb of the cell's newest traced run."""
    dirs = glob.glob(os.path.join(ROOT, ".bench_out", f"{cell}-*-1",
                                  "trace"))
    if not dirs:
        raise FileNotFoundError(f"no traced run of {cell} under .bench_out")
    return trace_reduce.find_xplane(max(dirs, key=os.path.getmtime))


def _minus(a: list, b: list) -> list:
    """Sorted disjoint intervals of `a` outside those of `b`."""
    out, k = [], 0
    for start, end in a:
        while k < len(b) and b[k][1] <= start:
            k += 1
        j = k
        while j < len(b) and b[j][0] < end:
            if b[j][0] > start:
                out.append([start, b[j][0]])
            start = max(start, b[j][1])
            j += 1
        if start < end:
            out.append([start, end])
    return out


def _length(a: list) -> int:
    return sum(e - s for s, e in a)


def _merge(a: list, b: list) -> list:
    return trace_reduce.union(
        [["", s, e - s] for s, e in a] + [["", s, e - s] for s, e in b])


def session_start_ns(path: str):
    """The wall clock (ns) at which the trace's own clock reads 0."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            return dict(plane.stats).get("profile_start_time")
    return None


def ring_binds(waves, start_ns) -> list:
    """The window's `wave.bind` spans as `sched/bind` events on the
    trace's clock."""
    if not waves or start_ns is None:
        return []
    return [[PREFIX + "bind", int(w["bind"][0] * 1e9) - start_ns,
             int(w["bind"][1] * 1e9)]
            for w in waves.values() if "bind" in w]


def _ranked(by_state: dict) -> dict:
    order = profile.PHASES + getattr(profile, "IDLE_STATES", ())
    return {s: by_state[s] for s in order if s in by_state}


def host_states(trace: dict) -> dict:
    """phase or idle state -> the union of its annotations' intervals
    over every host thread, in rank order; {} when the trace holds
    none."""
    by_state: dict = {}
    for plane in trace["planes"]:
        if trace_reduce.is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            for event in line["events"]:
                if event[0].startswith(PREFIX):
                    by_state.setdefault(event[0][len(PREFIX):],
                                        []).append(event)
    return _ranked({s: trace_reduce.union(events)
                    for s, events in by_state.items()})


def shares(trace: dict, extra=()) -> dict:
    """-> {state: % of the device-idle time of the slice}, states being
    the phases, the idle states and "uncovered"; {} without a device
    line or without annotations. The slice is what the annotations and
    the device's operations span together; `extra` events count inside
    it only."""
    states = host_states(trace)
    ops = [e for plane in trace["planes"]
           if trace_reduce.is_device(plane["name"])
           for e in trace_reduce._line(plane, trace_reduce.OPS_LINE)]
    if not states or not ops:
        return {}
    busy = trace_reduce.union(ops)
    lo = min([busy[0][0]] + [iv[0][0] for iv in states.values()])
    hi = max([busy[-1][1]] + [iv[-1][1] for iv in states.values()])
    for name, start, dur in extra:
        start, end = max(start, lo), min(start + dur, hi)
        if start < end:
            state = name[len(PREFIX):]
            states[state] = _merge(states.get(state, []), [[start, end]])
    idle = _minus([[lo, hi]], busy)
    total = _length(idle)
    out, claimed = {}, []
    for state, intervals in _ranked(states).items():
        mine = _minus(intervals, claimed)
        out[state] = 100.0 * _length(_minus(mine, busy)) / total
        claimed = _merge(claimed, mine)
    out["uncovered"] = 100.0 * _length(_minus(idle, claimed)) / total
    return out


def clock_check(trace: dict, waves=None, start_ns=None) -> dict:
    """Every program the device ran was launched from inside one of the
    DISPATCHERS' timers, so on one clock each "XLA Modules" event
    begins inside such an annotation or, the launch being asynchronous,
    shortly after its end. -> the count of modules, how many began
    inside an annotation, and for the others whether the nearest
    annotation is the one they ran ahead of (`early`: the device's
    clock is that far ahead of the host's) or the one they followed
    (`after`), with the largest distance of each kind in us. With the
    ring's wave traces: how far each `sched/wave` annotation begins
    from the nearest `wave.gather` span (the ring's clock against the
    profiler's; they are opened a few statements apart)."""
    host = [event for plane in trace["planes"]
            if not trace_reduce.is_device(plane["name"])
            for line in plane["lines"] for event in line["events"]
            if event[0].startswith(PREFIX)]
    spans = trace_reduce.union(
        e for e in host if e[0][len(PREFIX):] in DISPATCHERS)
    starts = [s for s, _e in spans]
    inside, early, after = 0, [], []
    for plane in trace["planes"]:
        if not trace_reduce.is_device(plane["name"]):
            continue
        for _n, start, _d in trace_reduce._line(plane,
                                                trace_reduce.MODULES_LINE):
            k = bisect.bisect_right(starts, start) - 1
            if k >= 0 and start <= spans[k][1]:
                inside += 1
                continue
            behind = start - spans[k][1] if k >= 0 else float("inf")
            ahead = starts[k + 1] - start if k + 1 < len(starts) \
                else float("inf")
            (early if ahead < behind else after).append(
                min(ahead, behind) / 1000.0)
    out = {"modules": inside + len(early) + len(after), "inside": inside,
           "early": len(early), "early_us_max": max(early, default=None),
           "after": len(after), "after_us_max": max(after, default=None)}
    if waves and start_ns is not None:
        ring = sorted(int(w["gather"][0] * 1e9) - start_ns
                      for w in waves.values() if "gather" in w)
        off = []
        for name, start, _d in host:
            if name == PREFIX + "wave" and ring:
                k = bisect.bisect_left(ring, start)
                off.append(min(abs(start - r)
                               for r in ring[max(0, k - 1):k + 1]))
        if off:
            off.sort()
            out["ring_us_median"] = off[len(off) // 2] / 1000.0
            out["ring_us_max"] = off[-1] / 1000.0
    return out


def _read_trace(run: dict) -> dict:
    """The five names share one pass over the trace."""
    if "device_idle_by_host" not in run:
        waves = run["snapshots"].get("device_idle_by_host", (None, None))[1]
        try:
            path = newest_trace(run["cell"]["name"])
            trace = trace_reduce.load_xplane(path, keep=lambda name: True)
            start_ns = session_start_ns(path)
            binds = ring_binds(waves, start_ns)
            found = shares(trace, binds)
        except FileNotFoundError as e:
            print(f"device_idle_by_host: {e}", file=sys.stderr)
            trace, found = None, {}
        if found:
            print("device_idle_by_host: % of the device-idle time, by "
                  f"host state: {found}; from the trace alone: "
                  f"{shares(trace)}; clock check: "
                  f"{clock_check(trace, waves, start_ns)}", file=sys.stderr)
            # a state the slice never saw took none of it
            found = {"encode": 0.0, "bind": 0.0, "wire": 0.0, **found,
                     "waiting": found.get("queue_wait", 0.0)
                     + found.get("gather", 0.0)}
        elif trace is not None:
            print("device_idle_by_host: no sched/ annotation or no device "
                  "line in the trace", file=sys.stderr)
        run["device_idle_by_host"] = found
    return run["device_idle_by_host"]


def read(run):
    state = run["metric"].split(".")[1]
    return _read_trace(run).get(state)
