"""From a profiler trace to device numbers: busy time, program
launches, the operations that took most time, the longest idle gaps.

The trace is the plain form `load_xplane` makes of jax.profiler's
.xplane.pb: {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}. The reduction works on that
form alone, so a small recorded trace checks it (tests/benchmark).

A TPU's plane is "/device:TPU:<n>". Its "XLA Ops" line holds one event
per operation executed and its "XLA Modules" line one per program
launched. Busy time is the union of the operation intervals: an
operation nested in another (a fusion inside a while loop) counts once.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, keep=lambda plane: True) -> dict:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not keep(plane.name):
            continue
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device(plane_name: str) -> bool:
    return plane_name.startswith(DEVICE_PREFIX)


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def short(name: str) -> str:
    """The trace names an operation by its whole HLO line and a program
    by its name and fingerprint: keep `fusion.320` and `jit_run`."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def union(events) -> list:
    """Sorted, merged [start, end] intervals of the events."""
    merged = []
    for start, end in sorted((s, s + d) for _n, s, d in events):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def reduce(trace: dict, top: int = 10) -> dict:
    """-> {"chips", "busy_s" (mean over the chips that ran anything),
    "launches" (programs started, all chips), "device_ops",
    "idle_gaps"}. A trace in which no device ran an operation gives
    busy_s 0.0 and chips 0."""
    busy, launches = [], 0
    by_op: dict = {}
    gaps = []
    for plane in trace["planes"]:
        if not is_device(plane["name"]):
            continue
        ops = _line(plane, OPS_LINE)
        modules = sorted(_line(plane, MODULES_LINE), key=lambda e: e[1])
        launches += len(modules)
        if not ops:
            continue
        merged = union(ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, _s, dur in ops:
            by_op[short(name)] = by_op.get(short(name), 0) + dur
        # a gap is named for the program that ended it
        starts = [m[1] for m in modules]
        k = 0
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            while k < len(starts) and starts[k] + modules[k][2] < s1:
                k += 1
            nxt = short(modules[k][0]) if k < len(modules) \
                else "end-of-trace"
            gaps.append([f"unattributed;before:{nxt}", (s1 - e0) / 1e9])
    # nested operations each carry their whole duration: the table says
    # which names the time sits under, and its rows overlap
    device_ops = sorted(([n, d / 1e9] for n, d in by_op.items()),
                        key=lambda r: -r[1])[:top]
    gaps.sort(key=lambda r: -r[1])
    return {
        "chips": len(busy),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "launches": launches,
        "device_ops": device_ops,
        "idle_gaps": gaps[:top],
    }


def describe(trace: dict) -> list:
    """Plane and line names with event counts: what a human looks at
    before trusting the reduction on a new runtime."""
    return [[p["name"], [[ln["name"], len(ln["events"])]
                         for ln in p["lines"]]] for p in trace["planes"]]
