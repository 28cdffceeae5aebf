"""The wave traces of the window, from the daemon's span ring
(trace/spans.BUFFER): one `scheduler.wave` root a wave with the stage
children wave.gather / .prepare / .algorithm / .assume / .bind
(scheduler/core._WaveTrace). Taken at the window's end, since the check
batch and the drain run before a reader's `read`."""

import sys
import time

_window_began = None


def snapshot(ctx):
    """None at the window's start; at its end {trace id: {"pods",
    stage: (start, duration), ...}} for the waves that began in it, or
    None where the ring no longer holds the window (said on stderr) or
    the program records no wave traces."""
    global _window_began
    if "waves" in ctx:  # one pass serves every reader of this snapshot
        return ctx["waves"]
    from kubernetes_tpu.trace import spans

    ctx["waves"] = None
    if _window_began is None or not hasattr(spans.BUFFER, "since"):
        _window_began = time.time()
        return None
    kept = spans.BUFFER.since(_window_began)
    if kept is None:
        print("wave spans: the ring's oldest span is younger than the "
              "window's start; nothing read", file=sys.stderr)
        return None
    waves: dict = {}
    for s in kept:
        if s["name"] == "scheduler.wave":
            waves.setdefault(s["trace_id"], {})["pods"] = \
                s.get("attrs", {}).get("pods", 0)
        elif s["name"].startswith("wave."):
            waves.setdefault(s["trace_id"], {})[s["name"][5:]] = (
                s["start"], s["duration"])
    ctx["waves"] = {k: w for k, w in waves.items() if w.get("pods")}
    return ctx["waves"]


def weighted_median_ms(run, reader, value):
    """The pod-weighted median, in ms, of value(wave) in seconds over
    the window's waves; waves for which it is None do not count."""
    waves = run["snapshots"][reader][1]
    if not waves:
        return None
    pairs = sorted((v, w["pods"]) for w in waves.values()
                   for v in [value(w)] if v is not None)
    half = sum(p for _v, p in pairs) / 2.0
    seen = 0
    for v, pods in pairs:
        seen += pods
        if seen >= half:
            return 1000.0 * v
    return None
