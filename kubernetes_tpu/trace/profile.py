"""Per-phase wire-path profiling + XLA compile attribution.

The headline bench showed a 3x run-to-run swing on the wire path with
nothing attributing where the time goes (encode? TLV decode? bind
fan-out?). This module owns the phase vocabulary and the timers the
layers hang on their seams:

    encode    snapshot/batch encode (full or incremental wave view)
    probe     device predicate-probe dispatch (models/probe)
    score     the fused predicate+priority scan program (models/batch)
    replay    host/device replay + carry-fold commits (models/replay,
              models/zreplay, the packed apply)
    transfer  host<->device shipping (models/pack Packer.ship)
    wire      TLV watch-frame decode + response decode in the client
    bind      the async bind commit (wave bulk bind included)
    prepare   the scheduling loop before the algorithm: FIFO drains,
              duplicate filter, snapshot, gang plan (scheduler/core)
    assume    the scheduling loop after it: failure handling, the
              assume loop, the hand-over to the bind pool
    ingest    what a watch consumer does with decoded events before it
              asks for the next frame: store, FIFO, scheduler cache,
              the incremental snapshot's upkeep (runtime/binary)

and two IDLE states on the same timeline, entered through the same
timer, which are waiting and not work:

    queue_wait  the loop blocked in next_pod(): nothing to schedule
    gather      the wave-gather sleep (scheduler/core)

The three phases after ``bind`` rank below every phase that existed
before them, so they take only time that no earlier phase claimed, and
the idle states rank last of all. ``exclusive_totals()`` returns the
working phases, ``idle_totals()`` the idle states: phases + idle + the
time inside no timer is the window.

Timers observe into ``scheduler_wave_phase_seconds{phase=...}``; the
bench prints a per-rep breakdown by diffing ``phase_totals()`` around
the measurement window. Timers are gated on the trace switch
(KUBERNETES_TPU_TRACE): disabled, each is a no-op costing one global
read, which is what the <=5% overhead budget is measured against.

Whoever starts a jax profiler in this process (the daemon's
/debug/profile, the benchmark's traced run) also calls
``set_annotations(True)``: every timer then opens a
``jax.profiler.TraceAnnotation("sched/<phase>")`` as well, so that the
host phases lie in the host plane of the same .xplane.pb, on the same
clock, as the device's "XLA Ops" line. Off (the default) a timer pays
one more global read; the switch is no environment variable.

XLA compile time is attributed separately from execute time by routing
jax.monitoring's '/jax/core/compile/backend_compile_duration' events
into ``scheduler_xla_compile_seconds`` — the first jit call of a fresh
program shape shows up there instead of silently fattening whichever
phase it landed in.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List

from kubernetes_tpu.metrics import (
    scheduler_wave_phase_seconds,
    scheduler_xla_compile_seconds,
)
from kubernetes_tpu.trace import spans as _span

#: the closed phase vocabulary (the bench table iterates this order)
PHASES = ("encode", "probe", "score", "replay", "transfer", "wire", "bind",
          "prepare", "assume", "ingest")
#: waiting, not work: ranked after every phase, reported apart
IDLE_STATES = ("queue_wait", "gather")
_TIMELINE = PHASES + IDLE_STATES

#: jax.profiler.TraceAnnotation while annotations are on, else None
_ANNOTATION = None
# the phase open on this thread (what a compile is put down to)
_TLS = threading.local()


def set_annotations(on: bool) -> bool:
    """Open a TraceAnnotation("sched/<phase>") with every phase timer
    (and "sched/wave" per wave), for as long as a profiler runs.
    -> whether they were on before."""
    global _ANNOTATION
    was = _ANNOTATION is not None
    if on:
        import jax

        _ANNOTATION = jax.profiler.TraceAnnotation
    else:
        _ANNOTATION = None
    return was


def annotation(name: str):
    """``with annotation("sched/wave"): ...``: a TraceAnnotation while
    annotations are on, else the shared no-op."""
    cls = _ANNOTATION
    return _NULL if cls is None else cls(name)


class _ExclusiveAccountant:
    """Partition wall time across phases. Phase occurrences overlap
    freely across threads (16 bind-pool binds in flight while the next
    wave encodes while two watch readers decode), so summing
    per-occurrence wall overcounts wildly — the first bench table read
    344% of window wall. This accountant keeps ONE global timeline:
    every phase enter/exit advances it and attributes the elapsed slice
    to the highest-priority phase currently active (the PHASES order;
    bind last, so the wait-on-apiserver lane soaks up only what nothing
    else claims). Per-phase exclusive totals therefore sum to <= wall
    exactly, and the shortfall is genuine idle time."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rank = {p: i for i, p in enumerate(_TIMELINE)}
        self._depth = [0] * len(_TIMELINE)
        self._active = -1  # lowest active rank, -1 = nothing entered
        self._last = time.perf_counter()
        self._totals = [0.0] * len(_TIMELINE)

    def enter(self, phase: str) -> None:
        i = self._rank[phase]
        with self._lock:
            # the clock read MUST happen under the lock: a pre-lock
            # read raced against a contended writer produces a stale
            # timestamp, negative slices, and a _last that moves
            # backwards (double-attributing the same wall slice)
            now = time.perf_counter()
            if self._active >= 0:
                self._totals[self._active] += now - self._last
            self._last = now
            self._depth[i] += 1
            if self._active < 0 or i < self._active:
                self._active = i

    def exit(self, phase: str) -> None:
        i = self._rank[phase]
        with self._lock:
            now = time.perf_counter()
            if self._active >= 0:
                self._totals[self._active] += now - self._last
            self._last = now
            self._depth[i] -= 1
            if i == self._active:
                nxt = -1
                for j in range(i, len(self._depth)):
                    if self._depth[j]:
                        nxt = j
                        break
                self._active = nxt

    def snapshot(self) -> Dict[str, float]:
        """Seconds per phase and idle state, in _TIMELINE order."""
        with self._lock:
            now = time.perf_counter()
            if self._active >= 0:
                self._totals[self._active] += now - self._last
            self._last = now
            return dict(zip(_TIMELINE, self._totals))


_ACCOUNTANT = _ExclusiveAccountant()


class _PhaseTimer:
    __slots__ = ("_hist", "_phase", "_t0", "_outer", "_ann")

    def __init__(self, hist, phase):
        self._hist = hist
        self._phase = phase

    def __enter__(self) -> "_PhaseTimer":
        cls = _ANNOTATION
        if cls is None:
            self._ann = None
        else:
            self._ann = cls("sched/" + self._phase)
            self._ann.__enter__()
        self._outer = getattr(_TLS, "phase", None)
        _TLS.phase = self._phase
        _ACCOUNTANT.enter(self._phase)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._hist.observe(time.perf_counter() - self._t0)
        _ACCOUNTANT.exit(self._phase)
        _TLS.phase = self._outer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _NullTimer:
    __slots__ = ()

    def __enter__(self) -> "_NullTimer":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullTimer()

# child histograms resolved once (labels() takes a lock on first use)
_HIST = {p: scheduler_wave_phase_seconds.labels(p) for p in _TIMELINE}


def phase_timer(phase: str):
    """``with phase_timer("probe"): ...`` — observes wall seconds into
    the phase histogram (per-occurrence work) and the exclusive
    timeline (wall partition); no-op while tracing is disabled. Takes
    an idle state's name too."""
    if not _span._ENABLED:
        return _NULL
    return _PhaseTimer(_HIST[phase], phase)


def phase_totals() -> Dict[str, float]:
    """Cumulative per-occurrence seconds per phase since process start
    (histogram sums; zero-filled over the vocabulary so diffs are
    stable). Occurrences overlap across threads — for a partition of
    wall use exclusive_totals()."""
    sums = scheduler_wave_phase_seconds.sums()
    return {p: sums.get(p, 0.0) for p in PHASES}


def exclusive_totals() -> Dict[str, float]:
    """Cumulative EXCLUSIVE seconds per working phase (the
    single-timeline partition): diffs over a window sum to <= the
    window's wall, so the bench breakdown reads as 'where the wall
    went'. The shortfall is waiting (idle_totals()) plus the time
    inside no timer at all."""
    snap = _ACCOUNTANT.snapshot()
    return {p: snap[p] for p in PHASES}


def idle_totals() -> Dict[str, float]:
    """Cumulative EXCLUSIVE seconds per idle state, on the timeline of
    exclusive_totals(): waiting that no working phase overlapped. A
    window's phases + idle + the time inside no timer is the window."""
    snap = _ACCOUNTANT.snapshot()
    return {p: snap[p] for p in IDLE_STATES}


# -- the incremental encoder's totals -------------------------------------------

_encoder_lock = threading.Lock()
#: pod events the incremental encoder (snapshot/incremental.py) applied
#: in this process, the batches they came in, and the events inside a
#: batch that had to be applied one at a time (host ports, affinity, a
#: gone-node slot); served on /debug/traces as "encoder"
_ENCODER = {"events": 0, "batches": 0, "per_event_fallbacks": 0}


def count_encoder_batch(events: int, per_event_fallbacks: int) -> None:
    """One batch of `events` pod events went into the snapshot arrays."""
    with _encoder_lock:
        _ENCODER["events"] += events
        _ENCODER["batches"] += 1
        _ENCODER["per_event_fallbacks"] += per_event_fallbacks


def encoder_totals() -> Dict[str, int]:
    with _encoder_lock:
        return dict(_ENCODER)


#: lookups of a pending pod's encoded row by template in the incremental
#: encoder's store (snapshot/pending_rows.py): rows gathered, rows
#: encoded, whole-store rebuilds; served on /debug/traces as
#: "pending_rows"
_PENDING_ROWS = {"row_hits": 0, "row_misses": 0, "row_resets": 0}


def count_pending_rows(hits: int, misses: int, resets: int) -> None:
    with _encoder_lock:
        _PENDING_ROWS["row_hits"] += hits
        _PENDING_ROWS["row_misses"] += misses
        _PENDING_ROWS["row_resets"] += resets


def pending_row_totals() -> Dict[str, int]:
    with _encoder_lock:
        return dict(_PENDING_ROWS)


# -- the single-chip wave driver's totals -----------------------------------------

_wave_lock = threading.Lock()
#: what the wave driver (models/wave.WaveScheduler.schedule_backlog) did
#: in this process, all waves: pods decided by each path, device
#: programs launched by kind, pods that fitted nowhere, what the
#: grouped header probe did (models/wave.GROUP_COUNTERS) and what the
#: grouped device replay's loops ran (models/wave.ZREPLAY_COUNTERS);
#: served on /debug/traces as "wave"
_WAVE: Dict[str, Any] = {"waves": 0, "pods_by_path": {},
                         "dispatches_by_kind": {}, "pods_unplaced": 0,
                         "group_runs": 0, "group_d2h_bytes": 0,
                         "group_reprobes": 0, "zreplay_steps": 0,
                         "zreplay_slots": 0, "zreplay_picks": 0}


def count_wave(pods_by_path: Dict[str, int], dispatches: Dict[str, int],
               unplaced: int) -> None:
    """One wave is decided: `pods_by_path` pods went through each path,
    `dispatches` programs were launched by kind, `unplaced` pods
    fitted nowhere."""
    with _wave_lock:
        _WAVE["waves"] += 1
        _WAVE["pods_unplaced"] += unplaced
        for key, add in (("pods_by_path", pods_by_path),
                         ("dispatches_by_kind", dispatches)):
            tally = _WAVE[key]
            for k, n in add.items():
                tally[k] = tally.get(k, 0) + n


def count_wave_group(counted: Dict[str, int]) -> None:
    """A grouped header probe was replayed on the host: its runs, the
    bytes it fetched, whether it stopped early (`group_*` of _WAVE); or
    a grouped device replay came back: the steps and run slots its
    loops ran, the pods it placed (`zreplay_*`)."""
    with _wave_lock:
        for k, n in counted.items():
            _WAVE[k] += n


def wave_totals() -> Dict[str, Any]:
    with _wave_lock:
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in _WAVE.items()}


# -- XLA compile-vs-execute attribution ---------------------------------------

_install_lock = threading.Lock()
_installed = False
#: the last 64 programs built in this process, oldest first: which step
#: recompiled (served on /debug/traces as "compiles")
_COMPILES: deque = deque(maxlen=64)


def recent_compiles() -> List[dict]:
    """[{"program", "phase", "seconds", "cache", "at"}], oldest first.
    `program` is the name jax.monitoring passes (the jitted function's),
    `phase` the phase timer open on the compiling thread, `cache` says
    whether the persistent cache served it ("hit") or XLA built it."""
    return list(_COMPILES)


def install_compile_listener() -> None:
    """Idempotently subscribe to jax.monitoring compile-duration events.
    Safe without jax (or on versions without monitoring): the listener
    just never fires. Installed unconditionally of the trace switch —
    compile attribution is a metric, not a span, and events only fire
    on (rare) fresh-shape compiles."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
        try:
            from jax import monitoring
        except Exception:
            return

        def _on_event(event: str, **kw) -> None:
            # fires inside the compile it belongs to, on its thread
            if event.endswith("compilation_cache/cache_hits"):
                _TLS.cache_hit = True

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event.endswith("backend_compile_duration"):
                scheduler_xla_compile_seconds.observe(duration)
                phase = getattr(_TLS, "phase", None)
                hit = getattr(_TLS, "cache_hit", False)
                _TLS.cache_hit = False
                _COMPILES.append({
                    "program": str(kw.get("fun_name") or phase or ""),
                    "phase": phase, "seconds": duration,
                    "cache": "hit" if hit else "miss", "at": time.time(),
                })

        try:
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            pass
