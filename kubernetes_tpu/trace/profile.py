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

Beside the one timeline every timer keeps a ledger of its own THREAD
(``thread_totals()``): self wall seconds (a nested timer's time is taken
from the one that encloses it on that thread), self CPU seconds of the
thread and entries, under the role the thread's owner declared
(``thread_role``: loop / binder / informer, else "other"). The
timeline says which phase the process was in; the ledger says whose
time it was: a wave's period is set by the loop's thread alone, and on
that thread wall less CPU is time it was runnable and not running (the
interpreter lock, the OS). ``device_wait()`` marks a host read that
blocks on the device, on the reading thread's record under the key
"device_wait" (no phase: it overlays the phase it happens in) and, while
annotations are on, as ``sched/device_wait`` on the profiler's clock.
The ledger costs an entry two ``time.thread_time()`` reads, a system
call: 0.3 us each on a plain kernel, 5.8 us each on the sandboxed host
of the benchmark's chip, where the clock also ticks in 10 ms steps; at
the 30 to 250 entries a second the daemon makes that is under 0.4% of
a core (PERF.md section 6, PR 41).

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
from threading import get_ident
from time import perf_counter, thread_time
from typing import Any, Dict, List, Optional

import numpy as np

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

#: a host read that blocks on the device: a key of a thread's record
#: beside the phases and idle states, and no phase itself
DEVICE_WAIT = "device_wait"
#: what a thread is called until its owner says (`thread_role`)
OTHER = "other"


class _ThreadLedger:
    """One thread's own account: [wall s, cpu s, entries] per phase,
    idle state and DEVICE_WAIT. Only its thread writes it, so a timer
    takes no lock; the keys are all there from the start, so a reader
    on another thread never meets a dict that changes size."""

    __slots__ = ("role", "thread", "cells", "open")

    def __init__(self):
        self.role = OTHER
        self.thread = threading.current_thread()
        self.cells = {k: [0.0, 0.0, 0] for k in _TIMELINE + (DEVICE_WAIT,)}
        self.open = None  # the innermost _PhaseTimer open on the thread


_ledgers_lock = threading.Lock()
_LEDGERS: List[_ThreadLedger] = []
#: role -> key -> [wall, cpu, entries] of threads that have ended
_RETIRED: Dict[str, Dict[str, list]] = {}


def _add_cells(into: Dict[str, Dict[str, list]], rec: _ThreadLedger) -> None:
    mine = into.setdefault(
        rec.role, {k: [0.0, 0.0, 0] for k in rec.cells})
    for key, cell in rec.cells.items():
        wall, cpu, count = cell
        acc = mine[key]
        acc[0] += wall
        acc[1] += cpu
        acc[2] += count


def _ledger() -> _ThreadLedger:
    """This thread's record, registered once; the records of threads
    that have ended are folded into _RETIRED on the way, so a process
    that starts threads all its life keeps a bounded list."""
    try:
        return _TLS.ledger
    except AttributeError:
        pass
    rec = _TLS.ledger = _ThreadLedger()
    with _ledgers_lock:
        gone = [r for r in _LEDGERS if not r.thread.is_alive()]
        for r in gone:
            _add_cells(_RETIRED, r)
            _LEDGERS.remove(r)
        _LEDGERS.append(rec)
    return rec


def thread_role(role: str) -> None:
    """Said once by the code that owns the calling thread, at the top
    of its function: the role its time is booked under in
    thread_totals(). A thread nobody spoke for is "other"."""
    _ledger().role = role


def thread_totals() -> Dict[str, Dict[str, Dict[str, float]]]:
    """{role: {phase | idle state | "device_wait": {"wall", "cpu",
    "count"}}}, cumulative like exclusive_totals(): each thread's SELF
    seconds inside its own timers, summed over the threads of a role.
    Unlike the timeline's these overlap across threads and add up to
    more than the wall; within one thread they add up to at most its
    wall. "device_wait" lies inside the phase it was read in and is not
    taken from it."""
    with _ledgers_lock:
        out = {role: {k: list(c) for k, c in cells.items()}
               for role, cells in _RETIRED.items()}
        for rec in _LEDGERS:
            _add_cells(out, rec)
    return {role: {k: {"wall": c[0], "cpu": c[1], "count": c[2]}
                   for k, c in cells.items()}
            for role, cells in out.items()}


class _PhaseTimer:
    __slots__ = ("_hist", "_phase", "_t0", "_c0", "_ann", "_rec", "_up",
                 "_inner_wall", "_inner_cpu")

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
        rec = self._rec = _ledger()
        self._up = rec.open
        rec.open = self
        self._inner_wall = self._inner_cpu = 0.0
        _TLS.phase = self._phase
        _ACCOUNTANT.enter(self._phase)
        self._c0 = thread_time()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        wall = perf_counter() - self._t0
        cpu = thread_time() - self._c0
        self._hist.observe(wall)
        _ACCOUNTANT.exit(self._phase)
        up = self._up
        _TLS.phase = None if up is None else up._phase
        rec = self._rec
        # a timer held open across a generator's yield can be closed by
        # whoever drops the generator: another thread's CPU clock says
        # nothing of this one's, so such an exit is not booked
        if rec.open is self and rec.thread.ident == get_ident():
            rec.open = up
            cell = rec.cells[self._phase]
            cell[0] += wall - self._inner_wall
            cell[1] += cpu - self._inner_cpu
            cell[2] += 1
            if up is not None:
                up._inner_wall += wall
                up._inner_cpu += cpu
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _DeviceWait:
    __slots__ = ("_t0", "_c0", "_ann")

    def __enter__(self) -> "_DeviceWait":
        cls = _ANNOTATION
        if cls is None:
            self._ann = None
        else:
            self._ann = cls("sched/" + DEVICE_WAIT)
            self._ann.__enter__()
        self._c0 = thread_time()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        wall = perf_counter() - self._t0
        cpu = thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        cell = _ledger().cells[DEVICE_WAIT]
        cell[0] += wall
        cell[1] += cpu
        cell[2] += 1
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


def device_wait():
    """``with device_wait(): picks = np.asarray(chosen)`` round a host
    read that waits for the device: booked on the reading thread's
    record as DEVICE_WAIT (thread_totals()), and while annotations are
    on a TraceAnnotation("sched/device_wait") that ends when the host
    has the value, on the clock of the device's "XLA Modules" line. An
    enqueue gets none. No-op while tracing is disabled."""
    if not _span._ENABLED:
        return _NULL
    return _DeviceWait()


def fetch(x) -> np.ndarray:
    """np.asarray(x) of a device array, as a device_wait()."""
    with device_wait():
        return np.asarray(x)


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
#: batch that had to be applied one at a time (host ports, a gone-node
#: slot); served on /debug/traces as "encoder"
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
#: grouped device replay's loops ran (models/wave.ZREPLAY_COUNTERS), the
#: steps the scan's loop ran and its buckets hold
#: (models/wave.SCAN_COUNTERS), what the runs with a self-anti veto did
#: (models/wave.ANTI_COUNTERS), which
#: encoder made each wave's snapshot, which scope gate sent it to the
#: from-scratch one, and how often the incremental one rebuilt its
#: inter-pod tables whole, by reason; served on /debug/traces as "wave"
_WAVE: Dict[str, Any] = {"waves": 0, "pods_by_path": {},
                         "dispatches_by_kind": {}, "pods_unplaced": 0,
                         # models/wave.LOOP_COUNTERS: the wave loop's
                         # steps by kind, its flushes of the scan
                         "steps_by_kind": {}, "scan_flushes": 0,
                         "group_runs": 0, "group_d2h_bytes": 0,
                         "group_reprobes": 0, "zreplay_steps": 0,
                         "zreplay_slots": 0, "zreplay_rescores": 0,
                         "zreplay_picks": 0, "scan_steps": 0,
                         "scan_bucket_steps": 0, "anti_runs": 0,
                         "anti_picks": 0, "anti_nodes_excluded": 0,
                         "waves_by_encoder": {}, "encoder_fallbacks": {},
                         "interpod_rebuilds": {},
                         # models/wave.count_runs: why runs went to the
                         # scan, and the runs that own a required
                         # podAffinity term; scheduler/tpu_algorithm's
                         # re-warm of the scan at new inter-pod widths
                         "scan_reasons": {}, "affinity_runs": 0,
                         "affinity_nodes_excluded": 0, "rewarms": 0,
                         "rewarm_seconds": 0.0, "rewarm_programs": 0,
                         "rewarm_mismatches": 0}


def count_wave(pods_by_path: Dict[str, int], dispatches: Dict[str, int],
               unplaced: int, steps_by_kind: Dict[str, int]) -> None:
    """One wave is decided: `pods_by_path` pods went through each path,
    `dispatches` programs were launched by kind, `unplaced` pods
    fitted nowhere, and the loop ran `steps_by_kind` steps."""
    with _wave_lock:
        _WAVE["waves"] += 1
        _WAVE["pods_unplaced"] += unplaced
        for key, add in (("pods_by_path", pods_by_path),
                         ("dispatches_by_kind", dispatches),
                         ("steps_by_kind", steps_by_kind)):
            tally = _WAVE[key]
            for k, n in add.items():
                tally[k] = tally.get(k, 0) + n


def count_wave_group(counted: Dict[str, int]) -> None:
    """A grouped header probe was replayed on the host: its runs, the
    bytes it fetched, whether it stopped early (`group_*` of _WAVE); or
    a grouped device replay came back: the steps and run slots its
    loops ran, the steps that rescored, the pods it placed
    (`zreplay_*`); or a scan came back: the steps its loop ran and its
    pod bucket holds (`scan_*`); or the loop handed pods to the scan
    (`scan_flushes`); or a run with a self-anti veto was
    decided (`anti_*`); or a wave held runs that own a required podAffinity
    term (`affinity_*`); or the daemon warmed the scan again
    (`rewarm*`)."""
    with _wave_lock:
        for k, n in counted.items():
            _WAVE[k] += n


def count_wave_reasons(reasons: Dict[str, int]) -> None:
    """A wave sent runs of `min_run` pods and more to the scan:
    `reasons` pods by why (models/wave.SCAN_REASONS)."""
    with _wave_lock:
        tally = _WAVE["scan_reasons"]
        for reason, n in reasons.items():
            tally[reason] = tally.get(reason, 0) + n


def count_wave_encoder(encoder: str, fallback: Optional[str],
                       rebuilds: Optional[Dict[str, int]] = None) -> None:
    """A wave's snapshot came from `encoder` ("incremental" or "full"),
    sent there by the incremental encoder's scope gate `fallback`, if
    by any; `rebuilds` whole rebuilds of its inter-pod tables came
    before it, by reason."""
    with _wave_lock:
        for key, k in (("waves_by_encoder", encoder),
                       ("encoder_fallbacks", fallback)):
            if k:
                _WAVE[key][k] = _WAVE[key].get(k, 0) + 1
        tally = _WAVE["interpod_rebuilds"]
        for reason, n in (rebuilds or {}).items():
            tally[reason] = tally.get(reason, 0) + n


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


#: programs built in this process since the listener went in (each a
#: `backend_compile_duration` event, served from the persistent cache
#: or not): a difference of two reads says what a stretch built
_COMPILE_COUNT = [0]


def compile_count() -> int:
    return _COMPILE_COUNT[0]


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
                _COMPILE_COUNT[0] += 1
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
