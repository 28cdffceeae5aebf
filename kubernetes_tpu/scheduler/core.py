"""The scheduler control loop.

Reference: plugin/pkg/scheduler/scheduler.go (Config:50, Run:89 =
wait.Until(scheduleOne, 0), scheduleOne:93: pop -> Schedule -> AssumePod
-> async bind) and generic_scheduler.go:72 Schedule with the extender
chain (:166-177, :276-298).

TPU-first deviation (by design, not accident): when the algorithm
supports backlog scheduling (the TPU batch program), scheduleOne drains
every pod already waiting in the FIFO and schedules the whole wave in
one device program — sequential-equivalent by construction (the scan
threads resource commitments), so the decisions match the reference's
one-at-a-time loop while amortizing snapshot + dispatch cost.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.metrics import (
    scheduler_algorithm_latency,
    scheduler_binding_latency,
    scheduler_e2e_latency,
    scheduler_pod_queue_wait_seconds,
)
from kubernetes_tpu.oracle.scheduler import (
    FitError,
    GenericScheduler,
    prioritize_nodes,
    select_host,
)
from kubernetes_tpu.oracle.state import ClusterState
from kubernetes_tpu.snapshot.encode import pod_feature_key
from kubernetes_tpu.trace import profile as trace_profile
from kubernetes_tpu.trace import spans as trace_span
from kubernetes_tpu.utils.clock import DEFAULT_CLOCK
from kubernetes_tpu.utils.trace import Trace

log = logging.getLogger(__name__)


class ExtendedGenericScheduler(GenericScheduler):
    """GenericScheduler + the HTTP extender chain."""

    def __init__(self, predicates, priorities, extenders=()):
        super().__init__(predicates=predicates, priorities=priorities)
        self.extenders = list(extenders)

    def schedule(self, pod: Pod, state: ClusterState) -> str:
        trace = Trace(f"Scheduling {pod.metadata.namespace}/{pod.metadata.name}")
        if not state.node_infos:
            raise FitError(pod, {})
        trace.step("Computing predicates")
        fits, failed = self.find_nodes_that_fit(pod, state)
        # extender Filter chain (generic_scheduler.go:166-177)
        for ext in self.extenders:
            if not fits:
                break
            nodes = [state.node_infos[n].node for n in fits]
            kept, ext_failed = ext.filter(pod, nodes)
            fits = [n.metadata.name for n in kept]
            failed.update(ext_failed)
        if not fits:
            raise FitError(pod, failed)
        trace.step("Prioritizing")
        priority_list = prioritize_nodes(pod, state, self.priorities, fits)
        # extender Prioritize fan-in (generic_scheduler.go:276-298)
        if self.extenders:
            combined = dict(priority_list)
            for ext in self.extenders:
                nodes = [state.node_infos[n].node for n in fits]
                for host, score in ext.prioritize(pod, nodes):
                    if host in combined:
                        combined[host] += score * ext.weight
            priority_list = [(n, combined[n]) for n in fits]
        trace.step("Selecting host")
        host = select_host(priority_list, self.last_node_index)
        self.last_node_index += 1
        # the reference logs cycles >20ms (generic_scheduler.go:79)
        trace.log_if_long(0.02)
        return host


#: SchedulerConfig's defaults for max_batch and wave_floor, which say
#: why; the warmup compiles every pod bucket up to WAVE_CAP
WAVE_CAP = 4096
WAVE_FLOOR = 1024


@dataclass
class SchedulerConfig:
    """scheduler.go:50 Config — the dependency set scheduleOne needs."""

    scheduler_cache: object = None  # SchedulerCache
    algorithm: object = None  # .schedule(pod, state) / .schedule_backlog
    binder: Callable[[Pod, str], None] = None
    pod_condition_updater: Callable[[Pod, str, str], None] = None
    # batch form: [(pod, status, reason)] in one API request (wave
    # failure paths must stay O(1) requests in backlog size)
    pod_condition_updater_many: Callable = None
    next_pod: Callable[[], Pod] = None
    # pop up to this many additional waiting pods per cycle (0 = strictly
    # serial, reference-identical pacing)
    drain_waiting: Callable[[int], List[Pod]] = None
    # pods -> the time.monotonic() stamp each got where it entered the
    # queue (NaN where it has none), taken out of the queue's side
    # table: what the wave's queue-wait histogram is reduced from.
    # None = no stamps (and with tracing off the factory keeps none).
    queue_stamps: Callable[[List[Pod]], object] = None
    # wave cap: with power-of-two bucketing in the TPU algorithm this also
    # bounds the set of compiled program shapes — each fresh shape costs a
    # full XLA compile. Runs of identical pods bypass
    # the scan entirely (models/wave.py), so large waves are cheap for
    # template-created backlogs. 4096 measured ~1.5x faster than 8192
    # end-to-end on the 30k-pod density run: smaller waves pipeline
    # better against the async bulk binds and watch ingest (decisions
    # are sequential-equivalent regardless of the cap).
    max_batch: int = WAVE_CAP
    # Burst-adaptive wave gathering: when a drain catches a burst
    # mid-arrival (extra pods were already waiting) but the wave is
    # still under this floor, the driver briefly waits for the queue to
    # fill before dispatching — per-wave fixed cost (state encode +
    # device dispatch) amortizes over 10-100x more pods. Decisions are
    # sequential-equivalent regardless of wave boundaries, so gathering
    # changes pacing, never placement. An idle-arrival singleton skips
    # the wait entirely (zero added latency when there is no burst).
    # 0 disables gathering.
    wave_floor: int = WAVE_FLOOR
    # minimum gather window; the driver scales it adaptively up to
    # wave_gather_max by the PREVIOUS wave's measured wall cost, so
    # cheap waves dispatch almost immediately while expensive waves
    # (big clusters, cold caches) wait long enough for the arrival
    # stream to amortize their fixed cost
    wave_gather_seconds: float = 0.02
    wave_gather_max: float = 1.0
    # bulk binder for wave commits: one API request per wave instead of a
    # per-pod round-trip flood (the per-pod shell was the daemon's
    # throughput ceiling); None falls back to per-pod binder
    binder_many: Callable = None
    # schedulable-node filter (factory.go:412 getNodeConditionPredicate
    # applied through the NodeLister, generic_scheduler.go:81)
    node_lister: object = None
    error: Callable[[Pod, Exception], None] = None
    recorder: object = None  # EventRecorder
    # gang workload semantics (scheduler/gang.GangDirector): wave
    # planning for PodGroups — all-or-nothing parking, priority
    # ordering, preemption, throughput-aware placement scores. None =
    # plain reference behavior (the default profile; waves without
    # gang-labeled pods are untouched either way).
    gang_director: object = None
    snapshot_extras: Callable[[], dict] = None  # listers for ClusterState
    stop_everything: threading.Event = field(default_factory=threading.Event)


class _LazyState:
    """Builds the ClusterState on first attribute access."""

    def __init__(self, build):
        object.__setattr__(self, "_build_fn", build)
        object.__setattr__(self, "_built", None)

    def _real(self) -> ClusterState:
        if self._built is None:
            object.__setattr__(self, "_built", self._build_fn())
        return self._built

    def __getattr__(self, name):
        return getattr(self._real(), name)


class _AssignedOnce:
    """A state read by one pass of the host predicates, with the list
    of every assigned pod built once: the inter-pod affinity predicate
    asks for it at every node it reaches, a walk over the whole cluster
    per node."""

    def __init__(self, state):
        self._state = state
        self._assigned = None

    def all_assigned_pods(self):
        if self._assigned is None:
            self._assigned = self._state.all_assigned_pods()
        return self._assigned

    def __getattr__(self, name):
        return getattr(self._state, name)


class _WaveTrace:
    """One trace per wave in the span ring (trace/spans.BUFFER): the
    root `scheduler.wave` and its stage children `wave.gather` (first
    pop to wave start), `wave.prepare` (duplicate filter, snapshot,
    gang plan), `wave.algorithm` and `wave.assume`, recorded together
    when the cycle ends, and `wave.bind` (submitted to the pool ->
    acknowledged), which the pool thread records under the same ids.
    The stages are consecutive: a mark ends one and begins the next."""

    __slots__ = ("trace_id", "span_id", "marks", "attrs")
    STAGES = ("wave.gather", "wave.prepare", "wave.algorithm",
              "wave.assume")

    def __init__(self):
        self.trace_id = trace_span.new_trace_id()
        self.span_id = trace_span.new_span_id()
        self.marks = [time.time()]
        self.attrs: dict = {}

    def mark(self) -> None:
        self.marks.append(time.time())

    def finish(self) -> None:
        """Record the wave and the stages it reached (an early return
        closes the stage it was in)."""
        self.mark()
        for name, t0, t1 in zip(self.STAGES, self.marks, self.marks[1:]):
            trace_span.record_span(name, self.trace_id, t0, t1,
                                   parent_id=self.span_id)
        trace_span.record_span(
            "scheduler.wave", self.trace_id, self.marks[0], self.marks[-1],
            span_id=self.span_id, **self.attrs)

    def queue_wait(self, stamps, now: float) -> None:
        """The wave's pods' waits in the queue, from their stamps, in
        one pass: into the histogram and min/median/max on the span."""
        import numpy as np

        waits = now - stamps
        waits = waits[~np.isnan(waits)]
        if waits.size:
            scheduler_pod_queue_wait_seconds.observe_many(waits)
            self.attrs.update(
                queue_wait_min=float(waits.min()),
                queue_wait_median=float(np.median(waits)),
                queue_wait_max=float(waits.max()))


class Scheduler:
    """scheduler.go Scheduler."""

    def __init__(self, config: SchedulerConfig):
        self.config = config
        # bounded bind pool: the reference spawns a goroutine per bind
        # (scheduler.go:124); Python threads are ~3 orders costlier, so a
        # reused pool keeps wave-sized bind floods cheap
        from concurrent.futures import ThreadPoolExecutor

        self._bind_pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="bind",
            initializer=trace_profile.thread_role, initargs=("binder",),
        )
        # previous wave's algorithm wall seconds — the adaptive
        # wave-gather window scales off it
        self._last_wave_secs = 0.0
        gd = config.gang_director
        if gd is not None and getattr(gd, "recorder", None) is None:
            # the recorder is assigned on the config after factory
            # assembly; hand it to the director for Preempted events
            gd.recorder = config.recorder

    def run(self) -> threading.Thread:
        """scheduler.go:89 Run — the loop in a daemon thread."""
        thread = threading.Thread(
            target=self._loop, name="scheduler", daemon=True
        )
        thread.start()
        return thread

    def stop(self) -> None:
        self.config.stop_everything.set()
        self._bind_pool.shutdown(wait=False)

    def _loop(self) -> None:
        # the thread whose steps, one after the other, are a wave's
        # period: its own ledger in trace/profile.thread_totals()
        trace_profile.thread_role("loop")
        while not self.config.stop_everything.is_set():
            try:
                self.schedule_one()
            except StopIteration:
                return
            except Exception:
                log.exception("scheduleOne failed")

    # -- one cycle -----------------------------------------------------------

    def _snapshot(self) -> ClusterState:
        """Deferred: the TPU wave path schedules off the incrementally
        maintained snapshot (snapshot/incremental.py) and never touches
        this ClusterState, so the O(cluster) cache clone only happens
        when something actually reads it (oracle path, fallback encode,
        failure explanation)."""
        return _LazyState(self._build_snapshot)

    def _build_snapshot(self) -> ClusterState:
        extras = self.config.snapshot_extras() if self.config.snapshot_extras else {}
        state = self.config.scheduler_cache.snapshot(**extras)
        if self.config.node_lister is None:
            return state
        # restrict candidate nodes to the lister's schedulable set; the
        # full state stays reachable for assigned-pod topology lookups
        # (oracle._restrict_state semantics)
        allowed = {
            n.metadata.name for n in self.config.node_lister.list()
        }
        sub = ClusterState(
            services=state.services,
            controllers=state.controllers,
            replica_sets=state.replica_sets,
            pvs=state.pvs,
            pvcs=state.pvcs,
        )
        sub.node_infos = {
            name: info
            for name, info in state.node_infos.items()
            if name in allowed and info.node is not None
        }
        sub.full = state
        return sub

    def schedule_one(self) -> None:
        """scheduler.go:93 scheduleOne (+ the TPU wave extension)."""
        with trace_profile.phase_timer("queue_wait"):
            pod = self.config.next_pod()
        if pod is None:
            raise StopIteration
        wt = _WaveTrace() if trace_span.enabled() else None
        try:
            with trace_profile.annotation("sched/wave"):
                self._schedule_wave_from(pod, wt)
        finally:
            if wt is not None:
                wt.finish()

    def _gather_wave(self, pod: Pod) -> List[Pod]:
        """The wave: the popped pod, whatever else waits, and (a burst
        in flight) what a short wait adds. Draining is `prepare` work;
        the sleep is the idle state `gather`."""
        cfg = self.config
        wave: List[Pod] = [pod]
        if cfg.drain_waiting is None or not hasattr(
            cfg.algorithm, "schedule_backlog"
        ):
            return wave
        with trace_profile.phase_timer("prepare"):
            wave += cfg.drain_waiting(cfg.max_batch - 1)
        floor = min(cfg.wave_floor, cfg.max_batch)
        if 1 < len(wave) < floor and cfg.wave_gather_seconds > 0:
            # burst in flight (the drain caught extra pods): give
            # arrivals a moment to fill the wave so the per-wave
            # fixed cost amortizes. The window scales with the
            # previous wave's measured cost — a 100 ms wave is
            # worth waiting ~2x that to fill, a 5 ms wave is not.
            # Two consecutive empty probes = the burst ended;
            # dispatch what we have. Idle singletons never reach
            # here — no added latency when nothing is arriving.
            window = min(
                max(2.0 * self._last_wave_secs,
                    cfg.wave_gather_seconds),
                cfg.wave_gather_max,
            )
            deadline = time.monotonic() + window
            idle_probes = 0
            while len(wave) < floor and time.monotonic() < deadline:
                with trace_profile.phase_timer("gather"):
                    time.sleep(0.005)
                with trace_profile.phase_timer("prepare"):
                    more = cfg.drain_waiting(cfg.max_batch - len(wave))
                if more:
                    wave += more
                    idle_probes = 0
                else:
                    idle_probes += 1
                    if idle_probes >= 2:
                        break
        return wave

    def _schedule_wave_from(self, pod: Pod,
                            wt: Optional[_WaveTrace]) -> None:
        cfg = self.config
        wave = self._gather_wave(pod)
        if wt is not None:
            wt.mark()  # wave start: gather ends, prepare begins
            if cfg.queue_stamps is not None:
                stamps = cfg.queue_stamps(wave)
                if stamps is not None:
                    wt.queue_wait(stamps, time.monotonic())
        with trace_profile.phase_timer("prepare"):
            cache = cfg.scheduler_cache
            if cache is not None and hasattr(cache, "pod_keys"):
                # duplicate watch deliveries (relist after a broken pipe)
                # re-enqueue pods already decided; scheduling them again
                # would phantom-commit capacity inside the wave. One
                # locked key-set copy, not one lock round-trip per pod.
                known = cache.pod_keys()
                fresh = [
                    p for p in wave
                    if f"{p.metadata.namespace}/{p.metadata.name}"
                    not in known
                ]
                if len(fresh) != len(wave):
                    log.debug(
                        "dropped %d duplicate-delivery pods from the wave",
                        len(wave) - len(fresh),
                    )
                    wave = fresh
                if not wave:
                    return
                pod = wave[0]  # the popped pod itself may have been dropped
            start = DEFAULT_CLOCK.now()
            wall_start = time.time() if wt is not None else 0.0
            state = self._snapshot()
            gang_layout: List[dict] = []
            if cfg.gang_director is not None:
                # gang planning: park minMember-short gangs before they
                # touch the backlog, order [singletons | gangs by priority]
                # with members contiguous, attach throughput score rows.
                # Waves without gang-labeled pods come back untouched.
                wave, gang_layout, pre_parked = \
                    cfg.gang_director.plan_wave(wave, state)
                if pre_parked:
                    self._handle_failures(pre_parked, reason="GangParked")
                if not wave:
                    return
                pod = wave[0]
        if wt is not None:
            wt.attrs["pods"] = len(wave)
            wt.mark()  # prepare ends, algorithm begins
        try:
            if len(wave) == 1 and not gang_layout:
                hosts: List[Optional[str]] = [
                    cfg.algorithm.schedule(wave[0], state)
                ]
                errors: Dict[int, Exception] = {}
            else:
                hosts, errors = self._schedule_wave(
                    wave, state, gangs=gang_layout or None)
        except Exception as e:
            # histograms are microsecond-unit like the reference's
            # (metrics.go ExponentialBuckets(1000, 2, 15) over us)
            scheduler_algorithm_latency.observe(
                (DEFAULT_CLOCK.now() - start) * 1e6
            )
            if wt is not None:
                wt.attrs["error"] = type(e).__name__
            self._handle_failure(pod, e)
            return
        self._last_wave_secs = DEFAULT_CLOCK.now() - start
        scheduler_algorithm_latency.observe(
            self._last_wave_secs * 1e6
        )
        if wt is not None:
            wt.mark()  # algorithm ends, assume begins
        with trace_profile.phase_timer("assume"):
            if cfg.gang_director is not None and gang_layout:
                # all-or-nothing enforcement over the returned hosts (the
                # wave driver already discarded eligible-run partials;
                # this also covers scan/mesh fallbacks) + preemption
                # planning for parked gangs with priority
                hosts, gang_errors = cfg.gang_director.after_wave(
                    wave, list(hosts), gang_layout, state)
                errors.update(gang_errors)
            if wt is not None:
                # attribute the wave's algorithm window to every traced
                # pod's own trace (one wall-clock read, per-pod dict gets)
                wall_end = time.time()
                for p, host in zip(wave, hosts):
                    tid = trace_span.extract(p)
                    if tid:
                        trace_span.record_span(
                            "scheduler.schedule", tid, wall_start, wall_end,
                            pod=f"{p.metadata.namespace}/{p.metadata.name}",
                            node=host or "", wave=len(wave),
                        )

            successes: List[Tuple[Pod, str]] = []
            failures: List[Tuple[Pod, Exception]] = []
            for i, (p, host) in enumerate(zip(wave, hosts)):
                if host is None:
                    failures.append((p, errors.get(i) or FitError(p, {})))
                    continue
                successes.append((p, host))
            self._handle_failures(failures)
            if successes:
                self._assume_and_bind_wave(successes, start, wt)

    def _handle_failures(
        self, failed: List[Tuple[Pod, Exception]],
        reason: str = "FailedScheduling",
    ) -> None:
        """Wave-failure handling with O(1) apiserver requests: the
        PodScheduled=False condition updates for the whole wave go out
        as ONE batch request (one PATCH per pod otherwise — O(backlog)
        requests the moment a cluster fills up); events and re-queues
        stay per-pod."""
        if not failed:
            return
        cfg = self.config
        # indexes still needing the per-pod condition update: everything
        # by default; the batch removes the items it committed. A batch
        # that raises (connection drop, 403) or returns per-item
        # failures must NOT silently lose those pods' updates — they
        # fall back to the per-pod updater, like the pre-batch path.
        unbatched = set(range(len(failed)))
        if cfg.pod_condition_updater_many is not None and len(failed) > 1:
            try:
                res = cfg.pod_condition_updater_many(
                    [(p, "False", "Unschedulable") for p, _ in failed]
                )
                for i, r in enumerate(res[:len(failed)]):
                    if isinstance(r, dict) and r.get("status") == "Success":
                        unbatched.discard(i)
            except Exception:
                log.debug("bulk condition update failed", exc_info=True)
        for i, (p, err) in enumerate(failed):
            self._handle_failure(p, err, reason=reason,
                                 update_condition=i in unbatched)

    def _schedule_wave(
        self, wave: Sequence[Pod], state: ClusterState, gangs=None
    ) -> Tuple[List[Optional[str]], Dict[int, Exception]]:
        if gangs:
            try:
                hosts = self.config.algorithm.schedule_backlog(
                    wave, state, gangs=gangs)
            except TypeError:
                # algorithm without gang support (oracle/extender
                # shells): schedule plainly; the director's post-hoc
                # all-or-nothing check still guards the binds
                hosts = self.config.algorithm.schedule_backlog(wave,
                                                               state)
        else:
            hosts = self.config.algorithm.schedule_backlog(wave, state)
        errors: Dict[int, Exception] = {}
        explained: Dict[tuple, Exception] = {}
        for i, (p, h) in enumerate(zip(wave, hosts)):
            if h is None:
                errors[i] = self._explain_failure(p, state, explained)
        return list(hosts), errors

    def _explain_failure(
        self, pod: Pod, state: ClusterState,
        explained: Dict[tuple, Exception],
    ) -> Exception:
        """Recover per-node failure reasons for an unschedulable pod by
        running the host predicates (the device program reports
        fit/no-fit only). Once per template and wave: the predicates
        read of the pending pod only what its feature key holds, and
        every failure of a wave is explained on the same state, so
        `explained` (feature key -> the first such pod's error) serves
        a template's other pods. A pass walks every node, and a full
        cluster fails a template's pods by the thousand: a pass per pod
        held the loop for minutes, the informers behind it, and the
        deletes that would have made room were never seen."""
        key = pod_feature_key(pod)
        first = explained.get(key)
        if first is None:
            try:
                _, failed = GenericScheduler().find_nodes_that_fit(
                    pod, _AssignedOnce(state))
                first = FitError(pod, failed)
            except Exception as e:  # pragma: no cover
                first = e
            explained[key] = first
            return first
        if isinstance(first, FitError):
            return FitError(pod, first.failed_predicates, first.detail)
        return first

    def _assume_and_bind_wave(
        self, pairs: List[Tuple[Pod, str]], cycle_start: float,
        wt: Optional[_WaveTrace] = None,
    ) -> None:
        """Wave commit (scheduler.go:112-152 AssumePod + async bind, wave
        form): assume every pod, then bind — ONE bulk request when the
        binder supports it, else per-pod. Per-pod semantics hold: each
        item succeeds or fails independently; a failure forgets its
        assume and re-queues through the error handler."""
        cfg = self.config

        # shallow_copy, not copy.copy: the stdlib route detours
        # through __reduce_ex__ per object (~25us for pod+spec), which
        # at 30k binds/wave-burst was the scheduler's single largest
        # in-window Python cost
        from kubernetes_tpu.api.types import shallow_copy as _shallow

        assumed_all = []
        for pod, host in pairs:
            assumed = _shallow(pod)
            assumed.spec = _shallow(pod.spec)
            assumed.spec.node_name = host
            assumed_all.append(assumed)
        if hasattr(cfg.scheduler_cache, "assume_pods"):
            results = cfg.scheduler_cache.assume_pods(assumed_all)
        else:
            results = []
            for assumed in assumed_all:
                try:
                    cfg.scheduler_cache.assume_pod(assumed)
                    results.append(None)
                except Exception as e:
                    results.append(e)
        assumed_list = []
        bind_pairs: List[Tuple[Pod, str]] = []
        for (pod, host), assumed, err in zip(pairs, assumed_all, results):
            if err is not None:
                # Assume races happen: a duplicate FIFO delivery (broken
                # watch -> relist) pops a pod whose earlier decision is
                # already in the cache. Never bind on top of it — route
                # through the error handler, which refetches and
                # re-queues only if the pod is genuinely still
                # unassigned (factory.go:476-512), so true duplicates
                # drop out cleanly.
                log.warning(
                    "assume failed for %s: %s; re-queueing",
                    pod.metadata.name, err,
                )
                if cfg.error is not None:
                    cfg.error(pod, err)
                continue
            assumed_list.append(assumed)
            bind_pairs.append((pod, host))
        if not bind_pairs:
            return
        pairs = bind_pairs

        def fail(pod, assumed, err):
            try:
                cfg.scheduler_cache.forget_pod(assumed)
            except Exception:
                pass
            self._handle_failure(pod, err, reason="FailedBinding")

        def succeed(pod, host, per_bind, now):
            scheduler_binding_latency.observe(per_bind * 1e6)
            scheduler_e2e_latency.observe((now - cycle_start) * 1e6)
            tid = trace_span.extract(pod)
            if tid:
                # span timestamps are wall-clock; the clock above is
                # monotonic, so re-anchor the duration at "now"
                wall = time.time()
                trace_span.record_span(
                    "scheduler.bind", tid, wall - per_bind, wall, node=host,
                )
            if cfg.recorder is not None:
                cfg.recorder.eventf(
                    pod,
                    "Normal",
                    "Scheduled",
                    "Successfully assigned %s to %s",
                    pod.metadata.name,
                    host,
                )

        submitted = time.time()

        def bind_all() -> None:
            with trace_profile.phase_timer("bind"):
                acked = _bind_all_inner()
            if wt is not None:
                # the wave's own trace, recorded from the pool thread
                trace_span.record_span(
                    "wave.bind", wt.trace_id, submitted, acked,
                    parent_id=wt.span_id, pods=len(pairs))

        def _bind_all_inner() -> float:
            """-> the wall clock when the apiserver had answered."""
            bind_start = DEFAULT_CLOCK.now()
            if cfg.binder_many is not None and len(pairs) > 1:
                try:
                    results = cfg.binder_many(pairs)
                except Exception as e:
                    acked = time.time()
                    for (pod, _h), assumed in zip(pairs, assumed_list):
                        fail(pod, assumed, e)
                    return acked
                acked = time.time()
                now = DEFAULT_CLOCK.now()
                per = (now - bind_start) / len(pairs)
                for i, ((pod, host), assumed) in enumerate(
                    zip(pairs, assumed_list)
                ):
                    res = results[i] if i < len(results) else {
                        "status": "Failure",
                        "message": "missing bind result",
                    }
                    if res.get("status") == "Success":
                        succeed(pod, host, per, now)
                    else:
                        fail(pod, assumed, RuntimeError(
                            res.get("message", "bind failed")
                        ))
                return acked
            for (pod, host), assumed in zip(pairs, assumed_list):
                t0 = DEFAULT_CLOCK.now()
                try:
                    cfg.binder(pod, host)
                except Exception as e:
                    fail(pod, assumed, e)
                    continue
                now = DEFAULT_CLOCK.now()
                succeed(pod, host, now - t0, now)
            return time.time()

        # async bind (scheduler.go:124-152), on the shared pool
        try:
            self._bind_pool.submit(bind_all)
        except RuntimeError:
            # stop() shut the pool down mid-cycle: bind inline so the
            # assumed pods aren't orphaned until TTL expiry
            bind_all()

    def _handle_failure(
        self, pod: Pod, err: Exception, reason: str = "FailedScheduling",
        update_condition: bool = True,
    ) -> None:
        cfg = self.config
        log.debug("failed to schedule %s: %s", pod.metadata.name, err)
        if cfg.recorder is not None:
            cfg.recorder.eventf(pod, "Warning", reason, "%s", err)
        if update_condition and cfg.pod_condition_updater is not None:
            try:
                cfg.pod_condition_updater(pod, "False", "Unschedulable")
            except Exception:
                log.debug("condition update failed", exc_info=True)
        if cfg.error is not None:
            cfg.error(pod, err)
