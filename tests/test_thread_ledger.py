"""A ledger per thread beside the one timeline (PR 41): each phase
timer books its own thread's self wall and CPU seconds under the role
the thread's owner declared, `device_wait` marks the blocking reads, and
both are served where the rest is (/debug/traces, /metrics)."""

import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client import LocalTransport, RESTClient
from kubernetes_tpu.client.informer import Informer, ResourceEventHandler
from kubernetes_tpu.metrics import (
    registry,
    scheduler_thread_phase_seconds_total,
)
from kubernetes_tpu.scheduler import core
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.trace import profile, spans
from kubernetes_tpu.trace.httpd import render_traces

KEYS = profile.PHASES + profile.IDLE_STATES + (profile.DEVICE_WAIT,)


def _moved(before, after):
    """thread_totals() is cumulative and process-wide: what a test did
    is the difference of two reads."""
    zero = {"wall": 0.0, "cpu": 0.0, "count": 0}
    return {role: {key: {f: cell[f] - before.get(role, {}).get(key, zero)[f]
                         for f in cell}
                   for key, cell in cells.items()}
            for role, cells in after.items()}


def _in_thread(fn, role=None):
    """Run fn on a thread of its own, under `role` if given; -> what
    the ledger gained and the thread's wall seconds."""
    took = []

    def body():
        if role is not None:
            profile.thread_role(role)
        t0 = time.perf_counter()
        fn()
        took.append(time.perf_counter() - t0)

    before = profile.thread_totals()
    th = threading.Thread(target=body)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    return _moved(before, profile.thread_totals()), took[0]


def _spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def test_two_threads_with_roles_keep_their_own_self_time():
    """Overlapping timers on two threads: the one timeline gives the
    overlap to the top-ranked phase, the ledger gives each thread its
    own; a nested timer's time is taken from the one that encloses
    it."""
    go = threading.Barrier(2, timeout=10)

    def loop():
        go.wait()
        with profile.phase_timer("replay"):
            time.sleep(0.06)

    def informer():
        go.wait()
        with profile.phase_timer("ingest"):
            time.sleep(0.02)
            with profile.phase_timer("wire"):
                time.sleep(0.04)

    before = profile.thread_totals()
    walls = {}

    def run(role, fn):
        profile.thread_role(role)
        t0 = time.perf_counter()
        fn()
        walls[role] = time.perf_counter() - t0

    threads = [threading.Thread(target=run, args=("ledger-loop", loop)),
               threading.Thread(target=run, args=("ledger-informer",
                                                  informer))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
        assert not th.is_alive()
    moved = _moved(before, profile.thread_totals())
    mine, theirs = moved["ledger-loop"], moved["ledger-informer"]
    assert mine["replay"]["count"] == 1 and mine["replay"]["wall"] >= 0.055
    assert mine["wire"]["count"] == mine["ingest"]["count"] == 0
    # replay outranks wire and ingest on the timeline, and took nothing
    # from them here
    assert theirs["wire"]["wall"] >= 0.035
    assert 0.015 <= theirs["ingest"]["wall"] <= 0.035  # 0.06 less the 0.04
    assert theirs["replay"]["count"] == 0
    for role, cells in (("ledger-loop", mine), ("ledger-informer", theirs)):
        assert sum(c["wall"] for c in cells.values()) <= walls[role] + 1e-6


@pytest.mark.parametrize("body, spins", [
    (lambda: time.sleep(0.1), False), (lambda: _spin(0.1), True),
], ids=["a thread that sleeps reads wall far over cpu",
        "a thread that spins reads the cpu it got"])
def test_wall_against_cpu_says_whether_the_thread_ran(body, spins):
    own = []

    def timed():
        with profile.phase_timer("encode"):
            c0 = time.thread_time()
            body()
            own.append(time.thread_time() - c0)

    moved, _wall = _in_thread(timed, role="ledger-clock")
    cell = moved["ledger-clock"]["encode"]
    assert cell["wall"] >= 0.095
    if spins:
        # what share of the wall a spinning thread gets is the
        # machine's; that the ledger read the thread's own clock is not
        assert own[0] <= cell["cpu"] <= own[0] + 0.005
        assert cell["cpu"] >= 0.1 * cell["wall"]
    else:
        assert cell["cpu"] <= 0.2 * cell["wall"]


def test_device_wait_is_booked_on_its_thread_and_is_no_phase():
    timeline = profile.exclusive_totals()
    got = []

    def read():
        with profile.phase_timer("score"):
            with profile.device_wait():
                time.sleep(0.03)
            got.append(profile.fetch(np.arange(4)))

    moved, _wall = _in_thread(read, role="ledger-wait")
    cells = moved["ledger-wait"]
    assert cells[profile.DEVICE_WAIT]["count"] == 2
    assert cells[profile.DEVICE_WAIT]["wall"] >= 0.028
    # it overlays the phase it happened in and takes nothing from it
    assert cells["score"]["wall"] >= cells[profile.DEVICE_WAIT]["wall"]
    assert got[0].tolist() == [0, 1, 2, 3]
    assert profile.DEVICE_WAIT not in profile.PHASES + profile.IDLE_STATES
    assert set(profile.exclusive_totals()) == set(timeline) \
        == set(profile.PHASES)
    assert set(cells) == set(KEYS)


def test_a_thread_nobody_spoke_for_is_other():
    def body():
        with profile.phase_timer("wire"):
            pass

    moved, _wall = _in_thread(body)
    assert moved[profile.OTHER]["wire"]["count"] == 1
    assert [role for role, cells in moved.items() if role != profile.OTHER
            and any(c["count"] for c in cells.values())] == []


def test_tracing_off_records_nothing_and_fetch_still_reads():
    before = profile.thread_totals()
    spans.set_enabled(False)
    try:
        with profile.phase_timer("encode"):
            with profile.device_wait():
                got = profile.fetch(np.arange(3))
    finally:
        spans.set_enabled(True)
    assert got.tolist() == [0, 1, 2]
    moved = _moved(before, profile.thread_totals())
    assert not any(c["count"] for cells in moved.values()
                   for c in cells.values())


class _Annotation:
    opened = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        _Annotation.opened.append("/" + self.name)
        return False


def test_device_wait_lies_in_the_host_plane_while_annotations_are_on(
        monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.opened = []
    was = profile.set_annotations(True)
    try:
        with profile.phase_timer("replay"):
            profile.fetch(np.arange(2))
    finally:
        profile.set_annotations(was)
    assert _Annotation.opened == ["sched/replay", "sched/device_wait",
                                  "/sched/device_wait", "/sched/replay"]
    _Annotation.opened = []
    was = profile.set_annotations(False)
    try:
        profile.fetch(np.arange(2))
    finally:
        profile.set_annotations(was)
    assert _Annotation.opened == []


def test_an_ended_threads_seconds_stay_in_the_totals():
    def body():
        with profile.phase_timer("bind"):
            time.sleep(0.01)

    first, _wall = _in_thread(body, role="ledger-gone")
    assert first["ledger-gone"]["bind"]["count"] == 1
    held = profile.thread_totals()["ledger-gone"]["bind"]
    # the next thread to register folds the ended one's record away
    _in_thread(body, role="ledger-next")
    with profile._ledgers_lock:
        assert not [r for r in profile._LEDGERS if r.role == "ledger-gone"]
    assert profile.thread_totals()["ledger-gone"]["bind"] == held


def test_a_timer_closed_by_another_thread_is_not_booked():
    """read_frames holds `ingest` open across its yield: whoever drops
    the generator closes the timer, and another thread's CPU clock says
    nothing of the one that opened it."""
    def frames():
        with profile.phase_timer("ingest"):
            yield 1

    held = []

    def opener():
        gen = frames()
        next(gen)
        held.append(gen)

    moved, _wall = _in_thread(opener, role="ledger-opener")
    assert moved["ledger-opener"]["ingest"]["count"] == 0  # still open
    before = profile.thread_totals()
    held.pop().close()  # on this thread
    moved = _moved(before, profile.thread_totals())
    assert not any(c["count"] for cells in moved.values()
                   for c in cells.values())
    assert profile._TLS.phase is None


def _pod(name):
    return t.Pod(
        metadata=t.ObjectMeta(name=name, namespace="default"),
        spec=t.PodSpec(containers=[
            t.Container(name="c", requests={"cpu": "100m"})]))


def test_the_loop_and_the_bind_pool_say_their_roles():
    cache = SchedulerCache(ttl=30)
    cache.add_node(t.Node(
        metadata=t.ObjectMeta(name="n1"),
        status=t.NodeStatus(
            allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
            conditions=[t.NodeCondition("Ready", "True")])))
    queue = [_pod("a"), _pod("b")]
    bound = threading.Event()

    class Algorithm:
        def schedule(self, pod, state):
            return "n1"

        def schedule_backlog(self, pods, state):
            return ["n1"] * len(pods)

    def next_pod():
        if queue:
            return queue.pop(0)
        bound.wait(5)
        return None  # ends the loop

    def bind_many(pairs):
        bound.set()
        return [{"status": "Success"}] * len(pairs)

    cfg = core.SchedulerConfig(
        scheduler_cache=cache, algorithm=Algorithm(),
        binder=lambda p, h: bound.set(), binder_many=bind_many,
        next_pod=next_pod,
        drain_waiting=lambda n: [queue.pop(0) for _ in range(
            min(n, len(queue)))],
        wave_gather_seconds=0)
    before = profile.thread_totals()
    sched = core.Scheduler(cfg)
    thread = sched.run()
    thread.join(timeout=30)
    assert not thread.is_alive()
    sched._bind_pool.shutdown(wait=True)
    moved = _moved(before, profile.thread_totals())
    assert moved["loop"]["queue_wait"]["count"] == 2
    assert moved["loop"]["prepare"]["count"] >= 1
    assert moved["loop"]["assume"]["count"] == 1
    assert moved["loop"]["bind"]["count"] == 0
    assert moved["binder"]["bind"]["count"] == 1
    assert moved["binder"]["prepare"]["count"] == 0


def test_an_informers_threads_are_informers():
    client = RESTClient(LocalTransport(APIServer()))
    seen = threading.Event()

    def on_add(obj):
        with profile.phase_timer("ingest"):
            seen.set()

    before = profile.thread_totals()
    inf = Informer(client.pods(), ResourceEventHandler(on_add=on_add)).run()
    try:
        assert inf.wait_for_sync()
        client.pods().create(_pod("x"))
        assert seen.wait(5)
    finally:
        inf.stop()
    moved = _moved(before, profile.thread_totals())
    assert moved["informer"]["ingest"]["count"] >= 1
    assert moved.get(profile.OTHER, {}).get(
        "ingest", {"count": 0})["count"] == 0


def test_debug_traces_carries_the_threads():
    with profile.phase_timer("prepare"):
        pass
    threads = render_traces({})["threads"]
    mine = threads[profile.OTHER]
    assert set(mine) == set(KEYS)
    assert set(mine["prepare"]) == {"wall", "cpu", "count"}
    assert mine["prepare"]["count"] >= 1


def test_metrics_carries_the_counter_family():
    def body():
        with profile.phase_timer("wire"):
            _spin(0.01)

    _in_thread(body, role="ledger-scrape")
    lines = [ln for ln in registry.render().splitlines()
             if ln.startswith("scheduler_thread_phase_seconds_total{")
             and 'role="ledger-scrape"' in ln and 'phase="wire"' in ln]
    by_clock = {ln.split('clock="')[1].split('"')[0]: float(ln.rsplit(" ", 1)[1])
                for ln in lines}
    assert set(by_clock) == {"wall", "cpu"}
    assert by_clock["wall"] >= 0.009 and by_clock["cpu"] > 0.0
    assert scheduler_thread_phase_seconds_total.get(
        clock="wall", phase="wire", role="ledger-scrape") \
        == pytest.approx(by_clock["wall"])
