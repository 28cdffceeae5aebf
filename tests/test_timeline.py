"""One timeline inside the daemon (PR 25): the accountant's new phases
and idle states, the profiler annotations behind their switch, the
queue's stamps and the wave's queue-wait histogram, a wave as one trace
with stage children, the span ring's window, the compile record, the
collector's counters and /debug/profile."""

import gc
import glob
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.client.cache.fifo import FIFO
from kubernetes_tpu.scheduler import core
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.factory import _ResponsibleFIFO
from kubernetes_tpu.trace import profile
from kubernetes_tpu.trace import spans
from kubernetes_tpu.trace.spans import TraceBuffer


def _pod(name, cpu="100m"):
    return t.Pod(
        metadata=t.ObjectMeta(name=name, namespace="default"),
        spec=t.PodSpec(containers=[
            t.Container(name="c", requests={"cpu": cpu})]),
    )


def _node(name, cpu="4"):
    return t.Node(
        metadata=t.ObjectMeta(name=name),
        status=t.NodeStatus(
            allocatable={"cpu": cpu, "memory": "32Gi", "pods": "110"},
            conditions=[t.NodeCondition("Ready", "True")]),
    )


@pytest.fixture()
def annotations_off():
    was = profile.set_annotations(False)
    yield
    profile.set_annotations(was)


# -- the accountant -----------------------------------------------------------


def test_new_phases_rank_after_bind_and_idle_states_last():
    assert profile.PHASES[:7] == ("encode", "probe", "score", "replay",
                                  "transfer", "wire", "bind")
    assert profile.PHASES[7:] == ("prepare", "assume", "ingest")
    assert profile.IDLE_STATES == ("queue_wait", "gather")
    assert set(profile.idle_totals()) == set(profile.IDLE_STATES)
    assert set(profile.exclusive_totals()) == set(profile.PHASES)
    assert set(profile.phase_totals()) == set(profile.PHASES)


@pytest.mark.parametrize("later", ["prepare", "assume", "ingest",
                                   "queue_wait", "gather"])
def test_a_new_phase_overlapping_bind_takes_nothing_from_it(later):
    acct = profile._ExclusiveAccountant()
    t0 = time.perf_counter()
    acct.enter("bind")
    time.sleep(0.03)
    acct.enter(later)  # ranked after bind: bind keeps the lane
    time.sleep(0.03)
    acct.exit("bind")
    time.sleep(0.03)  # only now is it the later one's time
    acct.exit(later)
    wall = time.perf_counter() - t0
    totals = acct.snapshot()
    assert totals["bind"] >= 0.055
    assert 0.025 <= totals[later] <= wall - totals["bind"] + 1e-6
    assert sum(totals.values()) <= wall + 1e-6


def test_phases_plus_idle_stay_within_the_wall():
    acct = profile._ExclusiveAccountant()
    t0 = time.perf_counter()
    acct.enter("queue_wait")
    time.sleep(0.02)
    acct.enter("ingest")  # an informer thread works while the loop waits
    time.sleep(0.02)
    acct.exit("ingest")
    acct.exit("queue_wait")
    time.sleep(0.02)  # inside no timer: nobody's
    acct.enter("prepare")
    time.sleep(0.02)
    acct.exit("prepare")
    wall = time.perf_counter() - t0
    totals = acct.snapshot()
    work = sum(totals[p] for p in profile.PHASES)
    idle = sum(totals[p] for p in profile.IDLE_STATES)
    assert totals["ingest"] >= 0.015 and totals["prepare"] >= 0.015
    assert 0.015 <= idle <= 0.03  # the part no work overlapped
    assert work + idle <= wall - 0.015


# -- annotations --------------------------------------------------------------


class _CountingAnnotation:
    opened = []

    def __init__(self, name):
        _CountingAnnotation.opened.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_switch_off_costs_no_trace_annotation(monkeypatch, annotations_off):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    _CountingAnnotation.opened = []
    with profile.phase_timer("encode"):
        pass
    with profile.annotation("sched/wave"):
        pass
    assert _CountingAnnotation.opened == []
    assert profile.set_annotations(True) is False  # it was off
    with profile.annotation("sched/wave"):
        with profile.phase_timer("encode"):
            with profile.phase_timer("gather"):
                pass
    assert _CountingAnnotation.opened == ["sched/wave", "sched/encode",
                                          "sched/gather"]


def test_phase_timer_notes_the_phase_open_on_its_thread():
    with profile.phase_timer("probe"):
        assert profile._TLS.phase == "probe"
        with profile.phase_timer("transfer"):
            assert profile._TLS.phase == "transfer"
        assert profile._TLS.phase == "probe"
    assert profile._TLS.phase is None


# -- the queue's stamps and the wave's histogram ------------------------------


def test_fifo_stamps_through_add_pop_and_delete():
    now = [100.0]
    fifo = FIFO()
    feed = _ResponsibleFIFO(fifo, "default-scheduler", clock=lambda: now[0])
    a, b, c = _pod("a"), _pod("b"), _pod("c")
    feed.add(a)
    now[0] = 101.0
    feed.add(b)
    feed.update(a)  # an update of a waiting pod keeps its first stamp
    now[0] = 102.0
    feed.add(c)
    feed.delete(c)  # deleted while it waited: no stamp left behind
    assert set(feed._stamps) == {"default/a", "default/b"}
    popped = [fifo.pop(timeout=0), fifo.pop(timeout=0)]
    stamps = feed.take_stamps(popped + [c])
    assert stamps.tolist()[:2] == [100.0, 101.0] and np.isnan(stamps[2])
    assert feed._stamps == {}  # emptied by the take
    # a relist keeps the stamp of a pod that was already waiting
    feed.add(a)
    now[0] = 110.0
    feed.replace([a, b])
    assert feed._stamps == {"default/a": 102.0, "default/b": 110.0}


def test_no_stamps_with_tracing_off():
    feed = _ResponsibleFIFO(FIFO(), "default-scheduler")
    spans.set_enabled(False)
    try:
        feed.add(_pod("a"))
        assert feed._stamps == {}
        assert feed.take_stamps([_pod("a")]) is None
    finally:
        spans.set_enabled(True)


def test_histogram_observes_a_wave_in_one_pass():
    from kubernetes_tpu.metrics import Histogram

    one, many = (Histogram("h_seconds", "h", buckets=[0.01, 0.02, 0.5])
                 for _ in range(2))
    values = np.array([0.001, 0.01, 0.015, 0.3, 7.0])
    for v in values:
        one.observe(float(v))
    many.observe_many(values)
    assert many.bucket_counts() == one.bucket_counts() == [2, 1, 1, 1]
    assert many.count == 5 and many.sum == pytest.approx(one.sum)


def test_queue_wait_buckets_are_10ms_wide_up_to_half_a_second():
    from kubernetes_tpu.metrics import scheduler_pod_queue_wait_seconds

    b = scheduler_pod_queue_wait_seconds.buckets
    upto = [x for x in b if x <= 0.5]
    assert upto[0] == 0.01 and upto[-1] == 0.5
    assert max(y - x for x, y in zip(upto, upto[1:])) <= 0.01 + 1e-9
    later = [x for x in b if x > 0.5]
    assert later and all(y == 2 * x for x, y in zip(later, later[1:]))


# -- a wave is one trace ------------------------------------------------------


class _Backlog:
    def schedule(self, pod, state):
        return "n1"

    def schedule_backlog(self, pods, state):
        time.sleep(0.01)
        return ["n1"] * len(pods)


def _one_wave(pods, stamps=None):
    cache = SchedulerCache(ttl=30)
    cache.add_node(_node("n1"))
    bound = []
    queue = list(pods)

    def bind_many(pairs):
        bound.append(threading.current_thread().name)
        return [{"status": "Success"}] * len(pairs)

    cfg = core.SchedulerConfig(
        scheduler_cache=cache, algorithm=_Backlog(),
        binder=lambda p, h: bound.append(threading.current_thread().name),
        binder_many=bind_many,
        next_pod=lambda: queue.pop(0),
        drain_waiting=lambda n: [queue.pop(0) for _ in range(
            min(n, len(queue)))],
        queue_stamps=stamps,
        wave_gather_seconds=0,
    )
    sched = core.Scheduler(cfg)
    began = time.time()
    sched.schedule_one()
    sched._bind_pool.shutdown(wait=True)
    return [s for s in spans.BUFFER.since(began)
            if s["name"] == "scheduler.wave"
            or s["name"].startswith("wave.")], bound


def test_one_trace_id_across_the_wave_and_its_five_children():
    from kubernetes_tpu.metrics import scheduler_pod_queue_wait_seconds

    before = scheduler_pod_queue_wait_seconds.count
    pods = [_pod(f"w{i}") for i in range(4)]
    now = time.monotonic()
    stamps = {p.metadata.name: now - 0.1 * (i + 1)
              for i, p in enumerate(pods)}
    got, bound = _one_wave(
        pods, stamps=lambda wave: np.array(
            [stamps[p.metadata.name] for p in wave]))
    by_name = {s["name"]: s for s in got}
    assert set(by_name) == {"scheduler.wave", "wave.gather", "wave.prepare",
                            "wave.algorithm", "wave.assume", "wave.bind"}
    root = by_name["scheduler.wave"]
    assert {s["trace_id"] for s in got} == {root["trace_id"]}
    for name, s in by_name.items():
        if name != "scheduler.wave":
            assert s["parent_id"] == root["span_id"]
    # consecutive stages: each begins where the one before ended
    stages = [by_name["wave." + n]
              for n in ("gather", "prepare", "algorithm", "assume")]
    for a, b in zip(stages, stages[1:]):
        assert a["start"] + a["duration"] == pytest.approx(b["start"])
    assert by_name["wave.algorithm"]["duration"] >= 0.009
    # bind: recorded by the pool thread, from hand-over to acknowledgement
    assert bound and all(name.startswith("bind") for name in bound)
    bind = by_name["wave.bind"]
    assert bind["attrs"]["pods"] == 4
    assert bind["start"] >= by_name["wave.assume"]["start"]
    # the wave carries its size and its pods' wait in the queue
    attrs = root["attrs"]
    assert attrs["pods"] == 4
    assert 0.09 <= attrs["queue_wait_min"] <= attrs["queue_wait_median"] \
        <= attrs["queue_wait_max"] <= 0.6
    assert scheduler_pod_queue_wait_seconds.count == before + 4


def test_a_wave_that_fails_still_closes_its_trace():
    class Boom(_Backlog):
        def schedule_backlog(self, pods, state):
            raise RuntimeError("algorithm down")

    cache = SchedulerCache(ttl=30)
    cache.add_node(_node("n1"))
    queue = [_pod("x1"), _pod("x2")]
    cfg = core.SchedulerConfig(
        scheduler_cache=cache, algorithm=Boom(), binder=lambda p, h: None,
        next_pod=lambda: queue.pop(0),
        drain_waiting=lambda n: [queue.pop(0)] if queue else [],
        error=lambda p, e: None, wave_gather_seconds=0,
    )
    began = time.time()
    core.Scheduler(cfg).schedule_one()
    names = [s["name"] for s in spans.BUFFER.since(began)
             if s["name"] == "scheduler.wave"
             or s["name"].startswith("wave.")]
    assert sorted(names) == ["scheduler.wave", "wave.algorithm",
                             "wave.gather", "wave.prepare"]


# -- the ring -----------------------------------------------------------------


def test_ring_refuses_a_window_it_no_longer_holds():
    ring = TraceBuffer(capacity=4)
    for i in range(4):
        ring.record({"name": "s", "start": 10.0 + i, "duration": 0.1})
    # nothing evicted yet: an early window start is simply all of it
    assert [s["start"] for s in ring.since(0.0)] == [10.0, 11.0, 12.0, 13.0]
    assert [s["start"] for s in ring.since(12.0)] == [12.0, 13.0]
    ring.record({"name": "s", "start": 14.0, "duration": 0.1})
    assert ring.since(10.5) is None  # the span of 10.0 is gone: cannot say
    assert [s["start"] for s in ring.since(11.0)] == [11.0, 12.0, 13.0, 14.0]


def test_default_ring_holds_a_minute_of_waves():
    # 60 s at 20 waves a second, six spans each
    assert spans.BUFFER._spans.maxlen >= 60 * 20 * 6


# -- compiles, collector, /debug/profile --------------------------------------


def test_compile_record_names_the_program_and_the_phase():
    import jax
    import jax.numpy as jnp

    profile.install_compile_listener()

    def timeline_test_program(x):
        return x * 3 + 1

    with profile.phase_timer("probe"):
        jax.jit(timeline_test_program)(jnp.arange(7)).block_until_ready()
    mine = [c for c in profile.recent_compiles()
            if "timeline_test_program" in c["program"]]
    assert len(mine) == 1
    assert mine[0]["phase"] == "probe" and mine[0]["seconds"] > 0
    assert mine[0]["cache"] in ("hit", "miss")
    assert len(profile.recent_compiles()) <= 64


def test_gc_counters_on_an_apiservers_metrics():
    from kubernetes_tpu import metrics
    from kubernetes_tpu.apiserver.server import APIServer

    metrics.install_gc_metrics()
    metrics.install_gc_metrics()  # idempotent: one hook
    assert sum(1 for cb in gc.callbacks
               if getattr(cb, "__name__", "") == "on_gc") == 1
    before = metrics.process_gc_pause_seconds_total.get(generation="2")
    gc.collect()
    assert metrics.process_gc_pause_seconds_total.get(generation="2") > before
    api = APIServer()
    host, port = api.serve_http()
    try:
        text = urllib.request.urlopen(
            f"http://{host}:{port}/metrics").read().decode()
    finally:
        api.shutdown_http()
    for g in "012":
        assert f'process_gc_pause_seconds_total{{generation="{g}"}}' in text
        assert f'process_gc_long_pauses_total{{generation="{g}"}}' in text


def test_debug_profile_writes_a_trace_with_the_phases_in_it(annotations_off):
    from jax.profiler import ProfileData

    from kubernetes_tpu.trace.httpd import start_component_server

    server, port = start_component_server()
    stop = threading.Event()

    def work():
        while not stop.is_set():
            with profile.phase_timer("encode"):
                time.sleep(0.002)

    worker = threading.Thread(target=work)
    worker.start()
    try:
        url = f"http://127.0.0.1:{port}/debug/profile"
        reply = json.load(urllib.request.urlopen(url + "?seconds=0.3"))
        with pytest.raises(urllib.error.HTTPError) as too_long:
            urllib.request.urlopen(url + "?seconds=11")
        assert too_long.value.code == 400
    finally:
        stop.set()
        worker.join()
        server.shutdown()
    assert profile._ANNOTATION is None  # switched back off afterwards
    found = glob.glob(reply["directory"] +
                      "/plugins/profile/*/*.xplane.pb")
    assert found, reply
    names = {e.name for plane in ProfileData.from_file(found[0]).planes
             for line in plane.lines for e in line.events}
    assert "sched/encode" in names


# -- named programs, a cumulative launch tally --------------------------------


def _jit_sites():
    """(file, line, what is jitted) for every jax.jit call and @jax.jit
    decorator under models/ and parallel/."""
    import ast
    import os

    import kubernetes_tpu.models as models
    import kubernetes_tpu.parallel as parallel

    sites = []
    for package in (models, parallel):
        root = os.path.dirname(package.__file__)
        for fname in sorted(os.listdir(root)):
            if not fname.endswith(".py"):
                continue
            where = f"{os.path.basename(root)}/{fname}"
            tree = ast.parse(open(os.path.join(root, fname)).read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and \
                        ast.unparse(node.func) == "jax.jit":
                    sites.append((where, node.lineno, node.args[0]))
                elif isinstance(node, ast.FunctionDef) and any(
                        ast.unparse(d) == "jax.jit"
                        for d in node.decorator_list):
                    sites.append((where, node.lineno,
                                  ast.Name(id=node.name)))
    return sites


def test_every_jit_site_under_models_and_parallel_jits_a_named_function():
    import ast

    sites = _jit_sites()
    assert len(sites) >= 13, sites
    assert sum(1 for fname, _l, _a in sites
               if fname.startswith("parallel/")) >= 3, sites
    for fname, line, arg in sites:
        where = f"{fname}:{line}"
        # a lambda reads jit__lambda and a functools.partial
        # jit__unknown in a trace: neither says which program it is
        assert isinstance(arg, ast.Name), (where, ast.unparse(arg))
        assert arg.id not in ("run", "fn", "f"), where


def test_the_wave_loops_arrows_point_down():
    """models/waveloop knows no jax, no driver, nothing of `parallel`
    or `scheduler`; the mesh driver takes no private name from the
    single-chip driver's module or the loop's; and none of the three
    holds a wave's state in closures again (`nonlocal`)."""
    import ast
    import os

    import kubernetes_tpu

    root = os.path.dirname(kubernetes_tpu.__file__)
    trees = {f: ast.parse(open(os.path.join(root, f)).read())
             for f in ("models/waveloop.py", "models/wave.py",
                       "parallel/mesh.py")}

    def imports(tree):
        """(module, name) for every import, wherever it stands."""
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    yield alias.name, None
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    yield node.module, alias.name

    for module, name in imports(trees["models/waveloop.py"]):
        parts = (module + "." + (name or "")).split(".")
        assert parts[0] != "jax", (module, name)
        assert not set(parts) & {"parallel", "scheduler", "wave"}, \
            (module, name)
    for module, name in imports(trees["parallel/mesh.py"]):
        if module in ("kubernetes_tpu.models.wave",
                      "kubernetes_tpu.models.waveloop"):
            assert not name.startswith("_"), (module, name)
    for fname, tree in trees.items():
        assert not [n.lineno for n in ast.walk(tree)
                    if isinstance(n, ast.Nonlocal)], fname


def test_the_mesh_drivers_programs_carry_their_own_names():
    """Every program the sharded driver builds says which it is in a
    trace and among the compiles: jit_mesh_scan, jit_mesh_group_probe,
    jit_mesh_apply_group, jit_mesh_probe, jit_mesh_apply and the
    resident state's jit_mesh_scatter."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    profile.install_compile_listener()
    t_before = time.time()
    algo = TPUScheduleAlgorithm(
        mesh=Mesh(np.array(jax.devices()[:2]), ("nodes",)))
    state = ClusterState.build([_node(f"tm{i:03d}") for i in range(200)])
    wave = algo._wave
    # a run (the grouped header probe and fold), then two lone pods
    # (the sharded scan)
    assert all(algo.schedule_backlog(
        [_pod(f"tmp{i}") for i in range(48)]
        + [_pod("tm-a", cpu="150m"), _pod("tm-b", cpu="250m")], state))
    assert wave.dispatches == {"group_probe": 1, "apply": 1, "scan": 1}
    # the state never learned of those 50 binds: the next view differs
    # from the mirrors on their nodes' rows, under a quarter of the
    # 256 bucket, and the resident row scatter ships them
    assert all(algo.schedule_backlog([_pod("tm-c", cpu="150m")], state))
    assert wave.resident.stats["scatters"] >= 1
    # and the per-run probe and fold, which the resident modes bypass
    wave.reuse_default = "reship"
    assert all(algo.schedule_backlog(
        [_pod(f"tmq{i}") for i in range(48)], state))
    assert wave.dispatches == {"probe": 1, "apply": 1}
    assert wave.stats["waves"] == 3
    assert wave.stats["dispatches"] == 6 == sum(
        wave.stats["dispatches_by_kind"].values())
    built = {c["program"] for c in profile.recent_compiles()
             if c["at"] >= t_before}
    for name in ("mesh_scan", "mesh_group_probe", "mesh_apply_group",
                 "mesh_scatter", "mesh_probe", "mesh_apply"):
        assert f"jit({name})" in built, (name, sorted(built))
    assert not any("unnamed" in p or "unknown" in p or "lambda" in p
                   for p in built), sorted(built)


def test_programs_a_backlog_builds_carry_their_own_names():
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    profile.install_compile_listener()
    # by time, not by place: the ring keeps the last 64, and a worker
    # that built more before this test would leave nothing past its end
    t_before = time.time()
    state = ClusterState.build([_node(f"tl{i}") for i in range(24)])
    algo = TPUScheduleAlgorithm()
    pods = [_pod(f"tlp{i}") for i in range(48)]
    wave = algo._wave
    assert all(algo.schedule_backlog(pods, state))
    first = wave.stats["dispatches"]
    assert first >= 1 and first == sum(wave.dispatches.values())
    assert all(algo.schedule_backlog(
        [_pod(f"tlq{i}") for i in range(48)], state))
    # _wave_setup emptied the per-wave dict; the total went on counting
    assert wave.stats["waves"] == 2
    assert wave.stats["dispatches"] == first + sum(wave.dispatches.values())
    assert wave.stats["dispatches"] > sum(wave.dispatches.values())
    built = [c["program"] for c in profile.recent_compiles()
             if c["at"] >= t_before]
    assert {"jit(pack_unpack)", "jit(wave_apply_packed)"} <= set(built)
    assert any(p.startswith("jit(probe_fused_") for p in built), built
    for program in built:
        assert "<" not in program and program not in ("jit(run)", ""), built
