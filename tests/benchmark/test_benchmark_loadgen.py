"""The load generator's arithmetic, and what it may import."""

import collections
import os
import subprocess
import sys

import pytest

from benchmark import deploy, loadgen

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 12345


def test_loadgen_imports_no_jax_scheduler_or_apiserver():
    """One process holds the chip, and it is not this one."""
    code = (
        "import sys\n"
        "sys.argv = ['loadgen']\n"
        "import runpy\n"
        "mod = runpy.run_path('benchmark/loadgen.py', run_name='loadgen')\n"
        "mod['Generator']('http://127.0.0.1:9', "
        "mod['deploy'].load_json('benchmark/configs/density-1k.json'), "
        "mod['deploy'].load_json('benchmark/traffic/steady.json'), 1, 1.0)\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith("
        "('jax.', 'kubernetes_tpu.scheduler', 'kubernetes_tpu.apiserver', "
        "'kubernetes_tpu.models', 'kubernetes_tpu.oracle'))]\n"
        "assert not bad, bad\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("rate", [300, 1000, 2750])
def test_every_seed_offers_the_same_ticks_in_another_order(rate):
    a = loadgen.tick_sizes(rate, 100, 7, 0)
    b = loadgen.tick_sizes(rate, 100, BIG_SEED, 0)
    c = loadgen.tick_sizes(rate, 100, 7, 1)
    assert len(a) == 100 and sum(a) == rate * 10
    assert collections.Counter(a) == collections.Counter(b)
    assert collections.Counter(a) == collections.Counter(c)
    assert a != b and a != c
    assert a == loadgen.tick_sizes(rate, 100, 7, 0)
    # the Poisson law's own spread, from no seed but the order's
    mean = rate / 10
    var = sum((n - mean) ** 2 for n in a) / len(a)
    assert 0.8 * mean < var < 1.2 * mean


def _many_controllers(count):
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    cfg["controllers"].update(count=count, name_format="rc-{t}")
    cfg["pods"]["labels"] = {"rc": "rc-{t}"}
    return cfg


def test_template_order_and_check_batch_come_from_the_seed():
    cfg = _many_controllers(500)
    spec = {"pods": 512}
    assert loadgen.template_order(cfg, 5) == loadgen.template_order(cfg, 5)
    assert loadgen.template_order(cfg, 5) != loadgen.template_order(cfg, 6)
    assert sorted(loadgen.template_order(cfg, BIG_SEED)) == list(range(500))
    batch = loadgen.check_backlog(cfg, spec, BIG_SEED)
    assert batch == loadgen.check_backlog(cfg, spec, BIG_SEED)
    assert batch != loadgen.check_backlog(cfg, spec, BIG_SEED + 1)
    assert len(batch) == 512 and len(set(batch)) > 250


def test_percentile_is_nearest_rank():
    values = sorted(float(i) for i in range(1, 101))
    assert loadgen.percentile(values, 0.50) == 50.0
    assert loadgen.percentile(values, 0.99) == 99.0
    assert loadgen.percentile([3.0], 0.99) == 3.0
    assert loadgen.percentile([], 0.5) is None


def _generator(loop):
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    traffic = deploy.load_json(os.path.join(
        REPO, "benchmark", "traffic",
        "steady.json" if loop == "open" else "fill.json"))
    return loadgen.Generator("http://127.0.0.1:9", cfg, traffic, 3, 10.0)


def test_open_loop_times_a_pod_from_when_it_was_due():
    """A tick that left 40 ms late still counts the 40 ms: latency is
    seen minus DUE, and the lateness is reported beside it."""
    gen = _generator("open")
    t0, t1 = 1000.0, 1010.0
    # (seen, due): due at tick 1001.0, sent late, seen 100 ms after due
    bind_times = [(1001.1, 1001.0)] * 99 + [(1003.0, 1002.0)]
    bind_times += [(999.5, 999.0), (1010.5, 1010.2)]  # outside the window
    late = [(1001.0, 0.040), (1002.0, 0.001), (999.0, 5.0)]
    out = gen.reduce(t0, t1, bind_times, [], late, {}, [], [])
    assert out["attempted"] == 100 and out["failed"] == 0
    assert out["bind_latency_p50_ms"] == pytest.approx(100.0, abs=1e-6)
    assert out["bind_latency_p99_ms"] == pytest.approx(100.0, abs=1e-6)
    assert out["bind_latency_max_ms"] == pytest.approx(1000.0, abs=1e-6)
    assert out["loadgen_late_p99_ms"] == pytest.approx(40.0, abs=1e-6)
    assert out["bound_in_window"] == 100


def test_a_pod_unbound_at_the_deadline_is_failed_and_worst_in_the_tail():
    gen = _generator("open")
    bind_times = [(1001.1, 1001.0)] * 50
    out = gen.reduce(1000.0, 1010.0, bind_times, [], [],
                     {"p-t0-1": (1002.0, 0)}, [], [(1003.0, 2)])
    assert out["attempted"] == 53 and out["failed"] == 3
    assert out["unbound_at_deadline"] == 1
    # 1,010 + 15 s of drain - 1,002: at least 23 s, the worst in the tail
    assert out["bind_latency_p99_ms"] == pytest.approx(23000.0)
    assert out["bind_latency_p50_ms"] == pytest.approx(100.0, abs=1e-6)


def test_closed_loop_counts_what_it_sent_and_bound_in_the_window():
    gen = _generator("closed")
    sends = [(999.0, 1500), (1000.5, 1500), (1009.9, 1500), (1010.1, 1500)]
    acks = [(1000.5, 0.050), (1009.9, 0.150)]
    bind_times = [(1000.0 + i * 0.001, 999.0) for i in range(5000)]
    out = gen.reduce(1000.0, 1010.0, bind_times, acks, [], {}, sends, [])
    assert out["attempted"] == 3000 and out["failed"] == 0
    assert out["pods_bound_per_s"] == pytest.approx(500.0)
    assert out["create_ack_p50_ms"] == pytest.approx(50.0)
    assert len(out["bind_seen"]) == 5000


def test_deployment_objects_are_the_sources_shapes():
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    nodes = deploy.nodes(cfg)
    assert len(nodes) == 1000 and nodes[4]["metadata"]["name"] == "node-00004"
    assert nodes[0]["metadata"]["labels"] == {}
    assert nodes[0]["status"]["allocatable"] == {
        "cpu": "4", "memory": "32Gi", "pods": "110"}
    (rc,) = deploy.controllers(cfg)
    assert rc["metadata"]["name"] == "rc1"
    assert rc["spec"] == {"selector": {"name": "sched-perf"},
                          "replicas": 30000}
    pod = deploy.pod(cfg, 0, name="p-t0-00000001")
    assert pod["metadata"]["labels"] == {"name": "sched-perf"}
    assert pod["spec"]["containers"][0]["requests"] == {
        "cpu": "100m", "memory": "500Mi"}
    assert deploy.milli_cpu("100m") == 100 and deploy.milli_cpu("4") == 4000
    assert deploy.mem_bytes("500Mi") == 500 * 2 ** 20
    many = _many_controllers(500)
    rcs = deploy.controllers(many)
    assert len(rcs) == 500 and rcs[7]["spec"]["selector"] == {"rc": "rc-7"}
    assert deploy.pod(many, 7)["metadata"]["labels"] == {"rc": "rc-7"}


def test_churn_takes_bound_pods_out_when_the_system_is_over_its_line():
    """Bound pods at their line, and a backlog that a stall let grow
    past the cap: the churn deletes the oldest bound pods until the
    pods in the system are back at the population."""
    gen = _generator("open")
    gen.population, gen.cap, gen.hold = 150, 50, 100
    deleted = []

    class Door:
        def commit_batch(self, items):
            deleted.extend(i["name"] for i in items)
            gen.stop.set()  # one batch takes all that is over the line
            return []

    gen.churner = Door()
    gen.bound_order.extend(f"p-t0-{i:08d}" for i in range(100))
    gen.node_of.update((name, "node-00000") for name in gen.bound_order)
    gen.sent = 100 + 80  # 80 pods wait unbound: 30 over the line
    gen.churn()
    assert deleted == [f"p-t0-{i:08d}" for i in range(30)]
    assert gen.in_system() == 150 and len(gen.bound_order) == 70


def test_churn_stands_still_while_the_check_runs():
    """The check batch lands on a cluster that nothing else changes."""
    import threading
    import time

    gen = _generator("open")
    gen.population, gen.cap, gen.hold = 150, 50, 100
    gen.bound_order.extend(f"p-t0-{i:08d}" for i in range(120))
    gen.sent = 120
    gen.churner = None  # a delete would raise
    gen.churning.clear()
    t = threading.Thread(target=gen.churn, daemon=True)
    t.start()
    time.sleep(0.2)
    gen.stop.set()
    t.join(timeout=2)
    assert len(gen.bound_order) == 120 and not gen.errors
