"""The deployment whose templates ask for different resources,
hetero-1k: its file against the contract and the source's formula, the
pods it makes against goldens, its plain reference
(benchmark/reference_shapes.py) against the program's serial oracle,
the controls against the reference, and both of its cells on the served
path at a tiny size."""

import copy
import hashlib
import json
import os
import random

import numpy as np
import pytest

from benchmark import (
    check,
    control_precision,
    control_shapes,
    controls,
    deploy,
    reference_shapes,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 39
MI = 2 ** 20


def _cfg(nodes=None, population=None):
    """hetero-1k, or hetero-1k cut to a test's size: only counts
    change, the 40 controllers and their 40 shapes stay."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "hetero-1k.json"))
    if nodes is not None:
        cfg["nodes"]["count"] = nodes
    if population is not None:
        cfg["pods"]["population"] = population
    return cfg


# -- the deployment file ------------------------------------------------------

def test_the_file_is_the_sources_deployment():
    cfg = _cfg()
    entry = next(c for c in deploy.load_manifest()["configs"]
                 if c["name"] == "hetero-1k")
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == ["hollow_fleet"]
    assert cfg["reference"] == "benchmark/reference_shapes.py"
    assert cfg["nodes"]["count"] == 1000 and cfg["nodes"]["zones"] == []
    assert cfg["nodes"]["allocatable"] == {
        "cpu": "4", "memory": "32Gi", "pods": "110"}
    assert deploy.nodes(cfg)[7]["metadata"]["labels"] == {
        "kubernetes.io/hostname": "node-00007"}
    # 40 controllers of 250 replicas are the source's 10,000 pods
    c = cfg["controllers"]
    assert (c["count"], c["replicas"]) == (40, 250)
    assert c["count"] * c["replicas"] == cfg["pods"]["population"] == 10000
    made = deploy.controllers(cfg)
    assert [rc["metadata"]["name"] for rc in made] == \
        [f"het-{t}" for t in range(40)]
    assert made[39]["spec"] == {"selector": {"rc": "het-39"},
                                "replicas": 250}
    assert cfg["scheduler"]["env"] == {"KUBERNETES_TPU_MESH": "off",
                                       "KUBERNETES_TPU_WARM_SCAN": "1"}
    assert set(cfg["guarantees"]) == {"bound_once", "capacity", "decisions",
                                      "arithmetic", "durability"}


def test_the_shapes_are_the_sources_formula():
    """bench.py:813-831: pod i asks for cpu 50 + (i % 8) * 25 m and
    memory 100 + (i % 5) * 100 Mi: 40 pairs over its 10,000 pods, 250
    pods each."""
    cfg = _cfg()
    shapes = cfg["pods"]["shapes"]
    assert len(shapes) == 40 and all(set(s) == {"requests"} for s in shapes)
    ours = [(deploy.milli_cpu(s["requests"]["cpu"]),
             deploy.mem_bytes(s["requests"]["memory"]) // MI)
            for s in (deploy.template_shape(cfg, t) for t in range(40))]
    assert ours == [(50 + (t % 8) * 25, 100 + (t % 5) * 100)
                    for t in range(40)]
    assert len(set(ours)) == 40
    source = [(50 + (i % 8) * 25, 100 + (i % 5) * 100) for i in range(10000)]
    assert sorted(set(source)) == sorted(ours)
    assert {source.count(pair) for pair in ours} == {250}
    # every shape states its own requests: none leans on the default
    assert all(set(s["requests"]) == {"cpu", "memory"} for s in shapes)


#: sha256 (16 hex) of a template's pod as sorted JSON
GOLDEN_PODS = {7: "199e28b02f60c321", 39: "c97ca6ae06a010a8"}


def _sha(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def test_a_templates_pod_is_what_it_was():
    cfg = _cfg()
    assert deploy.pod(cfg, 0, name="p-t0-00000000") == {
        "kind": "Pod", "apiVersion": "v1",
        "metadata": {"namespace": "default", "labels": {"rc": "het-0"},
                     "name": "p-t0-00000000"},
        "spec": {"containers": [{
            "name": "pause", "image": "kubernetes/pause:go",
            "requests": {"cpu": "50m", "memory": "100Mi"}}]}}
    for t, golden in GOLDEN_PODS.items():
        assert _sha(deploy.pod(cfg, t, name=f"p-t{t}-00000001")) == golden
    made = deploy.pod(cfg, 39, prefix="x")
    assert made["metadata"]["generateName"] == "xt39-"
    assert made["spec"]["containers"][0]["requests"] == {
        "cpu": "225m", "memory": "500Mi"}


# -- the reference against the program's oracle -------------------------------

def _filled(cfg, pods, rng):
    """A cluster with `pods` bound at random where they fit: uneven,
    as a window leaves it. -> (cluster, [(template, node)])"""
    cluster = reference_shapes.Cluster(cfg)
    placed = []
    n = cfg["nodes"]["count"]
    for _ in range(pods):
        t, node = rng.randrange(40), rng.randrange(n)
        cluster.pod_cpu = int(cluster.shape_cpu[t])
        cluster.pod_mem = int(cluster.shape_mem[t])
        if cluster.fits()[node]:
            cluster.bind(t, node)
            placed.append((t, node))
    return cluster, placed


#: (nodes, pods bound at random, seed); the last three are full enough
#: that PodFitsResources filters nodes and then leaves pods unplaced
ORACLE_CASES = [(40, 200, 1), (25, 60, 2 ** 31 + 2), (30, 0, 3),
                (8, 190, 4), (12, 300, 5), (6, 150, 2 ** 31 + 6)]


@pytest.mark.parametrize("nodes,fill,seed", ORACLE_CASES)
def test_reference_decides_as_the_programs_oracle(nodes, fill, seed):
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler

    scheme = rest.default_scheme
    rng = random.Random(seed)
    cfg = _cfg(nodes)
    cluster, placed = _filled(cfg, fill, rng)
    bound = []
    for i, (t, node) in enumerate(placed):
        pod = scheme.decode(deploy.pod(cfg, t, name=f"old-{i}"))
        pod.spec.node_name = cluster.names[node]
        bound.append(pod)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    backlog = [rng.randrange(40) for _ in range(150)]
    pods = [scheme.decode(deploy.pod(cfg, t, name=f"new-{i:04d}"))
            for i, t in enumerate(backlog)]
    counter = rng.randrange(10_000)
    want = GenericScheduler(last_node_index=counter).schedule_backlog(
        pods, state)
    start = copy.deepcopy(cluster)
    got = reference_shapes.decide(cluster, backlog, counter)
    assert [cluster.names[g] if g is not None else None
            for g in got] == want
    assert (None in want) == (nodes <= 12)
    assert cluster.over_allocatable() == 0
    held = reference_shapes.verify(start, backlog, got)
    assert held["mismatches"] == 0 and held["checked"] == 150
    residue, modulus = held["counter"]
    assert counter % modulus == residue


def test_a_bound_pod_commits_its_own_templates_requests():
    cfg = _cfg(3)
    cluster = reference_shapes.Cluster(cfg)
    cluster.bind(39, 1)  # 225m / 500Mi
    cluster.bind(0, 1)   # 50m / 100Mi
    cluster.ranking(17)  # scoring another template commits nothing
    assert cluster.req_cpu.tolist() == [0, 275, 0]
    assert cluster.req_mem.tolist() == [0, 600 * MI, 0]
    assert cluster.pods.tolist() == [0, 2, 0]
    assert cluster.peers[39].tolist() == [0, 1, 0]


def test_the_reference_reads_requests_only():
    cfg = _cfg(3)
    cfg["pods"]["shapes"][3]["nodeSelector"] = {"disk": "ssd"}
    with pytest.raises(ValueError, match="nodeSelector"):
        reference_shapes.Cluster(cfg)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_shapes.py", "control_shapes.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            assert "kubernetes_tpu" not in f.read()


# -- the controls -------------------------------------------------------------

@pytest.mark.parametrize("nodes,fill,seed", ORACLE_CASES)
def test_scoring_every_template_with_the_mean_shape_moves_picks(
        nodes, fill, seed):
    """The control that says whether a cluster can see that the
    requests differ: the reference ranking every template with
    137m / 300Mi, binding what each asks for."""
    rng = random.Random(seed)
    cluster, _ = _filled(_cfg(nodes), fill, rng)
    backlog = [rng.randrange(40) for _ in range(150)]
    blind = control_shapes.scored_with_the_mean(cluster)
    assert set(blind.scored_cpu) == {137}
    assert set(blind.scored_mem) == {300 * MI}
    assert blind.shape_cpu.tolist() == cluster.shape_cpu.tolist()
    sound = reference_shapes.decide(copy.deepcopy(cluster), backlog, seed % 97)
    made = reference_shapes.decide(blind, backlog, seed % 97)
    assert reference_shapes.verify(copy.deepcopy(cluster), backlog,
                                   sound)["mismatches"] == 0
    assert reference_shapes.verify(copy.deepcopy(cluster), backlog,
                                   made)["mismatches"] >= 10


def test_no_total_these_shapes_can_make_tells_float32_from_float64():
    """BalancedResourceAllocation is int(10 - |cpu - mem| * 10) with
    the fractions of 4 CPU and 32Gi. Every total these shapes can make
    is a multiple of 25m and of 100Mi, which keeps 10 * |cpu - mem|
    either on a tenth exactly or 2.4e-4 away from one: over all 51,993
    totals under the allocatable, float32 truncates to the score
    float64 gives. So the float32 control moves no pick on this
    deployment, and its file claims no precision for this term."""
    cpu = np.arange(1, 160)[:, None] * 25
    mem = np.arange(1, 328)[None, :] * 100 * MI

    def score(real):
        c = cpu.astype(real) / real(4000)
        m = mem.astype(real) / real(32 * 1024 * MI)
        return (real(10) - np.abs(c - m) * real(10)).astype(np.int64)

    assert score(np.float32).shape == (159, 327)
    assert np.array_equal(score(np.float32), score(np.float64))
    assert "no precision is claimed" in _cfg()["guarantees"]["arithmetic"]


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 43])
def test_the_three_controls_on_a_record_as_the_generator_writes_it(seed):
    """benchmark/control_shapes.py, benchmark/controls.py and
    benchmark/control_precision.py on one record: the run's own picks
    read 0, the mean shape and the stale wave move picks, float32
    moves none."""
    rng = random.Random(seed)
    cfg = _cfg(120)
    assert check.load_reference(cfg).__name__.endswith("reference_shapes")
    cluster, placed = _filled(cfg, 240, rng)
    before = {f"p-t{t}-{i:08d}": cluster.names[node]
              for i, (t, node) in enumerate(placed)}
    backlog = [t for _ in range(52) for t in [rng.randrange(40)] * 40]
    assert len(backlog) == 2 * controls.STALE_WAVE + 32
    names = [f"check-{i:05d}" for i in range(len(backlog))]
    picks = reference_shapes.decide(copy.deepcopy(cluster), backlog,
                                    seed % 1000)
    after = dict(before, **{nm: cluster.names[p]
                            for nm, p in zip(names, picks)})
    record = {"check": {"backlog": backlog, "names": names,
                        "before": before, "after": after}}
    assert control_shapes.mean_shape(record, cfg)["sound"] == 0
    assert control_shapes.mean_shape(record, cfg)["mean_shape"] >= 100
    assert controls.stale_wave(record, cfg)["stale_wave"] >= 100
    assert control_precision.lower_precision(record, cfg) == {
        "sound": 0, "float32": 0}
    # a deployment with one shape has no such control to read
    one = deploy.load_config("density-1k")
    one["nodes"]["count"] = 6
    flat = {"check": {"backlog": [0] * 8,
                      "names": names[:8], "before": {}, "after": {}}}
    assert set(control_shapes.mean_shape(flat, one)) == {"sound"}


# -- the served path, at a tiny size ------------------------------------------

def _serve_tiny(d, workload, traffic, seed, seconds):
    """A cell of hetero-1k through benchmark/run.serve below its look
    for a chip: 24 nodes, the 40 controllers and their shapes, the mix
    cut to match; only counts change. -> (the result, the generator's
    record, the deployment)"""
    from benchmark import run

    cfg = _cfg(24, 560)
    (d / "hetero-1k.json").write_text(json.dumps(cfg))
    (d / "mix.json").write_text(json.dumps(traffic))
    manifest = deploy.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    saved = dict(os.environ)
    try:
        result = run.serve(cell, str(d / "hetero-1k.json"),
                           str(d / "mix.json"), seed, seconds, True,
                           manifest)
    finally:
        os.environ.clear()
        os.environ.update(saved)
    record = deploy.load_json(os.path.join(
        REPO, ".bench_out", f"{workload}-{seed}-1", "loadgen.json"))
    return result, record, cfg


def _tiny_rows():
    """The rows mix cut to the tiny deployment: two runs of 40 a
    request, a check batch of eight runs."""
    rows = deploy.load_json(deploy.traffic_path("rows"))
    rows.update(workers=2, chunk=80, backlog_cap=320, warm_s=0.5,
                drain_s=4.0,
                prefill_steps=[{"one_of_each": True}, {"pods": 80}],
                check={"runs": 8})
    return rows


@pytest.fixture(scope="module")
def traced_rows(tmp_path_factory):
    return _serve_tiny(tmp_path_factory.mktemp("tiny-hetero-rows"),
                       "hetero-1k.rows", _tiny_rows(), BIG_SEED, 4.0)


@pytest.fixture(scope="module")
def traced_fill(tmp_path_factory):
    fill = deploy.load_json(deploy.traffic_path("fill"))
    fill.update(workers=2, chunk=50, backlog_cap=128, warm_s=0.5,
                drain_s=2.0, check={"pods": 64})
    return _serve_tiny(tmp_path_factory.mktemp("tiny-hetero-fill"),
                       "hetero-1k.fill", fill, BIG_SEED + 1, 2.0)


def _reports_its_cells_metrics(result, workload):
    got = result["metrics"]
    want = {m["name"]: m["unit"]
            for m in deploy.load_manifest()["per_layer"]
            if workload in m["workloads"]}
    # the CPU has no device plane: a reader that finds nothing to read
    # returns nothing, and the line leaves the metric out
    host_side = {n for n in want if not n.startswith("device_")}
    assert host_side <= set(got) <= set(want)
    assert {"group_host_share.fill", "replay_us_per_pod.fill",
            "group_d2h_bytes_per_kpod.fill"} <= host_side
    for name, entry in got.items():
        assert entry["unit"] == want[name]
    return got


def _correct_on_all_eight_counts(result):
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["numbers"]) == 8
    assert all(tuple(pair) == (0, 0) for pair in result["numbers"].values())


def test_served_rows_are_decided_by_the_grouped_header_probe(traced_rows):
    """Runs of 40 of several templates on nodes without zones: one
    grouped header probe a group, the host rebuilds each run's table
    against what the runs before it committed and replays the picks."""
    result, record, _cfg_ = traced_rows
    _correct_on_all_eight_counts(result)
    batch = record["check"]["backlog"]
    assert len(batch) == 320 and len(set(batch)) > 1
    got = _reports_its_cells_metrics(result, "hetero-1k.rows")
    assert got["group_host_share.fill"]["value"] > 50
    assert got["wave_path_share.grouped.fill"]["value"] \
        >= got["group_host_share.fill"]["value"]
    assert got["wave_path_share.scan.fill"]["value"] < 50
    assert got["window_compiles.fill"]["value"] == 0
    assert got["replay_us_per_pod.fill"]["value"] > 0
    # a run slot's eleven header rows and the resource block's six, 8
    # bytes a node slot, 8 run slots at the least
    assert got["group_d2h_bytes_per_kpod.fill"]["value"] > 0
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})["wave"]
    assert shown["group_runs"] > 0 and shown["group_reprobes"] >= 0
    assert shown["group_d2h_bytes"] >= (8 * 11 + 6) * 24 * 8


def test_served_lone_pods_are_decided_by_the_scan(traced_fill):
    """The 40 controllers dealt in turn make runs of length 1: the
    serial scan decides every pod with another request row each step,
    and the grouped header probe none."""
    result, record, _cfg_ = traced_fill
    _correct_on_all_eight_counts(result)
    assert len(set(record["check"]["backlog"])) > 20
    got = _reports_its_cells_metrics(result, "hetero-1k.fill")
    assert got["wave_path_share.scan.fill"] == {"value": 100.0, "unit": "%"}
    assert got["group_host_share.fill"] == {"value": 0.0, "unit": "%"}
    assert got["group_d2h_bytes_per_kpod.fill"]["value"] == 0
    assert got["window_compiles.fill"]["value"] == 0
    assert got["score_us_per_pod.fill"]["value"] > 0


def test_the_controls_fail_on_the_served_runs_own_records(
        traced_rows, traced_fill):
    """The mean shape and the stale wave, read on the cluster each
    window left and on the run's own check batch: both move picks, and
    the run's own read 0."""
    for _result, record, cfg in (traced_rows, traced_fill):
        read = control_shapes.mean_shape(record, cfg)
        assert read["sound"] == 0 and read["mean_shape"] >= 1
        stale = controls.stale_wave(record, cfg)
        assert stale["sound"] == 0 and stale["stale_wave"] >= 1


def _runs_replayed_without_the_commits_before_them(monkeypatch):
    """The step the host replay exists to prevent: every run of a group
    rebuilt against the usage the probe shipped, as if the runs before
    it had committed nothing."""
    from kubernetes_tpu.models import hosttab, wave

    sound = hosttab.resource_tables
    first = {}

    def stale(config, pod, alloc, usage, rows):
        base = first.setdefault(id(alloc), usage.copy())
        return sound(config, pod, alloc, base, rows)

    monkeypatch.setattr(wave.hosttab, "resource_tables", stale)


def _every_run_scored_with_one_request(monkeypatch):
    """A driver that kept one request vector for a whole group."""
    from kubernetes_tpu.models import hosttab, wave

    sound = hosttab.resource_tables

    def blind(config, pod, alloc, usage, rows):
        pod = dict(pod, nz_mcpu=137, nz_mem=300 * MI)
        return sound(config, pod, alloc, usage, rows)

    monkeypatch.setattr(wave.hosttab, "resource_tables", blind)


@pytest.mark.parametrize("breakage", [
    _runs_replayed_without_the_commits_before_them,
    _every_run_scored_with_one_request,
])
def test_rows_run_with_the_host_replay_broken_is_not_correct(
        tmp_path, monkeypatch, breakage):
    breakage(monkeypatch)
    result, _record, _cfg_ = _serve_tiny(tmp_path, "hetero-1k.rows",
                                         _tiny_rows(), BIG_SEED + 2, 2.0)
    assert result["correct"] is False
    assert result["numbers"]["picks_off_reference"][0] >= 1


# -- the new readers on a program without the counters ------------------------

def test_new_readers_give_nothing_on_a_program_without_the_counters():
    """The parent commit keeps `pods_by_path` and no `group_d2h_bytes`:
    the reader returns nothing and the result line leaves the metric
    out; a program older still keeps neither."""
    from types import SimpleNamespace as NS

    from benchmark.layers import group_d2h_bytes_per_kpod, group_host_share

    def sched(stats):
        return {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
            _wave=NS(stats=stats)))))}

    paths = {"scan": 5, "single": 0, "group_host": 7, "group_device": 0}
    parent = sched({"waves": 3, "pods_by_path": paths})
    snap = group_d2h_bytes_per_kpod.snapshot(parent)
    assert snap == {}
    run = {"snapshots": {"group_d2h_bytes_per_kpod": (snap, snap)},
           "loadgen": {"bound_in_window": 100}}
    assert group_d2h_bytes_per_kpod.read(run) is None
    older = group_host_share.snapshot(sched({"waves": 3}))
    assert older == {}
    assert group_host_share.read(
        {"snapshots": {"group_host_share": (older, older)}}) is None
    # the parent's tally is enough for the share
    zero = dict.fromkeys(paths, 0)
    assert group_host_share.read(
        {"snapshots": {"group_host_share": (zero, paths)}}) == \
        pytest.approx(100.0 * 7 / 12)


@pytest.mark.parametrize("reader,metric,want", [
    ("group_d2h_bytes_per_kpod", "group_d2h_bytes_per_kpod.fill", 2500.0),
    ("replay_us_per_pod", "replay_us_per_pod.fill", 150.0),
])
def test_the_per_pod_readers_divide_a_diff_by_the_pods_bound(
        reader, metric, want):
    import importlib

    mod = importlib.import_module("benchmark.layers." + reader)
    before = {"group_d2h_bytes": 1000, "replay": 1.0, "encode": 9.0}
    after = {"group_d2h_bytes": 6000, "replay": 1.3, "encode": 11.0}
    run = {"snapshots": {reader: (before, after)}, "metric": metric,
           "loadgen": {"bound_in_window": 2000}}
    assert mod.read(run) == pytest.approx(want)
    run["loadgen"]["bound_in_window"] = 0
    assert mod.read(run) is None
