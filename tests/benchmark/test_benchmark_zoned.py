"""The zoned plain reference (benchmark/reference_zoned.py) against the
program's serial oracle, and the controls against the reference: on
spread-3k's shape the comparison that decides `correct` has to fail a
batch that does not thread its commitments, one altered pick, and a
spread scorer computed below the float32 the deployment states."""

import copy
import os
import random

import numpy as np
import pytest

from benchmark import check, controls, deploy, reference_zoned

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(nodes, controllers=None, zones=("a", "b", "c")):
    """spread-3k cut to a test's size: only counts change."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "spread-3k.json"))
    cfg["nodes"]["count"] = nodes
    cfg["nodes"]["zones"] = list(zones)
    if controllers is not None:
        cfg["controllers"]["count"] = controllers
    return cfg


def _filled(cfg, pods, rng, skew=0.0):
    """A cluster with `pods` bound at random: uneven, as a window
    leaves it. -> (cluster, [(template, node)])"""
    cluster = reference_zoned.Cluster(cfg)
    placed = []
    templates = deploy.num_templates(cfg)
    n = cfg["nodes"]["count"]
    for _ in range(pods):
        t = 0 if rng.random() < skew else rng.randrange(templates)
        node = rng.randrange(n)
        if cluster.fits()[node]:
            cluster.bind(t, node)
            placed.append((t, node))
    return cluster, placed


def _as_the_window_leaves_it(cfg, rng):
    """The deployment's own proportions: about 24 of a controller's 40
    replicas bound (11,808 of 20,000), spread as the scheduler spreads
    them, so a node holds none or one of a controller's pods."""
    cluster = reference_zoned.Cluster(cfg)
    templates = deploy.num_templates(cfg)
    backlog = [t for _ in range(24) for t in range(templates)]
    rng.shuffle(backlog)
    reference_zoned.decide(cluster, backlog, rng.randrange(1000))
    return cluster


@pytest.mark.parametrize("nodes,controllers,zones,seed", [
    (45, 9, "abc", 1), (12, 1, "abc", 2), (30, 0, "abc", 3),
    (33, 6, "ab", 2 ** 31 + 5), (40, 20, "abc", 6), (31, 7, "abcd", 7),
    (24, 5, "", 8),
])
def test_zoned_reference_decides_as_the_programs_oracle(
        nodes, controllers, zones, seed):
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler

    scheme = rest.default_scheme
    rng = random.Random(seed)
    cfg = _cfg(nodes, controllers, zones)
    cluster, placed = _filled(cfg, 20 * nodes, rng, skew=0.3)
    bound = []
    for i, (t, node) in enumerate(placed):
        pod = scheme.decode(deploy.pod(cfg, t, name=f"old-{i}"))
        pod.spec.node_name = cluster.names[node]
        bound.append(pod)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    templates = deploy.num_templates(cfg)
    backlog = [rng.randrange(templates) for _ in range(150)]
    pods = [scheme.decode(deploy.pod(cfg, t, name=f"new-{i:04d}"))
            for i, t in enumerate(backlog)]
    counter = rng.randrange(10_000)
    want = GenericScheduler(last_node_index=counter).schedule_backlog(
        pods, state)
    start = copy.deepcopy(cluster)
    got = reference_zoned.decide(cluster, backlog, counter)
    assert [cluster.names[g] if g is not None else None
            for g in got] == want
    held = reference_zoned.verify(start, backlog, got)
    assert held["mismatches"] == 0 and held["checked"] == 150
    residue, modulus = held["counter"]
    assert counter % modulus == residue


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_empty_zoned_cluster_fills_as_the_oracle_does(seed):
    """No selected pod anywhere: upstream divides 0 by 0 for every
    zoned node alike, and the other scorers decide."""
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler

    scheme = rest.default_scheme
    rng = random.Random(seed)
    cfg = _cfg(15, 4)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], [],
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    backlog = [rng.randrange(4) for _ in range(60)]
    pods = [scheme.decode(deploy.pod(cfg, t, name=f"new-{i:04d}"))
            for i, t in enumerate(backlog)]
    want = GenericScheduler(last_node_index=seed % 97).schedule_backlog(
        pods, state)
    cluster = reference_zoned.Cluster(cfg)
    got = reference_zoned.decide(cluster, backlog, seed % 97)
    assert [cluster.names[g] for g in got] == want


def test_the_zone_term_moves_picks():
    """The same cluster scored with and without its zones decides
    differently: the zone share is live in this deployment."""
    rng = random.Random(5)
    cfg = _cfg(60, 10)
    zoned, placed = _filled(cfg, 300, rng, skew=0.2)
    flat = reference_zoned.Cluster(_cfg(60, 10, zones=()))
    for t, node in placed:
        flat.bind(t, node)
    backlog = [rng.randrange(10) for _ in range(200)]
    assert reference_zoned.decide(zoned, backlog, 3) \
        != reference_zoned.decide(flat, backlog, 3)


def test_one_altered_pick_is_one_mismatch():
    rng = random.Random(31)
    cfg = _cfg(60, 10)
    cluster, _ = _filled(cfg, 600, rng)
    backlog = [rng.randrange(10) for _ in range(200)]
    picks = reference_zoned.decide(copy.deepcopy(cluster), backlog, 9)
    assert reference_zoned.verify(copy.deepcopy(cluster), backlog,
                                  picks)["mismatches"] == 0
    # the last pick, so that every pick before it still stands on the
    # cluster its scheduler saw
    at_last = copy.deepcopy(cluster)
    for t, node in zip(backlog[:-1], picks[:-1]):
        at_last.bind(t, node)
    ties = at_last.ranking(backlog[-1])
    picks[-1] = next(i for i in range(60) if i not in set(ties.tolist()))
    held = reference_zoned.verify(copy.deepcopy(cluster), backlog, picks)
    assert held["mismatches"] == 1


@pytest.mark.parametrize("nodes,controllers,seed", [
    (300, 50, 21), (300, 50, 2 ** 31 + 22), (600, 100, 23),
])
def test_lower_precision_spread_scorer_fails_the_comparison(
        nodes, controllers, seed):
    """The precision control: the same reference with the spread score
    in float16, the nearest precision below the float32 the deployment
    states, moves at least one pick of a seeded batch on this
    deployment's shape (nodes : controllers : zones = 6 : 1, 3 zones,
    about 24 pods of a controller bound). A scorer computed below
    float32 cannot pass."""
    rng = random.Random(seed)
    cfg = _cfg(nodes, controllers)
    cluster = _as_the_window_leaves_it(cfg, rng)
    backlog = [rng.randrange(controllers) for _ in range(512)]
    sound = reference_zoned.decide(copy.deepcopy(cluster), backlog, seed % 1000)
    half = copy.deepcopy(cluster)
    half.real = np.float16
    low = reference_zoned.decide(half, backlog, seed % 1000)
    assert reference_zoned.verify(copy.deepcopy(cluster), backlog,
                                  sound)["mismatches"] == 0
    assert reference_zoned.verify(copy.deepcopy(cluster), backlog,
                                  low)["mismatches"] >= 1


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 43])
def test_stale_wave_control_fails_on_the_zoned_deployment(seed):
    """benchmark/controls.py on a record as the load generator writes
    it, with the zoned deployment's file: the run's own picks read 0,
    the stale wave's do not."""
    rng = random.Random(seed)
    cfg = _cfg(300, 50)
    assert check.load_reference(cfg).__name__.endswith("reference_zoned")
    cluster = _as_the_window_leaves_it(cfg, rng)
    before = {}
    serial = 0
    for t in range(50):
        for node in np.flatnonzero(cluster.peers[t]):
            for _ in range(int(cluster.peers[t, node])):
                before[f"p-t{t}-{serial:08d}"] = cluster.names[node]
                serial += 1
    backlog = [rng.randrange(50) for _ in range(2 * controls.STALE_WAVE)]
    names = [f"check-{i:05d}" for i in range(len(backlog))]
    picks = reference_zoned.decide(copy.deepcopy(cluster), backlog,
                                   seed % 1000)
    after = dict(before, **{nm: cluster.names[p]
                            for nm, p in zip(names, picks)})
    record = {"check": {"backlog": backlog, "names": names,
                        "before": before, "after": after}}
    read = controls.stale_wave(record, cfg)
    assert read["sound"] == 0 and read["stale_wave"] >= 1


# -- the served path, at a tiny zoned size ------------------------------------

#: the per-layer entries the readers wave_path_share.py and
#: score_us_per_pod.py are written for. BENCHMARK.json does not carry
#: them yet: tests/benchmark/test_benchmark_encode.py pins its last two
#: per-layer entries, and a PR that adds a cell may edit no file the
#: benchmark has (PERF.md section 7). A manifest with them appended
#: runs them as it will once that line is relaxed.
PATH_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": "single-chip driver", "moves": "pods_bound_per_s",
     "workloads": ["spread-3k.fill"]}
    for name, unit, better, source in [
        ("wave_path_share.scan.fill", "%", "lower", "program_counter"),
        ("wave_path_share.grouped.fill", "%", "higher", "program_counter"),
        ("score_us_per_pod.fill", "us/pod", "lower", "program_span"),
    ]]


def _manifest_with_path_metrics():
    manifest = copy.deepcopy(deploy.load_manifest())
    manifest["per_layer"] += copy.deepcopy(PATH_METRICS)
    return manifest



@pytest.fixture(scope="module")
def traced_zoned_fill(tmp_path_factory):
    """spread-3k.fill through benchmark/run.serve below its look for a
    chip: 18 nodes in 3 zones, 12 controllers, the fill mix cut to
    match; only counts change."""
    import json

    from benchmark import run

    d = tmp_path_factory.mktemp("tiny-zoned")
    cfg = _cfg(18, 12)
    cfg["pods"]["population"] = 300
    (d / "spread-3k.json").write_text(json.dumps(cfg))
    fill = deploy.load_json(deploy.traffic_path("fill"))
    fill.update(workers=2, chunk=50, backlog_cap=128, warm_s=0.5,
                drain_s=2.0, check={"pods": 64})
    (d / "fill.json").write_text(json.dumps(fill))
    manifest = _manifest_with_path_metrics()
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "spread-3k.fill")
    saved = dict(os.environ)
    try:
        return run.serve(cell, str(d / "spread-3k.json"),
                         str(d / "fill.json"), 2 ** 31 + 77, 2.0, True,
                         manifest)
    finally:
        os.environ.clear()
        os.environ.update(saved)


def test_served_zoned_picks_read_zero_on_all_eight_counts(traced_zoned_fill):
    result = traced_zoned_fill
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["numbers"]) == 8
    assert all(tuple(pair) == (0, 0) for pair in result["numbers"].values())


def test_traced_zoned_run_reports_which_path_decided(traced_zoned_fill):
    """Controllers dealt in turn make runs of length 1: the serial scan
    decides every pod, the grouped paths none, and `score` is where its
    time shows."""
    got = traced_zoned_fill["metrics"]
    want = {m["name"]: m["unit"]
            for m in _manifest_with_path_metrics()["per_layer"]
            if "spread-3k.fill" in m["workloads"]}
    host_side = {n for n in want if not n.startswith("device_")}
    assert host_side <= set(got) <= set(want)
    assert got["wave_path_share.scan.fill"] == {"value": 100.0, "unit": "%"}
    assert got["wave_path_share.grouped.fill"] == {"value": 0.0, "unit": "%"}
    assert got["score_us_per_pod.fill"]["unit"] == "us/pod"
    assert got["score_us_per_pod.fill"]["value"] > 0
    assert got["dispatches_per_wave.fill"]["value"] >= 1
    # and /debug/traces tells the same story for the process
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})["wave"]
    assert shown["pods_by_path"]["scan"] > 0
    assert shown["dispatches_by_kind"]["scan"] > 0


def test_new_readers_give_nothing_on_a_program_without_the_tally():
    """The parent commit keeps no `pods_by_path`: the reader returns
    nothing and the result line leaves the metric out."""
    from types import SimpleNamespace as NS

    from benchmark.layers import wave_path_share

    old = NS(scheduler=NS(config=NS(algorithm=NS(_wave=NS(
        stats={"waves": 3, "dispatches": 6})))))
    snap = wave_path_share.snapshot({"sched": old})
    assert snap == {}
    run = {"snapshots": {"wave_path_share": (snap, snap)},
           "metric": "wave_path_share.scan.fill"}
    assert wave_path_share.read(run) is None


@pytest.mark.parametrize("metric,share", [
    ("wave_path_share.scan.fill", 75.0),
    ("wave_path_share.grouped.fill", 20.0),
])
def test_wave_path_share_is_a_diff_over_the_window(metric, share):
    from benchmark.layers import wave_path_share

    before = {"scan": 1000, "single": 10, "group_host": 0,
              "group_device": 5}
    after = {"scan": 1750, "single": 60, "group_host": 120,
             "group_device": 85}
    run = {"snapshots": {"wave_path_share": (before, after)},
           "metric": metric}
    assert wave_path_share.read(run) == pytest.approx(share)
