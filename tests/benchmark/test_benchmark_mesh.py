"""mesh-20k and its cell mesh-20k.fill: the manifest's rules for them,
the deployment's file, the precision control on a run's record
(benchmark/control_precision.py), and the three readers written for
the mesh driver (mesh_collective_share, mesh_shard_skew,
mesh_h2d_bytes_per_kpod) on runs made by hand and on the served path at
a tiny size, on the test process's virtual devices."""

import copy
import json
import os
import random

import numpy as np
import pytest

from benchmark import check, control_precision, deploy, reference_zoned
from benchmark.layers import (
    mesh_collective_share,
    mesh_h2d_bytes_per_kpod,
    mesh_shard_skew,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the per-layer entries the three readers are written for.
#: BENCHMARK.json does not carry them: tests/benchmark/
#: test_benchmark_encode.py pins its last two per-layer entries, the
#: builder's contract reads an entry put in the middle of a list as a
#: change to what was there, and a PR that adds a cell may edit no file
#: the benchmark has (PERF.md section 7). A manifest with them appended
#: runs them as it will once that line is relaxed. `pods_per_wave.fill`
#: and `dispatches_per_wave.fill` are true on the mesh since the
#: algorithm names its driver `_wave` on either build, and wait beside
#: them: on the parent's mesh algorithm their `snapshot` raises, in
#: untraced runs too.
MESH_METRICS = [
    {"name": name, "unit": unit, "better": "lower", "source": source,
     "layer": layer, "moves": "pods_bound_per_s",
     "workloads": ["mesh-20k.fill"]}
    for name, unit, source, layer in [
        ("mesh_collective_share.fill", "%", "device_trace", "device"),
        ("mesh_shard_skew.fill", "ratio", "program_counter",
         "mesh driver"),
        ("mesh_h2d_bytes_per_kpod.fill", "B/kpod", "program_counter",
         "mesh driver"),
    ]]


def _manifest_with_mesh_metrics():
    manifest = copy.deepcopy(deploy.load_manifest())
    manifest["per_layer"] += copy.deepcopy(MESH_METRICS)
    for m in manifest["per_layer"]:
        if m["name"] in ("pods_per_wave.fill", "dispatches_per_wave.fill"):
            m["workloads"].append("mesh-20k.fill")
    return manifest


# -- the manifest and the deployment's file -----------------------------------

def test_the_cell_is_the_manifests_one_four_chip_cell():
    manifest = deploy.load_manifest()
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "mesh-20k.fill")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mesh-20k", "fill", 4)
    assert manifest["workloads"][-1] is cell  # appended
    assert [w["name"] for w in manifest["workloads"]
            if w["chips"] == 4] == ["mesh-20k.fill"]
    config = next(c for c in manifest["configs"] if c["name"] == "mesh-20k")
    assert manifest["configs"][-1] is config
    assert config["reduced"] == ["hollow_fleet"]
    assert os.path.exists(os.path.join(REPO, config["file"]))
    assert os.path.exists(deploy.traffic_path("fill"))
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200


def test_the_cell_reports_two_end_to_end_metrics_and_its_layers():
    from benchmark import run

    manifest = deploy.load_manifest()
    ends = {m["name"] for m in run.metrics_of(manifest, "end_to_end",
                                              "mesh-20k.fill")}
    assert ends == {"pods_bound_per_s", "setup_s"}
    layers = {m["name"]: m for m in run.metrics_of(manifest, "per_layer",
                                                   "mesh-20k.fill")}
    assert len(layers) >= 10 and all(
        m["moves"] == "pods_bound_per_s" for m in layers.values())
    assert {"device_idle_share.fill", "window_compiles.fill",
            "create_ack_p50_ms.fill",
            "device_idle_by_host.encode.fill"} <= set(layers)
    # off the lists whose readers are not true on the mesh, or raise on
    # the parent's mesh algorithm, or divide what the program did
    # between two reads of its counters by the window's length: the
    # harness makes the second read when the profiler has written its
    # trace, 45 s after a 50 s window's end on four chips (PERF.md
    # section 7)
    assert not {"h2d_bytes_per_kpod.fill", "encode_us_per_pod.fill",
                "pods_per_wave.fill", "dispatches_per_wave.fill",
                "sched_host_busy_share.fill", "sched_idle_share.fill",
                "sched_unattributed_share.fill",
                "api_requests_per_kpod.fill",
                "apiserver_gc_pause_share.fill"} & set(layers)
    # every listed metric's reader is a file that is there
    for name in layers:
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layers", name.split(".", 1)[0] + ".py"))


def test_the_deployments_file_states_its_size_and_its_guarantees():
    cfg = deploy.load_config("mesh-20k")
    zoned = deploy.load_config("spread-3k")
    assert cfg["source"] == next(
        c["source"] for c in deploy.load_manifest()["configs"]
        if c["name"] == "mesh-20k")
    # the source's own counts: 5k nodes and 50k pods (configs[4]), 500
    # controllers (configs[3])
    assert cfg["nodes"]["count"] == 5_000
    assert cfg["controllers"]["count"] == 500
    assert cfg["controllers"]["count"] * cfg["controllers"]["replicas"] \
        == cfg["pods"]["population"] == 50_000
    assert cfg["scheduler"]["env"] == {"KUBERNETES_TPU_MESH": "force",
                                       "KUBERNETES_TPU_WARM_SCAN": "1"}
    # every shard of the node axis holds nodes
    assert "4 chips" in cfg["deployment"]
    assert "1,280 / 1,280 / 1,280 / 1,160" in cfg["deployment"]
    assert cfg["reduced"] == ["hollow_fleet"] and cfg["assumed"]
    # spread-3k's shapes, semantics and reference: only the scale and
    # the driver differ
    assert cfg["reference"] == zoned["reference"] == \
        "benchmark/reference_zoned.py"
    assert check.load_reference(cfg).__name__.endswith("reference_zoned")
    for key in ("allocatable", "labels", "zones", "name_format"):
        assert cfg["nodes"][key] == zoned["nodes"][key]
    for key in ("requests", "labels", "container"):
        assert cfg["pods"][key] == zoned["pods"][key]
    assert set(cfg["guarantees"]) == set(zoned["guarantees"])
    # the same arithmetic is stated, and held (the control below)
    stated = "SelectorSpreadPriority in float32 as upstream computes it"
    assert zoned["guarantees"]["arithmetic"].startswith(stated)
    assert cfg["guarantees"]["arithmetic"].startswith(stated)
    assert "control_precision.py" in cfg["guarantees"]["arithmetic"]
    nodes = deploy.nodes(cfg)
    assert len(nodes) == 5_000
    assert nodes[4_999]["metadata"]["labels"] == {
        "kubernetes.io/hostname": "znode-04999",
        "failure-domain.beta.kubernetes.io/zone": "abc"[4_999 % 3]}
    assert len(deploy.controllers(cfg)) == 500


# -- the precision control, on a record as the load generator writes it -------

def _record_at_the_deployments_proportions(cfg, rng, batch):
    """A cluster as a window leaves it (84 of a controller's 100
    replicas bound, spread as the scheduler spreads them) and a check
    batch of the controllers dealt in turn, decided by the reference in
    the stated precision: the record of a sound run."""
    controllers = cfg["controllers"]["count"]
    order = list(range(controllers))
    rng.shuffle(order)
    cluster = reference_zoned.Cluster(cfg)
    filled = [order[i % controllers] for i in range(84 * controllers)]
    reference_zoned.decide(cluster, filled, rng.randrange(1000))
    before, serial = {}, 0
    for t in range(controllers):
        for node in np.flatnonzero(cluster.peers[t]):
            for _ in range(int(cluster.peers[t, node])):
                before[f"p-t{t}-{serial:08d}"] = cluster.names[node]
                serial += 1
    backlog = [order[(len(filled) + i) % controllers] for i in range(batch)]
    names = [f"check-{i:05d}" for i in range(batch)]
    picks = reference_zoned.decide(copy.deepcopy(cluster), backlog,
                                   rng.randrange(1000))
    after = dict(before, **{nm: cluster.names[p]
                            for nm, p in zip(names, picks)})
    return {"check": {"backlog": backlog, "names": names,
                      "before": before, "after": after}}


@pytest.mark.parametrize("nodes,controllers,seed", [
    (300, 30, 51), (300, 30, 2 ** 31 + 52), (600, 60, 53),
])
def test_lower_precision_scorer_fails_on_this_deployments_shape(
        nodes, controllers, seed):
    """benchmark/control_precision.py on a record of this deployment cut
    to a test's size (nodes : controllers = 10 : 1, 3 zones, 100
    replicas a controller): the run's own picks read 0, the float16
    scorer's do not, and the stale wave's number rides beside them."""
    cfg = deploy.load_config("mesh-20k")
    cfg["nodes"]["count"] = nodes
    cfg["controllers"]["count"] = controllers
    record = _record_at_the_deployments_proportions(
        cfg, random.Random(seed), 16 * controllers)
    read = control_precision.lower_precision(record, cfg)
    assert read["sound"] == 0 and read["float16"] >= 1


def test_precision_control_says_nothing_where_no_precision_is_stated():
    """density-1k's reference states none: only the run's own count."""
    cfg = deploy.load_config("density-1k")
    cfg["nodes"]["count"] = 20
    record = {"check": {"backlog": [0, 0], "names": ["check-0", "check-1"],
                        "before": {}, "after": {}}}
    assert set(control_precision.lower_precision(record, cfg)) == {"sound"}


# -- the readers, on runs made by hand ----------------------------------------

def _traced(ops, chips=4, busy_s=2.0):
    return {"trace": {"chips": chips, "busy_s": busy_s, "device_ops": ops}}


def test_collective_share_is_the_collectives_time_over_all_chips_busy_time():
    run = _traced([["fusion.1591", 5.0], ["all-reduce.76", 0.6],
                   ["multiply_reduce_fusion.2", 0.5], ["all-gather.14", 0.3],
                   ["collective-permute.2", 0.06], ["psum.161", 0.2],
                   ["reduce-scatter", 0.04], ["add_reduce_fusion.9", 1.9]])
    # 1.2 s under collectives (jax names an all-reduce after the psum
    # that made it) of 2.0 s x 4 chips
    assert mesh_collective_share.read(run) == pytest.approx(15.0)
    assert mesh_collective_share.read(_traced([["fusion.1", 1.0]])) == 0.0


@pytest.mark.parametrize("run", [
    {}, {"trace": None},
    _traced([["all-reduce.3", 0.8]], chips=1),
    _traced([["all-reduce.3", 0.8]], chips=0, busy_s=0.0),
    _traced([], chips=4, busy_s=0.0),
], ids=["untraced", "no-trace", "one-chip", "no-chip", "idle"])
def test_collective_share_gives_nothing_without_a_mesh_at_work(run):
    assert mesh_collective_share.read(run) is None


def test_shard_skew_is_the_fullest_shard_over_the_mean():
    run = {"snapshots": {"mesh_shard_skew": (
        [100, 100, 100, 100], [8292, 8292, 3716, 100])}}
    # the window's picks: 8,192 / 8,192 / 3,616 / 0, as 20,000 nodes
    # filled four shards of 8,192 slots from the front before the mesh
    # driver's node axis grew by a step (PERF.md section 6)
    assert mesh_shard_skew.read(run) == pytest.approx(8192 / 5000.0)
    even = {"snapshots": {"mesh_shard_skew": ([0] * 4, [50] * 4)}}
    assert mesh_shard_skew.read(even) == 1.0


def test_h2d_bytes_are_a_diff_over_the_window_per_thousand_pods():
    run = {"snapshots": {"mesh_h2d_bytes_per_kpod": (
        {"bytes": 1_000_000}, {"bytes": 81_000_000})},
        "loadgen": {"bound_in_window": 40_000}}
    assert mesh_h2d_bytes_per_kpod.read(run) == pytest.approx(2_000_000.0)


def test_mesh_readers_give_nothing_on_a_program_without_the_tallies():
    """The parent's mesh algorithm has no `_wave`, and the single-chip
    driver's stats have neither key: each reader returns nothing, does
    not raise, and the result line leaves the metric out."""
    from types import SimpleNamespace as NS

    def sched(algorithm):
        return {"sched": NS(scheduler=NS(config=NS(algorithm=algorithm)))}

    parents_mesh = sched(NS(_mesh_sched=NS(dispatches={})))
    one_chip = sched(NS(_wave=NS(stats={"waves": 3, "dispatches": 6})))
    for ctx in (parents_mesh, one_chip):
        skew = mesh_shard_skew.snapshot(ctx)
        assert skew == []
        assert mesh_shard_skew.read(
            {"snapshots": {"mesh_shard_skew": (skew, skew)}}) is None
        h2d = mesh_h2d_bytes_per_kpod.snapshot(ctx)
        assert h2d == {"bytes": None}
        assert mesh_h2d_bytes_per_kpod.read(
            {"snapshots": {"mesh_h2d_bytes_per_kpod": (h2d, h2d)},
             "loadgen": {"bound_in_window": 10}}) is None


# -- the served path, sharded, at a tiny zoned size ---------------------------

@pytest.fixture(scope="module")
def traced_mesh_fill(tmp_path_factory):
    """mesh-20k.fill through benchmark/run.serve below its look for a
    chip, the daemon's node axis sharded over the test process's
    virtual devices: 96 nodes in 3 zones, 30 controllers, the fill mix
    cut to match; only counts change."""
    from benchmark import run

    d = tmp_path_factory.mktemp("tiny-mesh")
    cfg = deploy.load_config("mesh-20k")
    cfg["nodes"]["count"] = 96
    cfg["controllers"]["count"] = 30
    cfg["pods"]["population"] = 400
    (d / "mesh-20k.json").write_text(json.dumps(cfg))
    fill = deploy.load_json(deploy.traffic_path("fill"))
    fill.update(workers=2, chunk=50, backlog_cap=128, warm_s=0.5,
                drain_s=2.0, check={"pods": 64})
    (d / "fill.json").write_text(json.dumps(fill))
    manifest = _manifest_with_mesh_metrics()
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "mesh-20k.fill")
    saved = dict(os.environ)
    # waves here hold at most the 128 pods of the backlog cap: the
    # warm-up stops at the 256 bucket instead of compiling up to 4,096
    # and a device holds 8 node slots, not 256, so that the 96 nodes
    # lie on every shard
    from kubernetes_tpu.scheduler import core, tpu_algorithm

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(core, "WAVE_CAP", 256)
        patch.setattr(tpu_algorithm, "MESH_SLOTS_PER_SHARD", 8)
        try:
            # (a window of five seconds: under a whole test run's load
            # the profiler's start and a wave on eight virtual devices
            # can take two, and a window that binds nothing reports
            # neither the generator's metrics nor the bytes a pod)
            return run.serve(cell, str(d / "mesh-20k.json"),
                             str(d / "fill.json"), 2 ** 31 + 91, 5.0, True,
                             manifest)
        finally:
            os.environ.clear()
            os.environ.update(saved)


def test_served_mesh_picks_read_zero_on_all_eight_counts(traced_mesh_fill):
    result = traced_mesh_fill
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["numbers"]) == 8
    assert all(tuple(pair) == (0, 0) for pair in result["numbers"].values())


def test_traced_mesh_run_reports_the_mesh_drivers_metrics(traced_mesh_fill):
    got = traced_mesh_fill["metrics"]
    want = {m["name"] for m in _manifest_with_mesh_metrics()["per_layer"]
            if "mesh-20k.fill" in m["workloads"]}
    # a CPU has no device plane: what reads the device trace is left out
    host_side = {n for n in want if not n.startswith(
        ("device_", "mesh_collective_share"))}
    assert host_side <= set(got) <= want
    assert got["window_compiles.fill"]["value"] == 0
    assert got["mesh_shard_skew.fill"]["unit"] == "ratio"
    assert 1.0 <= got["mesh_shard_skew.fill"]["value"] <= 8.0
    assert got["mesh_h2d_bytes_per_kpod.fill"]["value"] > 0
    assert got["pods_per_wave.fill"]["value"] > 0
    assert got["dispatches_per_wave.fill"]["value"] >= 1
    # and /debug/traces tells the same story for the process: the
    # sharded scan decided, off the incremental encoder's view
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})
    assert shown["wave"]["pods_by_path"]["scan"] > 0
    assert shown["wave"]["dispatches_by_kind"]["scan"] > 0
    assert shown["encoder"]["events"] > 0 and shown["encoder"]["batches"] > 0
    assert shown["pending_rows"]["row_hits"] > 0
    built = {c["program"] for c in shown["compiles"]}
    assert not any("unnamed" in p or "unknown" in p for p in built), built
