"""The deployment whose services differ in kind, mixed-5k: its file
against the source's counts and the stated shapes, the pods it makes,
the manifest's entries (by membership, never by position), its plain
reference (benchmark/reference_mixed.py) against the program's serial
oracle on the five shapes dealt in rows (through the escape and with a
preferred-affinity maximum above 0), the priority's arithmetic written
out by hand with positive and negative weights in one cluster, the
stated memory in the two resource priorities, the reference's refusals,
the two read-back guarantees as the comparison holds them, the
controls, the three readers, and its cell on the served path at a tiny
size with the daemon's re-warm of the run programs."""

import copy
import json
import os
import random

import numpy as np
import pytest

from benchmark import check, control_mixed, controls, deploy, reference_mixed
from tests.benchmark.test_benchmark_antiaffinity import (
    _correct_on_all_eight_counts,
    _oracle_state,
    _record,
    _serve_tiny,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mixed-5k.rows-12k"
BIG_SEED = 2 ** 31 + 49
ANNOTATION = "scheduler.alpha.kubernetes.io/affinity"
ZONE = "failure-domain.beta.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
NEW_METRICS = {"steps_per_wave.fill": ("steps/wave", "lower"),
               "scan_pods_per_flush.fill": ("pods", "higher"),
               "self_preferred_scan_share.fill": ("%", "lower")}
#: the `.fill` lists of the zone-affinity cell that the mixed cell stays
#: out of: the first reads 0 on one zone by construction, the second is
#: held to one cell by tests/benchmark/test_benchmark_podaffinity.py
LEFT_OUT = ("affinity_excluded_node_share.fill", "interpod_scan_share.fill")


def _cfg(nodes=None, replicas=None):
    """mixed-5k, or mixed-5k cut to a test's size: only counts change,
    the ten controllers, the five kinds, the one zone and the terms
    stay."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "mixed-5k.json"))
    if nodes is not None:
        cfg["nodes"]["count"] = nodes
    if replicas is not None:
        cfg["controllers"]["replicas"] = replicas
        cfg["pods"]["population"] = replicas * cfg["controllers"]["count"]
    return cfg


def _selector(k):
    return {"matchExpressions": [{"key": "group", "operator": "In",
                                  "values": [f"g{k}", f"g{k + 5}"]}]}


def _stated(k):
    """What shape k's annotation states (None for the plain shape)."""
    soft = [{"weight": 1, "podAffinityTerm": {
        "labelSelector": _selector(k), "topologyKey": HOST}}]
    return [None,
            {"podAffinity": {REQUIRED: [{
                "labelSelector": _selector(1), "topologyKey": ZONE}]}},
            {"podAntiAffinity": {REQUIRED: [{
                "labelSelector": _selector(2), "topologyKey": HOST}]}},
            {"podAffinity": {PREFERRED: soft}},
            {"podAntiAffinity": {PREFERRED: soft}}][k]


def test_the_file_is_the_sources_deployment():
    cfg = _cfg()
    manifest = deploy.load_manifest()
    entry, = [c for c in manifest["configs"] if c["name"] == "mixed-5k"]
    assert entry["file"] == "benchmark/configs/mixed-5k.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for part in ("scheduler_perf", "performance-config.yaml",
                 "MixedSchedulingBasePod", "5000Nodes"):
        assert part in entry["source"]
    assert entry["reduced"] == cfg["reduced"] == ["hollow_fleet"]
    assert deploy.load_config("mixed-5k") == cfg
    # every count is the source's 5000Nodes workload's
    assert cfg["nodes"]["count"] == 5000
    assert cfg["nodes"]["allocatable"] == {"cpu": "4", "memory": "32Gi",
                                           "pods": "110"}
    assert cfg["nodes"]["zones"] == ["zone1"]
    assert deploy.node_labels(cfg, 4321) == {HOST: "node-04321",
                                             ZONE: "zone1"}
    assert cfg["pods"]["requests"] == {"cpu": "100m", "memory": "500Mi"}
    assert len(cfg["pods"]["shapes"]) == 5
    assert cfg["controllers"] == {**cfg["controllers"], "count": 10,
                                  "replicas": 1200,
                                  "name_format": "mix-{t}"}
    mix = deploy.load_json(deploy.traffic_path("rows-12k"))
    held = cfg["pods"]["population"] - mix["backlog_cap"]
    assert cfg["pods"]["population"] == 12_000 and held == 10_000
    assert held // 5 == 2000  # initPods a template
    assert cfg["scheduler"]["env"] == {"KUBERNETES_TPU_MESH": "off",
                                       "KUBERNETES_TPU_WARM_SCAN": "1"}
    assert cfg["reference"] == "benchmark/reference_mixed.py"
    assert {"bound_once", "capacity", "decisions", "anti_affinity",
            "zone_affinity", "arithmetic"} <= set(cfg["guarantees"])
    assert len(cfg["assumed"]) >= 6 and "not at all" in cfg["cut_to_size"]


def test_the_mix_is_rows_2k_parameter_for_parameter():
    mix = deploy.load_json(deploy.traffic_path("rows-12k"))
    base = deploy.load_json(deploy.traffic_path("rows-2k"))
    for key in ("loop", "workers", "chunk", "replicas_in_a_row",
                "backlog_cap", "warm_s", "check"):
        assert mix[key] == base[key], key
    assert mix["name"] == "rows-12k" and mix["check"] == {"runs": 50}
    assert 0 < mix["trace_slice_s"] <= 4.0
    assert mix["prefill_steps"][0] == {"one_of_each": True}
    assert all(set(step) <= {"one_of_each", "pods"}
               for step in mix["prefill_steps"])


def test_the_cell_and_its_metrics_are_in_the_manifest_by_membership():
    manifest = deploy.load_manifest()
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "mixed-5k", "traffic": "rows-12k",
                    "chips": 1}
    assert 0 < len(cell["why"]) <= 200
    assert len(manifest["workloads"]) >= 10
    assert [w["name"] for w in manifest["workloads"]
            if w["chips"] == 4] == ["mesh-20k.fill"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better) in NEW_METRICS.items():
        m = by_name[name]
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "single-chip driver",
                     "moves": "pods_bound_per_s", "workloads": [CELL]}
    bound, = [m for m in manifest["end_to_end"]
              if m["name"] == "pods_bound_per_s"]
    assert CELL in bound["workloads"]
    # it joins every `.fill` list that holds the zone-affinity cell but
    # two, and no other list than its own three
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            continue
        joins = (m["name"].endswith(".fill")
                 and "podaffinity-2k.rows-2k" in m["workloads"]
                 and m["name"] not in LEFT_OUT)
        assert (CELL in m["workloads"]) == joins, m["name"]
    for name in LEFT_OUT:
        assert by_name[name]["workloads"] == ["podaffinity-2k.rows-2k"]
    # a run of the cell finds its files, and every reader of it loads
    from benchmark import run

    found, cfg_path, mix_path = run.find_cell(manifest, CELL)
    assert found == cell and os.path.exists(cfg_path)
    assert mix_path == deploy.traffic_path("rows-12k")
    readers = run.load_readers(
        run.metrics_of(manifest, "per_layer", CELL),
        os.path.join(REPO, "benchmark", "layers"))
    assert set(NEW_METRICS) <= set(readers)
    assert all(hasattr(mod, "read") for mod in readers.values())
    assert all(hasattr(readers[name], "snapshot") for name in NEW_METRICS)


def test_a_templates_pod_is_of_its_kind():
    cfg = _cfg()
    for t in range(10):
        pod = deploy.pod(cfg, t, name=f"p-{t}")
        assert pod["metadata"]["labels"] == {"group": f"g{t}"}
        assert pod["spec"]["containers"][0]["requests"] \
            == {"cpu": "100m", "memory": "500Mi"}
        stated = _stated(t % 5)
        if stated is None:
            assert "annotations" not in pod["metadata"]
        else:
            assert json.loads(
                pod["metadata"]["annotations"][ANNOTATION]) == stated
    # the program reads each annotation as the kind it is
    from kubernetes_tpu.api.types import get_affinity
    from kubernetes_tpu.client import rest

    kinds = []
    for t in range(5):
        affinity = get_affinity(rest.default_scheme.decode(
            deploy.pod(cfg, t, name=f"q-{t}")))
        kinds.append(affinity and tuple(
            len(getattr(getattr(affinity, side), when, ()))
            for side in ("pod_affinity", "pod_anti_affinity")
            for when in ("required_during_scheduling_ignored_during_execution",
                         "preferred_during_scheduling_ignored_during_execution")))
    assert kinds == [None, (1, 0, 0, 0), (0, 0, 1, 0), (0, 1, 0, 0),
                     (0, 0, 0, 1)]


def test_the_reference_reads_the_terms_off_the_shapes():
    cluster = reference_mixed.Cluster(_cfg(12))
    assert cluster.templates == 10 and cluster.num_zones == 1
    assert (cluster.pod_cpu, cluster.pod_mem) == (100, 500 * 2 ** 20)
    for t, terms in enumerate(cluster.terms):
        want = {"affinity": 0, "anti": 0, "pref_affinity": 0, "pref_anti": 0}
        if t % 5:
            want[("affinity", "anti", "pref_affinity",
                  "pref_anti")[t % 5 - 1]] = 1
        assert {k: len(terms[k]) for k in want} == want
        assert terms["states_anti"] == (t % 5 in (2, 4))
        for kind in want:
            for term in terms[kind]:
                mask, dom = term[-2], term[-1]
                assert mask.tolist() == [int(u % 5 == t % 5)
                                         for u in range(10)]
                zone = kind == "affinity"
                assert len(set(dom.tolist())) == (1 if zone else 12)
                if kind.startswith("pref"):
                    assert term[0] == 1


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_mixed.py", "control_mixed.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            source = f.read()
        assert "kubernetes_tpu" not in source.split('"""', 2)[2]


@pytest.mark.parametrize("change,message", [
    (lambda p: p["shapes"][1].update(nodeSelector={"a": "b"}),
     "nodeSelector"),
    (lambda p: p["shapes"][0].update(requests={"cpu": "200m"}),
     "one request shape"),
    (lambda p: p["requests"].pop("memory"), "stated cpu and memory"),
    (lambda p: p["shapes"][2].update(annotations={ANNOTATION: json.dumps(
        {"podAntiAffinity": {REQUIRED: [{
            "labelSelector": _selector(2), "topologyKey": ""}]}})}),
     "empty topologyKey"),
    (lambda p: p["shapes"][3].update(annotations={ANNOTATION: json.dumps(
        {"podAffinity": {PREFERRED: [{"weight": 1, "podAffinityTerm": {
            "labelSelector": _selector(3), "topologyKey": HOST,
            "namespaces": ["kube-system"]}}]}})}), "namespaces"),
    (lambda p: p["shapes"][4].update(annotations={ANNOTATION: json.dumps(
        {"podAntiAffinity": {PREFERRED: [{"weight": 1, "podAffinityTerm": {
            "labelSelector": {"matchExpressions": [{
                "key": "group", "operator": "NotIn", "values": ["g4"]}]},
            "topologyKey": HOST}}]}})}), "NotIn"),
    (lambda p: p["shapes"][1].update(annotations={ANNOTATION: json.dumps(
        {"nodeAffinity": {}})}), "nodeAffinity"),
])
def test_the_reference_refuses_what_it_does_not_score(change, message):
    cfg = _cfg(8)
    change(cfg["pods"])
    with pytest.raises(ValueError, match=message):
        reference_mixed.Cluster(cfg)
    for name in ("density-1k", "antiaffinity-2k"):
        # the references of the deployments it borrows from refuse it
        other = check.load_reference(deploy.load_config(name))
        with pytest.raises(ValueError):
            other.Cluster(_cfg(8))


def _serial(cfg, cluster, live, backlog, names, counter):
    """The program's serial oracle and the reference on one backlog,
    each on its own copy of the cluster; the picks bound to `live`."""
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import GenericScheduler

    scheme = rest.default_scheme
    pods = [scheme.decode(deploy.pod(cfg, t, name=nm))
            for nm, t in zip(names, backlog)]
    want = GenericScheduler(last_node_index=counter).schedule_backlog(
        pods, _oracle_state(cfg, live))
    start = copy.deepcopy(cluster)
    got = reference_mixed.decide(cluster, backlog, counter)
    assert [cluster.names[g] if g is not None else None
            for g in got] == want
    held = reference_mixed.verify(start, backlog, got)
    assert held["mismatches"] == 0 and held["checked"] == len(backlog)
    residue, modulus = held["counter"]
    assert counter % modulus == residue
    for nm, t, g in zip(names, backlog, got):
        if g is not None:
            live[nm] = (t, g)
    return got


#: (one-zone nodes, rounds, runs a round, pods a run, share deleted
#: between rounds, seed): every stream begins with a round of lone pods
#: of all ten controllers (service 1's first pod passes by the escape;
#: from then on service 3's preferred podAffinity term gives a maximum
#: above 0 and service 4's a minimum below it); a share of 1.0 empties
#: the services, and the escape is met again. Small: the program's
#: serial oracle takes a second a pod once hundreds are bound
ORACLE_CASES = [(24, 3, 8, 6, 0.3, 1), (48, 2, 10, 8, 0.5, 2 ** 31 + 2),
                (12, 4, 6, 5, 1.0, 3), (96, 2, 12, 5, 0.2, 4)]


@pytest.mark.parametrize("nodes,rounds,runs,row,deleted,seed", ORACLE_CASES)
def test_reference_decides_as_the_programs_oracle(nodes, rounds, runs, row,
                                                  deleted, seed):
    """Pick for pick over a seeded stream of rows of all five kinds,
    binds after every round and deletes between rounds."""
    rng = random.Random(seed)
    cfg = _cfg(nodes)
    live = {}  # name -> (template, node)
    counter = rng.randrange(10_000)
    most = least = 0
    for r in range(rounds + 1):
        cluster = reference_mixed.Cluster(cfg)
        for t, node in live.values():
            cluster.bind(t, node)
        assert cluster.over_allocatable() == 0
        if r == 0:
            backlog = rng.sample(range(10), 10)
        else:
            backlog = [t for t in (rng.randrange(10) for _ in range(runs))
                       for _ in range(row)]
        names = [f"r{r}-{i:04d}" for i in range(len(backlog))]
        got = _serial(cfg, cluster, live, backlog, names, counter)
        counter += sum(g is not None for g in got)
        # the anti-affine service's pods are alone on their nodes, and
        # nothing else fits nowhere
        held = [n for t, n in live.values() if t % 5 == 2]
        assert len(held) == len(set(held))
        assert all(g is not None or t % 5 == 2
                   for t, g in zip(backlog, got))
        # what the priority's normalisation meets on this cluster
        fit = np.ones(nodes, bool)
        for t in (3, 4):
            cluster.incoming = t
            cluster._inter_pod_affinity(fit)
        most = max(most, int((cluster.peers[3] + cluster.peers[8]).max()))
        least = max(least, int((cluster.peers[4] + cluster.peers[9]).max()))
        for nm in rng.sample(sorted(live), int(deleted * len(live))):
            del live[nm]
    assert most > 0 and least > 0


def test_the_priority_sums_all_four_kinds_with_both_signs_in_one_cluster():
    """Six nodes, by hand. Bound: two pods of service 3 (preferred
    podAffinity) on node 0, one pod of service 4 (preferred
    podAntiAffinity) on node 1, one pod of service 1 (required
    podAffinity over the one zone) on node 2. Each total is upstream's
    sum, maxCount and minCount start at 0, the division is float64 and
    the score truncated."""
    cluster = reference_mixed.Cluster(_cfg(6))
    for t, node in ((3, 0), (8, 0), (4, 1), (1, 2)):
        cluster.bind(t, node)
    fit = np.ones(6, bool)

    def score(t):
        cluster.incoming = t
        return cluster._inter_pod_affinity(fit).tolist()

    # a pod of service 3: its own term counts the two on node 0, and
    # each of the two bound pods' terms selects it: 2 + 2 = 4 of 4
    assert score(3) == [10, 0, 0, 0, 0, 0]
    # a pod of service 4: -1 of its own term and -1 of the bound pod's
    # on node 1: totals 0 and -2, min -2, max 0
    assert score(9) == [10, 0, 10, 10, 10, 10]
    # a pod of service 1: the bound pod's REQUIRED podAffinity term
    # selects it, symmetric weight 1 on every node of the zone: all
    # equal above 0, max 1, min 0, so every node reads 10
    assert score(6) == [10] * 6
    # a plain pod, and one of the anti-affine service: no term counts
    assert score(0) == [0] * 6 and score(7) == [0] * 6
    # both signs in one total: give service 3's selector a match on the
    # incoming pod of service 4 by hand (a pod that two services select)
    both = copy.deepcopy(cluster)
    both.terms[9]["pref_affinity"] = cluster.terms[3]["pref_affinity"]
    both.incoming = 9
    # node 0: +2, node 1: -2, the rest 0: (total + 2) / 4 x 10, truncated
    assert both._inter_pod_affinity(fit).tolist() == [10, 0, 5, 5, 5, 5]
    # and only over the nodes that fit
    assert both._inter_pod_affinity(np.array([0, 1, 1, 1, 1, 1], bool)) \
        .tolist() == [0, 0, 10, 10, 10, 10]


def test_the_resource_priorities_count_the_stated_memory():
    cluster = reference_mixed.Cluster(_cfg(4))
    for _ in range(7):
        cluster.bind(0, 1)
    cluster.incoming = 5
    # node 1 with the pod on it: 800m of 4,000 and 4,000Mi of 32Gi
    assert cluster._scored_totals()[1].tolist()[1] == 8 * 500 * 2 ** 20
    assert cluster._least_requested().tolist() == [9, 8, 9, 9]
    assert cluster._balanced().tolist() == [9, 9, 9, 9]
    # the control's scorer counts 200Mi a pod: another balance on a
    # node that holds pods, and the fit is the stated one either way
    blind = copy.deepcopy(cluster)
    blind.memory_scored = control_mixed.DEFAULT_MEMORY
    assert blind._scored_totals()[1].tolist()[1] == 8 * 200 * 2 ** 20
    assert blind._balanced().tolist() == [9, 8, 9, 9]
    assert np.array_equal(blind.fits(), cluster.fits())


def test_two_of_the_anti_affine_service_on_a_node_are_not_correct():
    cfg = _cfg(16)
    cluster = reference_mixed.Cluster(cfg)
    before = {}
    for i, t in enumerate((0, 1, 2, 7, 3, 4)):
        cluster.bind(t, i)
        before[f"p-t{t}-{i:08d}"] = cluster.names[i]
    assert cluster.over_allocatable() == 0
    backlog = [2, 2, 7, 0, 6]
    picks = reference_mixed.decide(copy.deepcopy(cluster), backlog, 5)
    sound = check.decide(_record(cfg, before, backlog, picks, cluster.names),
                         cfg, out=open(os.devnull, "w"))
    assert sound["correct"] is True
    # a pod of controller 7 where controller 2's stands: one service
    two = copy.deepcopy(cluster)
    two.bind(7, 2)
    assert two.over_allocatable() == 1 and two.nodes_with_two().sum() == 1
    # two plain pods on a node, or two of a preferred service, are fine
    fine = copy.deepcopy(cluster)
    fine.bind(5, 0)
    fine.bind(9, 5)
    assert fine.over_allocatable() == 0
    astray = list(picks)
    astray[1] = 2  # onto the node that holds controller 2's pod
    bad = check.decide(_record(cfg, before, backlog, astray, cluster.names),
                       cfg, out=open(os.devnull, "w"))
    assert bad["correct"] is False
    assert bad["numbers"]["nodes_over_allocatable"][0] >= 1
    assert bad["numbers"]["picks_off_reference"][0] >= 1


def _control_record(cfg, seed, bound_runs, check_runs, row):
    """A record as the generator writes it: a cluster filled by the
    reference's own serial loop in runs of `row` and a seeded check
    batch decided the same way."""
    from benchmark import loadgen

    rng = random.Random(seed)
    cluster = reference_mixed.Cluster(cfg)
    order = loadgen.template_order(cfg, seed)
    stream = [loadgen.template_of(order, row, j)
              for j in range(bound_runs * row)]
    placed = reference_mixed.decide(cluster, stream, seed % 1000)
    assert None not in placed
    before = {f"p-t{t}-{i:08d}": cluster.names[node]
              for i, (t, node) in enumerate(zip(stream, placed))}
    runs = []
    while len(runs) < check_runs:
        t = rng.randrange(10)
        if not runs or t != runs[-1]:
            runs.append(t)
    backlog = [t for t in runs for _ in range(row)]
    picks = reference_mixed.decide(copy.deepcopy(cluster), backlog,
                                   seed % 1000 + len(stream))
    return _record(cfg, before, backlog, picks, cluster.names)


@pytest.mark.parametrize("seed", [49, 2 ** 31 + 491])
def test_each_control_reads_what_it_breaks(seed):
    """At 64 nodes with 32 pods of a service bound: the anti-affinity
    terms ignored put two of the service on a node, the preferred
    podAffinity terms ignored and the default memory move picks, and so
    does the stale wave. The required podAffinity term ignored moves
    none on one zone, at any size: every node is co-located with every
    pod, and the symmetric weight adds the same to all (the deployment
    file claims neither for the cell)."""
    cfg = _cfg(64, 64)
    assert check.load_reference(cfg).__name__.endswith("reference_mixed")
    record = _control_record(cfg, seed, bound_runs=20, check_runs=15, row=8)
    read = control_mixed.broken(record, cfg)
    assert set(read) == {"sound", *control_mixed.BROKEN,
                         "anti_ignored_nodes_with_two"}
    assert read["sound"] == 0
    assert read["affinity_ignored"] == 0
    assert read["anti_ignored"] >= 4
    assert read["anti_ignored_nodes_with_two"] >= 2
    assert read["pref_affinity_ignored"] >= 4
    assert read["preferred_ignored"] >= read["pref_affinity_ignored"] // 2
    assert read["default_memory"] >= 1
    stale = controls.stale_wave(record, cfg)
    assert stale["sound"] == 0 and stale["stale_wave"] >= 24
    # a deployment whose reference keeps no such switches reads `sound`
    flat = deploy.load_config("density-1k")
    flat["nodes"]["count"] = 6
    empty = {"check": {"backlog": [0] * 8, "before": {}, "after": {},
                       "names": [f"check-{i:05d}" for i in range(8)]}}
    assert set(control_mixed.broken(empty, flat)) == {"sound"}


def test_new_readers_give_nothing_on_a_program_without_the_counters():
    """The parent commit keeps `pods_by_path` and `scan_reasons` but
    neither `steps_by_kind` nor `scan_flushes`: those two readers return
    nothing and the result line leaves their metrics out."""
    from types import SimpleNamespace as NS

    from benchmark.layers import (
        scan_pods_per_flush,
        self_preferred_scan_share,
        steps_per_wave,
    )

    paths = {"scan": 95, "single": 5, "group_host": 0, "group_device": 0}

    def ctx(stats):
        return {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
            _wave=NS(stats=stats)))))}

    parent = ctx({"waves": 3, "pods_by_path": paths,
                  "scan_reasons": {"self_preferred": 40}})
    for mod in (steps_per_wave, scan_pods_per_flush):
        snap = mod.snapshot(parent)
        assert snap == {}
        name = mod.__name__.rsplit(".", 1)[-1]
        assert mod.read({"snapshots": {name: (snap, snap)}}) is None
    assert self_preferred_scan_share.snapshot(parent) \
        == {"self_preferred": 40, "decided": 100}
    older = ctx({"waves": 3, "pods_by_path": paths})
    assert self_preferred_scan_share.snapshot(older) == {}
    assert self_preferred_scan_share.read({"snapshots": {
        "self_preferred_scan_share": ({}, {})}}) is None


@pytest.mark.parametrize("reader,before,after,want", [
    ("steps_per_wave", {"steps": 30, "waves": 2},
     {"steps": 1230, "waves": 26}, 50.0),
    ("scan_pods_per_flush", {"pods": 1000, "flushes": 4},
     {"pods": 46_000, "flushes": 454}, 100.0),
    ("self_preferred_scan_share", {"self_preferred": 400, "decided": 1000},
     {"self_preferred": 30_400, "decided": 76_000}, 40.0),
])
def test_the_new_readers_divide_a_diff(reader, before, after, want):
    import importlib

    mod = importlib.import_module("benchmark.layers." + reader)
    run = {"snapshots": {reader: (before, after)},
           "metric": reader + ".fill"}
    assert mod.read(run) == pytest.approx(want)
    run["snapshots"][reader] = (after, after)  # nothing moved
    assert mod.read(run) is None


def test_the_readers_read_the_drivers_own_tallies():
    from types import SimpleNamespace as NS

    from benchmark.layers import scan_pods_per_flush, steps_per_wave

    stats = {"waves": 7, "scan_flushes": 18,
             "pods_by_path": {"scan": 1800, "single": 600,
                              "group_host": 0, "group_device": 600},
             "steps_by_kind": {"scan": 18, "single": 15, "group_host": 0,
                               "group_device": 6}}
    ctx = {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
        _wave=NS(stats=stats)))))}
    assert steps_per_wave.snapshot(ctx) == {"steps": 39, "waves": 7}
    assert scan_pods_per_flush.snapshot(ctx) == {"pods": 1800, "flushes": 18}


# -- the served path, at a tiny size ------------------------------------------

def _tiny_mix():
    """rows-12k cut to the tiny deployment: runs of 16, five runs a
    request, the cap a whole turn of the ten controllers, a check batch
    of ten runs."""
    mix = deploy.load_json(deploy.traffic_path("rows-12k"))
    mix.update(workers=2, chunk=80, replicas_in_a_row=16, backlog_cap=160,
               warm_s=0.5, drain_s=6.0, check={"runs": 10},
               prefill_steps=[{"one_of_each": True}, {"pods": 16},
                              {"pods": 16}, {"pods": 160}])
    return mix


@pytest.fixture(scope="module")
def traced_mixed(tmp_path_factory):
    """128 nodes in one zone, the ten controllers at 48 replicas: 96
    pods a service, 320 held bound, 64 of each kind."""
    from kubernetes_tpu.trace import profile

    before = profile.wave_totals()
    cfg = _cfg(128, 48)
    result, record = _serve_tiny(tmp_path_factory.mktemp("tiny-mixed"), CELL,
                                 cfg, _tiny_mix(), BIG_SEED, 4.0)
    return result, record, cfg, before, profile.wave_totals()


def test_a_served_wave_changes_path_run_by_run_and_stays_correct(
        traced_mixed):
    result, record, _cfg_, before, after = traced_mixed
    _correct_on_all_eight_counts(result)
    batch = record["check"]["backlog"]
    assert len(batch) == 160 and len({t % 5 for t in batch}) >= 3
    got = result["metrics"]
    want = {m["name"]: m["unit"]
            for m in deploy.load_manifest()["per_layer"]
            if CELL in m["workloads"]}
    # the CPU has no device plane: a reader that finds nothing to read
    # returns nothing, and the line leaves the metric out
    host_side = {n for n in want if not n.startswith("device_")}
    assert host_side <= set(got) <= set(want)
    for name, entry in got.items():
        assert entry["unit"] == want[name]
    # three services in five are the scan's, two of them for a preferred
    # term on their own copies; the plain and the anti-affine runs take
    # the device replay, alone or as neighbours, and no probe is read
    assert 50 <= got["wave_path_share.scan.fill"]["value"] <= 75
    assert 30 <= got["self_preferred_scan_share.fill"]["value"] <= 50
    assert got["wave_path_share.grouped.fill"]["value"] > 0
    assert 0 < got["anti_run_share.fill"]["value"] <= 25
    assert got["probe_us_per_pod.fill"]["value"] == 0.0
    assert got["replay_us_per_pod.fill"]["value"] > 0
    assert got["score_us_per_pod.fill"]["value"] > 0
    assert got["encode_full_share.fill"]["value"] == 0.0
    assert got["window_compiles.fill"]["value"] == 0
    # a wave is several steps, and a flush decides a stretch, not a wave
    assert got["steps_per_wave.fill"]["value"] > 1
    assert got["scan_pods_per_flush.fill"]["value"] \
        < got["pods_per_wave.fill"]["value"]
    steps = {k: after["steps_by_kind"].get(k, 0)
             - before["steps_by_kind"].get(k, 0) for k in
             after["steps_by_kind"]}
    assert steps["scan"] and steps["single"] and steps["group_device"]
    assert steps["group_host"] == 0
    assert after["scan_flushes"] - before["scan_flushes"] \
        == after["dispatches_by_kind"]["scan"] \
        - before["dispatches_by_kind"].get("scan", 0)
    moved = {k: after["scan_reasons"].get(k, 0)
             - before["scan_reasons"].get(k, 0)
             for k in after["scan_reasons"]}
    assert {k for k, v in moved.items() if v} == {"hard_affinity",
                                                  "self_preferred"}
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})
    assert {"steps_by_kind", "scan_flushes"} <= set(shown["wave"])


def test_the_rewarm_warms_the_run_programs_in_the_set_up(traced_mixed):
    result, record, _cfg_, before, after = traced_mixed
    assert after["rewarms"] - before["rewarms"] == 1
    assert after["rewarm_mismatches"] == before["rewarm_mismatches"]
    from kubernetes_tpu.trace import spans

    t0 = record["t0"]
    mine = [s for s in spans.BUFFER.snapshot(limit=16384)
            if s["name"] == "scheduler.rewarm" and s["start"] > t0 - 120]
    assert len(mine) == 1
    span, = mine
    assert span["start"] + span["duration"] < t0
    attrs = span["attrs"]
    assert attrs["buckets"] == [64, 128, 256, 512, 1024, 2048, 4096]
    assert attrs["left"] == 0 and attrs["programs"] > 0
    # each of the four device templates alone, the four side by side and
    # nine runs of two: the run-slot buckets of 8 and 16
    assert attrs["steps"]["single"] == 4
    assert attrs["steps"]["group_device"] == 2
    assert attrs["steps"]["scan"] >= 5
    # four specs under two topology keys, four logical terms, and a
    # domain a hostname
    assert (attrs["combos"], attrs["classes"], attrs["specs"],
            attrs["terms"], attrs["expansion"], attrs["domains"]) \
        == (2, 4, 4, 4, 1, 128)


def test_the_controls_read_the_served_runs_own_record(traced_mixed):
    _result, record, cfg, _before, _after = traced_mixed
    read = control_mixed.broken(record, cfg)
    assert read["sound"] == 0 and read["affinity_ignored"] == 0
    assert read["anti_ignored"] >= 1 and read["preferred_ignored"] >= 1
    assert controls.stale_wave(record, cfg)["sound"] == 0
