"""The deployment whose pods keep off each other's nodes,
antiaffinity-2k: its file against the contract and the source's shapes,
the pods it makes against goldens, its mix's arithmetic, its plain
reference (benchmark/reference_antiaffinity.py) against the program's
serial oracle, the controls against the reference (and why the
deployment has two controllers a group), the guarantee as the
comparison holds it, and its cell on the served path at a tiny size,
beside density-1k cut the same way."""

import copy
import hashlib
import json
import os
import random

import numpy as np
import pytest

from benchmark import (
    check,
    control_antiaffinity,
    controls,
    deploy,
    loadgen,
    reference_antiaffinity,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "antiaffinity-2k.rows-2k"
BIG_SEED = 2 ** 31 + 43
ANNOTATION = "scheduler.alpha.kubernetes.io/affinity"
MI = 2 ** 20


def _cfg(nodes=None, replicas=None):
    """antiaffinity-2k, or antiaffinity-2k cut to a test's size: only
    counts change, the ten controllers, the five groups and their terms
    stay."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "antiaffinity-2k.json"))
    if nodes is not None:
        cfg["nodes"]["count"] = nodes
    if replicas is not None:
        cfg["controllers"]["replicas"] = replicas
        cfg["pods"]["population"] = replicas * cfg["controllers"]["count"]
    return cfg


def _one_controller_a_group(cfg):
    """The same five groups under five controllers: what the source's
    bare pods would be if a template could be a group."""
    cfg = copy.deepcopy(cfg)
    cfg["controllers"]["count"] = 5
    return cfg


def _mix():
    return deploy.load_json(deploy.traffic_path("rows-2k"))


# -- the deployment file, the manifest's entries, the mix ---------------------

def test_the_file_is_the_sources_deployment():
    cfg = _cfg()
    manifest = deploy.load_manifest()
    entry = manifest["configs"][-1]
    assert entry["name"] == cfg["name"] == "antiaffinity-2k"
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == ["hollow_fleet"]
    assert cfg["reference"] == "benchmark/reference_antiaffinity.py"
    assert cfg["nodes"]["count"] == 2000 and cfg["nodes"]["zones"] == []
    assert cfg["nodes"]["allocatable"] == {
        "cpu": "4", "memory": "32Gi", "pods": "110"}
    assert deploy.nodes(cfg)[7]["metadata"]["labels"] == {
        "kubernetes.io/hostname": "node-00007"}
    # 5 groups x 1,000 under 10 controllers x 500: the source's 5,000
    c = cfg["controllers"]
    assert (c["count"], c["replicas"]) == (10, 500)
    assert c["count"] * c["replicas"] == cfg["pods"]["population"] == 5000
    made = deploy.controllers(cfg)
    assert [rc["metadata"]["name"] for rc in made] == \
        [f"anti-{t}" for t in range(10)]
    assert made[7]["spec"] == {"selector": {"group": "g7"}, "replicas": 500}
    # cpu alone: no memory request anywhere
    assert cfg["pods"]["requests"] == {"cpu": "100m"}
    assert len(cfg["pods"]["shapes"]) == 5
    assert all(set(s) == {"annotations"} for s in cfg["pods"]["shapes"])
    assert cfg["scheduler"] == {
        "provider": "TPUProvider",
        "env": {"KUBERNETES_TPU_MESH": "off",
                "KUBERNETES_TPU_WARM_SCAN": "1"}}
    assert cfg["apiserver"]["store"] == "memory"
    assert set(cfg["guarantees"]) == {"bound_once", "capacity", "decisions",
                                      "anti_affinity", "arithmetic",
                                      "durability"}
    assert len(cfg["assumed"]) == 3 and "cut_to_size" in cfg


def test_the_cell_and_its_metrics_are_appended_to_the_manifest():
    manifest = deploy.load_manifest()
    cell = manifest["workloads"][-1]
    assert cell == {**cell, "name": CELL, "config": "antiaffinity-2k",
                    "traffic": "rows-2k", "chips": 1}
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    new = ["probe_us_per_pod.fill", "anti_run_share.fill",
           "anti_excluded_node_share.fill", "encode_full_share.fill"]
    assert [m["name"] for m in manifest["per_layer"][-4:]] == new
    assert by_name["probe_us_per_pod.fill"]["workloads"] == \
        [CELL, "density-1k.fill"]
    for name in new[1:]:
        assert by_name[name]["workloads"] == [CELL]
    assert {by_name[n]["layer"] for n in new[:3]} == {"single-chip driver"}
    assert by_name[new[3]]["layer"] == "scheduler host side"
    assert {by_name[n]["moves"] for n in new} == {"pods_bound_per_s"}
    # it joins every `.fill` list that holds both of the cells whose
    # paths it shares, and the five it was told to; always at the end
    bound = next(m for m in manifest["end_to_end"]
                 if m["name"] == "pods_bound_per_s")
    assert bound["workloads"][-1] == CELL
    named = {"encode_us_per_pod.fill", "replay_us_per_pod.fill",
             "h2d_bytes_per_kpod.fill", "wave_path_share.scan.fill",
             "wave_path_share.grouped.fill"}
    for m in manifest["per_layer"][:-4]:
        cells = m["workloads"]
        joins = m["name"] in named or (
            m["name"].endswith(".fill") and "density-1k.fill" in cells
            and "hetero-1k.rows" in cells)
        assert (CELL in cells) == joins, m["name"]
        assert CELL not in cells[:-1]
    # every reader of the cell loads, and a run of the cell finds its files
    from benchmark import run

    found, cfg_path, mix_path = run.find_cell(manifest, CELL)
    assert found is cell and os.path.exists(cfg_path) \
        and os.path.exists(mix_path)
    readers = run.load_readers(
        run.metrics_of(manifest, "per_layer", CELL),
        os.path.join(REPO, "benchmark", "layers"))
    assert set(new) <= set(readers)
    assert all(hasattr(mod, "read") for mod in readers.values())


def test_the_mix_holds_the_parameters_it_was_given():
    mix, cfg = _mix(), _cfg()
    assert {k: mix[k] for k in (
        "loop", "workers", "replicas_in_a_row", "chunk", "backlog_cap",
        "check", "warm_s")} == {
        "loop": "closed", "workers": 6, "replicas_in_a_row": 40,
        "chunk": 200, "backlog_cap": 2000, "check": {"runs": 50},
        "warm_s": 3.0}
    assert mix["prefill_steps"][0] == {"one_of_each": True}
    # whole runs only, in the steps and in a request
    assert all(step["pods"] % 40 == 0 for step in mix["prefill_steps"][1:])
    assert mix["chunk"] == 5 * mix["replicas_in_a_row"]
    assert 0 < mix["trace_slice_s"] <= 4.0
    for key in ("chunk", "backlog_cap", "prefill_steps", "trace_slice_s",
                "check"):
        assert mix[key + "_is"]
    # the hold: what the accepted mixes' cap of 8,192 would make negative
    # (loadgen.Generator: hold = population - backlog_cap)
    assert cfg["pods"]["population"] - mix["backlog_cap"] == 3000
    assert cfg["pods"]["population"] - deploy.load_json(
        deploy.traffic_path("rows"))["backlog_cap"] < 0
    # 300 of a controller, 600 of a group: 30% of the nodes
    assert 3000 // 5 / cfg["nodes"]["count"] == 0.3
    # the check batch ends at the population, and no controller is
    # drawn twice running, on any seed
    for seed in (0, 1, 77, BIG_SEED, 2 ** 31 + 1234):
        batch = loadgen.check_backlog(cfg, mix["check"], seed, 40)
        assert 3000 + len(batch) == cfg["pods"]["population"]
        runs = batch[::40]
        assert batch == [t for t in runs for _ in range(40)]
        assert all(a != b for a, b in zip(runs, runs[1:]))
        # no group can outgrow the nodes: at most every other run is one
        # controller's, 25 runs of 50
        assert max(np.bincount(np.array(runs) % 5) * 40) + 600 <= 2000
    # the stream: ten controllers take turns, so a request of five runs
    # holds five controllers, and two requests side by side never put
    # one controller's runs next to each other
    order = loadgen.template_order(cfg, BIG_SEED)
    stream = [loadgen.template_of(order, 40, j) for j in range(0, 4000, 40)]
    assert sorted(stream[:10]) == list(range(10))
    for a in range(0, 100, 5):
        for b in range(0, 100, 5):
            if a != b:
                assert stream[a + 4] != stream[b]


#: sha256 (16 hex) of a template's pod as sorted JSON
GOLDEN_PODS = {0: "49b076325f47bb9e", 7: "af0fe0e319d387d4"}


def _sha(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def test_a_templates_pod_is_what_it_was():
    cfg = _cfg()
    for t in range(10):
        made = deploy.pod(cfg, t, name=f"p-t{t}-00000001")
        assert made["metadata"]["labels"] == {"group": f"g{t}"}
        # cpu alone, as the source: no memory request
        assert made["spec"] == {"containers": [{
            "name": "pause", "image": "kubernetes/pause:go",
            "requests": {"cpu": "100m"}}]}
        stated = json.loads(made["metadata"]["annotations"][ANNOTATION])
        k = t % 5
        assert stated == {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchExpressions": [{
                    "key": "group", "operator": "In",
                    "values": [f"g{k}", f"g{k + 5}"]}]},
                "topologyKey": "kubernetes.io/hostname"}]}}
        # the term selects the pod's own group: itself and its twin
        assert made["metadata"]["labels"]["group"] in \
            stated["podAntiAffinity"][
                "requiredDuringSchedulingIgnoredDuringExecution"][0][
                "labelSelector"]["matchExpressions"][0]["values"]
    assert deploy.pod(cfg, 3, prefix="x")["metadata"]["generateName"] \
        == "xt3-"
    for t, golden in GOLDEN_PODS.items():
        assert _sha(deploy.pod(cfg, t, name=f"p-t{t}-00000001")) == golden
    # the program reads the same term off the pod
    from kubernetes_tpu.api.types import get_affinity, has_pod_affinity
    from kubernetes_tpu.client import rest

    pod = rest.default_scheme.decode(deploy.pod(cfg, 7, name="p"))
    assert has_pod_affinity(pod)
    term, = get_affinity(pod).pod_anti_affinity \
        .required_during_scheduling_ignored_during_execution
    assert term.topology_key == "kubernetes.io/hostname"
    assert list(term.label_selector.match_expressions[0].values) == ["g2", "g7"]


# -- the reference ------------------------------------------------------------

def test_the_reference_reads_the_terms_off_the_shapes():
    cluster = reference_antiaffinity.Cluster(_cfg(8))
    want = np.zeros((10, 10), np.int64)
    for t in range(10):
        want[t, t % 5] = want[t, t % 5 + 5] = 1
    assert np.array_equal(cluster.match, want)
    assert (cluster.pod_cpu, cluster.pod_mem) == (100, 0)
    assert (cluster.nz_pod_cpu, cluster.nz_pod_mem) == (100, 200 * MI)
    # matchLabels and matchExpressions both, and what it does not score
    shape = {"requests": {"cpu": "100m"}, "annotations": {ANNOTATION: json.dumps({
        "podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"group": "g1"},
                                  "matchExpressions": [{
                                      "key": "tier", "operator": "In",
                                      "values": ["a", "b"]}]},
                "topologyKey": "kubernetes.io/hostname"}]}})}}
    wants = reference_antiaffinity.term_of(shape)
    assert reference_antiaffinity.selects(wants, {"group": "g1", "tier": "b"})
    assert not reference_antiaffinity.selects(wants, {"group": "g1"})
    assert not reference_antiaffinity.selects(None, {"group": "g1"})
    assert reference_antiaffinity.term_of({"requests": {}}) is None
    for broken in (
            {"podAffinity": {}, "podAntiAffinity": {}},
            {"podAntiAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{}]}},
            {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"a": "b"}},
                    "topologyKey": "failure-domain.beta.kubernetes.io/zone",
                }]}},
            {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchExpressions": [{
                        "key": "a", "operator": "NotIn", "values": ["b"]}]},
                    "topologyKey": "kubernetes.io/hostname"}]}}):
        with pytest.raises(ValueError):
            reference_antiaffinity.term_of(
                {"annotations": {ANNOTATION: json.dumps(broken)}})
    with pytest.raises(ValueError):
        reference_antiaffinity.term_of({"nodeSelector": {"a": "b"}})
    # InterPodAffinityPriority, written out: 0 on every node
    assert not cluster._inter_pod_affinity().any()


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_antiaffinity.py", "control_antiaffinity.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            source = f.read()
        assert "kubernetes_tpu" not in source.split('"""', 2)[2]


def _oracle_state(cfg, live):
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import ClusterState

    scheme = rest.default_scheme
    bound = []
    for name, (t, node) in live.items():
        pod = scheme.decode(deploy.pod(cfg, t, name=name))
        pod.spec.node_name = deploy.node_name(cfg, node)
        bound.append(pod)
    return ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])


#: (nodes, rounds, pods a round, share deleted between rounds, seed);
#: the small ones fill: a group takes every node and its pods fit
#: nowhere on both sides
ORACLE_CASES = [(40, 3, 40, 0.3, 1), (24, 5, 40, 0.2, 2 ** 31 + 2),
                (8, 4, 30, 0.25, 3), (48, 3, 50, 0.5, 4),
                (12, 6, 25, 0.1, 2 ** 31 + 5)]


@pytest.mark.parametrize("nodes,rounds,batch,deleted,seed", ORACLE_CASES)
def test_reference_decides_as_the_programs_oracle(nodes, rounds, batch,
                                                  deleted, seed):
    """Pick for pick over a seeded stream, binds after every round and
    deletes between rounds: the serial generic scheduler of the program
    (default provider) against the reference, each on its own copy of
    the cluster."""
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import GenericScheduler

    scheme = rest.default_scheme
    rng = random.Random(seed)
    cfg = _cfg(nodes)
    live = {}  # name -> (template, node)
    counter = rng.randrange(10_000)
    nowhere = 0
    for r in range(rounds):
        cluster = reference_antiaffinity.Cluster(cfg)
        for t, node in live.values():
            cluster.bind(t, node)
        assert cluster.over_allocatable() == 0
        backlog = [t for t in (rng.randrange(10) for _ in range(batch // 5))
                   for _ in range(5)]
        names = [f"r{r}-{i:04d}" for i in range(len(backlog))]
        pods = [scheme.decode(deploy.pod(cfg, t, name=nm))
                for nm, t in zip(names, backlog)]
        want = GenericScheduler(last_node_index=counter).schedule_backlog(
            pods, _oracle_state(cfg, live))
        start = copy.deepcopy(cluster)
        got = reference_antiaffinity.decide(cluster, backlog, counter)
        assert [cluster.names[g] if g is not None else None
                for g in got] == want
        held = reference_antiaffinity.verify(start, backlog, got)
        assert held["mismatches"] == 0 and held["checked"] == len(backlog)
        residue, modulus = held["counter"]
        assert counter % modulus == residue
        counter += sum(g is not None for g in got)
        nowhere += got.count(None)
        for nm, t, g in zip(names, backlog, got):
            if g is not None:
                live[nm] = (t, g)
        for nm in rng.sample(sorted(live), int(deleted * len(live))):
            del live[nm]
    assert (nowhere > 0) == (nodes <= 24)


def test_a_group_that_holds_every_node_fits_nowhere_on_both_sides():
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import GenericScheduler

    cfg = _cfg(6)
    live = {f"old-{i}": (i % 2 * 5 + 1, i) for i in range(6)}  # g1 and g6
    cluster = reference_antiaffinity.Cluster(cfg)
    for t, node in live.values():
        cluster.bind(t, node)
    assert len(cluster.ranking(1)) == len(cluster.ranking(6)) == 0
    assert len(cluster.ranking(2)) > 0
    pods = [rest.default_scheme.decode(deploy.pod(cfg, t, name=f"n{t}"))
            for t in (1, 6, 2)]
    want = GenericScheduler().schedule_backlog(pods,
                                               _oracle_state(cfg, live))
    assert want[:2] == [None, None] and want[2] is not None
    assert reference_antiaffinity.decide(cluster, [1, 6, 2], 0)[:2] \
        == [None, None]


def test_the_resource_priorities_count_the_non_zero_requests():
    """priorities.go:55-60: a container that states no memory counts
    200Mi in LeastRequested and BalancedResourceAllocation, 0 in the
    fit."""
    cfg = _cfg(2)
    cfg["nodes"]["allocatable"] = {"cpu": "1", "memory": "1Gi", "pods": "110"}
    cluster = reference_antiaffinity.Cluster(cfg)
    cluster.bind(0, 0)
    cluster.bind(1, 0)
    assert (cluster.req_cpu[0], cluster.req_mem[0]) == (200, 0)
    assert (cluster.nz_cpu[0], cluster.nz_mem[0]) == (200, 400 * MI)
    cluster.incoming = 2
    # node 0 with the pod: cpu 300 of 1000, memory 600Mi of 1024Mi
    assert list(cluster._least_requested()) == [(7 + 4) // 2, (9 + 8) // 2]
    assert list(cluster._balanced()) == [
        int(10 - abs(0.3 - 600 / 1024) * 10),
        int(10 - abs(0.1 - 200 / 1024) * 10)]
    cluster.nonzero_defaults = False  # what the control scores with
    assert list(cluster._least_requested()) == [(7 + 10) // 2, (9 + 10) // 2]
    # the fit counts what is stated: five pods of 200Mi would not fit
    # 1Gi, and do
    for t in (2, 3, 4):
        cluster.bind(t, 0)
    cluster.incoming = 5
    assert cluster.fits().tolist() == [False, True]  # the term, not memory
    cluster.term_holds = False
    assert cluster.fits().tolist() == [True, True]


# -- the guarantee, as the comparison holds it --------------------------------

def _record(cfg, before, backlog, picks, names_of):
    names = [f"check-{i:05d}" for i in range(len(backlog))]
    after = dict(before, **{nm: names_of[p]
                            for nm, p in zip(names, picks) if p is not None})
    return {"check": {"backlog": backlog, "names": names, "before": before,
                      "after": after, "created": len(backlog)},
            "live": dict(after), "at_rest": True, "double_bound": 0}


def test_two_of_a_group_on_a_node_is_over_allocatable_and_not_correct():
    cfg = _cfg(16)
    cluster = reference_antiaffinity.Cluster(cfg)
    before = {}
    for i, t in enumerate((0, 1, 2, 5, 8)):
        cluster.bind(t, i)
        before[f"p-t{t}-{i:08d}"] = cluster.names[i]
    assert cluster.over_allocatable() == 0
    backlog = [3, 3, 7]
    picks = reference_antiaffinity.decide(copy.deepcopy(cluster), backlog, 5)
    sound = check.decide(_record(cfg, before, backlog, picks, cluster.names),
                         cfg, out=open(os.devnull, "w"))
    assert sound["correct"] is True
    assert all(v == 0 for v, _limit in sound["numbers"].values())
    # a pod of controller 5 (group 0) where controller 0's pod stands
    two = copy.deepcopy(cluster)
    two.bind(5, 0)
    assert two.over_allocatable() == 1
    # two of ONE controller count as well; two groups on a node do not
    same = copy.deepcopy(cluster)
    same.bind(2, 2)
    other = copy.deepcopy(cluster)
    other.bind(3, 0)
    assert (same.over_allocatable(), other.over_allocatable()) == (1, 0)
    broken = _record(cfg, before, backlog, picks, cluster.names)
    broken["check"]["after"]["p-t5-99999999"] = cluster.names[0]
    broken["live"]["p-t5-99999999"] = cluster.names[0]
    said = check.decide(broken, cfg, out=open(os.devnull, "w"))
    assert said["correct"] is False
    assert said["numbers"]["nodes_over_allocatable"] == (1, 0)
    # and a check pick onto a node the term excludes is off the reference
    onto = list(picks)
    onto[2] = 2  # controller 7 is group 2's, as the pod bound on node 2
    said = check.decide(_record(cfg, before, backlog, onto, cluster.names),
                        cfg, out=open(os.devnull, "w"))
    assert said["correct"] is False
    assert said["numbers"]["picks_off_reference"][0] >= 1
    assert said["numbers"]["nodes_over_allocatable"] == (1, 0)


# -- the controls -------------------------------------------------------------

def _control_record(cfg, seed, bound_runs, check_runs, row):
    """A record as the generator writes it: a cluster filled by the
    reference's own serial loop in runs of `row` and a seeded check
    batch decided the same way."""
    rng = random.Random(seed)
    n = deploy.num_templates(cfg)
    cluster = reference_antiaffinity.Cluster(cfg)
    order = loadgen.template_order(cfg, seed)
    stream = [loadgen.template_of(order, row, j)
              for j in range(bound_runs * row)]
    placed = reference_antiaffinity.decide(cluster, stream, seed % 1000)
    before = {f"p-t{t}-{i:08d}": cluster.names[node]
              for i, (t, node) in enumerate(zip(stream, placed))}
    assert None not in placed
    runs = []
    while len(runs) < check_runs:
        t = rng.randrange(n)
        if not runs or t != runs[-1]:
            runs.append(t)
    backlog = [t for t in runs for _ in range(row)]
    picks = reference_antiaffinity.decide(copy.deepcopy(cluster), backlog,
                                          seed % 1000 + len(stream))
    return _record(cfg, before, backlog, picks, cluster.names)


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 47])
def test_the_term_ignored_moves_picks_with_two_controllers_a_group(seed):
    """Finding 5 of ISSUE 43, pinned: a controller's selector is its
    pods' own labels, so with ONE controller a group SelectorSpread
    keeps off exactly the nodes the term excludes and a scheduler that
    never read the term picks the same nodes; with two, spread counts a
    controller's pods and the term the group's, and the control moves
    picks and leaves nodes holding two of a group. It is why the
    deployment has ten controllers."""
    cfg = _cfg(96, 16)
    assert check.load_reference(cfg).__name__.endswith(
        "reference_antiaffinity")
    record = _control_record(cfg, seed, bound_runs=10, check_runs=12, row=8)
    read = control_antiaffinity.broken(record, cfg)
    assert read["sound"] == 0
    assert read["term_ignored"] >= 20
    assert read["term_ignored_nodes_with_two"] >= 5
    # what this cell cannot see: every pod asks for the same, so the
    # two resource priorities rank the nodes alike on either requests
    assert read["stated_requests"] == 0
    assert controls.stale_wave(record, cfg)["sound"] == 0
    one = _one_controller_a_group(cfg)
    record = _control_record(one, seed, bound_runs=10, check_runs=12, row=8)
    read = control_antiaffinity.broken(record, one)
    assert (read["sound"], read["term_ignored"],
            read["term_ignored_nodes_with_two"]) == (0, 0, 0)
    # a deployment without terms has no such control to read
    flat = deploy.load_config("density-1k")
    flat["nodes"]["count"] = 6
    empty = {"check": {"backlog": [0] * 8, "before": {}, "after": {},
                       "names": [f"check-{i:05d}" for i in range(8)]}}
    assert set(control_antiaffinity.broken(empty, flat)) == {"sound"}


# -- the served path, at a tiny size ------------------------------------------

def _tiny_mix():
    """rows-2k cut to the tiny deployment: runs of 16, five runs a
    request, the cap a whole turn of the ten controllers (so every
    group always has bound pods, as the hold of 3,000 sees to at full
    size), a check batch of five runs."""
    mix = _mix()
    mix.update(workers=2, chunk=80, replicas_in_a_row=16, backlog_cap=160,
               warm_s=0.5, drain_s=6.0, check={"runs": 5},
               prefill_steps=[{"one_of_each": True}, {"pods": 16},
                              {"pods": 16}, {"pods": 160}])
    return mix


def _serve_tiny(d, workload, cfg, mix, seed, seconds, manifest=None):
    from benchmark import run

    (d / "cfg.json").write_text(json.dumps(cfg))
    (d / "mix.json").write_text(json.dumps(mix))
    manifest = manifest or deploy.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    saved = dict(os.environ)
    try:
        result = run.serve(cell, str(d / "cfg.json"), str(d / "mix.json"),
                           seed, seconds, True, manifest)
    finally:
        os.environ.clear()
        os.environ.update(saved)
    record = deploy.load_json(os.path.join(
        REPO, ".bench_out", f"{workload}-{seed}-1", "loadgen.json"))
    return result, record


@pytest.fixture(scope="module")
def traced_anti(tmp_path_factory):
    """128 nodes, the ten controllers at 32 replicas: 64 pods a group,
    160 held bound."""
    from kubernetes_tpu.trace import profile

    before = profile.wave_totals()
    cfg = _cfg(128, 32)
    result, record = _serve_tiny(tmp_path_factory.mktemp("tiny-anti"), CELL,
                                 cfg, _tiny_mix(), BIG_SEED, 4.0)
    return result, record, cfg, before, profile.wave_totals()


def _correct_on_all_eight_counts(result):
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["numbers"]) == 8
    assert all(tuple(pair) == (0, 0) for pair in result["numbers"].values())


def test_served_runs_are_decided_one_vetoed_probe_a_run(traced_anti):
    result, record, _cfg_, before, after = traced_anti
    _correct_on_all_eight_counts(result)
    batch = record["check"]["backlog"]
    assert len(batch) == 80 and len(set(batch)) > 1
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
    # every run carries the veto and goes through run_single; a run
    # that a wave's end cuts under 16 pods is the scan's
    assert got["anti_run_share.fill"]["value"] >= 90
    assert got["wave_path_share.grouped.fill"]["value"] == 0
    assert got["wave_path_share.scan.fill"]["value"] <= 10
    # 160 bound, 32 of a group, on 128 nodes: a quarter of the nodes
    # gone before a run starts, and what the wave's earlier runs took
    assert 15 <= got["anti_excluded_node_share.fill"]["value"] <= 75
    assert got["probe_us_per_pod.fill"]["value"] > 0
    # the gate, counted: every wave from the from-scratch encoder
    assert got["encode_full_share.fill"] == {"value": 100.0, "unit": "%"}
    moved = {k: after["encoder_fallbacks"].get(k, 0)
             - before["encoder_fallbacks"].get(k, 0)
             for k in after["encoder_fallbacks"]}
    assert set(k for k, v in moved.items() if v) == {"affinity"}
    assert moved["affinity"] == after["waves_by_encoder"]["full"] \
        - before["waves_by_encoder"].get("full", 0) > 0
    assert after["anti_runs"] > before["anti_runs"]
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})
    assert {"anti_runs", "anti_picks", "anti_nodes_excluded",
            "waves_by_encoder", "encoder_fallbacks"} <= set(shown["wave"])
    # not under `encoder`: tests/benchmark/test_benchmark_encode.py
    # holds that object to its three keys
    assert not {"waves_by_encoder", "encoder_fallbacks"} & set(
        shown["encoder"])


def test_the_controls_fail_on_the_served_runs_own_record(traced_anti):
    _result, record, cfg, _before, _after = traced_anti
    read = control_antiaffinity.broken(record, cfg)
    assert read["sound"] == 0 and read["term_ignored"] >= 1
    assert controls.stale_wave(record, cfg)["sound"] == 0


def test_density_cut_the_same_way_never_leaves_the_kept_snapshot(
        tmp_path):
    """`encode_full_share.fill` 0 and no fallback counted where no pod
    carries a term: the manifest lists the metric for the new cell
    alone, so the test lends it to density-1k.fill."""
    from kubernetes_tpu.trace import profile

    manifest = copy.deepcopy(deploy.load_manifest())
    for m in manifest["per_layer"]:
        if m["name"] in ("encode_full_share.fill", "anti_run_share.fill"):
            m["workloads"] = m["workloads"] + ["density-1k.fill"]
    cfg = deploy.load_config("density-1k")
    cfg["nodes"]["count"] = 128
    cfg["pods"]["population"] = 320
    mix = deploy.load_json(deploy.traffic_path("fill"))
    mix.update(workers=2, chunk=80, backlog_cap=160, warm_s=0.5,
               drain_s=4.0, check={"pods": 64})
    before = profile.wave_totals()
    result, _record_ = _serve_tiny(tmp_path, "density-1k.fill", cfg, mix,
                                   BIG_SEED + 1, 2.0, manifest)
    after = profile.wave_totals()
    _correct_on_all_eight_counts(result)
    got = result["metrics"]
    assert got["encode_full_share.fill"] == {"value": 0.0, "unit": "%"}
    assert got["anti_run_share.fill"] == {"value": 0.0, "unit": "%"}
    assert got["probe_us_per_pod.fill"]["value"] > 0
    assert after["encoder_fallbacks"] == before["encoder_fallbacks"]
    assert after["waves_by_encoder"]["incremental"] \
        > before["waves_by_encoder"].get("incremental", 0)


# -- the new readers on a program without the counters ------------------------

def test_new_readers_give_nothing_on_a_program_without_the_counters():
    """The parent commit keeps `pods_by_path` and none of the five new
    counters: each reader returns nothing and the result line leaves
    its metric out."""
    from types import SimpleNamespace as NS

    from benchmark.layers import (
        anti_excluded_node_share,
        anti_run_share,
        encode_full_share,
    )

    paths = {"scan": 5, "single": 95, "group_host": 0, "group_device": 0}
    parent = {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
        _wave=NS(stats={"waves": 3, "pods_by_path": paths})))))}
    for mod in (anti_run_share, anti_excluded_node_share, encode_full_share):
        snap = mod.snapshot(parent)
        assert snap == {}
        name = mod.__name__.rsplit(".", 1)[-1]
        assert mod.read({"snapshots": {name: (snap, snap)},
                         "config": {"nodes": {"count": 2000}}}) is None


@pytest.mark.parametrize("reader,before,after,want", [
    ("anti_run_share", {"anti_picks": 100, "decided": 1000},
     {"anti_picks": 2080, "decided": 3000}, 99.0),
    ("anti_excluded_node_share", {"excluded": 1000, "runs": 10},
     {"excluded": 41000, "runs": 60}, 40.0),
    ("encode_full_share", {"incremental": 7, "full": 1},
     {"incremental": 7, "full": 41}, 100.0),
    ("encode_full_share", {"incremental": 0, "full": 0},
     {"incremental": 30, "full": 10}, 25.0),
    ("probe_us_per_pod", {"probe": 1.0, "encode": 9.0},
     {"probe": 1.5, "encode": 11.0}, 250.0),
])
def test_the_new_readers_divide_a_diff(reader, before, after, want):
    import importlib

    mod = importlib.import_module("benchmark.layers." + reader)
    run = {"snapshots": {reader: (before, after)},
           "metric": reader + ".fill",
           "config": {"nodes": {"count": 2000}},
           "loadgen": {"bound_in_window": 2000}}
    assert mod.read(run) == pytest.approx(want)
    # nothing moved between the reads: nothing to divide by
    run["snapshots"][reader] = (after, after)
    run["loadgen"]["bound_in_window"] = 0
    assert mod.read(run) is None
