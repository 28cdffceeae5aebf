"""The deployment whose services each keep to one zone, podaffinity-2k:
its file against the contract and the stated shapes, the pods it makes,
the manifest's entries (by membership, never by position), its plain
reference (benchmark/reference_podaffinity.py) against the program's
serial oracle through the first pod of a service (the escape), its
second controller, a churn that empties a service and a wave of all
five, the priority's arithmetic written out by hand, the guarantee as
the comparison holds it, the controls, the two readers, and its cell on
the served path at a tiny size with the daemon's re-warm of the scan."""

import copy
import json
import os
import random

import numpy as np
import pytest

from benchmark import (
    check,
    control_podaffinity,
    controls,
    deploy,
    reference_podaffinity,
)
from tests.benchmark.test_benchmark_antiaffinity import (
    _correct_on_all_eight_counts,
    _oracle_state,
    _record,
    _serve_tiny,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "podaffinity-2k.rows-2k"
BIG_SEED = 2 ** 31 + 45
ANNOTATION = "scheduler.alpha.kubernetes.io/affinity"
ZONE = "failure-domain.beta.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
NEW_METRICS = ("interpod_scan_share.fill", "affinity_excluded_node_share.fill")


def _cfg(nodes=None, replicas=None):
    """podaffinity-2k, or podaffinity-2k cut to a test's size: only
    counts change, the ten controllers, the five services, the three
    zones and the terms stay."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "podaffinity-2k.json"))
    if nodes is not None:
        cfg["nodes"]["count"] = nodes
    if replicas is not None:
        cfg["controllers"]["replicas"] = replicas
        cfg["pods"]["population"] = replicas * cfg["controllers"]["count"]
    return cfg


def _selector(k):
    return {"matchExpressions": [{"key": "group", "operator": "In",
                                  "values": [f"g{k}", f"g{k + 5}"]}]}


# -- the deployment file and the manifest's entries ---------------------------

def test_the_file_is_the_stated_deployment():
    cfg = _cfg()
    manifest = deploy.load_manifest()
    entry, = [c for c in manifest["configs"] if c["name"] == "podaffinity-2k"]
    assert cfg["name"] == "podaffinity-2k"
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert entry["file"] == "benchmark/configs/podaffinity-2k.json"
    assert cfg["reduced"] == entry["reduced"] == ["hollow_fleet"]
    assert cfg["reference"] == "benchmark/reference_podaffinity.py"
    assert cfg["nodes"]["count"] == 2000
    assert cfg["nodes"]["zones"] == ["a", "b", "c"]
    assert cfg["nodes"]["allocatable"] == {
        "cpu": "4", "memory": "32Gi", "pods": "110"}
    made = deploy.nodes(cfg)
    assert made[7]["metadata"]["labels"] == {HOST: "node-00007", ZONE: "b"}
    assert np.bincount([ord(n["metadata"]["labels"][ZONE]) - ord("a")
                        for n in made]).tolist() == [667, 667, 666]
    c = cfg["controllers"]
    assert (c["count"], c["replicas"]) == (10, 500)
    assert c["count"] * c["replicas"] == cfg["pods"]["population"] == 5000
    rcs = deploy.controllers(cfg)
    assert [rc["metadata"]["name"] for rc in rcs] == \
        [f"aff-{t}" for t in range(10)]
    assert rcs[7]["spec"] == {"selector": {"group": "g7"}, "replicas": 500}
    assert cfg["pods"]["requests"] == {"cpu": "100m"}
    assert len(cfg["pods"]["shapes"]) == 5
    assert all(set(s) == {"annotations"} for s in cfg["pods"]["shapes"])
    assert cfg["scheduler"]["provider"] == "TPUProvider"
    assert cfg["scheduler"]["env"] == {"KUBERNETES_TPU_MESH": "off",
                                       "KUBERNETES_TPU_WARM_SCAN": "1"}
    assert cfg["scheduler"]["hardPodAffinitySymmetricWeight"] == 1 \
        == reference_podaffinity.HARD_POD_AFFINITY_SYMMETRIC_WEIGHT
    assert cfg["apiserver"]["store"] == "memory"
    assert set(cfg["guarantees"]) == {"bound_once", "capacity", "decisions",
                                      "zone_affinity", "arithmetic",
                                      "durability"}
    assert len(cfg["assumed"]) == 5 and "not at all" in cfg["cut_to_size"]
    # it differs from antiaffinity-2k in the zones and the annotation
    anti = deploy.load_config("antiaffinity-2k")
    for key in ("count", "name_format", "allocatable"):
        assert cfg["nodes"][key] == anti["nodes"][key]
    for key in ("population", "requests", "labels", "container"):
        assert cfg["pods"][key] == anti["pods"][key]
    assert (c["count"], c["replicas"]) == (anti["controllers"]["count"],
                                           anti["controllers"]["replicas"])


def test_the_cell_and_its_metrics_are_in_the_manifest_by_membership():
    manifest = deploy.load_manifest()
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "podaffinity-2k",
                    "traffic": "rows-2k", "chips": 1}
    assert 0 < len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["layer"] == "single-chip driver"
        assert m["moves"] == "pods_bound_per_s"
        assert m["source"] == "program_counter"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    bound, = [m for m in manifest["end_to_end"]
              if m["name"] == "pods_bound_per_s"]
    assert CELL in bound["workloads"]
    # it joins every `.fill` list that holds antiaffinity-2k's cell,
    # and score_us_per_pod.fill's; but not the list of the one reader
    # that finds nothing to read here (no run carries a self-anti veto)
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            continue
        cells = m["workloads"]
        joins = (m["name"] == "score_us_per_pod.fill"
                 or m["name"].endswith(".fill")
                 and "antiaffinity-2k.rows-2k" in cells
                 and m["name"] != "anti_excluded_node_share.fill")
        assert (CELL in cells) == joins, m["name"]
    # a run of the cell finds its files, and every reader of it loads
    from benchmark import run

    found, cfg_path, mix_path = run.find_cell(manifest, CELL)
    assert found == cell and os.path.exists(cfg_path)
    assert mix_path == deploy.traffic_path("rows-2k")
    readers = run.load_readers(
        run.metrics_of(manifest, "per_layer", CELL),
        os.path.join(REPO, "benchmark", "layers"))
    assert set(NEW_METRICS) <= set(readers)
    assert all(hasattr(mod, "read") for mod in readers.values())
    assert all(m["name"] in readers for m in manifest["per_layer"]
               if CELL in m["workloads"])


def test_a_templates_pod_carries_the_two_stated_terms():
    cfg = _cfg()
    for t in range(10):
        made = deploy.pod(cfg, t, name=f"p-t{t}-00000001")
        assert made["metadata"]["labels"] == {"group": f"g{t}"}
        assert made["spec"] == {"containers": [{
            "name": "pause", "image": "kubernetes/pause:go",
            "requests": {"cpu": "100m"}}]}
        stated = json.loads(made["metadata"]["annotations"][ANNOTATION])
        k = t % 5
        # two terms on one selector; neither states `namespaces`
        assert stated == {
            "podAffinity": {REQUIRED: [{
                "labelSelector": _selector(k), "topologyKey": ZONE}]},
            "podAntiAffinity": {PREFERRED: [{
                "weight": 100, "podAffinityTerm": {
                    "labelSelector": _selector(k), "topologyKey": HOST}}]}}
        assert f"g{t}" in _selector(k)["matchExpressions"][0]["values"]
    # the program reads the same terms off the pod
    from kubernetes_tpu.api.types import get_affinity, has_pod_affinity
    from kubernetes_tpu.client import rest

    pod = rest.default_scheme.decode(deploy.pod(cfg, 7, name="p"))
    assert has_pod_affinity(pod)
    affinity = get_affinity(pod)
    term, = affinity.pod_affinity \
        .required_during_scheduling_ignored_during_execution
    assert term.topology_key == ZONE and not term.namespaces
    soft, = affinity.pod_anti_affinity \
        .preferred_during_scheduling_ignored_during_execution
    assert (soft.weight, soft.pod_affinity_term.topology_key) == (100, HOST)
    assert list(term.label_selector.match_expressions[0].values) \
        == ["g2", "g7"]


# -- the reference ------------------------------------------------------------

def test_the_reference_reads_the_terms_off_the_shapes():
    cluster = reference_podaffinity.Cluster(_cfg(9))
    want = np.zeros(10, np.int64)
    want[[2, 7]] = 1
    terms = cluster.terms[7]
    (mask, dom), = terms["affinity"]
    assert mask.tolist() == want.tolist() and dom.tolist() == [0, 1, 2] * 3
    (weight, mask, dom), = terms["pref_anti"]
    assert weight == 100 and mask.tolist() == want.tolist()
    assert dom.tolist() == list(range(9))  # every node its own hostname
    assert terms["anti"] == terms["pref_affinity"] == []
    assert terms["states_anti"] is True
    assert (cluster.pod_cpu, cluster.pod_mem) == (100, 0)
    assert (cluster.nz_pod_cpu, cluster.nz_pod_mem) == (100, 200 * 2 ** 20)
    assert reference_podaffinity.terms_of({"requests": {}}) is None
    term = {"labelSelector": {"matchLabels": {"a": "b"}}, "topologyKey": ZONE}
    for broken in (
            {"nodeAffinity": {}},
            {"podAffinity": {REQUIRED: [{**term, "topologyKey": ""}]}},
            {"podAffinity": {REQUIRED: [{**term, "namespaces": []}]}},
            {"podAffinity": {REQUIRED: [{**term, "namespaces": ["x"]}]}},
            {"podAntiAffinity": {PREFERRED: [{
                "weight": 5, "podAffinityTerm": {
                    "labelSelector": {"matchExpressions": [{
                        "key": "a", "operator": "NotIn", "values": ["b"]}]},
                    "topologyKey": HOST}}]}},
            {"podAffinity": {REQUIRED: [{"labelSelector": {},
                                         "topologyKey": ZONE}]}},
            {"podAffinity": {"requiredDuringSchedulingRequiredDuringExecution":
                             [term]}}):
        with pytest.raises(ValueError):
            reference_podaffinity.terms_of(
                {"annotations": {ANNOTATION: json.dumps(broken)}})
    for shape in ({"nodeSelector": {"a": "b"}}, {"ports": [80]}):
        with pytest.raises(ValueError):
            reference_podaffinity.terms_of(shape)
    # a preferred term of weight 0 is no term (interpod_affinity.go:106)
    read = reference_podaffinity.terms_of({"annotations": {
        ANNOTATION: json.dumps({"podAffinity": {PREFERRED: [
            {"weight": 0, "podAffinityTerm": term}]}})}})
    assert read["pref_affinity"] == [] and read["states_anti"] is False
    # requests of a shape's own are refused by the cluster
    cfg = _cfg(9)
    cfg["pods"]["shapes"][0]["requests"] = {"cpu": "200m"}
    with pytest.raises(ValueError):
        reference_podaffinity.Cluster(cfg)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_podaffinity.py", "control_podaffinity.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            source = f.read()
        assert "kubernetes_tpu" not in source.split('"""', 2)[2]


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
    got = reference_podaffinity.decide(cluster, backlog, counter)
    assert [cluster.names[g] if g is not None else None
            for g in got] == want
    held = reference_podaffinity.verify(start, backlog, got)
    assert held["mismatches"] == 0 and held["checked"] == len(backlog)
    residue, modulus = held["counter"]
    assert counter % modulus == residue
    for nm, t, g in zip(names, backlog, got):
        if g is not None:
            live[nm] = (t, g)
    return got


#: (nodes, rounds, pods a round, share deleted between rounds, seed):
#: zoned clusters of 24 to 96 nodes. Every stream begins with a
#: service's first pod (the escape) and meets its second controller; a
#: share of 0.9 and more empties services, which choose a zone again
ORACLE_CASES = [(24, 4, 40, 0.3, 1), (48, 4, 60, 0.5, 2 ** 31 + 2),
                (36, 5, 30, 0.9, 3), (96, 3, 80, 0.2, 4),
                (27, 6, 25, 1.0, 2 ** 31 + 5)]


@pytest.mark.parametrize("nodes,rounds,batch,deleted,seed", ORACLE_CASES)
def test_reference_decides_as_the_programs_oracle(nodes, rounds, batch,
                                                  deleted, seed):
    """Pick for pick over a seeded stream that mixes all five services,
    binds after every round and deletes between rounds."""
    rng = random.Random(seed)
    cfg = _cfg(nodes)
    live = {}  # name -> (template, node)
    counter = rng.randrange(10_000)
    zones_taken = set()
    for r in range(rounds):
        cluster = reference_podaffinity.Cluster(cfg)
        for t, node in live.values():
            cluster.bind(t, node)
        assert cluster.over_allocatable() == 0
        backlog = [t for t in (rng.randrange(10) for _ in range(batch // 5))
                   for _ in range(5)]
        names = [f"r{r}-{i:04d}" for i in range(len(backlog))]
        got = _serial(cfg, cluster, live, backlog, names, counter)
        assert None not in got
        counter += len(got)
        # the guarantee: a service's live pods in one zone
        for k in range(5):
            zones = {int(cluster.zone[n]) for t, n in live.values()
                     if t % 5 == k}
            assert len(zones) <= 1
            zones_taken |= {(k, z) for z in zones}
        for nm in rng.sample(sorted(live), int(deleted * len(live))):
            del live[nm]
    if deleted >= 0.9:
        # an emptied service chose again, and not always the same zone
        assert len(zones_taken) > 5


def test_the_first_pod_goes_anywhere_and_the_service_follows_it():
    """predicates.go:819-843: with no pod of the service anywhere the
    required term holds on every node; the second pod, of the service's
    other controller, fits in the first one's zone alone."""
    cfg = _cfg(12)
    cluster = reference_podaffinity.Cluster(cfg)
    cluster.incoming = 3
    assert cluster.fits().all()
    live = {}
    first, second = _serial(cfg, cluster, live, [3, 8], ["a", "b"], 0)
    assert cluster.zone[first] == cluster.zone[second]
    assert first != second  # the soft hostname term spreads them
    cluster.incoming = 8
    assert cluster.fits().tolist() == (cluster.zone
                                       == cluster.zone[first]).tolist()
    # another service is free to choose
    cluster.incoming = 4
    assert cluster.fits().all()
    # a template without terms is neither held nor counted
    bare = _cfg(12)
    bare["pods"]["shapes"][0] = {}
    cluster = reference_podaffinity.Cluster(bare)
    cluster.bind(5, 0)
    cluster.incoming = 0
    assert cluster.fits().all()
    cluster.incoming = 5  # its term selects g0, whose pods own none
    assert cluster.fits().all() and not cluster.terms[0]["states_anti"]


def test_a_service_emptied_by_the_churn_chooses_a_zone_again():
    cfg = _cfg(24)
    live = {}
    cluster = reference_podaffinity.Cluster(cfg)
    got = _serial(cfg, cluster, live, [1] * 6 + [6] * 6,
                  [f"x{i}" for i in range(12)], 7)
    zone, = {int(cluster.zone[g]) for g in got}
    # the whole service goes; zone `zone` is filled by another's pods so
    # that the scores prefer the other zones
    live.clear()
    cluster = reference_podaffinity.Cluster(cfg)
    other = [n for n in range(24) if cluster.zone[n] == zone]
    for i, n in enumerate(other * 20):
        live[f"o{i}"] = (2, n)
        cluster.bind(2, n)
    again = _serial(cfg, cluster, live, [6] * 4 + [1] * 4,
                    [f"y{i}" for i in range(8)], 19)
    new_zone, = {int(cluster.zone[g]) for g in again}
    assert new_zone != zone


def test_a_required_anti_term_over_zones_holds_in_both_directions():
    """What the deployment does not state and the reference scores all
    the same: a required podAntiAffinity term, the pod's own and a bound
    pod's, over a topology that couples nodes; the symmetric half runs
    only for a pod that states a podAntiAffinity (upstream's gate)."""
    cfg = _cfg(12)
    cfg["controllers"]["count"] = 3
    term = {"labelSelector": {"matchLabels": {"group": "g1"}},
            "topologyKey": ZONE}
    cfg["pods"]["shapes"] = [
        {"annotations": {ANNOTATION: json.dumps(
            {"podAntiAffinity": {REQUIRED: [term]}})}},
        {"annotations": {ANNOTATION: json.dumps(
            {"podAntiAffinity": {PREFERRED: []}})}},
        {}]
    live = {}
    cluster = reference_podaffinity.Cluster(cfg)
    # g1 pods (template 1) take zones; template 0 keeps off them, and
    # template 1 off template 0's zones; template 2 states nothing
    got = _serial(cfg, cluster, live, [1, 0, 0, 1, 1, 2, 2, 0],
                  [f"z{i}" for i in range(8)], 3)
    zones = cluster.zone
    ones = {int(zones[g]) for t, g in zip([1, 0, 0, 1, 1, 2, 2, 0], got)
            if t == 1 and g is not None}
    zeros = {int(zones[g]) for t, g in zip([1, 0, 0, 1, 1, 2, 2, 0], got)
             if t == 0 and g is not None}
    assert ones and zeros and not ones & zeros


def test_the_priority_is_upstreams_sums_pinned_at_zero_and_truncated():
    """9 nodes in 3 zones; service 0 (templates 0 and 5) holds four pods
    in zone a: two on node 0, one on node 3, one on node 6's... none:
    node 6 is empty. For a pod of template 5: -100 a co-located match of
    its own preferred term, -100 for each bound pod's preferred term
    that selects it, +1 for each bound pod's required term over the
    zone: 4 - 200 c."""
    cluster = reference_podaffinity.Cluster(_cfg(9))
    for t, node in ((0, 0), (5, 0), (0, 3), (5, 3), (0, 3)):
        cluster.bind(t, node)
    cluster.incoming = 5
    fit = cluster.fits()
    assert fit.tolist() == [True, False, False] * 3  # zone a alone
    # totals: 5 - 200 * (2, 3, 0) on nodes 0, 3, 6; max 5, min -595
    score = cluster._inter_pod_affinity(fit)
    assert score.tolist() == [int(10 * (200 / 600)), 0, 0,
                              0, 0, 0, 10, 0, 0]
    # the symmetric weight off: max pinned at 0, min -600
    cluster.hard_weight = 0
    assert cluster._inter_pod_affinity(fit).tolist() == [
        int(10 * (200 / 600)), 0, 0, 0, 0, 0, 10, 0, 0]
    cluster.hard_weight = 1
    # the preferred terms off: every fitting node 5, max 5, min 0
    cluster.preferred_holds = False
    assert cluster._inter_pod_affinity(fit).tolist() == [10, 0, 0] * 3
    cluster.preferred_holds = True
    # all of the cluster fitting (the required term ignored): the other
    # zones' nodes total 0, between the two pins
    everywhere = np.ones(9, bool)
    score = cluster._inter_pod_affinity(everywhere)
    assert score[1] == int(10 * (595 / 600)) == 9 and score[6] == 10
    # another service's pod: nothing matches it, max == min, all 0
    cluster.incoming = 1
    assert not cluster._inter_pod_affinity(everywhere).any()


# -- the guarantee, as the comparison holds it --------------------------------

def test_a_service_in_two_zones_is_over_allocatable_and_not_correct():
    cfg = _cfg(18)
    cluster = reference_podaffinity.Cluster(cfg)
    before = {}
    for i, (t, node) in enumerate(((0, 0), (5, 3), (0, 6), (1, 1), (6, 4),
                                   (2, 2))):
        cluster.bind(t, node)
        before[f"p-t{t}-{i:08d}"] = cluster.names[node]
    assert cluster.over_allocatable() == 0
    backlog = [5, 5, 7, 3]
    picks = reference_podaffinity.decide(copy.deepcopy(cluster), backlog, 5)
    sound = check.decide(_record(cfg, before, backlog, picks, cluster.names),
                         cfg, out=open(os.devnull, "w"))
    assert sound["correct"] is True
    assert all(v == 0 for v, _limit in sound["numbers"].values())
    # a pod of service 0 in zone b: the node it stands on is counted,
    # the three in zone a are not
    astray = copy.deepcopy(cluster)
    astray.bind(5, 7)
    assert astray.over_allocatable() == 1
    astray.bind(0, 10)
    assert astray.over_allocatable() == 2
    broken = _record(cfg, before, backlog, picks, cluster.names)
    broken["check"]["after"]["p-t5-99999999"] = cluster.names[7]
    broken["live"]["p-t5-99999999"] = cluster.names[7]
    said = check.decide(broken, cfg, out=open(os.devnull, "w"))
    assert said["correct"] is False
    assert said["numbers"]["nodes_over_allocatable"] == (1, 0)
    # and a check pick into another zone is off the reference
    onto = list(picks)
    onto[0] = 8  # zone c; service 0 lies in zone a
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
    from benchmark import loadgen

    rng = random.Random(seed)
    cluster = reference_podaffinity.Cluster(cfg)
    order = loadgen.template_order(cfg, seed)
    stream = [loadgen.template_of(order, row, j)
              for j in range(bound_runs * row)]
    placed = reference_podaffinity.decide(cluster, stream, seed % 1000)
    assert None not in placed
    before = {f"p-t{t}-{i:08d}": cluster.names[node]
              for i, (t, node) in enumerate(zip(stream, placed))}
    runs = []
    while len(runs) < check_runs:
        t = rng.randrange(10)
        if not runs or t != runs[-1]:
            runs.append(t)
    backlog = [t for t in runs for _ in range(row)]
    picks = reference_podaffinity.decide(copy.deepcopy(cluster), backlog,
                                         seed % 1000 + len(stream))
    return _record(cfg, before, backlog, picks, cluster.names)


@pytest.mark.parametrize("seed", [45, 2 ** 31 + 451])
def test_each_control_moves_picks_where_it_should(seed):
    """At this size (32 pods of a service bound, so a bound service adds
    some 32 to its zone's nodes where a pod on the node takes 200) each
    of the required term ignored, the preferred terms ignored and the
    stale wave moves picks, and the required term ignored leaves
    services in two zones. The symmetric weight at 0 moves none: with
    the required term in place it adds the same to every node that
    fits. At full size, 600 to 1,000 pods of a service bound, the
    required term ignored moves none either (the weight alone holds the
    zone), and only both together do: the deployment file's
    `measured.controls`, and why its `guarantees.arithmetic` claims the
    pair at full size and the required term's own half by these tests."""
    cfg = _cfg(48, 32)
    assert check.load_reference(cfg).__name__.endswith(
        "reference_podaffinity")
    record = _control_record(cfg, seed, bound_runs=20, check_runs=12, row=8)
    read = control_podaffinity.broken(record, cfg)
    assert set(read) == {"sound", *control_podaffinity.BROKEN,
                         "required_ignored_nodes_astray",
                         "zone_unheld_nodes_astray"}
    assert read["sound"] == 0
    assert read["required_ignored"] >= 10
    assert read["required_ignored_nodes_astray"] >= 24
    assert read["preferred_ignored"] >= 10
    assert read["zone_unheld"] >= 48 and read["zone_unheld_nodes_astray"] >= 24
    assert read["symmetric_weight_0"] == 0
    stale = controls.stale_wave(record, cfg)
    assert stale["sound"] == 0 and stale["stale_wave"] >= 48
    # a deployment without terms has no such control to read
    flat = deploy.load_config("density-1k")
    flat["nodes"]["count"] = 6
    empty = {"check": {"backlog": [0] * 8, "before": {}, "after": {},
                       "names": [f"check-{i:05d}" for i in range(8)]}}
    assert set(control_podaffinity.broken(empty, flat)) == {"sound"}


def test_float32_normalises_to_float64s_score_on_every_total_here():
    """Why `float32_normal` reads 0 on every record and the file claims
    no precision for the normalisation: a service is 1,000 pods and a
    node holds at most 40 of one request shape, so max - min is under
    1,000 + 200 x 40, and over every whole total and span that small
    float32 truncates `10 * (total / span)` to float64's score (the
    spans to 2,000 in full here). The switch itself works: float16
    does not."""
    spans = np.arange(1, 2001)
    differ16 = 0
    for span in spans.tolist():
        total = np.arange(span + 1)
        want = (np.float64(10) * (total / np.float64(span))).astype(np.int64)
        got = (np.float32(10) * (total.astype(np.float32)
                                 / np.float32(span))).astype(np.int64)
        assert np.array_equal(want, got), span
        low = (np.float16(10) * (total.astype(np.float16)
                                 / np.float16(span))).astype(np.int64)
        differ16 += int(np.count_nonzero(low != want))
    assert differ16 > 1000
    cluster = reference_podaffinity.Cluster(_cfg(9))
    for t, node in ((0, 0), (5, 0), (0, 3), (0, 6), (5, 6), (0, 6)):
        cluster.bind(t, node)
    cluster.incoming = 5
    fit = cluster.fits()
    sound = cluster._inter_pod_affinity(fit)
    cluster.normal = np.float32
    assert np.array_equal(cluster._inter_pod_affinity(fit), sound)


# -- the two readers ----------------------------------------------------------

def test_new_readers_give_nothing_on_a_program_without_the_counters():
    """The parent commit keeps `pods_by_path` and neither `scan_reasons`
    nor the affinity counters: each reader returns nothing and the
    result line leaves its metric out."""
    from types import SimpleNamespace as NS

    from benchmark.layers import (
        affinity_excluded_node_share,
        interpod_scan_share,
    )

    paths = {"scan": 95, "single": 5, "group_host": 0, "group_device": 0}
    parent = {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
        _wave=NS(stats={"waves": 3, "pods_by_path": paths})))))}
    for mod in (interpod_scan_share, affinity_excluded_node_share):
        snap = mod.snapshot(parent)
        assert snap == {}
        name = mod.__name__.rsplit(".", 1)[-1]
        assert mod.read({"snapshots": {name: (snap, snap)},
                         "config": {"nodes": {"count": 2000}}}) is None


@pytest.mark.parametrize("reader,before,after,want", [
    ("interpod_scan_share", {"by_term": 100, "decided": 1000},
     {"by_term": 2080, "decided": 3000}, 99.0),
    ("affinity_excluded_node_share", {"excluded": 1000, "runs": 10},
     {"excluded": 66_701_000, "runs": 50_010}, 66.7),
])
def test_the_new_readers_divide_a_diff(reader, before, after, want):
    import importlib

    mod = importlib.import_module("benchmark.layers." + reader)
    run = {"snapshots": {reader: (before, after)},
           "metric": reader + ".fill",
           "config": {"nodes": {"count": 2000}}}
    assert mod.read(run) == pytest.approx(want)
    run["snapshots"][reader] = (after, after)  # nothing moved
    assert mod.read(run) is None


def test_the_scan_share_counts_the_three_term_reasons_alone():
    from types import SimpleNamespace as NS

    from benchmark.layers import interpod_scan_share

    stats = {"pods_by_path": {"scan": 900, "single": 100},
             "scan_reasons": {"hard_affinity": 400, "self_preferred": 200,
                              "zone_anti": 100, "volumes": 50, "config": 25}}
    ctx = {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
        _wave=NS(stats=stats)))))}
    assert interpod_scan_share.snapshot(ctx) == {"by_term": 700,
                                                 "decided": 1000}


# -- the served path, at a tiny size ------------------------------------------

def _tiny_mix():
    """rows-2k cut to the tiny deployment: runs of 16, five runs a
    request, the cap a whole turn of the ten controllers, a check batch
    of five runs."""
    mix = deploy.load_json(deploy.traffic_path("rows-2k"))
    mix.update(workers=2, chunk=80, replicas_in_a_row=16, backlog_cap=160,
               warm_s=0.5, drain_s=6.0, check={"runs": 5},
               prefill_steps=[{"one_of_each": True}, {"pods": 16},
                              {"pods": 16}, {"pods": 160}])
    return mix


@pytest.fixture(scope="module")
def traced_aff(tmp_path_factory):
    """96 nodes in three zones, the ten controllers at 32 replicas: 64
    pods a service, 160 held bound."""
    from kubernetes_tpu.trace import profile

    before = profile.wave_totals()
    cfg = _cfg(96, 32)
    result, record = _serve_tiny(tmp_path_factory.mktemp("tiny-aff"), CELL,
                                 cfg, _tiny_mix(), BIG_SEED, 4.0)
    return result, record, cfg, before, profile.wave_totals()


def test_served_runs_go_through_the_scan_with_the_terms_live(traced_aff):
    result, record, _cfg_, before, after = traced_aff
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
    # every run owns a required podAffinity term: the scan decides
    assert got["wave_path_share.scan.fill"]["value"] == 100.0
    assert got["wave_path_share.grouped.fill"]["value"] == 0.0
    assert got["anti_run_share.fill"]["value"] == 0.0
    assert got["encode_full_share.fill"]["value"] == 0.0
    assert got["score_us_per_pod.fill"]["value"] > 0
    # runs of 16 are `min_run` pods long; a run that a wave's end cuts
    # shorter is the scan's for its length and counted under no reason
    assert 80 <= got["interpod_scan_share.fill"]["value"] <= 100
    # two zones of three, as long as every service holds bound pods
    assert 60 <= got["affinity_excluded_node_share.fill"]["value"] <= 70
    assert got["window_compiles.fill"]["value"] == 0
    moved = {k: after["scan_reasons"].get(k, 0)
             - before["scan_reasons"].get(k, 0)
             for k in after["scan_reasons"]}
    assert {k for k, v in moved.items() if v} == {"hard_affinity"}
    assert after["affinity_runs"] > before["affinity_runs"]
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})
    assert {"scan_reasons", "affinity_runs", "affinity_nodes_excluded",
            "rewarms", "rewarm_seconds", "rewarm_programs",
            "rewarm_mismatches"} <= set(shown["wave"])


def test_the_rewarm_fires_once_in_the_set_up_and_never_in_the_window(
        traced_aff):
    result, record, _cfg_, before, after = traced_aff
    assert after["rewarms"] - before["rewarms"] == 1
    assert after["rewarm_programs"] > before["rewarm_programs"]
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
    # five specs under two topology keys, ten logical terms, and a
    # domain a hostname
    assert (attrs["combos"], attrs["classes"], attrs["specs"],
            attrs["terms"], attrs["expansion"], attrs["domains"]) \
        == (2, 10, 5, 10, 1, 96)


def test_the_controls_read_the_served_runs_own_record(traced_aff):
    _result, record, cfg, _before, _after = traced_aff
    read = control_podaffinity.broken(record, cfg)
    assert read["sound"] == 0 and read["preferred_ignored"] >= 1
    assert read["zone_unheld"] >= read["required_ignored"]
    assert controls.stale_wave(record, cfg)["sound"] == 0
