"""Wave fast-path conformance: the run-splitting driver (models/wave.py)
must be bit-identical to the serial scan — and therefore to the oracle —
on any backlog, fast-pathing eligible runs and falling back for the
rest with exact carry handoff.

The replay's float formulas and the selectHost round-robin are the risky
parts; fixtures here are tie-heavy (identical nodes), fill nodes to
capacity mid-run (fit-set changes → normalizer rebuilds), and mix
eligible runs with ineligible pods (volumes, inter-pod terms)."""

import copy
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container,
    ContainerPort,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    Service,
    ServiceSpec,
    Taint,
    NodeSpec,
)
from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig
from kubernetes_tpu.oracle import ClusterState, GenericScheduler
from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
from kubernetes_tpu.snapshot.encode import SnapshotEncoder, pod_feature_key

from tests.test_conformance import (
    ORACLE_PREDICATES,
    ORACLE_PRIORITIES,
    random_scenario,
)


def oracle_backlog(state, pending):
    oracle = GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES
    )
    return oracle.schedule_backlog(pending, state.clone())


def wave_backlog(state, pending, min_run=1):
    algo = TPUScheduleAlgorithm(min_run=min_run)
    return algo.schedule_backlog(pending, state)


def clone_named(pod: Pod, name: str) -> Pod:
    out = copy.deepcopy(pod)
    out.metadata.name = name
    return out


def density_nodes(n, pods_cap="110", cpu="4", mem="32Gi", taint_every=0):
    nodes = []
    for i in range(n):
        spec = NodeSpec()
        if taint_every and i % taint_every == 0:
            spec = NodeSpec(
                taints=[Taint(key="dedicated", value="a",
                              effect="PreferNoSchedule")]
            )
        nodes.append(
            Node(
                metadata=ObjectMeta(name=f"node-{i:04d}"),
                spec=spec,
                status=NodeStatus(
                    allocatable={"cpu": cpu, "memory": mem, "pods": pods_cap},
                    conditions=[NodeCondition("Ready", "True")],
                ),
            )
        )
    return nodes


def pause_pods(k, labels=None, requests=None):
    labels = labels or {"name": "sched-perf"}
    requests = requests or {"cpu": "100m", "memory": "500Mi"}
    return [
        Pod(
            metadata=ObjectMeta(name=f"pod-{i:06d}", labels=dict(labels)),
            spec=PodSpec(containers=[Container(requests=dict(requests))]),
        )
        for i in range(k)
    ]


def test_feature_key_implies_identical_rows():
    rng = random.Random(1234)
    state, pending = random_scenario(
        rng, n_nodes=6, n_existing=8, n_pending=20,
        interpod_p=0.3, volumes_p=0.3,
    )
    # clones share the feature key with their template by construction;
    # the property under test is key-equality => row-equality
    pending = pending + [
        clone_named(p, f"{p.metadata.name}-x") for p in pending[::2]
    ]
    enc = SnapshotEncoder(state, pending)
    batch = enc.encode_pods()
    by_key = {}
    for i, p in enumerate(pending):
        by_key.setdefault(pod_feature_key(p), []).append(i)
    import dataclasses

    checked_groups = 0
    for rows in by_key.values():
        if len(rows) < 2:
            continue
        checked_groups += 1
        a = rows[0]
        for b in rows[1:]:
            for f in dataclasses.fields(batch):
                v = getattr(batch, f.name)
                if f.name == "pod_keys" or not isinstance(v, np.ndarray):
                    continue
                if v.ndim >= 1 and v.shape[0] == batch.num_pods:
                    assert np.array_equal(v[a], v[b]), (
                        f"rows {a},{b} differ in {f.name}"
                    )
    assert checked_groups >= 1  # the fixture produced at least one run


def test_wave_homogeneous_tie_heavy_matches_oracle():
    # 20 identical nodes (every pick is a 20-way tie at first), service
    # selecting all pods => dynamic SelectorSpread with maxCount changes
    nodes = density_nodes(20)
    pods = pause_pods(150)
    state = ClusterState.build(
        nodes,
        services=[Service(metadata=ObjectMeta(name="svc"),
                          spec=ServiceSpec(selector={"name": "sched-perf"}))],
    )
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_capacity_exhaustion_tail():
    # 5 nodes x 4 pods cap = 20 slots for 40 pods: nodes leave the fit
    # set mid-run and the tail must be unschedulable (None), with the
    # round-robin counter frozen once scheduling stops
    nodes = density_nodes(5, pods_cap="4")
    pods = pause_pods(40)
    state = ClusterState.build(
        nodes,
        services=[Service(metadata=ObjectMeta(name="svc"),
                          spec=ServiceSpec(selector={"name": "sched-perf"}))],
    )
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert want[-1] is None and got.count(None) == 20


def test_wave_taints_and_fill_rebuilds():
    # PreferNoSchedule taints on every 3rd node make TaintToleration
    # normalize over a nonuniform count vector; tiny capacity forces
    # fit-set changes => per-event renormalization in the replay
    nodes = density_nodes(9, pods_cap="3", taint_every=3)
    pods = pause_pods(30)
    state = ClusterState.build(nodes)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_cpu_bound_fill():
    # cpu exhausts before the pod-count cap: res_fit flips from the
    # resource side of the table
    nodes = density_nodes(4, pods_cap="110", cpu="1", mem="32Gi")
    pods = pause_pods(50, requests={"cpu": "250m", "memory": "100Mi"})
    state = ClusterState.build(nodes)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert got.count(None) == 50 - 4 * 4


def test_wave_host_port_self_conflict():
    # a host port means each node takes exactly one copy of the run
    nodes = density_nodes(6)
    pods = [
        Pod(
            metadata=ObjectMeta(name=f"pod-{i}", labels={"app": "p"}),
            spec=PodSpec(containers=[
                Container(requests={"cpu": "100m"},
                          ports=[ContainerPort(host_port=8080)])
            ]),
        )
        for i in range(10)
    ]
    state = ClusterState.build(nodes)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert got.count(None) == 4 and len(set(x for x in got if x)) == 6


def test_wave_reprobe_on_table_horizon():
    # max_j=16 forces the replay to bail at the table horizon and
    # re-probe with a fresh carry; output must still be identical
    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.snapshot.pad import next_pow2
    from kubernetes_tpu.parallel.mesh import _pad_snapshot

    nodes = density_nodes(3)
    pods = pause_pods(100, requests={"cpu": "10m", "memory": "10Mi"})
    state = ClusterState.build(nodes)
    want = oracle_backlog(state, pods)

    enc = SnapshotEncoder(state, [pods[0]])
    snap = enc.encode_nodes()
    batch = enc.encode_pods()
    snap_p = _pad_snapshot(snap, next_pow2(snap.num_nodes, 4))
    ws = WaveScheduler(min_run=1, max_j=16)
    chosen, _, _ = ws.schedule_backlog(
        snap_p, batch, np.zeros(len(pods), np.int64)
    )
    got = [snap.node_names[c] if 0 <= c < snap.num_nodes else None
           for c in chosen]
    assert got == want


@pytest.mark.parametrize("seed", range(6))
def test_wave_mixed_backlog_random(seed):
    # random heterogeneous scenario, then pending expanded into runs:
    # every pod is cloned 0-6 times in place — runs of identical pods
    # interleaved with singles, some ineligible (volumes/interpod)
    rng = random.Random(1000 + seed)
    state, pending = random_scenario(
        rng,
        n_nodes=8,
        n_existing=10,
        n_pending=10,
        interpod_p=0.25 if seed % 2 else 0.0,
        volumes_p=0.25 if seed >= 3 else 0.0,
    )
    backlog = []
    for i, p in enumerate(pending):
        for c in range(rng.randint(1, 7)):
            backlog.append(clone_named(p, f"{p.metadata.name}-c{c}"))
    want = oracle_backlog(state, backlog)
    got = wave_backlog(state, backlog)
    assert got == want, (
        f"seed {seed}: first divergence at "
        f"{next(i for i, (a, b) in enumerate(zip(want, got)) if a != b)}"
        f" of {len(backlog)}"
    )


@pytest.mark.parametrize("seed", range(20))
def test_replay_c_matches_spec_fuzz(seed):
    # synthetic RunTables stress the C engine's bucket/Fenwick/rebuild
    # machinery far beyond what end-to-end fixtures reach: plateaus,
    # score raises (Balanced can go up), deep ties, horizon bails
    from kubernetes_tpu.models.probe import RunTables
    from kubernetes_tpu.models.replay import (
        _load_lib,
        replay_fast,
        replay_spec,
    )

    if _load_lib() is None:
        pytest.skip("native/_replay.so not built")
    rng = np.random.default_rng(seed)
    N = int(rng.integers(1, 40))
    J = int(rng.integers(2, 20))
    K = int(rng.integers(1, 120))
    # mostly-flat tables maximize ties; occasional jumps exercise
    # bucket moves in both directions
    tab = rng.integers(0, 4, (J, N)).astype(np.int64)
    if rng.random() < 0.5:
        # blend a reversed copy in: more plateaus and non-monotone rows
        tab = np.maximum(tab, tab[::-1])
    tab = np.sort(tab, axis=0)[::-1].copy()  # mostly decreasing in j
    if rng.random() < 0.4:  # inject raises
        r0 = int(rng.integers(0, J))
        tab[r0] = tab[r0] + rng.integers(0, 3, N)
    t = RunTables(
        fit_static=rng.random(N) < 0.9,
        res_fit=(rng.random((J, N)) < 0.97).cumprod(axis=0).astype(bool),
        tab=tab,
        static_add=rng.integers(0, 3, N).astype(np.int64),
        w_spread=int(rng.integers(0, 3)),
        spread_base=(rng.integers(0, 4, N).astype(np.int64)
                     if rng.random() < 0.7 else None),
        spread_selfmatch=bool(rng.random() < 0.7),
        has_selectors=bool(rng.random() < 0.8),
        w_na=int(rng.integers(0, 3)),
        na_counts=(rng.integers(0, 6, N).astype(np.int64)
                   if rng.random() < 0.5 else None),
        w_tt=int(rng.integers(0, 3)),
        tt_counts=(rng.integers(0, 4, N).astype(np.int64)
                   if rng.random() < 0.5 else None),
        w_ip=int(rng.integers(0, 3)),
        ip_totals=(rng.integers(-5, 6, N).astype(np.int64)
                   if rng.random() < 0.4 else None),
    )
    L0 = int(rng.integers(0, 1000))
    spec = replay_spec(t, K, L0)
    fast = replay_fast(t, K, L0)
    assert fast.n_done == spec.n_done
    assert np.array_equal(fast.chosen, spec.chosen)
    assert np.array_equal(fast.counts, spec.counts)
    assert fast.last_node_index == spec.last_node_index
    assert fast.scheduled == spec.scheduled


def test_wave_min_run_fallback_matches():
    # with min_run above every run length, everything goes through the
    # scan fallback — the driver must still match (pure handoff test)
    nodes = density_nodes(5)
    pods = pause_pods(20)
    state = ClusterState.build(nodes)
    assert wave_backlog(state, pods, min_run=64) == oracle_backlog(state, pods)


ZONE = "failure-domain.beta.kubernetes.io/zone"


def zoned_density_nodes(n, zones=("a", "b", "c"), unzoned_every=0,
                        pods_cap="110", **kw):
    nodes = density_nodes(n, pods_cap=pods_cap, **kw)
    for i, node in enumerate(nodes):
        if unzoned_every and i % unzoned_every == 0:
            continue  # leave some nodes without a zone (zone 0 path)
        node.metadata.labels[ZONE] = zones[i % len(zones)]
    return nodes


def spread_state(nodes):
    return ClusterState.build(
        nodes,
        services=[Service(metadata=ObjectMeta(name="svc"),
                          spec=ServiceSpec(selector={"name": "sched-perf"}))],
    )


def test_wave_zoned_spread_matches_oracle():
    # selector pods on a ZONED cluster stay on the fast path now: the
    # replay recomputes the 2/3 zone blend per pick
    # (selector_spreading.go:221-228)
    state = spread_state(zoned_density_nodes(18))
    pods = pause_pods(120)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_zoned_spread_mixed_unzoned_nodes():
    # zone 0 (no label) never joins the blend; zoned and unzoned nodes
    # coexist in the same fit set
    state = spread_state(zoned_density_nodes(15, unzoned_every=3))
    pods = pause_pods(90)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_zoned_capacity_exhaustion():
    # zones drain mid-run: nodes leave the fit set, per-zone counts
    # re-aggregate over the survivors, tail goes unschedulable
    state = spread_state(zoned_density_nodes(6, pods_cap="5"))
    pods = pause_pods(45)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert want[-1] is None


def test_wave_zoned_uneven_zone_sizes():
    # one big zone + one single-node zone: the blend must steer picks
    # toward the small zone exactly as the oracle does
    nodes = zoned_density_nodes(9, zones=("a",))
    nodes[-1].metadata.labels[ZONE] = "b"
    state = spread_state(nodes)
    pods = pause_pods(70)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


@pytest.mark.parametrize("seed", range(12))
def test_wave_zoned_random_backlogs(seed):
    rng = random.Random(1000 + seed)
    zones = ["a", "b", "c", "d"][: rng.randint(1, 4)]
    nodes = zoned_density_nodes(
        rng.randint(4, 24), zones=tuple(zones),
        unzoned_every=rng.choice([0, 2, 3]),
        pods_cap=str(rng.randint(3, 30)),
    )
    state = spread_state(nodes)
    pods = pause_pods(rng.randint(20, 160))
    # a second distinct template exercises run switching on the
    # zoned path (separate probes, shared carry)
    pods += pause_pods(rng.randint(10, 40),
                       requests={"cpu": "200m", "memory": "1Gi"})
    for i, p in enumerate(pods):
        p.metadata.name = f"pod-{i:06d}"
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def _anti_pods(k, labels, topo="kubernetes.io/hostname", name0=0,
               requests=None, sel_labels=None):
    from kubernetes_tpu.api.types import (
        Affinity, PodAffinityTerm, PodAntiAffinity, LabelSelector)
    import json
    out = []
    for i in range(k):
        p = Pod(
            metadata=ObjectMeta(name=f"anti-{name0 + i:05d}",
                                labels=dict(labels)),
            spec=PodSpec(containers=[Container(
                requests=dict(requests or {"cpu": "100m"}))]),
        )
        p.metadata.annotations = {
            "scheduler.alpha.kubernetes.io/affinity": json.dumps({
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": sel_labels or dict(labels)},
                        "topologyKey": topo,
                        "namespaces": [],
                    }],
                },
            })
        }
        out.append(p)
    return out


def hostname_nodes(n, **kw):
    nodes = density_nodes(n, **kw)
    for node in nodes:
        node.metadata.labels["kubernetes.io/hostname"] = node.metadata.name
    return nodes


def test_wave_self_anti_one_per_node():
    # the config-3 pattern: a run of identical pods, each with hard
    # anti-affinity to its own labels on hostname topology — exactly one
    # lands per node, surplus goes unschedulable; the run must stay on
    # the fast path via the res_fit self-veto
    nodes = hostname_nodes(12)
    pods = _anti_pods(20, {"app": "exclusive"})
    state = ClusterState.build(nodes)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    placed = [h for h in got if h]
    assert len(placed) == len(set(placed)) == 12 and got.count(None) == 8


def test_wave_self_anti_carry_feeds_later_pods():
    # an eligible self-anti run FOLLOWED by pods of a different template
    # that match the run's anti selector: the committed copies' own
    # terms must veto them via the carry fold (the symmetric check)
    nodes = hostname_nodes(8)
    first = _anti_pods(6, {"tier": "a"})
    # same labels (so the first run's anti terms match them) but a
    # different resource shape => different run
    second = _anti_pods(6, {"tier": "a"}, name0=100,
                        requests={"cpu": "200m"})
    state = ClusterState.build(nodes)
    pods = first + second
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    placed = [h for h in got if h]
    assert len(placed) == len(set(placed)) == 8  # 12 pods, 8 nodes, 1 each


def test_wave_nonself_anti_term_fold():
    # a run whose anti term matches OTHER labels only: no self-feedback
    # (fast-path eligible), but later pods carrying those labels must
    # see the committed copies' terms through the carry fold. The v1.3
    # quirk applies: the symmetric check only runs for candidates that
    # THEMSELVES have anti-affinity (predicates.go:884-921 is inside
    # the pod's own PodAntiAffinity branch), so the victims carry a
    # harmless anti term of their own to arm it.
    nodes = hostname_nodes(10)
    guards = _anti_pods(10, {"role": "guard"}, sel_labels={"role": "victim"})
    victims = _anti_pods(10, {"role": "victim"}, name0=200,
                         sel_labels={"role": "nobody"},
                         requests={"cpu": "50m"})
    state = ClusterState.build(nodes)
    pods = guards + victims
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    # every node hosts a guard whose term matches the victims, and the
    # victims' own anti-affinity arms the symmetric check: none land
    assert got[:10].count(None) == 0 and got[10:].count(None) == 10


def test_wave_plain_pod_ignores_existing_anti_owner():
    # ...and the quirk itself: a pod with NO anti-affinity of its own
    # sails past an existing anti-owner whose term matches it
    nodes = hostname_nodes(3)
    guards = _anti_pods(3, {"role": "guard"}, sel_labels={"role": "plain"})
    plain = pause_pods(3, labels={"role": "plain"})
    for i, p in enumerate(plain):
        p.metadata.name = f"plain-{i:05d}"
    state = ClusterState.build(nodes)
    pods = guards + plain
    got = wave_backlog(state, pods)
    assert got == oracle_backlog(state, pods)
    assert got.count(None) == 0


def test_wave_self_anti_zone_topology_falls_back():
    # zone-topology self anti-affinity couples nodes: must NOT take the
    # fast path, and the scan fallback must still match the oracle
    nodes = zoned_density_nodes(9, zones=("a", "b", "c"))
    pods = _anti_pods(9, {"app": "zonal"}, topo=ZONE)
    state = ClusterState.build(nodes)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert got.count(None) == 6  # one per zone


@pytest.mark.parametrize("seed", range(8))
def test_wave_self_anti_mixed_random(seed):
    rng = random.Random(2000 + seed)
    nodes = hostname_nodes(rng.randint(5, 16),
                           pods_cap=str(rng.randint(2, 8)))
    pods = []
    pods += _anti_pods(rng.randint(16, 40), {"g": "x"})
    pods += pause_pods(rng.randint(10, 50))
    pods += _anti_pods(rng.randint(16, 30), {"g": "y"},
                       name0=500, requests={"cpu": "150m"})
    rng.shuffle(pods)
    # keep runs contiguous enough to fast-path: stable-sort by template
    pods.sort(key=lambda p: pod_feature_key(p))
    for i, p in enumerate(pods):
        p.metadata.name = f"pod-{i:06d}"
    state = ClusterState.build(nodes)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


# -- service-member runs on the wave path (SA pin + SAA renormalization) -----


def _svc_policy(sa=True, saa=True, saa_weight=2):
    import json as _json

    from kubernetes_tpu.scheduler.policy import (
        load_policy, resolve_policy_tpu)

    preds = [{"name": "GeneralPredicates"}]
    if sa:
        preds.append({"name": "ZoneAffinity", "argument": {
            "serviceAffinity": {"labels": ["zone"]}}})
    prios = [{"name": "LeastRequestedPriority", "weight": 1}]
    if saa:
        prios.append({"name": "ZoneSpread", "weight": saa_weight,
                      "argument": {"serviceAntiAffinity": {
                          "label": "zone"}}})
    cfg = resolve_policy_tpu(load_policy(_json.dumps({
        "kind": "Policy", "predicates": preds, "priorities": prios,
    })), 1)
    assert cfg is not None
    return cfg


def _svc_oracle(state, pending, sa=True, saa=True, saa_weight=2):
    from kubernetes_tpu.oracle import predicates as opreds
    from kubernetes_tpu.oracle import priorities as oprios
    from kubernetes_tpu.oracle.scheduler import PriorityConfig

    preds = [("GeneralPredicates", opreds.general_predicates)]
    if sa:
        preds.append(
            ("ZoneAffinity", opreds.service_affinity_predicate(["zone"])))
    prios = [PriorityConfig(oprios.least_requested_priority, 1,
                            "LeastRequestedPriority")]
    if saa:
        prios.append(PriorityConfig(
            oprios.service_anti_affinity_priority("zone"), saa_weight,
            "ZoneSpread"))
    oracle = GenericScheduler(predicates=preds, priorities=prios)
    return oracle.schedule_backlog(pending, state.clone())


def _zone_nodes(n, zones=("za", "zb", "zc"), cap="110", unlabeled=0):
    nodes = []
    for i in range(n):
        labels = {"kubernetes.io/hostname": f"node-{i:04d}"}
        if i >= unlabeled:
            labels["zone"] = zones[i % len(zones)]
        nodes.append(Node(
            metadata=ObjectMeta(name=f"node-{i:04d}", labels=labels),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "32Gi", "pods": cap},
                conditions=[NodeCondition("Ready", "True")],
            ),
        ))
    return nodes


def _member_state(nodes, existing=()):
    return ClusterState.build(
        nodes,
        assigned_pods=list(existing),
        services=[Service(metadata=ObjectMeta(name="app"),
                          spec=ServiceSpec(selector={"app": "x"}))],
    )


def _members(k, name0=0, cpu="100m"):
    out = pause_pods(k, labels={"app": "x"}, requests={"cpu": cpu})
    for i, p in enumerate(out):
        p.metadata.name = f"mem-{name0 + i:05d}"
    return out


def test_wave_service_affinity_first_pick_pins():
    """An unpinned member run: the FIRST commit pins the zone and the
    rest of the run (and later runs) must follow — the replay's
    sa_refine path, bit-identical to the oracle."""
    cfg = _svc_policy(sa=True, saa=False)
    nodes = _zone_nodes(9)
    state = _member_state(nodes)
    pods = _members(40)
    algo = TPUScheduleAlgorithm(config=cfg)
    got = algo.schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=True, saa=False)
    assert got == want
    zones = {n.metadata.name: n.metadata.labels["zone"] for n in nodes}
    assert len({zones[h] for h in got if h}) == 1  # all in the pin zone


def test_wave_service_affinity_existing_peer_pins():
    """A member already assigned pins BEFORE the run: fit is static and
    the run must stay on the fast path landing in the peer's zone."""
    cfg = _svc_policy(sa=True, saa=False)
    nodes = _zone_nodes(9)
    peer = _members(1, name0=900)[0]
    peer.spec.node_name = "node-0004"  # zone zb
    state = _member_state(nodes, existing=[peer])
    pods = _members(30)
    got = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=True, saa=False)
    assert got == want
    zones = {n.metadata.name: n.metadata.labels["zone"] for n in nodes}
    assert {zones[h] for h in got if h} == {"zb"}


def test_wave_service_anti_affinity_spreads_values():
    """SAA only: member commits renormalize the per-value spread every
    pick (the replay's w_saa path)."""
    cfg = _svc_policy(sa=False, saa=True)
    nodes = _zone_nodes(9)
    state = _member_state(nodes)
    pods = _members(60)
    got = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=False, saa=True)
    assert got == want
    zones = {n.metadata.name: n.metadata.labels["zone"] for n in nodes}
    per_zone = {}
    for h in got:
        per_zone[zones[h]] = per_zone.get(zones[h], 0) + 1
    assert max(per_zone.values()) - min(per_zone.values()) <= 1


def test_wave_service_member_and_plain_runs_interleave():
    """Member runs + non-member runs share the carry: the fold must
    record member commits exactly for the later runs' static fits."""
    cfg = _svc_policy(sa=True, saa=True)
    nodes = _zone_nodes(12, unlabeled=2)
    state = _member_state(nodes)
    pods = _members(30) + pause_pods(30, labels={"app": "y"},
                                     requests={"cpu": "50m"})
    for i, p in enumerate(pods[30:]):
        p.metadata.name = f"plain-{i:05d}"
    got = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=True, saa=True)
    assert got == want


@pytest.mark.parametrize("seed", range(10))
def test_wave_service_runs_random(seed):
    rng = random.Random(3000 + seed)
    sa = rng.random() < 0.7
    saa = (not sa) or rng.random() < 0.7
    cfg = _svc_policy(sa=sa, saa=saa, saa_weight=rng.choice([1, 2]))
    nodes = _zone_nodes(rng.randint(4, 15),
                        zones=("za", "zb", "zc")[: rng.randint(1, 3)],
                        cap=str(rng.randint(3, 20)),
                        unlabeled=rng.choice([0, 0, 2]))
    existing = []
    if rng.random() < 0.5:
        peer = _members(1, name0=900)[0]
        peer.spec.node_name = nodes[rng.randrange(len(nodes))].metadata.name
        existing.append(peer)
    state = _member_state(nodes, existing=existing)
    pods = _members(rng.randint(20, 70))
    if rng.random() < 0.6:
        pods += _members(rng.randint(16, 30), name0=500, cpu="200m")
    got = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=sa, saa=saa,
                       saa_weight=cfg.priorities[-1][1] if saa else 2)
    assert got == want


def test_wave_sa_unlabeled_peer_repins_falls_back():
    """The re-pin hazard (review repro): the group IS pinned but the
    peer sits on an UNLABELED node, so the zone stays unresolved and a
    mid-run commit to a lower-ord labeled node re-pins. The tables
    can't express that — the run must fall back to the scan and still
    match the oracle bit-for-bit."""
    cfg = _svc_policy(sa=True, saa=False)
    nodes = _zone_nodes(9, unlabeled=9)  # start all-unlabeled
    for i, n in enumerate(nodes[:8]):
        n.metadata.labels["zone"] = ("za", "zb", "zc")[i % 3]
    # node-0008 stays unlabeled; the existing peer lives there
    peer = _members(1, name0=900)[0]
    peer.spec.node_name = "node-0008"
    state = _member_state(nodes, existing=[peer])
    pods = _members(30)
    cold = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=True, saa=False)
    assert cold == want


def test_wave_sa_unlabeled_nodes_unpinned_falls_back():
    """Unpinned group + partially-labeled cluster: the first pick might
    land on an unlabeled node and leave the label unresolved, so the
    first-pick refinement is not exact — fall back, match the oracle."""
    cfg = _svc_policy(sa=True, saa=False)
    nodes = _zone_nodes(9, unlabeled=3)
    state = _member_state(nodes)
    pods = _members(25)
    got = TPUScheduleAlgorithm(config=cfg).schedule_backlog(pods, state)
    want = _svc_oracle(state, pods, sa=True, saa=False)
    assert got == want


def test_wave_zoned_device_replay_equals_host_spec():
    """The device replay (models/zreplay, one lax.scan dispatch) and the
    host spec replay must produce identical decisions on zoned
    backlogs — both are compared to the oracle elsewhere; this pins
    them against each other directly, including a capacity-exhausted
    tail and an unzoned-node mix."""
    from kubernetes_tpu.models.replay import replay_spec

    nodes = zoned_density_nodes(14, zones=("a", "b"), unzoned_every=4,
                                pods_cap="7")
    state = spread_state(nodes)
    pods = pause_pods(120)  # 98 slots -> unschedulable tail
    dev = TPUScheduleAlgorithm()  # device replay for zoned runs
    host = TPUScheduleAlgorithm(replay=replay_spec)  # host opt-out
    got_dev = dev.schedule_backlog(pods, state.clone())
    got_host = host.schedule_backlog(pods, state.clone())
    assert got_dev == got_host
    assert got_dev == oracle_backlog(state, pods)
    assert got_dev.count(None) == 120 - 98


def test_wave_zoned_tainted_device_replay_matches_host():
    """The review's adversarial case: zoned cluster + PreferNoSchedule
    taints in play, where an integer rewrite of TaintToleration's
    (1.0 - c/mx)*10.0 double-rounding would diverge (mx=20, c=18 ->
    host 0, integer form 1). Pins device replay == host spec == oracle
    with live taint normalizers."""
    import json as _json

    from kubernetes_tpu.api.types import TAINTS_ANNOTATION, Toleration
    from kubernetes_tpu.models.replay import replay_spec

    nodes = zoned_density_nodes(8, zones=("a", "b"), pods_cap="40")
    # escalating intolerable PreferNoSchedule taint counts per node
    for i, node in enumerate(nodes):
        taints = [
            {"key": f"t{k}", "value": "v", "effect": "PreferNoSchedule"}
            for k in range(13 + i)
        ]
        node.metadata.annotations = {
            TAINTS_ANNOTATION: _json.dumps(taints)
        }
    state = spread_state(nodes)
    pods = pause_pods(90)
    for p in pods:
        p.spec.tolerations = [Toleration(key="t0", operator="Equal",
                                         value="v",
                                         effect="PreferNoSchedule")]
    got_dev = TPUScheduleAlgorithm().schedule_backlog(pods, state.clone())
    got_host = TPUScheduleAlgorithm(replay=replay_spec).schedule_backlog(
        pods, state.clone())
    want = oracle_backlog(state, pods)
    assert got_host == want
    assert got_dev == want


# -- grouped multi-run dispatch (fused wave groups) ---------------------------
#
# The grouped driver amortizes device round trips across DISTINCT
# templates: one header probe for K runs, host-rebuilt resource j-axes
# against the accumulating usage, one grouped fold. These fixtures hit
# every cross-run coupling channel the host adjustments must model
# exactly — resources, spread class counts, host ports — plus the
# channels that must BREAK grouping (own inter-pod terms), asserting
# bit-identity to the serial oracle throughout.


def template_pods(num_templates, per, labels=None, cpu0=50, mem_step=50,
                  name0=""):
    pods = []
    for t in range(num_templates):
        for i in range(per):
            pods.append(Pod(
                metadata=ObjectMeta(
                    name=f"{name0}tpl{t:03d}-{i:03d}",
                    labels=dict(labels or {"name": "sched-perf"}),
                ),
                spec=PodSpec(containers=[Container(requests={
                    "cpu": f"{cpu0 + t * 5}m",
                    "memory": f"{100 + (t % 7) * mem_step}Mi",
                })]),
            ))
    return pods


def test_wave_grouped_heterogeneous_spread_coupling():
    # 12 templates all selected by ONE service: every run's commits move
    # every later run's spread counts — the host class-count adjustment
    # path, live under the default provider config
    state = spread_state(density_nodes(15))
    pods = template_pods(12, 10)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_grouped_resource_coupling_fills_nodes():
    # tight capacity: earlier runs' commits exhaust nodes mid-group, so
    # later runs' host-rebuilt res_fit/LR/BA tables must reflect the
    # accumulated usage exactly; tail goes unschedulable
    nodes = density_nodes(4, pods_cap="110", cpu="2", mem="4Gi")
    state = ClusterState.build(nodes)
    pods = template_pods(8, 15, cpu0=200, mem_step=100)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert None in want  # the fixture really does exhaust capacity


def test_wave_grouped_port_conflicts_across_runs():
    # three templates sharing a host port (distinct resources => distinct
    # runs): a node taken by run A's copy must reject runs B/C — the
    # cross-run port veto; a fourth portless template is unaffected
    nodes = density_nodes(6)
    pods = []
    for t in range(3):
        for i in range(4):
            pods.append(Pod(
                metadata=ObjectMeta(name=f"pp{t}-{i}",
                                    labels={"app": "p"}),
                spec=PodSpec(containers=[
                    Container(requests={"cpu": f"{100 + t * 50}m"},
                              ports=[ContainerPort(host_port=8080)])
                ]),
            ))
    pods += template_pods(1, 5, labels={"app": "free"}, cpu0=75,
                          name0="free-")
    state = ClusterState.build(nodes)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    port_hosts = [h for h in got[:12] if h]
    assert len(port_hosts) == len(set(port_hosts)) == 6  # one per node


def test_wave_grouped_zoned_multi_template():
    # many selector templates on a ZONED cluster ride the grouped DEVICE
    # dispatch (zreplay.run_group): one outer loop, carry threaded run
    # to run — the config-4 shape
    state = spread_state(zoned_density_nodes(12))
    pods = template_pods(6, 15)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


def test_wave_grouped_zoned_capacity_tail():
    # zoned device group + capacity exhaustion inside the group
    state = spread_state(zoned_density_nodes(6, pods_cap="8"))
    pods = template_pods(5, 14)
    got = wave_backlog(state, pods)
    want = oracle_backlog(state, pods)
    assert got == want
    assert want[-1] is None


def test_wave_grouped_impure_run_breaks_group():
    # pure templates around an anti-affinity template (own terms =>
    # impure): the impure run must take the per-run path and its carry
    # fold must be visible to the later pure runs
    nodes = hostname_nodes(10)
    pods = template_pods(3, 8, labels={"g": "a"})
    pods += _anti_pods(8, {"g": "a"}, name0=500,
                       requests={"cpu": "300m"})
    pods += template_pods(3, 8, labels={"g": "a"}, cpu0=400,
                          name0="post-")
    state = ClusterState.build(nodes)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods)


@pytest.mark.parametrize("seed", range(8))
def test_wave_grouped_random_templates(seed):
    # randomized multi-template backlogs: varying template counts, run
    # lengths, capacities, zones, services, host ports — grouped (host
    # AND device), single, and scan paths interleave; bit-identity to
    # the oracle throughout
    rng = random.Random(4000 + seed)
    zones = ["a", "b", "c"][: rng.randint(1, 3)]
    if rng.random() < 0.5:
        nodes = zoned_density_nodes(
            rng.randint(5, 20), zones=tuple(zones),
            unzoned_every=rng.choice([0, 3]),
            pods_cap=str(rng.randint(4, 30)),
        )
    else:
        nodes = density_nodes(rng.randint(5, 20),
                              pods_cap=str(rng.randint(4, 30)))
    state = (spread_state(nodes) if rng.random() < 0.6
             else ClusterState.build(nodes))
    pods = []
    for t in range(rng.randint(3, 14)):
        k = rng.randint(1, 18)
        lbl = ({"name": "sched-perf"} if rng.random() < 0.7
               else {"app": f"x{t % 3}"})
        tpl = template_pods(1, k, labels=lbl, cpu0=40 + t * 7,
                            mem_step=30 + t, name0=f"s{t:02d}-")
        if rng.random() < 0.15:
            for p in tpl:
                p.spec.containers[0].ports = [
                    ContainerPort(host_port=7000 + t % 2)]
        pods.extend(tpl)
    assert wave_backlog(state, pods) == oracle_backlog(state, pods), (
        f"seed {seed}"
    )


def test_wave_grouped_probe_count_is_o1():
    # the regression the tentpole exists for: 100 distinct templates
    # must NOT issue 100 probes. One grouped header probe (plus its
    # deferred fold) covers the whole backlog.
    from kubernetes_tpu.models.batch import SchedulerConfig
    from kubernetes_tpu.scheduler.tpu_algorithm import (
        TPUScheduleAlgorithm,
    )

    nodes = density_nodes(50)
    state = ClusterState.build(nodes)
    pods = template_pods(100, 8, cpu0=20, mem_step=13)
    cfg = SchedulerConfig(
        predicates=("PodFitsResources",),
        priorities=(("LeastRequestedPriority", 1),
                    ("BalancedResourceAllocation", 1)),
    )
    algo = TPUScheduleAlgorithm(min_run=1, config=cfg)
    got = algo.schedule_backlog(pods, state)
    d = dict(algo._wave.dispatches)
    assert d.get("probe", 0) == 0, f"per-template probes: {d}"
    assert d.get("group_probe", 0) <= 1, f"grouped probes scaled: {d}"
    assert sum(d.values()) <= 3, (
        f"dispatches must be O(1) in templates, got {d}"
    )
    # and the decisions still match the oracle
    from kubernetes_tpu.oracle import GenericScheduler
    from kubernetes_tpu.oracle import predicates as opreds
    from kubernetes_tpu.oracle import priorities as oprios
    from kubernetes_tpu.oracle.scheduler import PriorityConfig

    oracle = GenericScheduler(
        predicates=[("PodFitsResources", opreds.pod_fits_resources)],
        priorities=[
            PriorityConfig(oprios.least_requested_priority, 1, "LR"),
            PriorityConfig(oprios.balanced_resource_allocation, 1,
                           "BA"),
        ],
    )
    assert got == oracle.schedule_backlog(pods, state.clone())


def test_wave_grouped_mesh_matches_oracle():
    # the grouped path through the MESH driver (sharded header probe +
    # shared host replay + sharded grouped fold) on the 8-virtual-device
    # CPU mesh; skipped automatically where jax.shard_map is absent
    import jax
    from jax.sharding import Mesh
    from kubernetes_tpu.parallel.mesh import MeshWaveScheduler
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder

    devices = jax.devices()
    assert len(devices) >= 8
    mesh = Mesh(np.array(devices[:8]), ("nodes",))
    nodes = density_nodes(13)  # not divisible by 8: padding live
    state = spread_state(nodes)
    pods = template_pods(7, 9)
    want = oracle_backlog(state, pods)

    # dedup positions -> unique rows (the driver contract)
    reps, rep_idx = {}, []
    uniq = []
    for i, p in enumerate(pods):
        k = pod_feature_key(p)
        if k not in reps:
            reps[k] = len(uniq)
            uniq.append(i)
        rep_idx.append(reps[k])
    enc2 = SnapshotEncoder(state, [pods[i] for i in uniq])
    snap = enc2.encode_nodes()
    batch = enc2.encode_pods()
    ws = MeshWaveScheduler(mesh, min_run=1)
    chosen, _, _ = ws.schedule_backlog(
        snap, batch, np.asarray(rep_idx, np.int64)
    )
    got = [snap.node_names[c]
           if 0 <= c < len(state.node_infos) else None for c in chosen]
    assert got == want
    d = ws.dispatches
    assert d.get("group_probe", 0) >= 1, f"mesh grouping idle: {d}"


def _wave_scheduler_run(state, pods, max_j=1024, replay=None,
                        last_node_index=0):
    """Drive WaveScheduler directly (dedup + pad like the algorithm
    shell): -> (hosts, the scheduler)."""
    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.pad import next_pow2

    uniq, rep_of, rep_idx = [], {}, []
    for p in pods:
        k = pod_feature_key(p)
        if k not in rep_of:
            rep_of[k] = len(uniq)
            uniq.append(p)
        rep_idx.append(rep_of[k])
    enc = SnapshotEncoder(state, uniq)
    snap = enc.encode_nodes()
    batch = enc.encode_pods()
    snap_p = _pad_snapshot(snap, next_pow2(snap.num_nodes, 4))
    ws = WaveScheduler(min_run=1, max_j=max_j, replay=replay)
    chosen, _, _ = ws.schedule_backlog(
        snap_p, batch, np.asarray(rep_idx, np.int64),
        last_node_index=last_node_index,
    )
    got = [snap.node_names[c] if 0 <= c < snap.num_nodes else None
           for c in chosen]
    return got, ws


def _wave_direct(state, pods, max_j):
    """The same with a clamped table horizon: -> (hosts, dispatches)."""
    got, ws = _wave_scheduler_run(state, pods, max_j=max_j)
    return got, ws.dispatches


def test_wave_grouped_host_horizon_resume():
    # huge per-node capacity + a clamped 128-row table horizon: runs
    # inside a HOST group trip the horizon mid-run, the group aborts,
    # the partial run resumes on the single path, and the remaining
    # runs regroup — decisions stay bit-identical to the oracle
    nodes = density_nodes(2, pods_cap="1000")
    state = ClusterState.build(nodes)
    pods = template_pods(3, 300, cpu0=1, mem_step=0)
    got, d = _wave_direct(state, pods, max_j=128)
    assert got == oracle_backlog(state, pods)
    assert d.get("probe", 0) >= 1, f"no single-path resume happened: {d}"


def test_wave_grouped_device_horizon_resume():
    # the same horizon abort through the grouped DEVICE dispatch: the
    # outer loop ends at the bail, later runs schedule nothing, the
    # host resumes from the bail point
    state = spread_state(zoned_density_nodes(2, pods_cap="1000"))
    pods = template_pods(3, 300, cpu0=1, mem_step=0)
    got, d = _wave_direct(state, pods, max_j=128)
    assert got == oracle_backlog(state, pods)
    assert d.get("zreplay", 0) >= 1, f"no single-path resume: {d}"


# -- the device replay's two loops end at the real runs and picks --------------
#
# The grouped program is compiled for a run-slot bucket (32 or 128:
# `waveloop.DEVICE_SLOT_BUCKETS`) and a pick bucket (a power of two from
# 64); its loops run the
# group's real run count and each run's real length. Each case is one
# the padding used to cover.


def _runs(lengths):
    """One template a run, `lengths[t]` pods of template t in a row."""
    pods = []
    for t, k in enumerate(lengths):
        pods += template_pods(1, k, cpu0=1 + 5 * t, name0=f"r{t:02d}-")
    return pods


def _roomy(n=24):
    return spread_state(zoned_density_nodes(n, cpu="16"))


REPLAY_LOOP_CASES = {
    # name: (state, run lengths, max_j, what the first grouped dispatch's
    #        counters must read: (run slots, pick steps, pods placed), or
    #        None where no grouped dispatch is made)
    "5-runs": (lambda: _roomy(12), [12] * 5, 1024, (5, 60, 60)),
    "8-runs": (lambda: _roomy(12), [10] * 8, 1024, (8, 80, 80)),
    "80-runs": (_roomy, [8] * 80, 1024, (80, 640, 640)),
    # one group, its pick bucket the longest run's (65 -> 128)
    "mixed-lengths": (_roomy, [40, 1, 7, 64, 65], 1024, (5, 177, 177)),
    # 6 nodes x 8 pods hold 48 of the 70: the fourth run places 6 and
    # pays its 14 steps, the fifth places none
    "exhausts-mid-run": (
        lambda: spread_state(zoned_density_nodes(6, pods_cap="8")),
        [14] * 5, 1024, (5, 70, 48)),
    # the middle run (300 pods on 2 nodes) trips the 128-row horizon at
    # its 255th pick; the loop ends there and the third slot is never
    # entered
    "horizon-bail-in-the-middle": (
        lambda: spread_state(zoned_density_nodes(2, pods_cap="1000")),
        [100, 300, 100], 128, (2, 355, 355)),
    # a lone run: jit_zreplay_run, through the same pick loop
    "lone-run": (lambda: _roomy(12), [64], 1024, None),
}


@pytest.mark.parametrize("case", sorted(REPLAY_LOOP_CASES))
def test_device_replay_loops_end_at_the_real_runs_and_picks(
        case, monkeypatch):
    from kubernetes_tpu.models.replay import replay_spec
    from kubernetes_tpu.models.zreplay import ZReplay

    make_state, lengths, max_j, first_ran = REPLAY_LOOP_CASES[case]
    state, pods = make_state(), _runs(lengths)
    came_back = []
    sound = ZReplay.run_group

    def recorded(self, *a, **kw):
        carry, chosen, n_done, L = sound(self, *a, **kw)
        came_back.append((np.asarray(chosen), np.asarray(n_done),
                          np.asarray(self.group_ran)))
        return carry, chosen, n_done, L

    monkeypatch.setattr(ZReplay, "run_group", recorded)
    want = oracle_backlog(state, pods)
    got, ws = _wave_scheduler_run(state, pods, max_j=max_j)
    got_host, _ = _wave_scheduler_run(state, pods, max_j=max_j,
                                      replay=replay_spec)
    assert got == got_host == want
    if first_ran is None:
        # the lone run's program counts its own pick steps; it has no
        # run slots
        assert not came_back and ws.dispatches == {"zreplay": 1}
        assert ws.stats["zreplay_steps"] == ws.stats["zreplay_picks"] \
            == lengths[0]
        assert ws.stats["zreplay_slots"] == 0
        return
    chosen, n_done, ran = came_back[0]
    slots, steps, picks = first_ran
    assert (int(ran[1]), int(ran[0]), int((chosen >= 0).sum())) == first_ran
    # far under what the buckets would have paid
    assert steps < chosen.shape[0] * chosen.shape[1]
    # a slot the loop never entered keeps the buffers' initial values
    assert not n_done[slots:].any() and (chosen[slots:] == -1).all()
    for g, k in enumerate(lengths[:slots]):
        assert (chosen[g, k:] == -1).all()
    if case == "horizon-bail-in-the-middle":
        assert list(n_done[:3]) == [100, 255, 0]
        assert ws.dispatches.get("zreplay", 0) >= 1  # the resume
    else:
        assert list(n_done[:slots]) == lengths
        assert ws.dispatches == {"zreplay_group": 1}
        assert (ws.stats["zreplay_slots"], ws.stats["zreplay_steps"],
                ws.stats["zreplay_picks"]) == first_ran


# -- the device replay carries the score without its spread term ---------------
#
# A pick step takes the non-spread score of the node it picked from the
# evaluation at j + 1 that opened its epoch; the epoch ends, and the next
# evaluates again, when a node is picked twice in it or a node leaves the
# fit set holding an extreme that a normaliser reads
# (models/zreplay._replay_run). Each case forces one of the two, or
# neither, and the picks must be the host replay's and the serial
# oracle's.


def _existing(node, i, requests):
    """A bound pod no service selects."""
    return Pod(
        metadata=ObjectMeta(name=f"held-{i:03d}", labels={"held": "yes"}),
        spec=PodSpec(node_name=node.metadata.name,
                     containers=[Container(requests=dict(requests))]))


def _one_short_of(what):
    """24 zoned nodes, every second one a single pod short of its
    allocatable pods / memory: its fit bit flips at its first pick."""
    nodes = zoned_density_nodes(
        24, cpu="16", **({"pods_cap": "3"} if what == "pods" else {}))
    held = []
    for i, node in enumerate(nodes[::2]):
        if what == "pods":
            held += [_existing(node, 2 * i + k, {"cpu": "10m"})
                     for k in range(2)]
        else:
            node.status.allocatable["memory"] = "300Mi"
            held.append(_existing(node, i, {"memory": "200Mi"}))
    state = spread_state(nodes)
    for pod in held:
        state.assign(pod)
    return state


def _preferring(pods):
    """Every pod prefers disktype=ssd (weight 5) and any `gen` (3)."""
    from kubernetes_tpu.api.types import (
        Affinity, NodeAffinity, NodeSelectorRequirement, NodeSelectorTerm,
        PreferredSchedulingTerm)

    def term(weight, **req):
        return PreferredSchedulingTerm(
            weight=weight, preference=NodeSelectorTerm(
                match_expressions=(NodeSelectorRequirement(**req),)))

    for p in pods:
        p.spec.affinity = Affinity(node_affinity=NodeAffinity(
            preferred_during_scheduling_ignored_during_execution=(
                term(5, key="disktype", operator="In", values=("ssd",)),
                term(3, key="gen", operator="Exists"))))
    return pods


def _preferred_nodes():
    """The nodes that hold NodeAffinity's maximum (both labels, count 8)
    take 2 pods each and the ssd ones 4: the maximum the normaliser
    divides by falls 8 -> 5 -> 3 as they leave the fit set."""
    nodes = zoned_density_nodes(24, cpu="16")
    for i, node in enumerate(nodes):
        if i % 4 == 0:
            node.metadata.labels["disktype"] = "ssd"
            node.status.allocatable["pods"] = "4"
        if i % 6 == 0:
            node.metadata.labels["gen"] = "2"
        if i % 12 == 0:
            node.status.allocatable["pods"] = "2"
    return spread_state(nodes)


def _tainted_nodes():
    """8 zoned nodes of 4 places with 13..20 PreferNoSchedule taints the
    pods tolerate one of: TaintToleration's maximum moves as they fill."""
    import json as _json

    from kubernetes_tpu.api.types import TAINTS_ANNOTATION

    nodes = zoned_density_nodes(8, zones=("a", "b"), pods_cap="4")
    for i, node in enumerate(nodes):
        node.metadata.annotations = {TAINTS_ANNOTATION: _json.dumps([
            {"key": f"t{k}", "value": "v", "effect": "PreferNoSchedule"}
            for k in range(13 + i)])}
    return spread_state(nodes)


def _tolerating(pods):
    from kubernetes_tpu.api.types import Toleration

    for p in pods:
        p.spec.tolerations = [Toleration(
            key="t0", operator="Equal", value="v",
            effect="PreferNoSchedule")]
    return pods


def _forty_shapes(per):
    """hetero-1k's 40 request pairs, `per` pods of each in a row."""
    pods = []
    for t in range(40):
        pods += [Pod(
            metadata=ObjectMeta(name=f"s{t:02d}-{i:02d}",
                                labels={"name": "sched-perf"}),
            spec=PodSpec(containers=[Container(requests={
                "cpu": f"{50 + (t % 8) * 25}m",
                "memory": f"{100 + (t % 5) * 100}Mi"})]))
            for i in range(per)]
    return pods


def _self_anti_rows():
    """Two runs with hostname self-anti-affinity on zoned nodes: the
    veto takes every picked node out of the fit set, and no such node
    holds an extreme a normaliser reads (the services score 0 on all
    three), so a run is one epoch."""
    nodes = zoned_density_nodes(12)
    for node in nodes:
        node.metadata.labels["kubernetes.io/hostname"] = node.metadata.name
    services = [Service(metadata=ObjectMeta(name=f"svc-{app}"),
                        spec=ServiceSpec(selector={"app": app}))
                for app in "xy"]
    return (ClusterState.build(nodes, services=services),
            _anti_pods(8, {"app": "x"})
            + _anti_pods(8, {"app": "y"}, name0=100,
                         requests={"cpu": "200m"}))


CARRIED_SCORE_CASES = {
    # name: (-> (state, pods), the first lastNodeIndex, whether the
    #        grouped program must have rescored: True / False / None
    #        where the case does not say)
    # 30 picks on 12 nodes: every node picked more than once in a run
    "node-picked-twice": (
        lambda: (_roomy(12), _runs([30, 30])), 0, True),
    "one-pod-short-of-pods": (
        lambda: (_one_short_of("pods"), _runs([20, 20])), 0, True),
    "one-pod-short-of-memory": (
        lambda: (_one_short_of("memory"), _runs([20, 20])), 0, True),
    "preferred-node-affinity": (
        lambda: (_preferred_nodes(), _preferring(_runs([30, 30]))), 0,
        True),
    "prefer-no-schedule-taints": (
        lambda: (_tainted_nodes(), _tolerating(_runs([20, 20]))), 0, True),
    # unequal requests leave unequal LeastRequested / Balanced scores
    # behind, which can outweigh a node's spread share (10 / 3 points):
    # a run may come back to a node, so the case does not say
    "forty-request-shapes": (
        lambda: (_roomy(48), _forty_shapes(10)), 0, None),
    # one shape on as many nodes: no run comes back to a node
    "one-shape-many-nodes": (
        lambda: (_roomy(48), _runs([10] * 12)), 0, False),
    "self-anti-veto": (_self_anti_rows, 0, False),
    # selectHost's remainder from both halves of a 64-bit index, ties
    # everywhere
    "round-robin-past-2-to-the-32": (
        lambda: (_roomy(12), _runs([12] * 3)), (1 << 33) + 5, None),
    "round-robin-at-the-32-bit-edge": (
        lambda: (_roomy(12), _runs([12] * 3)), (1 << 32) - 7, None),
}


@pytest.mark.parametrize("case", sorted(CARRIED_SCORE_CASES))
def test_device_replay_carries_the_score_and_picks_as_the_serial_does(case):
    from kubernetes_tpu.models.replay import replay_spec

    make, L0, rescored = CARRIED_SCORE_CASES[case]
    state, pods = make()
    oracle = GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES,
        last_node_index=L0)
    want = oracle.schedule_backlog(pods, state.clone())
    got, ws = _wave_scheduler_run(state, pods, last_node_index=L0)
    got_host, _ = _wave_scheduler_run(state, pods, replay=replay_spec,
                                      last_node_index=L0)
    assert got == got_host == want
    stats = ws.stats
    assert ws.dispatches.get("zreplay_group", 0) >= 1, ws.dispatches
    assert stats["zreplay_picks"] == sum(h is not None for h in got)
    if rescored is not None:
        assert (stats["zreplay_rescores"] > 0) == rescored, stats
    # an epoch holds a step at least
    assert stats["zreplay_rescores"] <= stats["zreplay_steps"]
