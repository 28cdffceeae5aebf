"""Incremental snapshot maintenance: snapshot-after-deltas must equal
snapshot-from-scratch (VERDICT round-1 item #2; reference analogue:
schedulercache/node_info.go:118-156 O(1) deltas + cache.go:77 clone).

Two layers of proof:
  1. semantic: after a random cache event stream, every decoded per-node
     quantity in the incremental arrays equals what a from-scratch
     SnapshotEncoder derives from the same cluster state;
  2. end-to-end: scheduling decisions through the cache-wired
     TPUScheduleAlgorithm (incremental wave path, with fallback gates)
     are identical to the sequential oracle on the equivalently
     restricted state, across interleaved event batches.
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container,
    ContainerPort,
    Node,
    NodeCondition,
    NodeStatus,
    NodeSpec,
    ObjectMeta,
    Pod,
    PodSpec,
    Service,
    ServiceSpec,
    Taint,
)
from kubernetes_tpu.oracle import ClusterState, GenericScheduler
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.factory import node_schedulable
from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
from kubernetes_tpu.snapshot.incremental import IncrementalEncoder
from kubernetes_tpu.utils.clock import FakeClock

from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

ZONE = "failure-domain.beta.kubernetes.io/zone"


class _Lister:
    def __init__(self):
        self.items = []

    def list(self):
        return list(self.items)


def rand_node(rng, name):
    labels = {"kubernetes.io/hostname": name}
    if rng.random() < 0.4:
        labels[ZONE] = rng.choice(["a", "b"])
    if rng.random() < 0.5:
        labels["disktype"] = rng.choice(["ssd", "hdd"])
    taints = None
    if rng.random() < 0.25:
        taints = [Taint(key="dedicated", value=rng.choice(["a", "b"]),
                        effect=rng.choice(["NoSchedule", "PreferNoSchedule"]))]
    conds = [NodeCondition("Ready", rng.choice(["True", "True", "True", "False"]))]
    if rng.random() < 0.2:
        conds.append(NodeCondition("MemoryPressure", "True"))
    return Node(
        metadata=ObjectMeta(name=name, labels=labels),
        spec=NodeSpec(taints=taints),
        status=NodeStatus(
            allocatable={
                "cpu": f"{rng.choice([1000, 2000, 4000])}m",
                "memory": str(rng.choice([2, 4, 8]) * 1024**3),
                "pods": str(rng.choice([5, 20, 110])),
            },
            conditions=conds,
        ),
    )


def rand_assigned(rng, i, node_name):
    reqs = {}
    if rng.random() < 0.8:
        reqs["cpu"] = f"{rng.choice([0, 100, 300])}m"
    if rng.random() < 0.8:
        reqs["memory"] = str(rng.choice([0, 256, 512]) * 1024**2)
    ports = []
    if rng.random() < 0.3:
        ports.append(ContainerPort(host_port=rng.choice([8080, 9090])))
    return Pod(
        metadata=ObjectMeta(
            name=f"assigned-{i}",
            labels=rng.choice([{"app": "web"}, {"app": "db"}, {}]),
        ),
        spec=PodSpec(
            node_name=node_name,
            containers=[Container(requests=reqs, ports=ports)],
        ),
    )


def rand_pending(rng, i):
    kw = {}
    if rng.random() < 0.3:
        kw["node_selector"] = rng.choice([{"disktype": "ssd"}, {ZONE: "a"}])
    return Pod(
        metadata=ObjectMeta(
            name=f"pending-{i}",
            labels=rng.choice([{"app": "web"}, {"app": "db"}]),
        ),
        spec=PodSpec(
            containers=[
                Container(requests={"cpu": "100m", "memory": "100Mi"})
            ],
            **kw,
        ),
    )


def drive_events(rng, cache, steps, live_nodes, live_pods, pod_seq):
    """Apply `steps` random mutations to the cache, mirroring them in
    live_nodes / live_pods dicts (name -> object)."""
    for _ in range(steps):
        op = rng.random()
        if op < 0.25 or not live_nodes:
            name = f"node-{rng.randrange(200):03d}"
            node = rand_node(rng, name)
            if name in live_nodes:
                cache.update_node(live_nodes[name], node)
            else:
                cache.add_node(node)
            live_nodes[name] = node
        elif op < 0.35 and live_nodes:
            name = rng.choice(list(live_nodes))
            cache.remove_node(live_nodes.pop(name))
        elif op < 0.75:
            pod_seq[0] += 1
            pod = rand_assigned(rng, pod_seq[0], rng.choice(list(live_nodes)))
            cache.add_pod(pod)
            live_pods[pod.metadata.name] = pod
        elif live_pods:
            name = rng.choice(list(live_pods))
            cache.remove_pod(live_pods.pop(name))


def restricted_state(cache, services=(), controllers=()):
    """core.py Scheduler._snapshot semantics: schedulable nodes only."""
    state = cache.snapshot(services=list(services), controllers=list(controllers))
    sub = ClusterState(services=list(services), controllers=list(controllers))
    sub.node_infos = {
        n: info
        for n, info in state.node_infos.items()
        if info.node is not None and node_schedulable(info.node)
    }
    sub.full = state
    return sub


@pytest.mark.parametrize("seed", range(4))
def test_incremental_semantic_equality(seed):
    rng = random.Random(7000 + seed)
    cache = SchedulerCache(clock=FakeClock(0.0))
    inc = IncrementalEncoder()
    cache.add_listener(inc.on_cache_event)
    live_nodes, live_pods, seq = {}, {}, [0]
    for _round in range(4):
        drive_events(rng, cache, 40, live_nodes, live_pods, seq)
        snap, _batch, _keep = inc.wave_view([rand_pending(rng, 0)])
        assert snap is not None
        v = inc.vocabs
        state = cache.snapshot()
        for name, info in state.node_infos.items():
            if info.node is None:
                slot = inc.slot_of[name]
                assert inc._node_gone[slot]
                continue
            slot = inc.slot_of[name]
            node = info.node
            # resources: cache aggregates vs incremental arrays
            assert snap.req_mcpu[slot] == info.requested_milli_cpu
            assert snap.req_mem[slot] == info.requested_memory
            assert snap.nz_mcpu[slot] == info.nonzero_milli_cpu
            assert snap.nz_mem[slot] == info.nonzero_memory
            assert snap.pod_count[slot] == len(info.pods)
            # labels: decode the kv bitset back to pairs
            got_kv = {
                kv
                for kv, kid in v.kv.ids.items()
                if snap.label_kv[slot, kid // 32] >> np.uint32(kid % 32) & 1
            }
            assert got_kv == set(node.metadata.labels.items())
            # taints (multiset via taint_count)
            from kubernetes_tpu.api.types import get_taints

            want_taints = {}
            for t in get_taints(node):
                k = (t.key, t.value, t.effect)
                want_taints[k] = want_taints.get(k, 0) + 1
            got_taints = {
                k: int(snap.taint_count[slot, tid])
                for k, tid in v.taints.ids.items()
                if snap.taint_count[slot, tid]
            }
            assert got_taints == want_taints
            # ports union
            want_ports = set()
            for p in info.pods:
                for c in p.spec.containers:
                    for pp in c.ports:
                        if pp.host_port:
                            want_ports.add(pp.host_port)
            got_ports = {
                port
                for port, pid in v.ports.ids.items()
                if snap.port_mask[slot, pid // 32] >> np.uint32(pid % 32) & 1
            }
            assert got_ports == want_ports
            # spread classes
            for ckey, cid in v.classes.ids.items():
                ns, labels_fs, deleted = ckey
                want = sum(
                    1
                    for p in info.pods
                    if p.namespace == ns
                    and frozenset(p.metadata.labels.items()) == labels_fs
                    and (p.metadata.deletion_timestamp is not None) == deleted
                )
                assert snap.class_count[slot, cid] == want
            # schedulability masking
            if node_schedulable(node):
                assert snap.alloc_mcpu[slot] > 0
            else:
                assert snap.alloc_pods[slot] == 0
        # every live slot maps to a live node or a gone-with-pods slot
        for name, slot in inc.slot_of.items():
            assert name in state.node_infos


@pytest.mark.parametrize("seed", range(4))
def test_incremental_decisions_match_oracle(seed):
    rng = random.Random(8000 + seed)
    cache = SchedulerCache(clock=FakeClock(0.0))
    svc_lister, rc_lister, rs_lister = _Lister(), _Lister(), _Lister()
    svc_lister.items = [
        Service(metadata=ObjectMeta(name="web"),
                spec=ServiceSpec(selector={"app": "web"}))
    ]
    algo = TPUScheduleAlgorithm(
        min_run=1, cache=cache, service_lister=svc_lister,
        controller_lister=rc_lister, replica_set_lister=rs_lister,
    )
    oracle = GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES
    )
    live_nodes, live_pods, seq = {}, {}, [0]
    pend_seq = 0
    for _round in range(5):
        drive_events(rng, cache, 30, live_nodes, live_pods, seq)
        pending = []
        for _ in range(rng.randint(1, 12)):
            pend_seq += 1
            p = rand_pending(rng, pend_seq)
            pending += [p] * rng.randint(1, 4)  # runs of identical pods
        state = restricted_state(cache, services=svc_lister.items)
        want = oracle.schedule_backlog(pending, state.clone())
        got = algo.schedule_backlog(pending, state)
        assert got == want, f"seed {seed} round {_round}"
        # decisions consumed: mirror what binding would do, so later
        # rounds schedule against the updated cluster
        for p, host in zip(pending, want):
            if host is None:
                continue
            import copy

            bound = copy.deepcopy(p)
            bound.metadata.name = f"{p.metadata.name}-b{len(live_pods)}"
            bound.spec.node_name = host
            cache.add_pod(bound)
            live_pods[bound.metadata.name] = bound


def test_pod_on_unsynced_node_invalidates_name_order():
    """A pod_add for a node the cache hasn't seen materializes a new slot
    and changes name_desc_order; wave_view must not report it in `keep`
    (a stale device copy would desync selectHost's tie-breaking)."""
    cache = SchedulerCache(clock=FakeClock())
    inc = IncrementalEncoder()
    cache.add_listener(inc.on_cache_event)
    rng = random.Random(0)
    for i in range(4):
        cache.add_node(rand_node(rng, f"node-{i:03d}"))

    def plain_pod(name, node):
        # identical class (namespace/labels) and no ports: introduces no
        # new vocab entries, so no width growth masks the slot's dirt
        return Pod(
            metadata=ObjectMeta(name=name, labels={"app": "web"}),
            spec=PodSpec(node_name=node,
                         containers=[Container(requests={"cpu": "100m"})]),
        )

    cache.add_pod(plain_pod("seed", "node-000"))
    snap1, _, _ = inc.wave_view([plain_pod("pend-0", "")])
    assert snap1 is not None
    # informer races: the pod lands before its node object syncs
    cache.add_pod(plain_pod("racer", "zz-unsynced-node"))
    # the wave-2 pending pod is shape-identical so no vocab growth
    # re-dirties the node side by accident
    snap2, _, keep = inc.wave_view([plain_pod("pend-1", "")])
    assert snap2 is not None
    changed = not np.array_equal(snap1.name_desc_order, snap2.name_desc_order)
    assert changed
    assert "name_desc_order" not in keep


@pytest.mark.parametrize("owner", [False, True],
                         ids=["plain", "term-owner"])
@pytest.mark.parametrize("step,nodes,slots", [
    (None, 70, 128), (32, 70, 96), (32, 96, 96), (32, 97, 128),
])
def test_node_axis_grows_by_its_step(step, nodes, slots, owner):
    """With a `slot_step` (the mesh driver's: a multiple of its
    devices) the node axis grows by that step and the view's node axis
    is a multiple of it; without one it doubles. The nodes' slots and
    what the view says of them are the same either way, and where the
    pending pod owns a term the nodes' domains are as long as every
    other node array (the kept `topo_dom` has room beyond the axis)."""
    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    cache = SchedulerCache(clock=FakeClock())
    inc = IncrementalEncoder(slot_step=step)
    cache.add_listener(inc.on_cache_event)
    rng = random.Random(step or 1)
    for i in range(nodes):
        cache.add_node(rand_node(rng, f"node-{i:03d}"))
    meta = ObjectMeta(name="pend", labels={"app": "web"})
    if owner:
        meta.annotations[AFFINITY_ANNOTATION] = _terms_json(
            "podAntiAffinity", [_term({"app": "web"}, HOSTNAME)])
    pending = [Pod(metadata=meta, spec=PodSpec(containers=[
        Container(requests={"cpu": "100m"})]))]
    snap, _batch, _keep = inc.wave_view(pending)
    assert snap.num_nodes == slots == len(snap.node_names)
    assert snap.node_names[:nodes] == [f"node-{i:03d}"
                                       for i in range(nodes)]
    assert not any(snap.node_names[nodes:])
    assert snap.ip_topo_dom.shape == ((1, slots) if owner else (0, 0))
    # and so they stay when the axis grows under the kept tables
    for i in range(nodes, nodes + 2 * (step or slots)):
        cache.add_node(rand_node(rng, f"node-{i:03d}"))
    snap, _batch, _keep = inc.wave_view(pending)
    assert snap.num_nodes > slots
    assert snap.ip_topo_dom.shape == (
        (1, snap.num_nodes) if owner else (0, 0))


def test_daemon_warmup_compiles_incremental_shapes():
    """warmup() in daemon mode must compile the programs the incremental
    wave path will actually run — the full encoder's static shapes differ
    (padded vocab widths), so warming via it leaves the cold compile on
    the first real wave."""
    cache = SchedulerCache(clock=FakeClock())
    algo = TPUScheduleAlgorithm(cache=cache, service_lister=_Lister(),
                                controller_lister=_Lister(),
                                replica_set_lister=_Lister())
    algo.warmup(6)
    assert algo._wave.scan._jitted and algo._wave.probe._jitted
    # now drive a real wave of the same shape through the daemon path
    rng = random.Random(1)
    for i in range(6):
        cache.add_node(Node(
            metadata=ObjectMeta(name=f"node-{i:03d}",
                                labels={"app": "warm"}),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")],
            ),
        ))
    pods = [Pod(metadata=ObjectMeta(name=f"p-{i}", labels={"app": "warm"}),
                spec=PodSpec(containers=[
                    Container(image="warm", requests={"cpu": "100m"})]))
            for i in range(max(algo._wave.min_run, 2))]
    state = restricted_state(cache)
    import logging

    import jax

    compiles = []

    class _H(logging.Handler):
        def emit(self, r):
            msg = r.getMessage()
            if "Finished XLA compilation" in msg:
                compiles.append(msg)

    h = _H()
    lg = logging.getLogger("jax._src.dispatch")
    prev_level = lg.level
    lg.addHandler(h)
    lg.setLevel(logging.DEBUG)
    jax.config.update("jax_log_compiles", True)
    try:
        got = algo.schedule_backlog(pods, state)
    finally:
        jax.config.update("jax_log_compiles", False)
        lg.removeHandler(h)
        lg.setLevel(prev_level)
    assert all(g is not None for g in got)
    # the wave must hit only programs warmup already compiled
    assert not compiles, compiles


# -- a batch of deltas against the same deltas one at a time ----------------

HOSTNAME = "kubernetes.io/hostname"


def _term(labels, key, namespaces=None):
    t = {"labelSelector": {"matchLabels": labels}, "topologyKey": key}
    if namespaces is not None:
        t["namespaces"] = namespaces
    return t


def _terms_json(side, required=(), preferred=()):
    import json

    return json.dumps({side: {
        "requiredDuringSchedulingIgnoredDuringExecution": list(required),
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": w, "podAffinityTerm": t} for w, t in preferred],
    }})


_BATCH_TEMPLATES = (
    # (labels, requests, host ports, affinity annotation)
    ({"name": "sched-perf"}, {"cpu": "100m", "memory": "500Mi"}, (), None),
    ({"app": "web"}, {"cpu": "250m"}, (), None),
    ({"app": "db", "tier": "be"}, {"memory": "1Gi"}, (), None),
    ({}, {}, (), None),
    ({"app": "web"}, {"cpu": "100m"}, (8080,), None),
    ({"app": "lb"}, {"cpu": "50m"}, (9090, 8080), None),
    # required anti-affinity to its own kind, by hostname
    ({"app": "near"}, {"cpu": "100m"}, (),
     _terms_json("podAntiAffinity", [_term({"app": "near"}, HOSTNAME)])),
    # required affinity by a second key, preferred by hostname
    ({"app": "db"}, {"cpu": "100m"}, (),
     _terms_json("podAffinity", [_term({"app": "web"}, ZONE)],
                 [(5, _term({"app": "db"}, HOSTNAME))])),
    # the empty key (any default failure domain: the inclusion-exclusion
    # expansion), required and against every namespace
    ({"app": "far"}, {"cpu": "50m"}, (),
     _terms_json("podAntiAffinity", [_term({"app": "lb"}, "", [])],
                 [(3, _term({"app": "web"}, ZONE))])),
    # preferred by the empty key, with host ports (the per-event path)
    ({"app": "lb"}, {"cpu": "50m"}, (7070,),
     _terms_json("podAffinity", (), [(2, _term({"app": "far"}, ""))])),
    # an annotation that does not parse: the poison
    ({"app": "odd"}, {"cpu": "10m"}, (), "{not json"),
    # a preferred term of weight 0 (owned, counted, never scored) beside
    # one with its namespaces spelled out
    ({"app": "web", "tier": "fe"}, {"cpu": "50m"}, (),
     _terms_json("podAntiAffinity", (), [
         (0, _term({"app": "db"}, ZONE)),
         (7, _term({"app": "web"}, HOSTNAME, ["default", "elsewhere"]))])),
)
_BATCH_WEIGHTS = (8, 4, 3, 2, 2, 1, 3, 2, 2, 1, 1, 2)


def _batch_pod(rng, name, node_name, fresh_class=None):
    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    labels, reqs, ports, affinity = rng.choices(
        _BATCH_TEMPLATES, _BATCH_WEIGHTS)[0]
    labels = dict(labels)
    if fresh_class is not None:  # a spread class nobody has seen yet
        labels["gen"] = fresh_class
    meta = ObjectMeta(name=name, labels=labels)
    if affinity is not None:
        meta.annotations[AFFINITY_ANNOTATION] = affinity
    if rng.random() < 0.05:
        meta.deletion_timestamp = "2026-01-01T00:00:00Z"
    return Pod(
        metadata=meta,
        spec=PodSpec(
            node_name=node_name,
            containers=[Container(
                requests=dict(reqs),
                ports=[ContainerPort(host_port=p) for p in ports],
            )],
        ),
    )


def _batch_events(rng, steps, nodes, pods, seq):
    """`steps` raw cache events: what SchedulerCache would send and what
    it never would (a re-add with no remove between, a remove of a pod
    nobody holds), so the encoder's defensive branches run too. `nodes`
    and `pods` (name -> object) follow what the stream leaves live.
    A node set again is a relabel (it may move the node's zone under its
    pods), a node removed leaves its pods lingering, an `unsynced-*`
    node is never known: each with term owners on it, some of the time."""
    events = []
    for _ in range(steps):
        op = rng.random()
        if op < 0.08 or not nodes:
            name = f"node-{rng.randrange(24):03d}"
            nodes[name] = rand_node(rng, name)
            events.append(("node_set", nodes[name]))
        elif op < 0.12:
            # under its pods, if it has any: they keep the row as a
            # gone-node slot until the last of them leaves
            name = rng.choice(list(nodes))
            events.append(("node_remove", nodes.pop(name)))
        elif op < 0.62:
            r = rng.random()
            if r < 0.08:
                node_name = f"unsynced-{rng.randrange(3)}"
            else:
                node_name = rng.choice(list(nodes))
            if pods and rng.random() < 0.1:
                name = rng.choice(list(pods))  # a re-add: an update
            else:
                seq[0] += 1
                name = f"pod-{seq[0]}"
            fresh = f"g{seq[0]}" if rng.random() < 0.06 else None
            pod = _batch_pod(rng, name, node_name, fresh)
            pods[name] = pod
            events.append(("pod_add", pod))
            if rng.random() < 0.15:  # in and out inside one batch
                events.append(("pod_remove", pods.pop(name)))
        elif pods:
            if rng.random() < 0.05:
                events.append(
                    ("pod_remove", _batch_pod(rng, "never-held", "node-000")))
                continue
            # the one pod that does not parse goes before the others:
            # it poisons every wave it sees
            odd = [n for n, p in pods.items()
                   if p.metadata.labels.get("app") == "odd"]
            name = rng.choice(odd or list(pods))
            events.append(("pod_remove", pods.pop(name)))
    return events


def _assert_same_view(a, b, context):
    import dataclasses

    assert (a is None) == (b is None), context
    if a is None:
        return
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        where = f"{context}: {f.name}"
        if isinstance(x, np.ndarray):
            assert isinstance(y, np.ndarray), where
            assert x.dtype == y.dtype and x.shape == y.shape, where
            assert np.array_equal(x, y, equal_nan=x.dtype.kind == "f"), where
        else:
            assert x == y, where


def _decoded_terms(fields, vocab, node_col):
    """The inter-pod tables by canonical key, whatever the numbering:
    {("count", spec, combo, node): n} for `term_count`,
    {(table, spec, topology key, combo, node): n} for the four owner
    tables, {("total", spec): n}; zeros left out. `fields` are the
    snapshot's `ip_*` arrays without the prefix, `vocab` the TermVocab
    they are numbered in, `node_col` the column of each node known."""
    out = {}
    topo_dom = fields["topo_dom"]
    for (s, q), u in vocab.units.ids.items():
        for node, col in node_col.items():
            d = topo_dom[q, col]
            if d >= 0 and fields["term_count"][u, d]:
                out["count", vocab.specs.items[s], vocab.topos.items[q],
                    node] = int(fields["term_count"][u, d])
    for table in ("own_anti", "rev_hard", "rev_pref", "rev_anti"):
        for (s, key), lt in vocab.lts.ids.items():
            for e, (u, _sign) in enumerate(vocab.lt_expansion[lt]):
                q = vocab.units.items[u][1]
                for node, col in node_col.items():
                    d = topo_dom[q, col]
                    if d >= 0 and fields[table][lt, e, d]:
                        out[table, vocab.specs.items[s], key,
                            vocab.topos.items[q], node] = int(
                                fields[table][lt, e, d])
    for spec, s in vocab.specs.ids.items():
        if fields["spec_total"][s]:
            out["total", spec] = int(fields["spec_total"][s])
    return out


def _assert_tables_equal_the_compilers(inc, snap, batch, nodes, pods,
                                       pending, ctx):
    """The kept inter-pod tables of `snap` against
    InterPodCompiler.compile over the cluster the events left (`nodes`,
    `pods`: what is live) and the same pending pods; and what the bound
    pods decide of the pending ones (`sym_reject`, `poison`)."""
    from kubernetes_tpu.snapshot.interpod import InterPodCompiler

    state = ClusterState.build(list(nodes.values()), list(pods.values()))
    names = [n for n, info in state.node_infos.items()
             if info.node is not None]
    compiler = InterPodCompiler(state, pending, names)
    prog = compiler.compile()
    want = _decoded_terms(
        {f: getattr(prog, f) for f in (
            "topo_dom", "term_count", "own_anti", "rev_hard", "rev_pref",
            "rev_anti", "spec_total")},
        compiler, {n: i for i, n in enumerate(names)})
    got = _decoded_terms(
        {f[3:]: getattr(snap, f) for f in (
            "ip_topo_dom", "ip_term_count", "ip_own_anti", "ip_rev_hard",
            "ip_rev_pref", "ip_rev_anti", "ip_spec_total")},
        inc.vocabs.terms,
        {n: inc.slot_of[n] for n in names})
    # the kept vocabulary only grows: it may hold specs and terms of
    # pods long gone, which the compiler never met. What it counts for
    # those nothing reads; an owner table holds nothing there
    known = set(compiler.specs.items)
    units = {(compiler.specs.items[s], compiler.topos.items[q])
             for s, q in compiler.units.items}
    for key in list(got):
        if key[1] not in known or (
                key[0] == "count" and key[1:3] not in units):
            assert key[0] in ("count", "total"), f"{ctx}: {key}"
            del got[key]
    assert got == want, ctx
    # a slot without a node has no domain, whatever its pods
    for name, slot in inc.slot_of.items():
        if name not in names and len(snap.ip_topo_dom):
            assert (snap.ip_topo_dom[:, slot] == -1).all(), ctx
    assert np.array_equal(batch.ip_sym_reject, prog.sym_reject), ctx
    assert batch.ip_poison.tolist() == [prog.poison] * len(pending), ctx
    # the pending side, all twelve of a pod's own fields, held to the
    # compiler's own derivation (straight from get_affinity: nothing of
    # `pod_terms` / `TermVocab.pod_rows`, which made the batch's) term
    # for term by canonical key, the two numberings apart
    terms = inc.vocabs.terms

    def named(vocab, lt):
        return (vocab.specs.items[vocab.lts.items[lt][0]],
                vocab.lts.items[lt][1])

    for i in range(len(pending)):
        for mine, theirs, beside in (("ip_ha_lt", "ha_lt", "ha_self"),
                                     ("ip_hq_lt", "hq_lt", None),
                                     ("ip_fwd_lt", "fwd_lt", "fwd_w")):
            a = [(named(terms, lt),
                  beside and getattr(batch, "ip_" + beside)[i, j].item())
                 for j, lt in enumerate(getattr(batch, mine)[i]) if lt >= 0]
            b = [(named(compiler, lt),
                  beside and getattr(prog, beside)[i, j].item())
                 for j, lt in enumerate(getattr(prog, theirs)[i]) if lt >= 0]
            assert a == b, f"{ctx}: {mine} of pod {i}"
        for column in ("own_hard", "own_pref", "own_anti_hard",
                       "own_anti_pref"):
            a = {named(terms, lt): int(w) for lt, w in
                 enumerate(getattr(batch, "ip_" + column)[i]) if w}
            b = {named(compiler, lt): int(w) for lt, w in
                 enumerate(getattr(prog, column)[i]) if w}
            assert a == b, f"{ctx}: {column} of pod {i}"
        for flag in ("has_affinity", "has_anti"):
            assert getattr(batch, "ip_" + flag)[i] == getattr(prog, flag)[i], \
                f"{ctx}: {flag} of pod {i}"
        assert {terms.specs.items[s]
                for s in np.flatnonzero(batch.ip_match_spec[i])} & known == {
            compiler.specs.items[s]
            for s in np.flatnonzero(prog.match_spec[i])}, ctx
    return prog


def _term_pending(rng, rnd):
    """A wave's pending pods: a plain one and some of the templates."""
    pending = [rand_pending(rng, rnd)]
    for j in range(rng.randint(0, 3)):
        pending.append(_batch_pod(rng, f"pend-{rnd}-{j}", ""))
    return pending


@pytest.mark.parametrize("seed", range(12))
def test_batched_deltas_equal_one_at_a_time(seed):
    """One apply_pending over a stream of cache events leaves exactly
    what applying the same events one by one leaves: every snapshot
    field, dtype for dtype, the batch, `keep`, the vocabularies' ids in
    their order of first appearance, and the encoder's own books, the
    kept inter-pod tables among them. And after every batch those
    tables are the from-scratch compiler's, by canonical key."""
    rng = random.Random(9100 + seed)
    batched, single = IncrementalEncoder(initial_slots=4), \
        IncrementalEncoder(initial_slots=4)
    nodes, pods, seq = {}, {}, [0]
    poisoned = 0
    for rnd in range(8):
        events = _batch_events(rng, rng.choice([5, 40, 120]), nodes, pods,
                               seq)
        for kind, obj in events:
            batched.on_cache_event(kind, obj)
            single.on_cache_event(kind, obj)
            single.apply_pending()
        pending = _term_pending(rng, rnd)
        snap_a, batch_a, keep_a = batched.wave_view(pending)
        snap_b, batch_b, keep_b = single.wave_view(pending)
        ctx = f"seed {seed} round {rnd}"
        # no gate holds a term-owning wave back: every round has a view
        assert snap_a is not None and batched.fallback is None, ctx
        _assert_same_view(snap_a, snap_b, ctx)
        _assert_same_view(batch_a, batch_b, ctx)
        assert keep_a == keep_b, ctx
        prog = _assert_tables_equal_the_compilers(
            batched, snap_a, batch_a, nodes, pods, pending, ctx)
        poisoned += prog.poison
        for vocab in ("classes", "ports", "kv", "keys", "taints", "zones"):
            assert (list(getattr(batched.vocabs, vocab).ids.items())
                    == list(getattr(single.vocabs, vocab).ids.items())), \
                f"{ctx}: vocabulary {vocab}"
        ta, tb = batched.vocabs.terms, single.vocabs.terms
        for vocab in ("specs", "topos", "units", "lts"):
            assert getattr(ta, vocab).items == getattr(tb, vocab).items, \
                f"{ctx}: vocabulary {vocab}"
        assert [d.ids for d in ta._doms] == [d.ids for d in tb._doms], ctx
        # the books a snapshot does not show
        for f in ("req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem",
                  "pod_count", "_pod_count_slot", "class_count",
                  "port_mask", "_node_gone", "_schedulable"):
            x, y = getattr(batched, f), getattr(single, f)
            assert x.dtype == y.dtype and np.array_equal(x, y), f"{ctx}: {f}"
        # (the tables themselves are the snapshot's; these are kept in
        # arrays with room to grow, cut here to what is in use)
        C, S = ta._matched
        assert (C, S) == tb._matched == (len(ta._classes), len(ta.specs)), ctx
        assert np.array_equal(ta.match[:C, :S], tb.match[:C, :S]), ctx
        assert np.array_equal(ta.unknown_anti[:S], tb.unknown_anti[:S]), ctx
        assert (ta.unparsed, ta._filled, ta.stale) == (
            tb.unparsed, tb._filled, tb.stale), ctx
        # one at a time rebuilds at every event that leaves the tables
        # stale; a batch once, however many it held
        assert set(ta.rebuilds) <= set(tb.rebuilds), ctx
        for f in ("slot_of", "_free", "node_names", "_port_counts",
                  "_contribs", "_order_dirty"):
            assert getattr(batched, f) == getattr(single, f), f"{ctx}: {f}"
        # and the sums are the held pods', whatever the order was
        want = np.zeros_like(batched.req_mcpu)
        for slot, c in batched._contribs.values():
            want[slot] += c.cpu
        assert np.array_equal(batched.req_mcpu, want), ctx
        assert set(batched._contribs) == {("default", n) for n in pods}, ctx
    assert batched.vocabs.terms.rebuilds, "no round left the tables stale"


def _plain_node(name, zone=None):
    labels = {HOSTNAME: name}
    if zone:
        labels[ZONE] = zone
    return Node(
        metadata=ObjectMeta(name=name, labels=labels),
        status=NodeStatus(
            allocatable={"cpu": "4", "memory": "8Gi", "pods": "110"},
            conditions=[NodeCondition("Ready", "True")]))


def _owner(name, node_name, annotation, labels=None):
    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    return Pod(
        metadata=ObjectMeta(name=name, labels=labels or {"app": "web"},
                            annotations={AFFINITY_ANNOTATION: annotation}),
        spec=PodSpec(node_name=node_name, containers=[
            Container(requests={"cpu": "100m"})]))


def _renamed(name, hostname, zone):
    node = _plain_node(name, zone=zone)
    node.metadata.labels[HOSTNAME] = hostname
    return node


_ANTI_WEB_ZONE = _terms_json(
    "podAntiAffinity", [_term({"app": "web"}, ZONE)],
    [(4, _term({"app": "web"}, ""))])

UNCOVERED_CASES = {
    # name: (the event after the first wave, the rebuild it is counted
    #        under if any, whether the wave's pods are rejected
    #        everywhere, whether it poisons the wave)
    "a-relabel-moves-a-domain": (
        lambda nodes, pods: ("node_set", _plain_node("n-1", zone="a")),
        "relabel", False, False),
    "a-relabel-that-moves-none": (
        lambda nodes, pods: ("node_set", _plain_node("n-1", zone="b")),
        None, False, False),
    # the node's old hostname domain had this node alone: the new one
    # takes the id it gave back, and nothing moved (`_Domains`)
    "a-relabel-to-a-hostname-nobody-has": (
        lambda nodes, pods: ("node_set", _renamed("n-1", "fresh", "b")),
        None, False, False),
    "a-node-removed-under-its-owners": (
        lambda nodes, pods: ("node_remove", nodes["n-1"]),
        "node_removed", True, False),
    "an-owner-on-an-unknown-node": (
        lambda nodes, pods: ("pod_add", _owner(
            "lost", "never-seen", _ANTI_WEB_ZONE)),
        None, True, False),
    "an-unknown-node-arrives-under-its-owner": (
        lambda nodes, pods: [
            ("pod_add", _owner("lost", "late", _ANTI_WEB_ZONE)),
            ("node_set", _plain_node("late", zone="b"))],
        "relabel", False, False),
    "an-annotation-that-does-not-parse": (
        lambda nodes, pods: ("pod_add", _owner("odd", "n-2", "{not json")),
        None, True, True),
    "a-new-topology-key": (
        lambda nodes, pods: ("pod_add", _owner("rack", "n-2", _terms_json(
            "podAffinity", [_term({"app": "web"}, "rack")]))),
        None, False, False),
}


@pytest.mark.parametrize("case", sorted(UNCOVERED_CASES))
def test_what_the_deltas_do_not_cover_is_exact_or_a_counted_rebuild(case):
    """Each event the pod-by-pod deltas cannot follow leaves the kept
    tables exactly the compiler's all the same: kept as a count (owners
    without a node, pods that do not parse), or rebuilt whole under a
    reason of its own; and no wave goes to the from-scratch encoder."""
    event, reason, rejected, poison = UNCOVERED_CASES[case]
    inc = IncrementalEncoder(initial_slots=4)
    nodes = {f"n-{i}": _plain_node(f"n-{i}", zone="ab"[i % 2])
             for i in range(4)}
    pods = {}
    for node in nodes.values():
        inc.on_cache_event("node_set", node)
    for i in range(6):
        pod = _owner(f"own-{i}", f"n-{i % 3}", _ANTI_WEB_ZONE)
        pods[pod.metadata.name] = pod
        inc.on_cache_event("pod_add", pod)
    pending = [_owner("pend", "", _ANTI_WEB_ZONE)]
    snap, batch, _keep = inc.wave_view(pending)
    assert inc.fallback is None and inc.take_rebuilds() == {}
    _assert_tables_equal_the_compilers(
        inc, snap, batch, nodes, pods, pending, f"{case}: before")
    events = event(nodes, pods)
    for kind, obj in events if isinstance(events, list) else [events]:
        inc.on_cache_event(kind, obj)
        if kind == "node_set":
            nodes[obj.metadata.name] = obj
        elif kind == "node_remove":
            del nodes[obj.metadata.name]
        else:
            pods[obj.metadata.name] = obj
    snap, batch, keep = inc.wave_view(pending)
    assert inc.fallback is None
    assert inc.take_rebuilds() == ({reason: 1} if reason else {})
    prog = _assert_tables_equal_the_compilers(
        inc, snap, batch, nodes, pods, pending, f"{case}: after")
    assert prog.sym_reject.tolist() == [rejected]
    assert prog.poison is poison
    # only the events that moved nothing leave the counting tables
    # kept; moved, they are one unit: none is kept, and a driver that
    # ships what differs from its last copy ships all six (`reship`)
    kept = case in ("a-relabel-that-moves-none",
                    "a-relabel-to-a-hostname-nobody-has")
    assert (keep >= inc.TERM_CARRY_FIELDS) == kept
    assert not keep & inc.TERM_CARRY_FIELDS or keep >= inc.TERM_CARRY_FIELDS
    assert inc.reship == (frozenset() if kept else inc.TERM_CARRY_FIELDS)
    # a quiet wave after it keeps all twelve
    _snap, _batch, keep = inc.wave_view(pending)
    assert keep >= inc.TERM_STATIC_FIELDS | inc.TERM_CARRY_FIELDS
    assert inc.reship == frozenset()
    assert inc.take_rebuilds() == {}


@pytest.mark.parametrize("lingering", [False, True],
                         ids=["empty-nodes-go", "a-node-goes-under-a-pod"])
def test_domains_given_back_are_taken_again(lingering):
    """Under node churn with a hostname term (a domain a node) the
    domain axis stays as wide as the most nodes ever live at once: a
    node that goes gives its domain back and the next one takes it, so
    the tables keep their shapes; what they count stays the
    compiler's."""
    anti = _terms_json("podAntiAffinity", [_term({"app": "web"}, HOSTNAME)])
    inc = IncrementalEncoder(initial_slots=8)
    nodes = {f"n-{i}": _plain_node(f"n-{i}", zone="ab"[i % 2])
             for i in range(6)}
    pods = {}
    for node in nodes.values():
        inc.on_cache_event("node_set", node)
    for i in range(3):
        pods[f"own-{i}"] = _owner(f"own-{i}", f"n-{i}", anti)
        inc.on_cache_event("pod_add", pods[f"own-{i}"])
    pending = [_owner("pend", "", anti)]
    snap, batch, _keep = inc.wave_view(pending)
    assert snap.ip_term_count.shape == (1, 6)
    rebuilds = {}
    for rnd in range(12):
        # the newest pod's node stays; an empty one goes (or, once, the
        # one under the oldest pod, which lingers until the pod goes)
        gone = next(iter(pods.values())).spec.node_name \
            if lingering and rnd == 3 else next(
            n for n in nodes
            if not any(p.spec.node_name == n for p in pods.values()))
        inc.on_cache_event("node_remove", nodes.pop(gone))
        name = f"n-{6 + rnd}"
        nodes[name] = _plain_node(name, zone="ab"[rnd % 2])
        inc.on_cache_event("node_set", nodes[name])
        if rnd % 2:
            old = pods.pop(next(iter(pods)))
            inc.on_cache_event("pod_remove", old)
            pods[f"own-{name}"] = _owner(f"own-{name}", name, anti)
            inc.on_cache_event("pod_add", pods[f"own-{name}"])
        snap, batch, _keep = inc.wave_view(pending)
        ctx = f"round {rnd}"
        _assert_tables_equal_the_compilers(
            inc, snap, batch, nodes, pods, pending, ctx)
        assert snap.ip_term_count.shape == (1, 6), ctx
        assert snap.ip_own_anti.shape == (1, 1, 6), ctx
        assert sorted(snap.ip_topo_dom[0][snap.ip_topo_dom[0] >= 0]) \
            == list(range(6)), ctx
        for reason, n in inc.take_rebuilds().items():
            rebuilds[reason] = rebuilds.get(reason, 0) + n
    assert rebuilds == ({"node_removed": 1} if lingering else {})


def _oracle_view(state):
    """A copy of a restricted state for the oracle to assume pods into,
    its `full` kept (ClusterState.clone drops it): the pods that linger
    on a removed node stay visible, as they are to the encoders."""
    full = state.full.clone()
    sub = ClusterState(services=list(state.services),
                       controllers=list(state.controllers))
    sub.node_infos = {n: full.node_infos[n] for n in state.node_infos}
    sub.full = full
    return sub


def _term_assigned(rng, i, node_name):
    pod = _batch_pod(rng, f"assigned-{i}", node_name)
    pod.metadata.deletion_timestamp = None
    return pod


def _served_term_waves(seed, mesh=None):
    """Rounds of cache events (term owners bound and deleted, nodes set
    again and removed) and waves whose pods own terms themselves,
    through the cache-wired TPUScheduleAlgorithm; every pick held to
    the serial generic scheduler's. -> the algorithm."""
    import copy

    rng = random.Random(8800 + seed)
    cache = SchedulerCache(clock=FakeClock(0.0))
    algo = TPUScheduleAlgorithm(
        min_run=1, mesh=mesh, cache=cache, service_lister=_Lister(),
        controller_lister=_Lister(), replica_set_lister=_Lister())
    oracle = GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES)
    live_nodes, live_pods, seq = {}, {}, [0]
    for i in range(12):
        name = f"node-{i:03d}"
        live_nodes[name] = _plain_node(name, zone="abc"[i % 3])
        cache.add_node(live_nodes[name])
    placed = 0
    for rnd in range(5):
        for _ in range(25):
            op = rng.random()
            if op < 0.1:
                name = rng.choice(list(live_nodes))
                node = _plain_node(name, zone=rng.choice(["a", "b", None]))
                cache.update_node(live_nodes[name], node)
                live_nodes[name] = node
            elif op < 0.14 and len(live_nodes) > 6:
                cache.remove_node(live_nodes.pop(rng.choice(list(live_nodes))))
            elif op < 0.7:
                seq[0] += 1
                pod = _term_assigned(rng, seq[0],
                                     rng.choice(list(live_nodes)))
                if pod.metadata.labels.get("app") == "odd":
                    continue  # a poisoned cycle places nothing: no test
                cache.add_pod(pod)
                live_pods[pod.metadata.name] = pod
            elif live_pods:
                cache.remove_pod(live_pods.pop(rng.choice(list(live_pods))))
        pending = []
        for j in range(rng.randint(2, 8)):
            p = _batch_pod(rng, f"pend-{rnd}-{j}", "")
            p.metadata.deletion_timestamp = None
            if p.metadata.labels.get("app") == "odd":
                continue
            pending += [p] + [copy.deepcopy(p) for _ in
                              range(rng.randint(0, 3))]
        for k, p in enumerate(pending):
            p.metadata.name = f"pend-{rnd}-{k}"
        state = restricted_state(cache)
        want = oracle.schedule_backlog(pending, _oracle_view(state))
        got = algo.schedule_backlog(pending, state)
        assert got == want, f"seed {seed} round {rnd}"
        assert algo._inc.fallback is None
        placed += sum(h is not None for h in want)
        for p, host in zip(pending, want):
            if host is None:
                continue
            bound = copy.deepcopy(p)
            bound.metadata.name = f"{p.metadata.name}-bound"
            bound.spec.node_name = host
            cache.add_pod(bound)
            live_pods[bound.metadata.name] = bound
    assert placed >= 10
    return algo


@pytest.mark.parametrize("seed", range(6))
def test_term_owning_waves_decide_as_the_oracle_from_the_kept_tables(seed):
    """Every wave's snapshot is the incremental encoder's, whatever
    terms the bound and the pending pods own, and every pick the serial
    generic scheduler's."""
    stats = _served_term_waves(seed)._wave.stats
    assert stats["waves_by_encoder"] == {"incremental": 5, "full": 0}
    assert stats["encoder_fallbacks"] == {}


@pytest.mark.parametrize("per_shard", [None, 1],
                         ids=["one-step", "three-steps"])
@pytest.mark.parametrize("seed", range(2))
def test_term_owning_waves_decide_as_the_oracle_on_the_mesh(
        seed, per_shard, monkeypatch):
    """The same through the mesh driver on four devices, whose
    resident state takes the kept tables by content: on a node axis of
    one step, and on one that grew twice (12 nodes at one slot a shard
    a step: 4, 8, 12 slots), where the kept `topo_dom` has doubled past
    the axis and the snapshot's is cut to it."""
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.scheduler import tpu_algorithm

    if per_shard:
        monkeypatch.setattr(tpu_algorithm, "MESH_SLOTS_PER_SHARD", per_shard)
    algo = _served_term_waves(
        seed, mesh=Mesh(np.array(jax.devices()[:4]), ("nodes",)))
    assert algo._inc._cap == (12 if per_shard else 1024)
    assert algo._inc.vocabs.terms.topo_dom.shape[1] == (
        16 if per_shard else 1024)


def test_kept_rows_across_waves_match_oracle_at_the_zoned_shape():
    """Three waves through the daemon path at the zoned deployment's
    shape (300 nodes in 3 zones x 50 controllers, dealt in turn), a
    controller added before the second and one deleted before the third:
    the rows kept from wave to wave (snapshot/pending_rows.py) follow
    the listers, and every pick is the serial generic scheduler's."""
    cache = SchedulerCache(clock=FakeClock(0.0))
    for i in range(300):
        name = f"znode-{i:05d}"
        cache.add_node(Node(
            metadata=ObjectMeta(name=name, labels={
                "kubernetes.io/hostname": name, ZONE: "abc"[i % 3]}),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")])))

    def controller(name, selector):
        from kubernetes_tpu.api.types import (
            ReplicationController, ReplicationControllerSpec)

        return ReplicationController(
            metadata=ObjectMeta(name=name),
            spec=ReplicationControllerSpec(selector=selector))

    rc_lister = _Lister()
    rc_lister.items = [controller(f"rc-{t}", {"rc": f"rc-{t}"})
                       for t in range(50)]
    algo = TPUScheduleAlgorithm(
        min_run=1, cache=cache, service_lister=_Lister(),
        controller_lister=rc_lister, replica_set_lister=_Lister())
    oracle = GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES)
    serial = 0
    for wave in range(3):
        if wave == 1:
            # selects every fifth controller's pods as one more group
            rc_lister.items.append(controller("tier", {"tier": "t0"}))
        elif wave == 2:
            del rc_lister.items[7]
        pending = []
        for _replica in range(2):
            for t in range(50):
                serial += 1
                pending.append(Pod(
                    metadata=ObjectMeta(
                        name=f"p-t{t}-{serial:06d}",
                        labels={"rc": f"rc-{t}", "tier": f"t{t % 5}"}),
                    spec=PodSpec(containers=[Container(requests={
                        "cpu": "100m", "memory": "500Mi"})])))
        state = restricted_state(cache, controllers=rc_lister.items)
        want = oracle.schedule_backlog(pending, state.clone())
        got = algo.schedule_backlog(pending, state)
        assert got == want, f"wave {wave}: first off at " + str(next(
            i for i, (a, b) in enumerate(zip(got, want)) if a != b))
        assert None not in got
        for p, host in zip(pending, want):
            p.spec.node_name = host
            cache.add_pod(p)
    rows = algo._inc.rows
    assert (rows.hits, rows.misses, rows.resets) == (100, 50, 0)
