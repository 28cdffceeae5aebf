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


@pytest.mark.parametrize("step,nodes,slots", [
    (None, 70, 128), (32, 70, 96), (32, 96, 96), (32, 97, 128),
])
def test_node_axis_grows_by_its_step(step, nodes, slots):
    """With a `slot_step` (the mesh driver's: a multiple of its
    devices) the node axis grows by that step and the view's node axis
    is a multiple of it; without one it doubles. The nodes' slots and
    what the view says of them are the same either way."""
    cache = SchedulerCache(clock=FakeClock())
    inc = IncrementalEncoder(slot_step=step)
    cache.add_listener(inc.on_cache_event)
    rng = random.Random(step or 1)
    for i in range(nodes):
        cache.add_node(rand_node(rng, f"node-{i:03d}"))
    pending = [Pod(metadata=ObjectMeta(name="pend", labels={"app": "web"}),
                   spec=PodSpec(containers=[
                       Container(requests={"cpu": "100m"})]))]
    snap, _batch, _keep = inc.wave_view(pending)
    assert snap.num_nodes == slots == len(snap.node_names)
    assert snap.node_names[:nodes] == [f"node-{i:03d}"
                                       for i in range(nodes)]
    assert not any(snap.node_names[nodes:])


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

_BATCH_TEMPLATES = (
    # (labels, requests, host ports, affinity annotation)
    ({"name": "sched-perf"}, {"cpu": "100m", "memory": "500Mi"}, (), None),
    ({"app": "web"}, {"cpu": "250m"}, (), None),
    ({"app": "db", "tier": "be"}, {"memory": "1Gi"}, (), None),
    ({}, {}, (), None),
    ({"app": "web"}, {"cpu": "100m"}, (8080,), None),
    ({"app": "lb"}, {"cpu": "50m"}, (9090, 8080), None),
    ({"app": "near"}, {"cpu": "100m"}, (),
     '{"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution":'
     ' [{"labelSelector": {"matchLabels": {"app": "near"}},'
     ' "topologyKey": "kubernetes.io/hostname"}]}}'),
)


def _batch_pod(rng, name, node_name, fresh_class=None):
    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    weights = (8, 4, 3, 2, 2, 1, 1)
    labels, reqs, ports, affinity = rng.choices(_BATCH_TEMPLATES, weights)[0]
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
    and `pods` (name -> object) follow what the stream leaves live."""
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
            # pods that hold the affinity gate shut go first, so that most
            # rounds end with a snapshot to compare
            gated = [n for n, p in pods.items() if p.metadata.annotations]
            name = rng.choice(gated or list(pods))
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


@pytest.mark.parametrize("seed", range(12))
def test_batched_deltas_equal_one_at_a_time(seed):
    """One apply_pending over a stream of cache events leaves exactly
    what applying the same events one by one leaves: every snapshot
    field, dtype for dtype, the batch, `keep`, the vocabularies' ids in
    their order of first appearance, and the encoder's own books."""
    rng = random.Random(9100 + seed)
    batched, single = IncrementalEncoder(initial_slots=4), \
        IncrementalEncoder(initial_slots=4)
    nodes, pods, seq = {}, {}, [0]
    views = 0
    for rnd in range(8):
        events = _batch_events(rng, rng.choice([5, 40, 120]), nodes, pods,
                               seq)
        for kind, obj in events:
            batched.on_cache_event(kind, obj)
            single.on_cache_event(kind, obj)
            single.apply_pending()
        pending = [rand_pending(rng, rnd)]
        snap_a, batch_a, keep_a = batched.wave_view(pending)
        snap_b, batch_b, keep_b = single.wave_view(pending)
        ctx = f"seed {seed} round {rnd}"
        _assert_same_view(snap_a, snap_b, ctx)
        _assert_same_view(batch_a, batch_b, ctx)
        assert keep_a == keep_b, ctx
        views += snap_a is not None
        for vocab in ("classes", "ports", "kv", "keys", "taints", "zones"):
            assert (list(getattr(batched.vocabs, vocab).ids.items())
                    == list(getattr(single.vocabs, vocab).ids.items())), \
                f"{ctx}: vocabulary {vocab}"
        # the books a snapshot does not show (or shows only when no
        # affinity pod holds the gate shut)
        for f in ("req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem",
                  "pod_count", "_pod_count_slot", "class_count",
                  "port_mask", "_node_gone", "_schedulable"):
            x, y = getattr(batched, f), getattr(single, f)
            assert x.dtype == y.dtype and np.array_equal(x, y), f"{ctx}: {f}"
        for f in ("slot_of", "_free", "node_names", "_port_counts",
                  "_affinity_pods", "_contribs", "_order_dirty"):
            assert getattr(batched, f) == getattr(single, f), f"{ctx}: {f}"
        # and the sums are the held pods', whatever the order was
        want = np.zeros_like(batched.req_mcpu)
        for slot, c in batched._contribs.values():
            want[slot] += c.cpu
        assert np.array_equal(batched.req_mcpu, want), ctx
        assert set(batched._contribs) == {("default", n) for n in pods}, ctx
    assert views >= 2, "the affinity gate hid every snapshot of this seed"


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
