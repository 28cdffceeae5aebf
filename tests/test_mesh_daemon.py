"""The daemon's mesh driver off the scheduler cache: the factory hands
the sharded algorithm the cache and the listers as it hands them to the
single-chip one, a wave's view comes from the incremental encoder, and
what a two- and a four-device mesh pick, fed through the cache-event
seam over several waves with churn between them, is what the
single-chip driver picks on the same events and what the zoned plain
reference (benchmark/reference_zoned.py) picks, pod for pod. The
driver's tallies add up, and a warm-up shaped after the cluster leaves
the waves nothing to compile."""

import copy
import random
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark import reference_zoned
from kubernetes_tpu.api.types import (
    Container,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ReplicationController,
    ReplicationControllerSpec,
)
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.trace import profile

ZONE = "failure-domain.beta.kubernetes.io/zone"
NODES, CONTROLLERS, REPLICAS = 96, 30, 8

#: the deployment as benchmark/configs/mesh-20k.json writes it, small
CFG = {
    "nodes": {"count": NODES, "name_format": "znode-{i:05d}",
              "allocatable": {"cpu": "4", "memory": "32Gi", "pods": "110"},
              "zones": ["a", "b", "c"]},
    "pods": {"requests": {"cpu": "100m", "memory": "500Mi"}},
    "controllers": {"count": CONTROLLERS},
}


@pytest.fixture(autouse=True)
def small_shards(monkeypatch):
    """Eight node slots a device and not 256: the 96 nodes then lie on
    every shard of a two- and of a four-device mesh (48 and 24 each),
    as a deployment's thousands lie on every chip."""
    from kubernetes_tpu.scheduler import tpu_algorithm

    monkeypatch.setattr(tpu_algorithm, "MESH_SLOTS_PER_SHARD", 8)


class _Lister:
    def __init__(self, items=()):
        self.items = list(items)

    def list(self):
        return list(self.items)


def _nodes():
    return [Node(
        metadata=ObjectMeta(name=f"znode-{i:05d}", labels={
            "kubernetes.io/hostname": f"znode-{i:05d}",
            ZONE: "abc"[i % 3]}),
        status=NodeStatus(
            allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
            conditions=[NodeCondition("Ready", "True")]))
        for i in range(NODES)]


def _controllers():
    return [ReplicationController(
        metadata=ObjectMeta(name=f"rc-{t}", namespace="default"),
        spec=ReplicationControllerSpec(selector={"rc": f"rc-{t}"}))
        for t in range(CONTROLLERS)]


def _pod(t, serial):
    return Pod(
        metadata=ObjectMeta(name=f"p-t{t}-{serial:06d}",
                            namespace="default", labels={"rc": f"rc-{t}"}),
        spec=PodSpec(containers=[Container(requests={
            "cpu": "100m", "memory": "500Mi"})]))


def _mesh(devices):
    return Mesh(np.array(jax.devices()[:devices]), ("nodes",)) \
        if devices > 1 else None


def _daemon_algorithm(devices):
    """-> (algorithm, its cache): built as the factory builds it, the
    nodes in through the cache's events."""
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    cache = SchedulerCache()
    algo = TPUScheduleAlgorithm(
        mesh=_mesh(devices), cache=cache, service_lister=_Lister(),
        controller_lister=_Lister(_controllers()),
        replica_set_lister=_Lister())
    for node in _nodes():
        cache.add_node(node)
    return algo, cache


def _served_waves(algo, cache, seed, waves=4, churn=25):
    """Waves of the controllers dealt in turn in a seeded order, each
    bound through the cache as the daemon binds (assumed, then
    confirmed by the informer's add), the oldest `churn` bound pods
    deleted between waves. -> per wave (templates, picked node names,
    (template, node name) of the pods deleted before it)."""
    rng = random.Random(seed)
    order = list(range(CONTROLLERS))
    bound, out, serial = [], [], 0
    for w in range(waves):
        gone = []
        if w:
            for pod in bound[:churn]:
                cache.remove_pod(pod)
                gone.append((int(pod.metadata.labels["rc"][3:]),
                             pod.spec.node_name))
            del bound[:churn]
        rng.shuffle(order)
        templates = [order[i % CONTROLLERS] for i in range(60)]
        pods = []
        for t in templates:
            pods.append(_pod(t, serial))
            serial += 1
        # the state argument is for a view the encoder cannot give: a
        # served wave never opens it
        hosts = algo.schedule_backlog(pods, None)
        assert all(hosts)
        for pod, host in zip(pods, hosts):
            q = copy.copy(pod)
            q.spec = copy.copy(pod.spec)
            q.spec.node_name = host
            cache.assume_pod(q)
            cache.add_pod(q)
            bound.append(q)
        out.append((templates, hosts, gone))
    return out


def _held_to_the_reference(served):
    """Mismatches of the served picks against the zoned plain reference,
    its cluster following the same binds and deletes."""
    cluster = reference_zoned.Cluster(CFG)
    mismatches = 0
    for templates, hosts, gone in served:
        for t, name in gone:
            node = cluster.index[name]
            cluster.req_cpu[node] -= cluster.pod_cpu
            cluster.req_mem[node] -= cluster.pod_mem
            cluster.pods[node] -= 1
            cluster.peers[t, node] -= 1
        held = reference_zoned.verify(
            cluster, templates, [cluster.index[h] for h in hosts])
        assert held["checked"] == len(templates)
        mismatches += held["mismatches"]
    return mismatches


@pytest.fixture(scope="module")
def single_chip_picks():
    algo, cache = _daemon_algorithm(1)
    return _served_waves(algo, cache, seed=2 ** 31 + 7)


def test_single_chip_daemon_picks_equal_the_zoned_references(
        single_chip_picks):
    assert _held_to_the_reference(single_chip_picks) == 0


@pytest.mark.parametrize("devices", [2, 4])
def test_mesh_daemon_picks_equal_the_references_and_the_single_chips(
        devices, single_chip_picks):
    encoder_before = profile.encoder_totals()
    rows_before = profile.pending_row_totals()
    algo, cache = _daemon_algorithm(devices)
    driver = algo._mesh_sched
    assert driver is algo._wave and driver.mesh.devices.size == devices
    assert algo._inc._slot_step == 8 * devices
    served = _served_waves(algo, cache, seed=2 ** 31 + 7)
    assert _held_to_the_reference(served) == 0
    assert [(t, h) for t, h, _g in served] == \
        [(t, h) for t, h, _g in single_chip_picks]
    # the view came from the incremental encoder: events in batches,
    # the templates' rows kept from wave to wave, no whole rebuild
    encoder = profile.encoder_totals()
    # (three waves' binds and the churn; the last wave's wait for the next view)
    assert encoder["events"] - encoder_before["events"] >= 180 + 75
    assert encoder["batches"] > encoder_before["batches"]
    rows = profile.pending_row_totals()
    assert rows["row_hits"] - rows_before["row_hits"] >= 3 * CONTROLLERS
    assert rows["row_resets"] == rows_before["row_resets"]
    # and the resident state shipped rows, not tables: placed once
    assert driver.resident.stats["rebuilds"] == 1
    assert driver.resident.stats["scatters"] >= 1

    # the tallies, cumulative: every pod handed in went one path (runs
    # of length 1: the sharded scan), every placed pod landed on one
    # shard, one dispatch a wave
    stats = driver.stats
    assert stats["waves"] == 4
    assert sum(stats["pods_by_path"].values()) == 240
    assert stats["pods_by_path"]["scan"] == 240
    assert stats["pods_unplaced"] == 0
    # (the encoder's node axis grows by 8 slots a device: 96 slots, a
    # shard is 96 / devices of them and every shard holds nodes)
    assert algo._inc._cap == NODES
    landed = np.bincount(
        [int(h[6:]) // (NODES // devices) for _t, hosts, _g in served
         for h in hosts], minlength=devices)
    assert stats["picks_by_shard"] == landed.tolist()
    assert min(stats["picks_by_shard"]) > 0
    assert sum(stats["picks_by_shard"]) == 240
    assert stats["dispatches"] == 4 == stats["dispatches_by_kind"]["scan"]
    assert driver.dispatches == {"scan": 1}  # the last wave's own
    assert stats["h2d_bytes_total"] == \
        driver.resident.stats["h2d_bytes_total"] > 0


def test_mesh_picks_hold_where_the_zone_share_lands_on_a_whole_number():
    """Sixteen waves and no churn bring every controller to 32 bound
    pods: its fullest zone passes 10, where the blend of the node share
    and the zone share, 10/3 + (2/3) x 10 x (10 - 9) / 10, is 4 in
    float32 as Go computes it and 3 with a weight one ulp off or in
    float16. The sharded scorer's own copy of the blend was one ulp off
    until it scored through ops/priorities.spread_blend; nothing saw it
    while no deployment's controllers passed a multiple of ten a zone.
    The comparison has the power: the reference in float16 moves
    picks on these very waves."""
    algo, cache = _daemon_algorithm(4)
    served = _served_waves(algo, cache, seed=5, waves=16, churn=0)
    assert _held_to_the_reference(served) == 0
    cluster = reference_zoned.Cluster(CFG)
    moved = 0
    for w, (templates, hosts, _gone) in enumerate(served):
        half = copy.deepcopy(cluster)
        half.real = np.float16
        low = reference_zoned.decide(half, templates, 60 * w)
        moved += reference_zoned.verify(copy.deepcopy(cluster), templates,
                                        low)["mismatches"]
        held = reference_zoned.verify(
            cluster, templates, [cluster.index[h] for h in hosts])
        assert held["mismatches"] == 0
    assert moved >= 1


def test_pods_that_fit_nowhere_are_tallied_on_the_mesh():
    algo, cache = _daemon_algorithm(2)
    big = [Pod(metadata=ObjectMeta(name=f"big-{i}", namespace="default",
                                   labels={"rc": "rc-0"}),
               spec=PodSpec(containers=[Container(requests={"cpu": "64"})]))
           for i in range(3)]
    hosts = algo.schedule_backlog(big + [_pod(1, 0)], None)
    assert hosts[:3] == [None, None, None] and hosts[3]
    assert algo.schedule_backlog([_pod(1, 1)], None)[0]
    stats = algo._wave.stats
    assert stats["pods_unplaced"] == 3
    assert sum(stats["pods_by_path"].values()) == 5
    assert sum(stats["picks_by_shard"]) == 2


@pytest.mark.parametrize("mode,sharded", [("force", True), ("off", False)])
def test_factory_hands_either_driver_the_cache_and_the_listers(
        monkeypatch, mode, sharded):
    from kubernetes_tpu.scheduler.algorithmprovider import (
        _tpu_algorithm_factory,
    )

    monkeypatch.setenv("KUBERNETES_TPU_MESH", mode)
    cache = SchedulerCache()
    listers = SimpleNamespace(
        scheduler_cache=cache, service_lister=_Lister(),
        controller_lister=_Lister(_controllers()),
        replica_set_lister=_Lister())
    algo = _tpu_algorithm_factory(listers)
    assert (algo._mesh_sched is not None) == sharded
    if sharded:
        assert algo._mesh_sched.mesh.devices.size == len(jax.devices())
    assert algo._service_lister is listers.service_lister
    assert algo._controller_lister is listers.controller_lister
    assert algo._replica_set_lister is listers.replica_set_lister
    # the incremental encoder listens to the cache that was handed in
    assert algo._inc is not None
    cache.add_node(_nodes()[0])
    algo._inc.apply_pending()
    assert "znode-00000" in algo._inc.slot_of


def test_mesh_warmup_is_shaped_after_the_cluster_it_serves(monkeypatch):
    """After the warm-up (the caller's nodes, the controllers'
    selectors, every scan bucket up to the wave cap) served waves of
    any size under the cap, with churn between them, build no program:
    neither a scan bucket nor a row scatter of the resident state."""
    from kubernetes_tpu.analysis.compile_guard import CompileSentinel
    from kubernetes_tpu.scheduler import core

    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    monkeypatch.setattr(core, "WAVE_CAP", 256)
    algo, cache = _daemon_algorithm(4)
    algo.warmup(NODES, phase="run", nodes=_nodes())
    warmed = dict(algo._wave.stats["dispatches_by_kind"])
    assert warmed["scan"] >= 3 and warmed["group_probe"] >= 2
    bound, serial = [], 0
    with CompileSentinel().expect_no_compiles("served mesh waves"):
        for size, churn in ((200, 0), (70, 90), (256, 40), (5, 120),
                            (130, 3)):
            for pod in bound[:churn]:
                cache.remove_pod(pod)
            del bound[:churn]
            pods = [_pod((serial + i) % CONTROLLERS, serial + i)
                    for i in range(size)]
            serial += size
            for pod, host in zip(pods, algo.schedule_backlog(pods, None)):
                q = copy.copy(pod)
                q.spec = copy.copy(pod.spec)
                q.spec.node_name = host
                cache.assume_pod(q)
                cache.add_pod(q)
                bound.append(q)
    assert algo._wave.resident.stats["scatters"] >= 3
