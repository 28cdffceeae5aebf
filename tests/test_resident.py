"""Device-resident sharded cluster state (parallel/resident): donation
and aliasing regression tests.

The round-7 contract: node tables live on device across waves; steady
state ships ZERO node-table bytes host->device; the fold programs donate
their carry so resident buffers mutate in place; node add/remove inside
the padded bucket updates via sharded row scatter bit-exactly to a full
rebuild; pjit executables are keyed so bucket-size changes compile once
and repeats compile never."""

import copy

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kubernetes_tpu.api.types import (
    Container,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
)
from kubernetes_tpu.models.wave import WaveScheduler
from kubernetes_tpu.oracle import ClusterState
from kubernetes_tpu.parallel.mesh import MeshWaveScheduler, _pad_snapshot
from kubernetes_tpu.parallel.resident import (
    CARRY_FIELDS,
    ResidentClusterState,
)
from kubernetes_tpu.snapshot.encode import SnapshotEncoder
from kubernetes_tpu.snapshot.pad import next_pow2


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) == 8, "conftest must force 8 CPU devices"
    return Mesh(np.array(devices), ("nodes",))


def _nodes(n, cpu="4"):
    return [
        Node(
            metadata=ObjectMeta(name=f"rnode-{i:05d}"),
            status=NodeStatus(
                allocatable={"cpu": cpu, "memory": "32Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")],
            ),
        )
        for i in range(n)
    ]


def _pods(n, cpu="100m", tag="t"):
    return [
        Pod(
            metadata=ObjectMeta(name=f"rp-{tag}-{i:06d}",
                                labels={"app": "resident"}),
            spec=PodSpec(containers=[Container(
                requests={"cpu": cpu, "memory": "500Mi"})]),
        )
        for i in range(n)
    ]


def _encode(state, rep_pods):
    enc = SnapshotEncoder(state, rep_pods)
    snap = enc.encode_nodes()
    batch = enc.encode_pods()
    return _pad_snapshot(snap, next_pow2(snap.num_nodes, 64)), batch


def _carry_ptrs(carry):
    ptrs = set()
    for leaf in carry:
        for s in leaf.addressable_shards:
            if s.data.size:
                ptrs.add(s.data.unsafe_buffer_pointer())
    return ptrs


def test_resident_buffers_stable_and_zero_table_bytes(mesh):
    """Across N steady-state waves: (a) zero node-table bytes ship
    host->device, (b) per-wave upload stays O(pending pods), (c) the
    donated folds keep the carry in the SAME device buffers (pointer
    set stable — donation aliases, never reallocates)."""
    state = ClusterState.build(_nodes(200))
    pods = _pods(1)
    snap, batch = _encode(state, pods)
    m = MeshWaveScheduler(mesh)
    rep_idx = np.zeros(128, np.int64)

    last = 0
    _o, carry, last = m.schedule_backlog(snap, batch, rep_idx, last,
                                         reuse="carry")
    warm_ptrs = _carry_ptrs(carry)
    uploads = []
    for _ in range(4):
        _o, carry, last = m.schedule_backlog(snap, batch, rep_idx, last,
                                             reuse="carry")
        assert m.resident.stats["wave_table_bytes"] == 0, (
            "steady-state wave shipped node-table bytes"
        )
        uploads.append(m.resident.stats["wave_h2d_bytes"])
        assert _carry_ptrs(carry) == warm_ptrs, (
            "carry left its resident buffers: donation is copying"
        )
    # pod row buffer + scatter-form counts only: KBs, not the ~200KB
    # the node tables of even this small cluster would cost
    assert max(uploads) < 64 * 1024, uploads
    assert m.resident.stats["rebuilds"] == 1


def test_resident_waves_match_single_chip_one_call(mesh):
    """Resident carry threading across schedule_backlog calls is
    bit-exact: K waves against the stale wave-0 snapshot must equal the
    single-chip scheduler's ONE call over the concatenated backlog
    (whose carry threads internally)."""
    state = ClusterState.build(_nodes(100, cpu="2"))
    pods = _pods(1)
    snap, batch = _encode(state, pods)
    m = MeshWaveScheduler(mesh)
    outs = []
    last = 0
    for _ in range(5):
        o, _c, last = m.schedule_backlog(
            snap, batch, np.zeros(96, np.int64), last, reuse="carry")
        outs.append(o)
    single = WaveScheduler()
    want, _c, _l = single.schedule_backlog(
        snap, batch, np.zeros(96 * 5, np.int64), 0)
    assert np.array_equal(np.concatenate(outs), want)


def test_auto_mode_daemon_shape_zero_table_bytes(mesh):
    """The daemon shape: binds commit into the cluster between waves
    and every wave re-encodes.  The mirror comparison must prove the
    re-encoded snapshot equals the resident state (our own binds and
    nothing else) and ship zero node-table bytes."""
    from kubernetes_tpu.scheduler.tpu_algorithm import (
        TPUScheduleAlgorithm,
    )

    state = ClusterState.build(_nodes(150))
    algo = TPUScheduleAlgorithm(mesh=mesh)

    def wave(n, tag):
        pods = _pods(n, tag=tag)
        hosts = algo.schedule_backlog(pods, state)
        for p, h in zip(pods, hosts):
            assert h is not None
            q = copy.copy(p)
            q.spec = copy.copy(p.spec)
            q.spec.node_name = h
            state.assign(q)

    wave(64, "w0")  # cold: placement + compiles
    resident = algo._mesh_sched.resident
    for i in range(3):
        wave(64, f"w{i + 1}")
        assert resident.stats["wave_table_bytes"] == 0, (
            f"daemon steady-state wave {i + 1} shipped node tables"
        )
    assert resident.stats["rebuilds"] == 1


def test_node_update_scatter_matches_rebuild(mesh):
    """A node changing inside the same padded bucket syncs via the
    donated row scatter — and the scattered resident state is
    bit-identical to a from-scratch rebuild of the new snapshot."""
    nodes = _nodes(50)
    state = ClusterState.build(nodes)
    pods = _pods(1)
    snap0, _b = _encode(state, pods)
    m_cfg = MeshWaveScheduler(mesh).config
    res = ResidentClusterState(mesh)
    res.sync(m_cfg, snap0, 0)
    assert res.stats["rebuilds"] == 1

    # node add + a capacity change, same 64-slot bucket
    nodes2 = _nodes(50) + _nodes(1, cpu="8")[:1]
    nodes2[-1].metadata.name = "rnode-00050"
    state2 = ClusterState.build(nodes2)
    snap1, _b1 = _encode(state2, pods)
    static_s, carry_s = res.sync(m_cfg, snap1, 0)
    assert res.stats["rebuilds"] == 1, "in-bucket change must not rebuild"
    assert res.stats["scatters"] >= 1, "row delta must ride the scatter"

    fresh = ResidentClusterState(mesh)
    static_f, carry_f = fresh.sync(m_cfg, snap1, 0)
    for k in static_f:
        a, b = np.asarray(static_s[k]), np.asarray(static_f[k])
        if a.dtype.kind == "f":
            assert np.array_equal(a, b, equal_nan=True), k
        else:
            assert np.array_equal(a, b), k
    for f, a, b in zip(CARRY_FIELDS, carry_s, carry_f):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f


@pytest.mark.parametrize("changed,scatters,replaces", [
    (60, 1, 0),   # the 64 bucket: under a quarter of 320 slots
    (70, 0, 1),   # under a quarter too, but its bucket of 128 is not
    (90, 0, 1),   # over a quarter
])
def test_row_bucket_past_the_scatter_share_re_places(
        mesh, changed, scatters, replaces):
    """On a node axis that is no power of two (the mesh driver's
    encoder grows it by a step) a count of changed rows under the
    scatter share can pad to a bucket over it: one that no warm-up
    compiles, since a wave of that many pods re-places. Such a sync
    re-places the tables, as one over the share does."""
    pods = _pods(1)
    m_cfg = MeshWaveScheduler(mesh).config

    def snap(cpu_of_first):
        nodes = _nodes(320)
        for node in nodes[:changed]:
            node.status.allocatable["cpu"] = cpu_of_first
        enc = SnapshotEncoder(ClusterState.build(nodes), pods)
        return enc.encode_nodes()

    res = ResidentClusterState(mesh)
    res.sync(m_cfg, snap("4"), 0)
    before = dict(res.stats)
    static_s, _carry = res.sync(m_cfg, snap("8"), 0)
    assert res.stats["rebuilds"] == before["rebuilds"] == 1
    assert res.stats["scatters"] - before["scatters"] == scatters
    assert res.stats["replaces"] - before["replaces"] == replaces
    want = np.where(np.arange(320) < changed, 8000, 4000)
    assert np.array_equal(np.asarray(static_s["alloc_mcpu"]), want)


def test_node_remove_scatter_matches_rebuild_and_decisions(mesh):
    """Node removal (a live node becomes a never-fit padded slot):
    scatter-synced resident state schedules identically to single-chip
    on the shrunken cluster."""
    from kubernetes_tpu.scheduler.tpu_algorithm import (
        TPUScheduleAlgorithm,
    )

    state = ClusterState.build(_nodes(40))
    algo = TPUScheduleAlgorithm(mesh=mesh)
    chip = TPUScheduleAlgorithm()
    p0 = _pods(32, tag="a")
    assert algo.schedule_backlog(p0, state) == chip.schedule_backlog(
        p0, state)

    state2 = ClusterState.build(_nodes(39))  # node 39 gone
    p1 = _pods(32, tag="b")
    got = algo.schedule_backlog(p1, state2)
    want = chip.schedule_backlog(p1, state2)
    assert got == want
    assert algo._mesh_sched.resident.stats["rebuilds"] == 1
    assert "rnode-00039" not in got


def test_pjit_cache_keyed_across_buckets(mesh):
    """Executable caching: a repeated (node bucket, J, M) shape
    compiles NOTHING; a new scatter-count bucket compiles exactly its
    own variants and then repeats free."""
    from kubernetes_tpu.analysis.compile_guard import CompileSentinel

    state = ClusterState.build(_nodes(1100))
    pods = _pods(1)
    snap, batch = _encode(state, pods)
    m = MeshWaveScheduler(mesh)
    sentinel = CompileSentinel()
    last = 0
    # wave A: 48 pods -> touch bucket M=64
    _o, _c, last = m.schedule_backlog(
        snap, batch, np.zeros(48, np.int64), last, reuse="carry")
    with sentinel.expect_no_compiles("repeat of wave A's buckets"):
        _o, _c, last = m.schedule_backlog(
            snap, batch, np.zeros(48, np.int64), last, reuse="carry")
    # wave B: 700 pods spread -> touch bucket M=1024 (new shape class,
    # compiles once)
    before = sentinel.compile_count()
    _o, _c, last = m.schedule_backlog(
        snap, batch, np.zeros(700, np.int64), last, reuse="carry")
    assert sentinel.compile_count() > before, (
        "a new scatter bucket size must be its own executable"
    )
    with sentinel.expect_no_compiles("repeat of wave B's buckets"):
        _o, _c, last = m.schedule_backlog(
            snap, batch, np.zeros(700, np.int64), last, reuse="carry")


def test_donated_fold_lowering_aliases_every_carry_leaf(mesh):
    """Executable-free donation check that runs on ANY backend: the
    donated form of the commit folds must alias every carry leaf
    input->output in the lowered module.  (Runtime donation is platform
    -gated; the contract is not.)"""
    from kubernetes_tpu.parallel.resident import host_carry, host_static

    state = ClusterState.build(_nodes(20))
    pods = _pods(1)
    snap, batch = _encode(state, pods)
    m = MeshWaveScheduler(mesh)
    N = snap.num_nodes
    nps = N // 8
    static = host_static(m.config, snap)
    hc = host_carry(snap, 0)
    carry = tuple(hc[f] for f in CARRY_FIELDS)
    from kubernetes_tpu.models.batch import BatchScheduler
    from kubernetes_tpu.models.pack import pack_arrays
    from kubernetes_tpu.parallel.mesh import _sparse_counts

    layout, buf = pack_arrays({
        f: np.asarray(getattr(batch, f)[0])
        for f in BatchScheduler.POD_FIELDS
    })
    idx, cnt = _sparse_counts(np.zeros(N, np.int64))
    from kubernetes_tpu.parallel.mesh import empty_leaves

    # the driver's own call shape: zero-size leaves (no inter-pod terms
    # here) keep their result sharding unspecified and hold no buffer
    # to alias; every leaf WITH bytes must alias
    empty = empty_leaves(carry)
    fn = m._apply_program(static, N, nps, layout, donate=True,
                          empty=empty)
    txt = fn.lower(static, carry, buf, idx, cnt).as_text()
    assert 0 < len(empty) < len(CARRY_FIELDS)
    assert (txt.count("tf.aliasing_output")
            == len(CARRY_FIELDS) - len(empty)), (
        "a donated carry leaf is silently copied in the lowered fold"
    )
    # with nothing declared empty every leaf aliases by declaration
    full = m._apply_program(static, N, nps, layout, donate=True)
    assert (full.lower(static, carry, buf, idx, cnt).as_text()
            .count("tf.aliasing_output")) == len(CARRY_FIELDS)
    undonated = m._apply_program(static, N, nps, layout, donate=False)
    txt2 = undonated.lower(static, carry, buf, idx, cnt).as_text()
    assert txt2.count("tf.aliasing_output") == 0


def test_soak_churn_smoke(mesh):
    """Short create/delete/reschedule churn against the resident mesh
    path (the bench --soak gate's shape): zero steady-state
    recompilation, zero node-table bytes on quiet waves, scatter or
    bounded re-place on delete waves."""
    from kubernetes_tpu.analysis.compile_guard import CompileSentinel
    from kubernetes_tpu.scheduler.tpu_algorithm import (
        TPUScheduleAlgorithm,
    )

    state = ClusterState.build(_nodes(120))
    algo = TPUScheduleAlgorithm(mesh=mesh)
    sentinel = CompileSentinel()
    bound = []
    serial = [0]

    def wave(n):
        pods = _pods(n, tag=f"s{serial[0]}")
        serial[0] += 1
        hosts = algo.schedule_backlog(pods, state)
        for p, h in zip(pods, hosts):
            if h is None:
                continue
            q = copy.copy(p)
            q.spec = copy.copy(p.spec)
            q.spec.node_name = h
            state.assign(q)
            bound.append((q, h))

    wave(48)
    wave(48)  # all shapes compiled
    resident = algo._mesh_sched.resident
    with sentinel.expect_no_compiles("soak steady state"):
        for i in range(4):
            if i == 2:  # delete half the oldest: the churn's other half
                for q, h in bound[:48]:
                    state.get_node_info_any(h).remove_pod(q)
                del bound[:48]
            wave(48)
            if i != 2:
                assert resident.stats["wave_table_bytes"] == 0, (
                    f"quiet churn wave {i} shipped node tables"
                )
    assert resident.stats["rebuilds"] == 1
