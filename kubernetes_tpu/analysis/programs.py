"""Registry of the scheduler's device programs at representative shapes.

Every hot program the wave/scan/mesh drivers dispatch is rebuilt here
exactly the way its driver builds it (same function bodies, same
jit/shard_map wrapping, same packed-buffer layouts) against a small
synthetic-but-real cluster snapshot (zoned nodes, two pod templates, a
grouped-run backlog) produced by the real encoder. The jaxpr auditor
traces these to enforce lowering/transfer contracts; tracing never
executes device code, so the registry is cheap enough for CI.

The shapes are representative, not production-sized: contract
violations of the audited classes (a primitive with no TPU lowering, a
host callback, an f64 upcast, an extra host-bound output) are
shape-independent — they appear at N=16 exactly as at N=16384.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class ProgramSpec:
    """One registered device program, ready to trace.

    ``carry_out_leaves`` — how many leading output leaves are the carry
    (device-resident across waves); the rest are host-bound per
    dispatch. ``expected_host_leaves`` is the transfer contract: the
    number of arrays this program may ship device->host per dispatch
    (None = unaudited).

    ``donate_argnums`` is the DONATION contract: the named args are
    resident-state buffers the program must mutate in place.  The
    auditor lowers the program and requires every donated input leaf to
    alias an output (``donated_leaves`` overrides the expected count;
    None = all leaves of the donated args) — a donated buffer XLA
    silently copies (un-donatable layout, shape/dtype drift) is a CI
    failure, not a perf mystery.
    """

    name: str
    fn: Callable
    args: Tuple[Any, ...]
    allow_f64: bool = False
    carry_out_leaves: int = 0
    expected_host_leaves: Optional[int] = None
    donate_argnums: Tuple[int, ...] = ()
    donated_leaves: Optional[int] = None
    #: SHARDING contract (sharding-drift audit): per-arg pytrees of the
    #: PartitionSpecs the program must declare as in_shardings (None =
    #: that arg unaudited), and the out_shardings pytree. Built from
    #: resident.carry_specs()/static_specs() so the audited placement
    #: is the one source the drivers share.
    arg_shardings: Optional[Tuple[Any, ...]] = None
    out_shardings_decl: Any = None
    #: SCATTER contract (scatter-contract audit): the (primitive,
    #: scatter_dims_to_operand_dims) forms a commit fold may contain.
    #: None = unaudited; anything outside the set — notably an
    #: overwrite `scatter` without unique indices — is a finding.
    scatter_allowed: Optional[Tuple[Tuple[str, Tuple[int, ...]], ...]] = None
    #: DTYPE contract (dtype-contract audit): static table name ->
    #: numpy dtype the program's matching input leaf must carry, for
    #: tables the narrow placement (ops/narrow) declares narrow.
    #: The auditor additionally rejects any widening
    #: convert_element_type from a narrow int to int32/int64 on a
    #: node-axis array inside the program (except pure gather/scatter
    #: index feeds) — a declared-narrow table silently upcast in-program
    #: pays the full-width bandwidth the shrink exists to save.
    narrow_dtypes: Optional[Tuple[Tuple[str, str], ...]] = None
    notes: str = ""


def _scenario(num_nodes: int = 13):
    """A zoned cluster of `num_nodes` + two-template backlog through
    the REAL encoder (the same row/vocab layout production snapshots
    have). The default is small and non-pow2 (exercises node padding);
    tests/test_chip_compile.py asks for the served path's real sizes."""
    from kubernetes_tpu.api.types import (
        Container,
        Node,
        NodeCondition,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
    )
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder

    zones = ["a", "b", "c"]
    nodes = [
        Node(
            metadata=ObjectMeta(
                name=f"audit-n{i:02d}",
                labels={
                    "kubernetes.io/hostname": f"audit-n{i:02d}",
                    "failure-domain.beta.kubernetes.io/zone": zones[i % 3],
                },
            ),
            status=NodeStatus(
                allocatable={"cpu": "8", "memory": "32Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")],
            ),
        )
        for i in range(num_nodes)
    ]
    existing = [
        Pod(
            metadata=ObjectMeta(name=f"audit-e{i}",
                                labels={"app": "web"}),
            spec=PodSpec(
                node_name=f"audit-n{i % num_nodes:02d}",
                containers=[Container(requests={"cpu": "500m",
                                                "memory": "1Gi"})],
            ),
        )
        for i in range(6)
    ]

    def template(tag: str, cpu: str, n: int) -> List[Pod]:
        return [
            Pod(
                metadata=ObjectMeta(name=f"audit-{tag}-{i:03d}",
                                    labels={"app": tag}),
                spec=PodSpec(containers=[Container(
                    requests={"cpu": cpu, "memory": "200Mi"})]),
            )
            for i in range(n)
        ]

    pending = template("alpha", "100m", 24) + template("beta", "250m", 20)
    state = ClusterState.build(nodes, assigned_pods=existing)
    snap, batch = SnapshotEncoder(state, pending).encode()
    return snap, batch


def build_programs(include_mesh: bool = True, num_nodes: int = 13,
                   run_length: int = 24) -> List[ProgramSpec]:
    """Construct every registered program + its representative args.

    Shapes follow the DRIVERS' OWN bucketing of a `num_nodes` cluster
    whose longest template run is `run_length` pods: the node axis pads
    to next_pow2(n, 64) (scheduler/tpu_algorithm), J comes from
    wave.pick_j, the replay lengths from wave.replay_k_bucket, the scan
    batch from the wave's pod_floor — so the same registry yields the
    toy audit shapes and the real widths the chip-compile tests use."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig
    from kubernetes_tpu.models.pack import pack_arrays
    from kubernetes_tpu.models.probe import WaveProbe
    from kubernetes_tpu.models.wave import (
        ZREPLAY_GROUP_K_FLOOR,
        ZREPLAY_K_FLOOR,
        WaveScheduler,
        group_buffer,
        pick_j,
        replay_k_bucket,
    )
    from kubernetes_tpu.models.zreplay import (
        _zreplay_fn,
        _zreplay_group_fn,
    )
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.pad import next_pow2, pad_batch

    config = SchedulerConfig()
    snap, batch = _scenario(num_nodes)
    snap = _pad_snapshot(snap, next_pow2(snap.num_nodes, 64))
    N = snap.num_nodes
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    num_values = int(snap.svc_num_values)

    sched = BatchScheduler(config)
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    static.update(BatchScheduler.config_static(config, snap))
    carry = sched.initial_carry(snap)
    carry_leaves = len(jax.tree_util.tree_leaves(carry))
    wave = WaveScheduler(config)
    # the scan pads its pod axis to a pow2 bucket (wave.scan_rows)
    scan_batch = pad_batch(batch,
                           next_pow2(batch.num_pods, wave.pod_floor))
    pods = {f: jnp.asarray(getattr(scan_batch, f))
            for f in BatchScheduler.POD_FIELDS}

    rep = 0  # template-alpha row
    pod_host = {f: np.asarray(getattr(batch, f))[rep]
                for f in BatchScheduler.POD_FIELDS}
    pod = {k: jnp.asarray(v) for k, v in pod_host.items()}
    layout, buf_host = pack_arrays(pod_host)
    buf = jnp.asarray(buf_host)
    counts = jnp.zeros((N,), jnp.int64)

    probe = WaveProbe(config)
    J, _rows = pick_j(config, wave.max_j, snap, batch, rep, run_length)

    specs: List[ProgramSpec] = [
        ProgramSpec(
            name="scan",
            fn=sched._compiled(num_zones, num_values),
            args=(static, carry, pods, np.int32(batch.num_pods)),
            allow_f64=True,  # reference-exact float64 score normalizers
            carry_out_leaves=carry_leaves,
            expected_host_leaves=2,  # chosen[P], the steps the loop ran
            notes="the serial-equivalent fallback path: one step a pod, "
                  "the trip count an operand",
        ),
        ProgramSpec(
            name="probe",
            fn=probe._compiled(num_zones, num_values, J),
            args=(static, carry, pod),
            carry_out_leaves=0,
            expected_host_leaves=1,  # ONE packed array
            notes="single-run packed probe (models/probe._probe_fn)",
        ),
    ]

    # narrow placements (ops/narrow): the probe traced against
    # narrowed static node tables at BOTH narrow widths, with the dtype
    # contract asserting the tables arrive narrow and are never widened
    # in-program (the placement bandwidth win is real, not cosmetic)
    from kubernetes_tpu.ops import narrow as _narrow

    for qdt in (np.int8, np.int16):
        qstatic = dict(static)
        decl = []
        for f in _narrow.NARROWABLE:
            host_f = np.asarray(getattr(snap, f))
            nat = _narrow.narrow_dtype(f, host_f)
            dt = np.dtype(qdt) if np.dtype(qdt).itemsize >= nat.itemsize \
                else nat
            qstatic[f] = jnp.asarray(host_f.astype(dt))
            decl.append((f, dt.str))
        specs.append(ProgramSpec(
            name=f"probe_quant_{np.dtype(qdt).name}",
            fn=probe._compiled(num_zones, num_values, J),
            args=(qstatic, carry, pod),
            carry_out_leaves=0,
            expected_host_leaves=1,
            narrow_dtypes=tuple(decl),
            notes="probe against quantized node tables "
                  f"({np.dtype(qdt).name} placement): decisions "
                  "bit-identical, tables never widened in-program",
        ))

    fused = probe._compiled_fused(num_zones, num_values, J, layout,
                                  wave._apply_fn)
    specs.append(ProgramSpec(
        name="probe_fused_same",
        fn=fused["same"],
        args=(static, carry, buf, counts),
        carry_out_leaves=carry_leaves,
        expected_host_leaves=1,
        scatter_allowed=(("scatter-add", (1,)),),
        notes="fold-own-commits + re-probe, one dispatch",
    ))

    # G=16 is the GANG-shaped grouped probe: the gang driver routes a
    # wave's all-or-nothing spans through this same builder (a gang is
    # a run group), so the gang path's transfer contract is audited at
    # its bench shape alongside the template shapes
    for G in (8, 16, 32):
        reps = [0, 24] * (G // 2)  # alternate the two templates
        G_bucket, glayout, gbuf_host = group_buffer(batch, reps[:G])
        gbuf = jnp.asarray(gbuf_host)
        grouped = probe._compiled_group(
            num_zones, num_values, G_bucket, glayout, None,
            wave._apply_fn, wave._apply_group_fn,
        )
        specs.append(ProgramSpec(
            name=f"group_probe_G{G_bucket}",
            fn=grouped,
            args=(static, carry, jnp.zeros(0, jnp.uint8),
                  jnp.zeros(0, jnp.int64), gbuf),
            carry_out_leaves=carry_leaves,
            expected_host_leaves=1,  # headers+usage CONCATENATED
            notes="grouped header probe: transfer count independent "
                  "of the template count G",
        ))
        if G == 8:
            gcounts = jnp.zeros((G_bucket, N), jnp.int64)

            def apply_group(static_, carry_, buf_, counts_,
                            _layout=glayout):
                return wave._apply_group_fn(_layout, static_, carry_,
                                            buf_, counts_)

            specs.append(ProgramSpec(
                name="apply_group",
                fn=jax.jit(apply_group),
                args=(static, carry, gbuf, gcounts),
                carry_out_leaves=carry_leaves,
                expected_host_leaves=0,  # the fold is carry-only
                scatter_allowed=(("scatter-add", (1,)),),
                notes="grouped commit fold (wave._apply_group_fn)",
            ))

    def apply_packed(static_, carry_, buf_, counts_):
        from kubernetes_tpu.models.pack import unpack as unpack_pod

        return wave._apply_fn(static_, carry_, unpack_pod(layout, buf_),
                              counts_)

    specs.append(ProgramSpec(
        name="apply",
        fn=jax.jit(apply_packed),
        args=(static, carry, buf, counts),
        carry_out_leaves=carry_leaves,
        expected_host_leaves=0,
        scatter_allowed=(("scatter-add", (1,)),),
        notes="single-run commit fold (wave._apply_fn, packed row)",
    ))

    # zoned device replay: single-run and grouped
    perm = np.asarray(snap.name_desc_order).astype(np.int64)
    zone_perm = jnp.asarray(
        np.ascontiguousarray(np.asarray(snap.zone_id)[perm], np.int32))
    veto_perm = jnp.asarray(np.zeros(N, bool))
    K = replay_k_bucket(run_length, ZREPLAY_K_FLOOR)
    zfn = jax.jit(functools.partial(
        _zreplay_fn, config, num_zones, num_values, J, K, layout,
        wave._apply_fn, False,
    ))
    specs.append(ProgramSpec(
        name="zreplay",
        fn=zfn,
        args=(static, carry, jnp.zeros(0, jnp.uint8),
              jnp.zeros(0, jnp.int64), buf, zone_perm, veto_perm,
              jnp.asarray(True), jnp.asarray(np.int64(32)),
              jnp.asarray(np.int32(K)), np.int64(0)),
        allow_f64=True,  # mirrors replay._scores float64 exactly
        carry_out_leaves=carry_leaves,
        # chosen, counts, L, n_done, the loop's own counters [2], the
        # nodes that fit at the probe
        expected_host_leaves=6,
        notes="zoned-spread device replay (models/zreplay)",
    ))
    Gz = 8
    reps = [0, 24] * (Gz // 2)
    Gz_bucket, gzlayout, gzbuf_host = group_buffer(batch, reps)
    Kg = replay_k_bucket(run_length, ZREPLAY_GROUP_K_FLOOR)
    zgfn = jax.jit(functools.partial(
        _zreplay_group_fn, config, num_zones, num_values, J, Kg,
        Gz_bucket, gzlayout, wave._apply_fn, None, None,
        wave._apply_group_fn,
    ))
    specs.append(ProgramSpec(
        name="zreplay_group",
        fn=zgfn,
        args=(static, carry, jnp.zeros(0, jnp.uint8),
              jnp.zeros(0, jnp.int64), jnp.asarray(gzbuf_host),
              zone_perm, jnp.asarray(np.zeros((Gz_bucket, N), bool)),
              jnp.asarray(np.ones(Gz_bucket, bool)),
              jnp.asarray(np.full(Gz_bucket, 32, np.int64)),
              jnp.asarray(np.full(Gz_bucket, Kg, np.int32)),
              np.int32(Gz), np.int64(0)),
        allow_f64=True,
        carry_out_leaves=carry_leaves,
        # chosen[G,K], n_done[G], L, the loops' own counters [3], the
        # nodes that fit at each run slot's probe [G]
        expected_host_leaves=5,
        notes="grouped zoned device replay: G runs, one dispatch",
    ))

    # gang preemption: the victim-selection scorer (ops/preempt.py) —
    # per-node candidate sort by (priority asc, newest first), freed-
    # resource prefix scan, shortest fitting prefix + cost. Integer-
    # only (no f64, no dot_general); ships exactly 3 host-bound arrays
    # (victims_needed, cost, eviction order) per dispatch.
    from kubernetes_tpu.ops.preempt import (
        INVALID_PRIO,
        _victim_score_fn,
        pack_candidates,
    )

    cand = [
        (snap.node_names[i % num_nodes], i % 3, i, (500, 1 << 20, 0, 1))
        for i in range(9)
    ]
    vprio, vord, vres, _idx = pack_candidates(
        [n for n in snap.node_names if n], cand,
        floor_nodes=16, floor_cands=8,
    )
    vfree = np.zeros((vprio.shape[0], 4), np.int64)
    vreq = np.array([1000, 2 << 20, 0, 1], np.int64)
    specs.append(ProgramSpec(
        name="victim_score",
        fn=jax.jit(_victim_score_fn),
        args=(jnp.asarray(vprio), jnp.asarray(vord),
              jnp.asarray(vres), jnp.asarray(vfree),
              jnp.asarray(vreq), jnp.int32(10)),
        carry_out_leaves=0,
        expected_host_leaves=3,
        notes="gang preemption victim scorer (ops/preempt.py): "
              "lowest-priority-first / fewest-victims / newest-first",
    ))

    # optimizing profile: the joint-assignment solvers
    # (scheduler/optimizer/ops/assign.py). Integer-only, scatter-free
    # by construction (the empty scatter_allowed set asserts it), and
    # ONE host-bound array per dispatch — the O(1)-dispatches-per-wave
    # budget the profile claims is this transfer contract.
    specs.extend(_assign_programs(snap, N))

    if include_mesh:
        specs.extend(_mesh_programs(config, snap, batch, layout,
                                    buf_host, carry_leaves))
    return specs


def _assign_args(N: int, P: int = 16):
    """Representative solver operands over an N-node cluster: P slots,
    two complementary request shapes (the packing case the profile
    exists for)."""
    rng = np.random.RandomState(7)
    fit = np.ones((P, N), bool)
    fit[:, N - 1] = False  # one unschedulable (padded-like) node
    score = rng.randint(0, 20, size=(P, N)).astype(np.int64)
    req = np.zeros((P, 4), np.int64)
    req[:, 0] = np.where(np.arange(P) % 2 == 0, 1000, 3000)
    req[:, 1] = np.int64(1) << 30
    req[:, 3] = 1
    commit = req.copy()
    check = np.ones((P, 4), bool)
    cap = np.zeros((N, 4), np.int64)
    cap[:, 0] = 4000
    cap[:, 1] = np.int64(32) << 30
    cap[:, 3] = 110
    prio = np.zeros(P, np.int32)
    order = np.arange(P, dtype=np.int32)
    return fit, score, req, commit, check, cap, prio, order


def _assign_programs(snap, N: int) -> List[ProgramSpec]:
    import functools

    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.scheduler.optimizer.ops.assign import (
        _auction_assign_fn,
        _beam_assign_fn,
        auction_rounds,
    )

    P = 16
    fit, score, req, commit, check, cap, prio, order = _assign_args(N, P)
    rounds = auction_rounds(P, N)
    return [
        ProgramSpec(
            name="assign_auction",
            fn=jax.jit(functools.partial(_auction_assign_fn, rounds)),
            args=(jnp.asarray(fit), jnp.asarray(score),
                  jnp.asarray(req), jnp.asarray(commit),
                  jnp.asarray(check), jnp.asarray(cap),
                  jnp.asarray(prio), jnp.asarray(order),
                  jnp.int64(8)),
            carry_out_leaves=0,
            expected_host_leaves=1,  # owner[P]
            scatter_allowed=(),  # scatter-free: one-hot winner max
            notes="optimizing-profile auction solver: epsilon-scaled "
                  "bidding rounds as one lax.scan dispatch",
        ),
        ProgramSpec(
            name="assign_beam",
            fn=jax.jit(functools.partial(_beam_assign_fn, 4, 4)),
            args=(jnp.asarray(fit), jnp.asarray(score),
                  jnp.asarray(req), jnp.asarray(commit),
                  jnp.asarray(check), jnp.asarray(cap)),
            carry_out_leaves=0,
            expected_host_leaves=1,  # owner[P]
            scatter_allowed=(),
            notes="optimizing-profile top-K beam solver (small waves): "
                  "one lax.scan over slots in solve order",
        ),
    ]


def _mesh_programs(config, snap, batch, pod_layout, pod_buf_host,
                   carry_leaves) -> List[ProgramSpec]:
    """The resident pjit variants, when this host can form a mesh.

    Programs come from the DRIVER'S OWN builders
    (MeshWaveScheduler._probe_program et al. and
    MeshBatchScheduler._exec's cache), so the audited shardings,
    donation declarations, and scatter-form commit signatures are the
    ones production dispatches — the registry cannot drift from the
    driver."""
    import jax

    if len(jax.devices()) < 2:
        return []

    from jax.sharding import Mesh

    from kubernetes_tpu.models.wave import group_buffer
    from kubernetes_tpu.parallel import mesh as M
    from kubernetes_tpu.parallel.resident import (
        CARRY_FIELDS,
        host_carry,
        host_static,
    )

    devices = np.array(jax.devices())
    mesh = Mesh(devices, (M.AXIS,))
    n_dev = devices.size
    snap_p = M._pad_snapshot(snap, n_dev)
    n = len(snap_p.node_names)
    n_per_shard = n // n_dev
    num_zones = max(int(snap_p.zone_id.max()) + 1, 1)
    num_values = int(snap_p.svc_num_values)

    from jax.sharding import PartitionSpec as PSpec

    from kubernetes_tpu.parallel.resident import carry_specs, static_specs

    static = host_static(config, snap_p)
    hc = host_carry(snap_p, 0)
    carry = tuple(hc[f] for f in CARRY_FIELDS)
    pods = {f: np.asarray(getattr(batch, f))
            for f in M.BatchScheduler.POD_FIELDS}
    J = 128
    M_bucket = 64
    wave = M.MeshWaveScheduler(mesh, config=config)
    # zero-size carry leaves keep their result shardings unspecified,
    # exactly as the driver dispatches them (mesh._carry_out_shardings)
    empty = M.empty_leaves(carry)

    # the sharding-drift declarations: the SAME single-source specs the
    # resident placement uses — the audit fails if the driver's jit
    # wrappers ever stop agreeing with them
    sspec = static_specs(static.keys())
    cspec = carry_specs()

    counts = np.zeros(n, np.int64)
    counts[: min(3, n)] = 2
    touch_idx, touch_cnt = M._sparse_counts(counts, floor=M_bucket)

    specs: List[ProgramSpec] = [
        ProgramSpec(
            name="mesh_scan",
            fn=wave.scan._jit_for(static, n, n_per_shard, num_zones,
                                  num_values, batch.num_pods,
                                  tuple(pods), empty=empty),
            args=(static, carry, pods),
            allow_f64=True,
            carry_out_leaves=carry_leaves,
            expected_host_leaves=1,
            # deliberately NOT donated: donation + lax.scan inside
            # shard_map miscompiles the SAA path on this jaxlib's CPU
            # backend (see MeshBatchScheduler._jit_for)
            arg_shardings=(sspec, cspec, {k: PSpec() for k in pods}),
            out_shardings_decl=(cspec, PSpec()),
            # the scan's one overwrite scatter (the chosen-index write)
            # asserts unique indices; every accumulation is scatter-add
            scatter_allowed=(("scatter", (0,)), ("scatter-add", (0,)),
                             ("scatter-add", (0, 1)),
                             ("scatter-add", (1,))),
            notes="sharded scan (MeshBatchScheduler._exec)",
        ),
        ProgramSpec(
            name="mesh_probe",
            fn=wave._probe_program(static, n, n_per_shard, num_zones,
                                   num_values, J, pod_layout),
            args=(static, carry, pod_buf_host),
            carry_out_leaves=0,
            expected_host_leaves=1,
            arg_shardings=(sspec, cspec, PSpec()),
            out_shardings_decl=PSpec(None, M.AXIS),
            notes="sharded single-run probe "
                  "(MeshWaveScheduler.probe_run)",
        ),
        ProgramSpec(
            name="mesh_apply",
            fn=wave._apply_program(static, n, n_per_shard, pod_layout,
                                   donate=True, empty=empty),
            args=(static, carry, pod_buf_host, touch_idx, touch_cnt),
            carry_out_leaves=carry_leaves,
            expected_host_leaves=0,
            donate_argnums=(1,),
            arg_shardings=(sspec, cspec, PSpec(), PSpec(), PSpec()),
            out_shardings_decl=cspec,
            scatter_allowed=(("scatter-add", (0,)),
                             ("scatter-add", (1,))),
            notes="sharded commit fold, scatter-form counts "
                  "(O(picks) shipment), donated resident carry",
        ),
    ]
    G_bucket, glayout, gbuf_host = group_buffer(batch, [0, 24, 0, 24])
    gcounts = np.zeros((G_bucket, n), np.int64)
    gcounts[0, : min(3, n)] = 1
    g_idx, g_cnt = M._sparse_group_counts(gcounts, floor=M_bucket)
    specs.append(ProgramSpec(
        name="mesh_group_probe",
        fn=wave._group_probe_program(static, n, n_per_shard, num_zones,
                                     num_values, G_bucket, glayout),
        args=(static, carry, gbuf_host),
        carry_out_leaves=0,
        expected_host_leaves=1,
        arg_shardings=(sspec, cspec, PSpec()),
        out_shardings_decl=PSpec(None, M.AXIS),
        notes="sharded grouped header probe: ONE host-bound array "
              "(usage block no longer ships — resident mirror)",
    ))
    specs.append(ProgramSpec(
        name="mesh_apply_group",
        fn=wave._apply_group_program(static, n, n_per_shard, glayout,
                                     donate=True, empty=empty),
        args=(static, carry, gbuf_host, g_idx, g_cnt),
        carry_out_leaves=carry_leaves,
        expected_host_leaves=0,
        donate_argnums=(1,),
        arg_shardings=(sspec, cspec, PSpec(), PSpec(), PSpec()),
        out_shardings_decl=cspec,
        scatter_allowed=(("scatter-add", (0, 1)),
                         ("scatter-add", (1,))),
        notes="sharded grouped commit fold, scatter-form counts, "
              "donated resident carry",
    ))
    # the optimizing profile's auction solver, pjit'd over the node
    # axis: the [slots x nodes] tensors shard like every other node-
    # axis program, slot-axis operands replicate, and the owner vector
    # comes back replicated (ONE host-bound array — the same transfer
    # contract as the single-chip form)
    import functools

    from kubernetes_tpu.scheduler.optimizer.ops.assign import (
        _auction_assign_fn,
        auction_rounds,
    )

    P_a = 16
    (a_fit, a_score, a_req, a_commit, a_check, a_cap, a_prio,
     a_order) = _assign_args(n, P_a)
    a_rounds = auction_rounds(P_a, n)
    assign_in = (
        PSpec(None, M.AXIS),  # fit [P, N]
        PSpec(None, M.AXIS),  # score [P, N]
        PSpec(),              # req [P, 4]
        PSpec(),              # commit [P, 4]
        PSpec(),              # check [P, 4]
        PSpec(M.AXIS, None),  # cap [N, 4]
        PSpec(),              # prio [P]
        PSpec(),              # order [P]
        PSpec(),              # eps0 scalar
    )
    from jax.sharding import NamedSharding

    mesh_assign = jax.jit(
        functools.partial(_auction_assign_fn, a_rounds),
        in_shardings=tuple(NamedSharding(mesh, s) for s in assign_in),
        out_shardings=NamedSharding(mesh, PSpec()),
    )
    specs.append(ProgramSpec(
        name="mesh_assign_auction",
        fn=mesh_assign,
        args=(a_fit, a_score, a_req, a_commit, a_check, a_cap, a_prio,
              a_order, np.int64(8)),
        carry_out_leaves=0,
        expected_host_leaves=1,
        arg_shardings=assign_in,
        out_shardings_decl=PSpec(),
        scatter_allowed=(),
        notes="optimizing-profile auction solver, node-axis sharded "
              "(mesh variant)",
    ))
    specs.append(_resident_scatter_program(mesh, config, snap_p, n,
                                           n_per_shard))
    return specs


def _resident_scatter_program(mesh, config, snap_p, n,
                              n_per_shard) -> ProgramSpec:
    """The resident-state row-scatter update (node add/remove inside
    the padded bucket), built exactly as ResidentClusterState._scatter
    builds it: donated resident arrays, one packed replicated row
    buffer."""
    import numpy as np

    from kubernetes_tpu.models.pack import pack_arrays
    from kubernetes_tpu.parallel.resident import (
        CARRY_FIELDS,
        ResidentClusterState,
        host_carry,
        host_static,
    )

    res = ResidentClusterState(mesh)
    static, carry = res.sync(config, snap_p, 0)
    hs = host_static(config, snap_p)
    hc = host_carry(snap_p, 0)
    fields = [
        ("alloc_mcpu", hs["alloc_mcpu"], 0),
        ("label_kv", hs["label_kv"], 0),
        ("__res__", hc["__res__"], 1),
    ]
    M_rows = 64
    rows = np.arange(min(3, n), dtype=np.int64)
    idx = np.full(M_rows, -1, np.int64)
    idx[: len(rows)] = rows
    packed = {"__idx__": idx}
    names, axes, spec_list, arrays = [], [], [], []
    sspec, cspec = res._specs(hs.keys())
    for f, host, ax in fields:
        r = np.moveaxis(host, ax, 0)[rows]
        pad = np.zeros((M_rows - len(rows),) + r.shape[1:], r.dtype)
        packed[f] = np.concatenate([r, pad])
        names.append(f)
        axes.append(ax)
        spec_list.append(cspec[f] if f in CARRY_FIELDS else sspec[f])
        arrays.append(carry[CARRY_FIELDS.index(f)]
                      if f in CARRY_FIELDS else static[f])
    layout, buf = pack_arrays(packed)
    run = res._scatter_program(tuple(names), tuple(axes),
                               tuple(spec_list), layout,
                               tuple(a.shape for _f, a, _x in fields),
                               n_per_shard, donate=True)
    from jax.sharding import PartitionSpec as PSpec

    return ProgramSpec(
        name="resident_scatter",
        fn=run,
        args=((tuple(arrays)), buf),
        carry_out_leaves=len(arrays),
        expected_host_leaves=0,
        donate_argnums=(0,),
        arg_shardings=(tuple(spec_list), PSpec()),
        out_shardings_decl=tuple(spec_list),
        # row replacement is add-into-zeroed-rows: commutative, and
        # collision-free by the host's packed unique row indices
        scatter_allowed=(("scatter-add", (0,)),),
        notes="resident node add/remove row scatter: donated in-place "
              "update, O(changed rows) shipment",
    )
