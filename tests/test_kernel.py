"""Round-19 kernel-path contracts: the Pallas probe build must be
bit-identical to the lax build, quantized table placement must be
lossless (including the int8 -> int16 boundary rebuild), the
double-buffered pipeline must reproduce the serial loop's decisions
exactly, the bf16 profile must ride the ShadowGate, and the trace
accountant must attribute staged encode seconds as probe overlap.

Every identity here is exact array/decision equality — the kernel
path's whole contract is that raw speed changes NOTHING observable."""

import json
import random
import types

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    Service,
    ServiceSpec,
)
from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig
from kubernetes_tpu.models.probe import WaveProbe
from kubernetes_tpu.models.wave import WaveScheduler
from kubernetes_tpu.oracle import ClusterState
from kubernetes_tpu.parallel import quant
from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
from kubernetes_tpu.snapshot.encode import SnapshotEncoder

from tests.test_conformance import random_scenario
from tests.test_wave import oracle_backlog


# -- parallel/quant units ------------------------------------------------------


def test_narrow_dtype_boundaries():
    def dt(vals, dtype=np.int32, name="zone_id"):
        return quant.narrow_dtype(name, np.asarray(vals, dtype))

    assert dt([0, 127]) == np.int8
    assert dt([0, 128]) == np.int16
    assert dt([-128, 0]) == np.int8
    assert dt([-129, 0]) == np.int16
    assert dt([0, 32767]) == np.int16
    # past int16: keep the original width (no int32 "narrowing" step)
    assert dt([0, 32768]) == np.int32
    assert dt([0, 32768], np.int64) == np.int64
    # empty tables place at the narrowest width and rebuild on growth
    assert dt([]) == np.int8


def test_narrow_dtype_scope():
    # only the declared-narrowable names shrink; bitsets/floats/bytes
    # pass through untouched
    big = np.arange(4, dtype=np.int64)
    assert quant.narrow_dtype("alloc_cpu", big) == np.int64
    assert quant.narrow_dtype("label_kv", np.zeros(4, np.uint32)) \
        == np.uint32
    assert quant.narrow_dtype("zone_id", np.zeros(4, np.float32)) \
        == np.float32
    assert quant.narrow_dtype("zone_id", np.zeros(4, np.int16)) \
        == np.int16  # already narrow: no re-audit churn


def test_narrow_eq_out_of_range_guard():
    import jax.numpy as jnp

    table = jnp.asarray(np.array([1, 2, 3, 127], np.int8))
    # in-range compare matches the wide compare exactly
    assert np.array_equal(
        np.asarray(quant.narrow_eq(table, jnp.asarray(3))),
        np.array([False, False, True, False]))
    # an out-of-vocab wide comparand must NOT alias into the narrow
    # range (300 % 256 = 44 would otherwise be a valid int8)
    assert not np.asarray(
        quant.narrow_eq(table, jnp.asarray(300))).any()
    assert not np.asarray(
        quant.narrow_eq(table, jnp.asarray(-300))).any()


def test_narrow_matvec_matches_wide():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    table = rng.integers(0, 100, (32, 8)).astype(np.int8)
    vec = rng.integers(0, 2, 8).astype(np.int32)  # 0/1 indicator
    got = np.asarray(quant.narrow_matvec(
        jnp.asarray(table), jnp.asarray(vec), np.int32))
    want = table.astype(np.int32) @ vec
    assert got.dtype == np.int32 and np.array_equal(got, want)


def test_shadow_gate_stride_and_fallback():
    g = quant.ShadowGate(stride=4)
    checks = [g.should_check() for _ in range(9)]
    assert checks == [True, False, False, False, True,
                      False, False, False, True]
    g.record(True)
    assert not g.fallen_back and g.divergence == 0
    g.record(False)
    assert g.fallen_back and g.divergence == 1
    # fallen back: no further waves sample
    assert not g.should_check()
    assert quant.ShadowGate(stride=0).should_check() is False


# -- quantized placement: device dtype + boundary rebuild ----------------------


def test_to_dev_many_narrow_placement_and_boundary_rebuild():
    ws = WaveScheduler(quant_mode="int")
    zid = (np.arange(24) % 3).astype(np.int32)
    snap = types.SimpleNamespace(zone_id=zid)
    out = ws._to_dev_many(snap, ["zone_id"], keep=frozenset())
    assert out["zone_id"].dtype == np.int8  # placed narrow
    assert ws._dev["zone_id"][3].dtype == np.int32  # mirror full width
    ships0 = ws.stats["table_ships"]

    # unchanged content: reuse, no bytes
    out = ws._to_dev_many(snap, ["zone_id"], keep=frozenset())
    assert out["zone_id"].dtype == np.int8
    assert ws.stats["table_ships"] == ships0
    assert ws.stats["table_bytes_reused"] > 0

    # vocab growth past int8: the placement dtype is part of the cache
    # key, so the first sync after an out-of-range value rebuilds wider
    snap.zone_id = zid.copy()
    snap.zone_id[5] = 200
    out = ws._to_dev_many(snap, ["zone_id"], keep=frozenset())
    assert out["zone_id"].dtype == np.int16
    assert ws.stats["table_ships"] == ships0 + 1

    # and past int16 -> full width
    snap.zone_id = zid.copy()
    snap.zone_id[5] = 40000
    out = ws._to_dev_many(snap, ["zone_id"], keep=frozenset())
    assert out["zone_id"].dtype == np.int32


def test_to_dev_many_wide_mode_off():
    ws = WaveScheduler(quant_mode="off")
    snap = types.SimpleNamespace(zone_id=(np.arange(8) % 3)
                                 .astype(np.int32))
    out = ws._to_dev_many(snap, ["zone_id"], keep=frozenset())
    assert out["zone_id"].dtype == np.int32


# -- probe builds: pallas == lax, bf16 == i64 on the audit scenario ------------


def _probe_inputs(J=64):
    import jax.numpy as jnp

    from kubernetes_tpu.analysis.programs import _scenario

    config = SchedulerConfig()
    snap, batch = _scenario()
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    num_values = int(snap.svc_num_values)
    sched = BatchScheduler(config)
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    static.update(BatchScheduler.config_static(config, snap))
    carry = sched.initial_carry(snap)
    pod = {f: jnp.asarray(np.asarray(getattr(batch, f))[0])
           for f in BatchScheduler.POD_FIELDS}
    return config, num_zones, num_values, J, static, carry, pod


def test_pallas_probe_bit_identical_to_lax():
    config, nz, nv, J, static, carry, pod = _probe_inputs()
    lax_out = WaveProbe(config, kernel="lax")._compiled(
        nz, nv, J)(static, carry, pod)
    # interpret mode by name: the compiled lowering exists on no CPU
    # backend and is refused on the TPU (tests/test_chip_compile.py)
    pal_out = WaveProbe(config, kernel="pallas-interpret")._compiled(
        nz, nv, J)(static, carry, pod)
    a = np.asarray(lax_out["packed"])
    b = np.asarray(pal_out["packed"])
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(a, b)


def test_bf16_probe_matches_i64_on_default_profile():
    # the default profile's summed |weight|*10 bound fits bf16's exact
    # integer range, so the bf16 accumulator is bit-identical here
    config, nz, nv, J, static, carry, pod = _probe_inputs()
    i64 = WaveProbe(config, score_mode="i64")._compiled(
        nz, nv, J)(static, carry, pod)
    b16 = WaveProbe(config, score_mode="bf16")._compiled(
        nz, nv, J)(static, carry, pod)
    assert np.array_equal(np.asarray(i64["packed"]),
                          np.asarray(b16["packed"]))


def test_probe_kernel_env_selection(monkeypatch):
    monkeypatch.delenv("KUBERNETES_TPU_KERNEL", raising=False)
    assert WaveProbe(SchedulerConfig()).kernel == "lax"
    monkeypatch.setenv("KUBERNETES_TPU_KERNEL", "pallas")
    # the environment asks for the COMPILED kernel; a backend that
    # cannot compile it refuses at construction instead of interpreting
    with pytest.raises(ValueError, match="interpret mode"):
        WaveProbe(SchedulerConfig())
    # explicit ctor arg beats the env (the shadow-driver seam)
    assert WaveProbe(SchedulerConfig(), kernel="lax").kernel == "lax"


# -- end-to-end bit-identity: quant / pipeline / full stack --------------------


def _staged_backlog(num_nodes=16, num_pods=120, templates=3, block=10):
    """Blocks of impure runs (soft anti-affinity against the NEXT
    group) — the shape where the pipeline actually stages; mirrors
    bench.build_multi at test scale."""
    nodes = [
        Node(
            metadata=ObjectMeta(
                name=f"kn-{i:03d}",
                labels={"kubernetes.io/hostname": f"kn-{i:03d}"},
            ),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")],
            ),
        )
        for i in range(num_nodes)
    ]
    pods = []
    for i in range(num_pods):
        t = (i // block) % templates
        p = Pod(
            metadata=ObjectMeta(name=f"kp-{i:04d}",
                                labels={"group": f"g{t}"}),
            spec=PodSpec(containers=[Container(
                requests={"cpu": "100m", "memory": "200Mi"})]),
        )
        p.metadata.annotations = {
            "scheduler.alpha.kubernetes.io/affinity": json.dumps({
                "podAntiAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [{
                        "weight": 1,
                        "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {
                                "group": f"g{(t + 1) % templates}"}},
                            "topologyKey": "kubernetes.io/hostname",
                            "namespaces": [],
                        },
                    }],
                },
            })
        }
        pods.append(p)
    services = [
        Service(metadata=ObjectMeta(name=f"ksvc-{t}"),
                spec=ServiceSpec(selector={"group": f"g{t}"}))
        for t in range(templates)
    ]
    return ClusterState.build(nodes, services=services), pods


def test_pipeline_decisions_identical_to_serial():
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.encode import pod_feature_key
    from kubernetes_tpu.snapshot.pad import next_pow2

    state, pods = _staged_backlog()
    uniq, rep_of, rep_list = [], {}, []
    for p in pods:
        k = pod_feature_key(p)
        if k not in rep_of:
            rep_of[k] = len(uniq)
            uniq.append(p)
        rep_list.append(rep_of[k])
    enc = SnapshotEncoder(state, uniq)
    snap = enc.encode_nodes()
    batch = enc.encode_pods()
    snap = _pad_snapshot(snap, next_pow2(snap.num_nodes, 4))
    rep_idx = np.asarray(rep_list, np.int64)

    serial = WaveScheduler(min_run=1, pipeline=False)
    piped = WaveScheduler(min_run=1, pipeline=True)
    s_chosen, s_carry, s_last = serial.schedule_backlog(
        snap, batch, rep_idx)
    p_chosen, p_carry, p_last = piped.schedule_backlog(
        snap, batch, rep_idx)
    assert np.array_equal(s_chosen, p_chosen)
    assert s_last == p_last
    # the pipelined driver actually staged (the wave kept per-wave
    # dispatch tallies; staging shows up as its own count)
    assert piped.dispatches.get("stage", 0) > 0
    assert serial.dispatches.get("stage", 0) == 0


def test_pipeline_env_gate(monkeypatch):
    monkeypatch.delenv("KUBERNETES_TPU_PIPELINE", raising=False)
    assert WaveScheduler().pipeline is False
    monkeypatch.setenv("KUBERNETES_TPU_PIPELINE", "1")
    assert WaveScheduler().pipeline is True
    assert WaveScheduler(pipeline=False).pipeline is False


def test_full_stack_matches_oracle_end_to_end(monkeypatch):
    # quant int + pipeline on, against the oracle: the whole round-19
    # stack must change nothing observable
    state, pods = _staged_backlog(num_nodes=12, num_pods=90,
                                  templates=3, block=10)
    want = oracle_backlog(state, pods)
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "int")
    monkeypatch.setenv("KUBERNETES_TPU_PIPELINE", "1")
    got = TPUScheduleAlgorithm().schedule_backlog(pods, state)
    assert got == want


@pytest.mark.parametrize("seed", [11, 23])
def test_quant_decision_identity_fuzz(monkeypatch, seed):
    rng = random.Random(seed)
    state, pending = random_scenario(
        rng, n_nodes=10, n_existing=12, n_pending=30,
        interpod_p=0.2, volumes_p=0.3)
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "off")
    wide = TPUScheduleAlgorithm().schedule_backlog(pending,
                                                   state.clone())
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "int")
    narrow = TPUScheduleAlgorithm().schedule_backlog(pending,
                                                     state.clone())
    assert narrow == wide


@pytest.mark.slow
@pytest.mark.parametrize("seed", [3, 5, 17, 29])
def test_quant_pipeline_identity_fuzz_slow(monkeypatch, seed):
    rng = random.Random(seed)
    state, pending = random_scenario(
        rng, n_nodes=14, n_existing=20, n_pending=60,
        interpod_p=0.3, volumes_p=0.3)
    monkeypatch.delenv("KUBERNETES_TPU_QUANT", raising=False)
    monkeypatch.delenv("KUBERNETES_TPU_PIPELINE", raising=False)
    base = TPUScheduleAlgorithm().schedule_backlog(pending,
                                                   state.clone())
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "int")
    monkeypatch.setenv("KUBERNETES_TPU_PIPELINE", "1")
    full = TPUScheduleAlgorithm().schedule_backlog(pending,
                                                   state.clone())
    assert full == base


# -- bf16 ShadowGate wiring ----------------------------------------------------


def test_bf16_profile_builds_shadow_and_matches(monkeypatch):
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "bf16")
    monkeypatch.setenv("KUBERNETES_TPU_QUANT_SHADOW", "1")
    state, pods = _staged_backlog(num_nodes=10, num_pods=60,
                                  templates=2, block=10)
    algo = TPUScheduleAlgorithm()
    assert algo._shadow_gate is not None
    assert algo._shadow_wave is not None
    got = algo.schedule_backlog(pods, state.clone())
    assert algo._shadow_gate.checked >= 1
    assert algo._shadow_gate.divergence == 0
    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "off")
    wide = TPUScheduleAlgorithm().schedule_backlog(pods, state.clone())
    assert got == wide


def test_bf16_shadow_divergence_falls_back(monkeypatch):
    from kubernetes_tpu.metrics import (
        scheduler_quant_shadow_divergence_total,
    )

    monkeypatch.setenv("KUBERNETES_TPU_QUANT", "bf16")
    monkeypatch.setenv("KUBERNETES_TPU_QUANT_SHADOW", "1")
    state, pods = _staged_backlog(num_nodes=8, num_pods=40,
                                  templates=2, block=10)
    algo = TPUScheduleAlgorithm()
    shadow = algo._shadow_wave
    real_fn = shadow.schedule_backlog

    def lying_shadow(*a, **kw):
        chosen, carry, last = real_fn(*a, **kw)
        bad = np.asarray(chosen).copy()
        bad[0] = -1 if bad[0] != -1 else 0
        return bad, carry, last

    shadow.schedule_backlog = lying_shadow
    before = scheduler_quant_shadow_divergence_total.get()
    algo.schedule_backlog(pods, state.clone())
    assert scheduler_quant_shadow_divergence_total.get() == before + 1
    assert algo._shadow_gate.fallen_back
    # after the trip the shadow (full-width) wave IS the driver; undo
    # the lie and confirm the next backlog schedules sanely through it
    shadow.schedule_backlog = real_fn
    got = algo.schedule_backlog(pods, state.clone())
    assert sum(1 for h in got if h is not None) > 0


# -- dtype contract (analysis gate) --------------------------------------------


def _audit_dtype(fn, args, narrow_dtypes):
    import jax

    from kubernetes_tpu.analysis.jaxpr_audit import _dtype_findings
    from kubernetes_tpu.analysis.programs import ProgramSpec

    spec = ProgramSpec(name="t", fn=fn, args=args,
                       narrow_dtypes=narrow_dtypes)
    return _dtype_findings(spec, jax.make_jaxpr(fn)(*args))


def test_dtype_contract_flags_widening():
    import jax.numpy as jnp

    def widens(static, x):
        # terminal use is a reduction, not a gather index — the widened
        # full-width table is genuinely materialized and consumed
        return jnp.sum(static["zone_id"].astype(jnp.int32) * x)

    args = ({"zone_id": jnp.zeros(16, jnp.int8)},
            jnp.ones(16, jnp.int32))
    found = _audit_dtype(widens, args, (("zone_id", "|i1"),))
    assert len(found) == 1 and "widening" in found[0].message


def test_dtype_contract_exempts_index_feeds():
    import jax.numpy as jnp

    def gathers(static, w):
        idx = static["zone_id"]  # narrow ids used ONLY as indices
        return w.at[idx].add(1), w[idx]

    args = ({"zone_id": jnp.zeros(16, jnp.int8)},
            jnp.ones(8, jnp.int64))
    assert _audit_dtype(gathers, args, (("zone_id", "|i1"),)) == []


def test_dtype_contract_flags_wide_arrival():
    import jax.numpy as jnp

    def f(static):
        return static["zone_id"] + 0

    args = ({"zone_id": jnp.zeros(16, jnp.int32)},)
    found = _audit_dtype(f, args, (("zone_id", "|i1"),))
    assert len(found) == 1 and "arrives" in found[0].message


def test_registered_quant_programs_clean():
    # the registry's probe_quant_* specs carry the contract; they must
    # trace clean end to end (the CI gate runs audit_all; this is the
    # fast in-suite slice for the two quant builds + pallas)
    from kubernetes_tpu.analysis.jaxpr_audit import audit_program
    from kubernetes_tpu.analysis.programs import build_programs

    specs = {s.name: s for s in build_programs(include_mesh=False)}
    for name in ("probe_quant_int8", "probe_quant_int16"):
        assert name in specs
        assert specs[name].narrow_dtypes
        assert audit_program(specs[name]) == []
