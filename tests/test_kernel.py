"""Single-chip driver contracts: narrow table placement (ops/narrow)
must be lossless (including the int8 -> int16 boundary rebuild), the
driver's decisions on backlogs of consecutive impure runs must equal
the oracle's, the dtype-contract audit must hold the narrow tables
narrow in-program, and the package's environment knobs are the ones
written out here.

Every identity here is exact array/decision equality — the kernel
path's whole contract is that raw speed changes NOTHING observable."""

import json
import os
import random
import re
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
from kubernetes_tpu.models.wave import WaveScheduler
from kubernetes_tpu.ops import narrow
from kubernetes_tpu.oracle import ClusterState
from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

from tests.test_conformance import random_scenario
from tests.test_wave import oracle_backlog


# -- ops/narrow units ----------------------------------------------------------


def test_narrow_dtype_boundaries():
    def dt(vals, dtype=np.int32, name="zone_id"):
        return narrow.narrow_dtype(name, np.asarray(vals, dtype))

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
    assert narrow.narrow_dtype("alloc_cpu", big) == np.int64
    assert narrow.narrow_dtype("label_kv", np.zeros(4, np.uint32)) \
        == np.uint32
    assert narrow.narrow_dtype("zone_id", np.zeros(4, np.float32)) \
        == np.float32
    assert narrow.narrow_dtype("zone_id", np.zeros(4, np.int16)) \
        == np.int16  # already narrow: no re-audit churn


def test_narrow_eq_out_of_range_guard():
    import jax.numpy as jnp

    table = jnp.asarray(np.array([1, 2, 3, 127], np.int8))
    # in-range compare matches the wide compare exactly
    assert np.array_equal(
        np.asarray(narrow.narrow_eq(table, jnp.asarray(3))),
        np.array([False, False, True, False]))
    # an out-of-vocab wide comparand must NOT alias into the narrow
    # range (300 % 256 = 44 would otherwise be a valid int8)
    assert not np.asarray(
        narrow.narrow_eq(table, jnp.asarray(300))).any()
    assert not np.asarray(
        narrow.narrow_eq(table, jnp.asarray(-300))).any()


def test_narrow_matvec_matches_wide():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    table = rng.integers(0, 100, (32, 8)).astype(np.int8)
    vec = rng.integers(0, 2, 8).astype(np.int32)  # 0/1 indicator
    got = np.asarray(narrow.narrow_matvec(
        jnp.asarray(table), jnp.asarray(vec), np.int32))
    want = table.astype(np.int32) @ vec
    assert got.dtype == np.int32 and np.array_equal(got, want)


def test_to_dev_many_ships_a_unit_whole_whatever_differs():
    """Fields their producer says moved as one (`reship`) go with the
    batch each whole, the unchanged and the barely changed with the
    rest: which tables a wave ships does not hang on which of them
    happen to differ. Left to the comparison, one is reused and one
    becomes a row scatter."""
    ws = WaveScheduler()
    snap = types.SimpleNamespace(
        a=np.arange(64, dtype=np.int64).reshape(16, 4),
        b=np.arange(16, dtype=np.int64), c=np.zeros((16, 2), np.int64))
    fields = ["a", "b", "c"]
    ws._to_dev_many(snap, fields, keep=frozenset())
    base = dict(ws.stats)
    snap.a = snap.a + 1  # every row
    snap.b = snap.b.copy()
    snap.b[3] = 99  # one row of sixteen
    out = ws._to_dev_many(snap, fields, keep=frozenset(),
                          reship=frozenset(fields))
    assert ws.stats["table_ships"] == base["table_ships"] + 3
    assert ws.stats["table_scatters"] == base["table_scatters"]
    assert ws.stats["table_reuses"] == base["table_reuses"]
    for f in fields:
        assert np.array_equal(np.asarray(out[f]), getattr(snap, f))
    snap.a = snap.a + 1
    snap.b = snap.b.copy()
    snap.b[4] = 98
    out = ws._to_dev_many(snap, fields, keep=frozenset())
    assert ws.stats["table_ships"] == base["table_ships"] + 4
    assert ws.stats["table_scatters"] == base["table_scatters"] + 1
    assert ws.stats["table_reuses"] == base["table_reuses"] + 1
    for f in fields:
        assert np.array_equal(np.asarray(out[f]), getattr(snap, f))


# -- narrow placement: device dtype + boundary rebuild -------------------------


def test_to_dev_many_narrow_placement_and_boundary_rebuild():
    ws = WaveScheduler()
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


def test_to_dev_many_holds_non_narrowable_table_at_full_width():
    # alloc_mcpu's values would fit int8; it is not on the declared
    # list (resource tables hold byte counts), so it places as it is
    ws = WaveScheduler()
    snap = types.SimpleNamespace(
        alloc_mcpu=np.full(8, 100, np.int64))
    out = ws._to_dev_many(snap, ["alloc_mcpu"], keep=frozenset())
    assert out["alloc_mcpu"].dtype == np.int64
    assert ws._dev["alloc_mcpu"][3].dtype == np.int64


# -- end-to-end decision identity against the oracle ---------------------------


def _staged_backlog(num_nodes=16, num_pods=120, templates=3, block=10):
    """Blocks of impure runs (soft anti-affinity against the NEXT
    group): no grouped header probe takes them. On one chip's own route
    they are one grouped device replay; on the host's (`replay=`)
    consecutive single runs, each handing its fold to the next run's
    probe."""
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


@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("num_nodes,num_pods,templates", [
    (16, 120, 3), (12, 90, 3), (10, 60, 2), (8, 40, 2)])
def test_full_stack_matches_oracle_end_to_end(num_nodes, num_pods,
                                              templates, route):
    from kubernetes_tpu.models.replay import replay_fast

    state, pods = _staged_backlog(num_nodes=num_nodes, num_pods=num_pods,
                                  templates=templates, block=10)
    want = oracle_backlog(state, pods)
    # min_run=1: blocks of 10 stay under the default min_run and would
    # all take the scan
    algo = TPUScheduleAlgorithm(
        min_run=1, replay=replay_fast if route == "host" else None)
    got = algo.schedule_backlog(pods, state)
    assert got == want
    if route == "host":
        # every pod through run_single, each run folding its predecessor
        assert algo._wave.stats["pods_by_path"]["single"] == num_pods
        assert algo._wave.dispatches["probe"] == num_pods // 10
    else:
        # the blocks are neighbours: one dispatch of the device replay
        assert algo._wave.stats["pods_by_path"]["group_device"] == num_pods
        assert algo._wave.dispatches == {"zreplay_group": 1}
        assert algo._wave.stats["zreplay_slots"] == num_pods // 10


@pytest.mark.parametrize("seed", [11, 23])
def test_quant_decision_identity_fuzz(seed):
    rng = random.Random(seed)
    state, pending = random_scenario(
        rng, n_nodes=10, n_existing=12, n_pending=30,
        interpod_p=0.2, volumes_p=0.3)
    want = oracle_backlog(state.clone(), pending)
    got = TPUScheduleAlgorithm().schedule_backlog(pending, state.clone())
    assert got == want


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
    # fast in-suite slice for the two narrow builds)
    from kubernetes_tpu.analysis.jaxpr_audit import audit_program
    from kubernetes_tpu.analysis.programs import build_programs

    specs = {s.name: s for s in build_programs(include_mesh=False)}
    for name in ("probe_quant_int8", "probe_quant_int16"):
        assert name in specs
        assert specs[name].narrow_dtypes
        assert audit_program(specs[name]) == []


# -- knob census ---------------------------------------------------------------


def test_environment_knob_census():
    # every KUBERNETES_TPU_* name the package mentions: an option added
    # or removed is a one-line diff here
    import kubernetes_tpu

    found = set()
    for root, _dirs, files in os.walk(
            os.path.dirname(kubernetes_tpu.__file__)):
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(root, fname)) as f:
                    found.update(
                        re.findall(r"KUBERNETES_TPU_[A-Z_]+", f.read()))
    assert sorted(found) == [
        "KUBERNETES_TPU_APF",
        "KUBERNETES_TPU_APF_BORROW",
        "KUBERNETES_TPU_APF_QUEUE_WAIT",
        "KUBERNETES_TPU_APF_SEATS",
        "KUBERNETES_TPU_AUDIT",
        "KUBERNETES_TPU_AUDIT_LOG",
        "KUBERNETES_TPU_AUDIT_RING",
        "KUBERNETES_TPU_DEFAULT_GC",
        "KUBERNETES_TPU_DEFRAG_BUDGET",
        "KUBERNETES_TPU_EVENT_TTL",
        "KUBERNETES_TPU_GIL_SWITCH_INTERVAL",
        "KUBERNETES_TPU_MESH",
        "KUBERNETES_TPU_NO_XLA_CACHE",
        "KUBERNETES_TPU_OPT_SLOTS",
        "KUBERNETES_TPU_PROFILE",
        "KUBERNETES_TPU_RACE_REPORT",
        "KUBERNETES_TPU_RACE_SANITIZER",
        "KUBERNETES_TPU_TELEMETRY",
        "KUBERNETES_TPU_TRACE",
        "KUBERNETES_TPU_WARM_SCAN",
        "KUBERNETES_TPU_WATCH_CACHE",
        "KUBERNETES_TPU_WATCH_CACHE_SIZES",
        "KUBERNETES_TPU_WATCH_COALESCE",
    ]
