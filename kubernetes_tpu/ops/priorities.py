"""Priority score kernels — integer/float arithmetic matched to the
reference operation-for-operation so int truncations agree.

Every kernel returns an int64[N] score vector in 0..10 for one pending
pod. Normalizing kernels (spread, node-affinity, taint-toleration) take
the fit mask because the reference normalizes over FILTERED nodes only
(PrioritizeNodes receives FakeNodeLister(filteredNodes),
generic_scheduler.go:109)."""

from __future__ import annotations

import jax.numpy as jnp

from kubernetes_tpu.ops import bitset
from kubernetes_tpu.ops.narrow import narrow_eq, narrow_matvec
from kubernetes_tpu.ops.predicates import _requirement_matrix

MAX_PRIORITY = 10


def taint_intolerable_counts(node_taint_count, pod_intolerable_prefer):
    """i64[N] per-list intolerable-taint counts. The node table may
    ride a narrowed placement dtype (ops/narrow): the 0/1 pod
    indicator casts DOWN to it and the contraction accumulates in
    int32, so the big table is never widened. Matches the plain int32
    matmul bit-for-bit."""
    return narrow_matvec(
        node_taint_count, pod_intolerable_prefer, jnp.int32
    ).astype(jnp.int64)


def _calculate_score(requested, capacity):
    """priorities.go:33 calculateScore — int64, truncating division;
    0 when capacity == 0 or requested > capacity."""
    safe_cap = jnp.where(capacity == 0, 1, capacity)
    score = ((capacity - requested) * 10) // safe_cap
    return jnp.where((capacity == 0) | (requested > capacity), 0, score)


def least_requested(pod_nz_mcpu, pod_nz_mem, nz_mcpu, nz_mem, alloc_mcpu, alloc_mem):
    """priorities.go:81 LeastRequestedPriority: avg of cpu+mem scores,
    over NonZeroRequest + the pod's own nonzero request."""
    total_cpu = nz_mcpu + pod_nz_mcpu
    total_mem = nz_mem + pod_nz_mem
    cpu_score = _calculate_score(total_cpu, alloc_mcpu)
    mem_score = _calculate_score(total_mem, alloc_mem)
    return (cpu_score + mem_score) // 2


def balanced_resource_allocation(
    pod_nz_mcpu, pod_nz_mem, nz_mcpu, nz_mem, alloc_mcpu, alloc_mem
):
    """priorities.go:215 BalancedResourceAllocation: float64 fractions,
    10 - |cpuFrac - memFrac| * 10, truncated; 0 if either frac >= 1
    (fractionOfCapacity returns 1 for capacity==0)."""
    total_cpu = (nz_mcpu + pod_nz_mcpu).astype(jnp.float64)
    total_mem = (nz_mem + pod_nz_mem).astype(jnp.float64)
    cpu_frac = jnp.where(
        alloc_mcpu == 0, 1.0, total_cpu / alloc_mcpu.astype(jnp.float64)
    )
    mem_frac = jnp.where(
        alloc_mem == 0, 1.0, total_mem / alloc_mem.astype(jnp.float64)
    )
    diff = jnp.abs(cpu_frac - mem_frac)
    score = (10.0 - diff * 10.0).astype(jnp.int64)
    return jnp.where((cpu_frac >= 1.0) | (mem_frac >= 1.0), 0, score)


def equal(num_nodes):
    """generic_scheduler.go:310 EqualPriority."""
    return jnp.ones((num_nodes,), jnp.int64)


def spread_counts(class_count, spread_match):
    """The contraction of selector spread: class_count i64[N, C] with a
    pod's spread_match i64[C] 0/1 -> i64[N], per node the same-namespace,
    non-deleted pods matching ANY selector of the pod. Contracted in
    int32: per-node pod counts are far below 2^31, and XLA's x64
    rewriter has no TPU lowering for s64 dot_general."""
    return (
        class_count.astype(jnp.int32) @ spread_match.astype(jnp.int32)
    ).astype(jnp.int64)


def zone_sums(counts, zone_id, num_zones):
    """counts[N] summed by zone_id -> [num_zones], as masked reductions
    over a [zones, N] membership and not a scatter-add by zone_id: on
    the chip the scatter is one serial update a node (245 us of the
    scan's 467 us step at N = 4096 in emulated int64; PERF.md, PR 31),
    the reduction a few microseconds, and the integers are the same."""
    in_zone = narrow_eq(zone_id[None, :], jnp.arange(num_zones)[:, None])
    return jnp.where(in_zone, counts[None, :], 0).sum(axis=1)


def spread_score(
    pod_has_selectors,
    counts,  # i64[N]: spread_counts
    zone_id,  # i32[N]
    num_zones,  # static int (vocab size incl. 0 == none)
    fit_mask,  # bool[N]
):
    """selector_spreading.go:84 CalculateSpreadPriority, from the
    counts on: maxCount and the zone aggregation run over FILTERED
    nodes only (nodes.Items is the filtered list). float32 math as in
    Go."""
    counts = jnp.where(fit_mask, counts, 0)
    max_count = counts.max(where=fit_mask, initial=0)

    # zone aggregation: zone 0 == "no zone" and never participates.
    # countsByZone exists for every zone seen among filtered nodes
    # (including zero counts), so haveZones == any filtered node is zoned.
    zcounts = zone_sums(counts, zone_id, num_zones)
    have_zones = jnp.any(fit_mask & (zone_id > 0))
    max_zone = jnp.where(jnp.arange(num_zones) > 0, zcounts, 0).max(initial=0)

    return spread_blend(pod_has_selectors, counts, max_count, zcounts,
                        max_zone, have_zones, zone_id)


def spread_blend(
    pod_has_selectors,
    counts,  # i64[N]: the masked counts
    max_count,  # their maximum over the nodes that fit
    zcounts,  # i64[Z]: zone_sums of them
    max_zone,  # the largest of the zoned ones
    have_zones,  # any fitting node is zoned
    zone_id,  # i32[N]
):
    """CalculateSpreadPriority's arithmetic, from the reductions on:
    the node share, the zone share and their blend in float32,
    truncated. The single-chip scorer reduces over its node axis, the
    sharded one (parallel/mesh._spread_sharded) over every shard's, and
    both score here, so that they cannot differ by an ulp."""
    f = jnp.full(counts.shape, jnp.float32(MAX_PRIORITY))
    f = jnp.where(
        max_count > 0,
        jnp.float32(MAX_PRIORITY)
        * ((max_count - counts).astype(jnp.float32) / max_count.astype(jnp.float32)),
        f,
    )
    node_zcount = zcounts[zone_id]
    # NO maxCountByZone>0 guard in the reference (selector_spreading.go:224):
    # 0/0 in float32 is NaN; Go's int(NaN) on amd64 is minInt64. We keep the
    # IEEE NaN through the blend and map it at the final conversion.
    zone_score = jnp.float32(MAX_PRIORITY) * (
        (max_zone - node_zcount).astype(jnp.float32) / max_zone.astype(jnp.float32)
    )
    # Go evaluates (1.0 - zoneWeighting) as an EXACT untyped-constant
    # expression rounded once to float32 — one ulp away from
    # f32(1) - f32(2/3). selector_spreading.go:226.
    blended = (f * jnp.float32(1.0 / 3.0)
               + jnp.float32(2.0 / 3.0) * zone_score)
    f = jnp.where(have_zones & (zone_id > 0), blended, f)
    # no selectors -> counts map empty -> maxCount 0 and zones skipped -> 10
    f = jnp.where(pod_has_selectors, f, jnp.float32(MAX_PRIORITY))
    return jnp.where(jnp.isnan(f), jnp.int64(-(2**63)), f.astype(jnp.int64))


def node_affinity_counts(
    pref_valid,  # bool[TP]
    pref_weight,  # i64[TP]
    pref_ops,
    pref_key,
    pref_set,
    pref_numkey,
    pref_num,  # [TP, R] programs
    label_kv,
    label_key,
    numval,
    set_table,
):
    """node_affinity.go:44-62: per-node sum of weights of matching
    preferred terms (the un-normalized counts)."""
    TP = pref_valid.shape[0]
    counts = jnp.zeros(label_kv.shape[:1], jnp.int64)
    for t in range(TP):
        m = _requirement_matrix(
            pref_ops[t],
            pref_key[t],
            pref_set[t],
            pref_numkey[t],
            pref_num[t],
            label_kv,
            label_key,
            numval,
            set_table,
        )
        counts = counts + jnp.where(m & pref_valid[t], pref_weight[t], 0)
    return counts


def normalize_counts_up(counts, max_count):
    """10 * count/max (float64, truncated); all-0 when max == 0
    (node_affinity.go:85-90)."""
    f = jnp.where(
        max_count > 0,
        10.0
        * (counts.astype(jnp.float64) / jnp.maximum(max_count, 1).astype(jnp.float64)),
        0.0,
    )
    return f.astype(jnp.int64)


def normalize_counts_down(counts, max_count):
    """(1 - count/max) * 10 (float64, truncated); all-10 when max == 0
    (taint_toleration.go:100-106)."""
    f = jnp.where(
        max_count > 0,
        (
            1.0
            - counts.astype(jnp.float64)
            / jnp.maximum(max_count, 1).astype(jnp.float64)
        )
        * 10.0,
        jnp.float64(MAX_PRIORITY),
    )
    return f.astype(jnp.int64)


def node_affinity_preferred(
    pref_valid,
    pref_weight,
    pref_ops,
    pref_key,
    pref_set,
    pref_numkey,
    pref_num,
    label_kv,
    label_key,
    numval,
    set_table,
    fit_mask,
):
    """node_affinity.go:44 CalculateNodeAffinityPriority: counts normalized
    by the max over FILTERED nodes."""
    counts = node_affinity_counts(
        pref_valid,
        pref_weight,
        pref_ops,
        pref_key,
        pref_set,
        pref_numkey,
        pref_num,
        label_kv,
        label_key,
        numval,
        set_table,
    )
    max_count = counts.max(where=fit_mask, initial=0)
    return normalize_counts_up(counts, max_count)


def taint_toleration(
    pod_intolerable_prefer,  # i32[TV] 0/1
    node_taint_count,  # i32[N, TV] multiplicities
    fit_mask,
):
    """taint_toleration.go:94: count PreferNoSchedule taints intolerable by
    the pod's PreferNoSchedule-filtered tolerations (per-LIST count — a
    node carrying duplicate taints counts each occurrence); normalize over
    filtered nodes; (1 - count/max) * 10 float64, truncated."""
    counts = taint_intolerable_counts(node_taint_count,
                                      pod_intolerable_prefer)
    max_count = counts.max(where=fit_mask, initial=0)
    return normalize_counts_down(counts, max_count)


def image_locality(node_img_size, pod_img_count):
    """priorities.go:149 ImageLocalityPriority -> i64 (N,).

    Per-container sum of the node-local size of its image (0 when absent),
    bucketed into 0..10 over the 23MB..1GB range (calculateScoreFromSize,
    priorities.go:192-207) with Go's integer division."""
    min_img = jnp.int64(23 * 1024 * 1024)
    max_img = jnp.int64(1000 * 1024 * 1024)
    if node_img_size.shape[1] == 0:
        return jnp.zeros((node_img_size.shape[0],), jnp.int64)
    sum_size = node_img_size @ pod_img_count  # i64 (N,)
    mid = 10 * (sum_size - min_img) // (max_img - min_img) + 1
    return jnp.where(
        sum_size < min_img,
        jnp.int64(0),
        jnp.where(sum_size >= max_img, jnp.int64(10), mid),
    )


def node_label(node_has_key, presence):
    """priorities.go:99 NewNodeLabelPriority -> i64 (N,): 10 where the
    key's presence matches the config, else 0 (no normalization)."""
    match = node_has_key if presence else ~node_has_key
    return jnp.where(match, jnp.int64(10), jnp.int64(0))
