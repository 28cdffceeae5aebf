"""Device kernels for the volume predicates (see snapshot/volumes.py for
the compilation). All bitset intersections over u32 words; popcounts for
the max-PD distinct-volume counts. Zero-width when the workload has no
volumes — XLA compiles the subsystem away."""

from __future__ import annotations

import jax.numpy as jnp

from kubernetes_tpu.ops.narrow import narrow_eq


def _intersects(a, b):
    """Any shared bit between (..., W) masks."""
    return (a & b).any(axis=-1) if a.shape[-1] else jnp.zeros(b.shape[:-1], bool)


def _popcount(mask):
    """(..., W) u32 -> (...) i64 bit count."""
    if not mask.shape[-1]:
        return jnp.zeros(mask.shape[:-1], jnp.int64)
    return (
        jnp.bitwise_count(mask).astype(jnp.int64).sum(axis=-1)
        if hasattr(jnp, "bitwise_count")
        else _popcount_manual(mask)
    )


def _popcount_manual(mask):
    x = mask.astype(jnp.uint32)
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return ((x * 0x01010101) >> 24).astype(jnp.int64).sum(axis=-1)


def no_disk_conflict(pod_rw, pod_ro, node_any, node_rw):
    """predicates.go:105 NoDiskConflict -> bool (N,). A writable use
    conflicts with any use; a read-only GCE use conflicts with a
    writable use."""
    return ~(_intersects(pod_rw, node_any) | _intersects(pod_ro, node_rw))


def max_pd_count(pod_mask, pod_bad, pod_has_new, node_mask, node_bad, max_volumes):
    """predicates.go:137 MaxPDVolumeCountChecker -> bool (N,)."""
    if not pod_mask.shape[-1]:
        return jnp.ones(node_bad.shape, bool) & ~pod_bad
    existing = _popcount(node_mask)
    new = _popcount(pod_mask & ~node_mask)
    ok = (~node_bad) & (existing + new <= jnp.int64(max_volumes))
    return ~pod_bad & (~pod_has_new | ok)


def volume_zone(
    pod_zone, pod_region, pod_fail, node_zone, node_region, node_has
):
    """predicates.go:271 VolumeZoneChecker -> bool (N,). Nodes without any
    zone/region label always pass (constraints empty)."""
    match = (
        ~pod_fail
        & ((pod_zone < 0) | narrow_eq(node_zone, pod_zone))
        & ((pod_region < 0) | narrow_eq(node_region, pod_region))
    )
    return ~node_has | match
