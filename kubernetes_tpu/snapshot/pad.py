"""Shape bucketing for the daemon path.

jit compiles per array shape; a live scheduler sees constantly-varying
(num_nodes, num_pending) pairs, and each fresh pair would pay a full XLA
compile (seconds to tens of seconds per program). Bucketing both axes to
powers of two bounds the number of compilations at log(N)*log(P) while
keeping results bit-identical: padded pods are marked unschedulable (the
scan yields -1 and commits nothing, so the round-robin counter and all
carry state are untouched), and padded nodes can never fit (zero
allocatable, pod-count check fails — mesh._pad_snapshot's dummy-node
construction)."""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from kubernetes_tpu.snapshot.encode import ClusterSnapshot, PodBatch


def next_pow2(n: int, floor: int = 1) -> int:
    out = max(floor, 1)
    while out < n:
        out *= 2
    return out


def pad_batch(batch: PodBatch, target: int) -> PodBatch:
    """Pad the pod axis to `target` with unschedulable no-op pods."""
    p = batch.num_pods
    pad = target - p
    if pad <= 0:
        return batch
    fields = {}
    for f in dataclasses.fields(batch):
        v = getattr(batch, f.name)
        if f.name == "pod_keys":
            fields[f.name] = list(v) + [("", f"\x00pad-{i}") for i in range(pad)]
        elif isinstance(v, np.ndarray):
            widths = [(0, pad)] + [(0, 0)] * (v.ndim - 1)
            fill = -1 if f.name in ("host_req", "ip_ha_lt", "ip_hq_lt",
                                    "ip_fwd_lt", "vp_vz_zone", "vp_vz_region") else 0
            fields[f.name] = np.pad(v, widths, constant_values=fill)
        else:
            fields[f.name] = v
    out = dataclasses.replace(batch, **fields)
    out.unschedulable[p:] = True
    return out


def pad_to_buckets(
    snap: ClusterSnapshot, batch: PodBatch, node_floor: int = 1, pod_floor: int = 1
) -> Tuple[ClusterSnapshot, PodBatch, int, int]:
    """-> (snap, batch, real_nodes, real_pods) with both axes padded to
    power-of-two buckets."""
    from kubernetes_tpu.parallel.mesh import _pad_snapshot

    n, p = snap.num_nodes, batch.num_pods
    n_bucket = next_pow2(n, node_floor)
    p_bucket = next_pow2(p, pod_floor)
    if n_bucket > n:
        snap = _pad_snapshot(snap, n_bucket)
    batch = pad_batch(batch, p_bucket)
    return snap, batch, n, p
