"""Narrow device placement of node tables.

The node-axis tables the device sweeps every wave ride full-width
int32/int64 even when their values are tiny vocab ids or multiplicity
counts. This module is the placement-time width audit: for each table
on the DECLARED narrow list it measures the value range and picks the
narrowest signed dtype that holds every entry, and the drivers place
THAT copy on device. Host mirrors always keep full width — narrowing
is a device-placement decision, never an encoder change — so the
diff/scatter machinery and the serial-oracle replay are untouched.

Vocab growth past a narrow range needs no special case: the chosen
dtype is part of the placement signature (resident._signature /
WaveScheduler's per-field cache key), so the first sync after an
out-of-range value lands rebuilds the table at the wider dtype.

Narrowing is LOSSLESS by construction:
  * every narrowed table is consumed by equality compares, gathers /
    scatter indices, or 0/1-weighted contractions, and integer
    promotion of in-range values preserves all of them;
  * compare sites use narrow_eq below, which casts the SMALL (pod-side)
    comparand down to the table dtype with an explicit wide-side range
    guard — the big table is never upcast (that upcast is exactly the
    bandwidth the shrink exists to save, and the jaxpr auditor's dtype
    contract makes it a CI failure).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# node tables eligible for dtype shrink. label_kv/label_key/taint_mask
# are u32 BITSETS (already dense — a dtype change would change their
# semantics) and the alloc_*/req_* resource tables hold byte counts
# that genuinely need 64 bits; the narrow wins are the vocab-id and
# multiplicity tables below.
NARROWABLE = ("taint_count", "zone_id", "vz_zone", "vz_region")

_NARROW_STEPS = (np.int8, np.int16)


def narrow_dtype(name: str, arr: np.ndarray) -> np.dtype:
    """The placement-time width audit: narrowest signed dtype holding
    every value of this table (int8 -> int16 -> keep). Non-narrowable
    names and non-int32/int64 tables pass through unchanged."""
    if name not in NARROWABLE or arr.dtype.kind != "i" \
            or arr.dtype.itemsize <= 2:
        return arr.dtype
    if arr.size == 0:
        return np.dtype(np.int8)
    lo = int(arr.min())
    hi = int(arr.max())
    for dt in _NARROW_STEPS:
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return np.dtype(dt)
    return arr.dtype


def narrow_eq(table, value):
    """Equality against a possibly-narrowed node table without
    upcasting it: the (small) comparand casts DOWN to the table dtype,
    guarded by a wide-side range check so out-of-vocab values can
    never alias into the narrow range. Exact for all inputs."""
    value = jnp.asarray(value)
    if table.dtype == value.dtype:
        return table == value
    info = jnp.iinfo(table.dtype)
    return (
        (table == value.astype(table.dtype))
        & (value >= info.min)
        & (value <= info.max)
    )


def narrow_matvec(table, vec, out_dtype):
    """table[N, K] @ vec[K] without widening the table: the comparand
    vector casts down to the table dtype (callers guarantee its values
    fit — e.g. 0/1 toleration indicators) and the contraction
    accumulates in `out_dtype` via dot_general's preferred element
    type. Matches the int32 matmul bit-for-bit for in-range values."""
    return jax.lax.dot_general(
        table, vec.astype(table.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.dtype(out_dtype),
    )
