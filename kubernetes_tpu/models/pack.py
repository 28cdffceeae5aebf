"""Single-transfer shipment of heterogeneous host arrays.

Every host->device transfer has a fixed cost (jax.device_put of a
pytree still puts one leaf at a time), and a cold scheduling wave ships
~75 small arrays — the static snapshot fields, the carry blocks, and
the pod row — which at one fixed cost each dominates daemon startup.
Packer.ship turns that into
ONE uint32 buffer transfer plus one jitted unpack program that bitcasts
and reshapes each field on device.  The unpack program is compiled once
per layout (field names/dtypes/shapes), so steady-state waves reuse it,
and layouts repeat across daemon restarts so the persistent compile
cache absorbs even that.

The buffer is made of 32-bit words because that is what the TPU holds
a 64-bit value in (a low and a high one): a field of 8-byte items ships
as its low words, then its high words, and unpacks as `high << 32 |
low`, which the chip's compiler turns into two copies; a field of
4-byte items is a bitcast of its words, and only fields of 1- and
2-byte items are widened from theirs. Neither the program's compile
time nor its temporaries then grow faster than the buffer. (Bitcasting
u8[n, 8] -> 64-bit out of a uint8 buffer took the v5e compiler 175 s for
the 17 MB pod rows of a 500-template wave, and u32[n, 2] -> 64-bit 2 GB
of temporaries, each pair padded to a tile's 128 lanes; the planes take
under a second and none. PERF.md, PR 28.) Host arrays are little-endian,
as every dtype.str in a layout says.

No reference counterpart: the Go scheduler's snapshot never leaves host
memory (schedulercache.GetNodeNameToInfoMap, cache.go:77); shipping it
to an accelerator is this framework's problem to solve.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.trace.profile import phase_timer


#: the buffer's item: one 32-bit word
WORD = np.dtype(np.uint32)


@jax.named_scope("unpack")
def _unpack(layout, buf):
    out = {}
    for name, dstr, shape, off, nb in layout:
        dt = np.dtype(dstr)
        as_bool = dt == np.bool_
        if nb == 0:  # a zero-size axis: materialize the empty array
            out[name] = jnp.zeros(shape, bool if as_bool else dt)
            continue
        first = off // WORD.itemsize
        # the barrier keeps a field's reshape below its own slice: moved
        # above it, a [n, 2] field relays the WHOLE buffer out with 2 of
        # a tile's 128 lanes in use (1 GB of temporaries for 17 MB)
        words = (nb + WORD.itemsize - 1) // WORD.itemsize
        seg = jax.lax.optimization_barrier(buf[first:first + words])
        if dt.itemsize > WORD.itemsize:  # 8-byte items: two planes
            n = nb // dt.itemsize
            low, high = seg[:n].astype(jnp.uint64), seg[n:].astype(jnp.uint64)
            arr = (high << jnp.uint64(32)) | low
            if dt != arr.dtype:
                arr = jax.lax.bitcast_convert_type(arr, dt)
        else:
            arr = seg if dt == WORD else jax.lax.bitcast_convert_type(
                seg, np.uint8 if as_bool else dt)
            if dt.itemsize < WORD.itemsize:
                # the last word may hold padding past the field's items
                arr = arr.reshape(-1)[:nb // dt.itemsize]
            if as_bool:
                arr = arr != 0
        out[name] = arr.reshape(shape)
    return out


def pack_arrays(arrays: dict):
    """-> (layout tuple, uint32 host buffer): the single-buffer form of a
    dict of numpy arrays. The layout is hashable (a jit cache key); the
    buffer unpacks on device via _unpack(layout, buf) — usable directly
    inside jit/shard_map bodies (the mesh wave passes pod rows this way
    so a run costs one replicated transfer, not one per field)."""
    items = sorted(arrays.items())
    layout = []
    off = 0
    for name, a in items:
        a = np.asarray(a)
        if a.dtype.itemsize not in (1, 2, 4, 8) or a.dtype.kind not in "biuf":
            raise TypeError(f"pack_arrays: field {name!r} has dtype "
                            f"{a.dtype}; items of 1, 2, 4 or 8 bytes only")
        # NB: ascontiguousarray promotes 0-d to (1,); keep the true
        # shape in the layout so scalars unpack as scalars
        shape = a.shape
        nb = a.nbytes
        layout.append((name, a.dtype.str, shape, off, nb))
        off += (nb + 7) & ~7  # 8-byte alignment: whole pairs of words
    buf = np.zeros(max(off // WORD.itemsize, 1), WORD)
    as_bytes = buf.view(np.uint8)
    for (name, _d, _s, o, nb), (_n, a) in zip(layout, items):
        if not nb:
            continue
        flat = np.ascontiguousarray(a).reshape(-1)
        if flat.dtype.itemsize > WORD.itemsize:
            halves = flat.view(WORD).reshape(-1, 2)  # (low, high) per item
            first = o // WORD.itemsize
            buf[first:first + flat.size] = halves[:, 0]
            buf[first + flat.size:first + 2 * flat.size] = halves[:, 1]
        else:
            as_bytes[o:o + nb] = flat.view(np.uint8)
    return tuple(layout), buf


def unpack(layout, buf):
    """Device-side inverse of pack_arrays (traceable)."""
    return _unpack(layout, buf)


class Packer:
    """Ships dicts of numpy arrays to the device in one transfer.

    ``h2d_bytes`` counts every byte shipped (class-wide total plus a
    per-instance tally) so the bench can report per-wave host->device
    transfer as a measured number — the single-chip counterpart of the
    mesh resident state's stats."""

    total_h2d_bytes = 0  # class-wide: all packers, process lifetime

    def __init__(self):
        self._unpack = {}
        self.h2d_bytes = 0

    def ship(self, arrays: dict) -> dict:
        """-> {name: device array}, one host->device transfer total."""
        # the host<->device "transfer" phase of the wire-path breakdown:
        # every wave's shipping funnels through here
        with phase_timer("transfer"):
            key, buf = pack_arrays(arrays)
            self.h2d_bytes += buf.nbytes
            Packer.total_h2d_bytes += buf.nbytes
            fn = self._unpack.get(key)
            if fn is None:
                pack_unpack = functools.partial(_unpack, key)
                # the program's name in a trace and a compile log:
                # `jit_pack_unpack`, where a bare partial has none
                pack_unpack.__name__ = "pack_unpack"
                fn = jax.jit(pack_unpack)
                self._unpack[key] = fn
            return fn(buf)
