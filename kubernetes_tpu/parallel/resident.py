"""Device-resident sharded cluster state for the mesh schedulers.

Until round 7 the mesh wave driver re-shipped the full node tables
host->device on EVERY schedule_backlog call: the static snapshot fields,
the carry blocks, and the per-run commit counts all rode `jnp.asarray`
at call time, so per-wave transfer was O(nodes) and the node axis could
not grow past ~5k without the upload dominating the wave.  This module
makes the sharded cluster state *live on device across waves*:

* **Placement** — every node-axis table is placed ONCE as a sharded
  array over ``Mesh((AXIS,))`` with an explicit ``NamedSharding``
  (node-axis leaves split across chips, vocab/count tables replicated).
  The pjit-compiled mesh programs declare the same shardings as
  ``in_shardings``/``out_shardings``, so steady-state dispatches touch
  resident buffers and ship nothing.

* **Mirrors** — a host numpy mirror of each resident array.  The wave
  driver's commits are folded into the mirrors with the exact integer
  arithmetic the device folds use (int64 adds, bitwise OR), so on the
  next wave "did the cluster change under us?" is a host-side
  ``array_equal`` against the fresh snapshot — zero transfer.  Carry
  channels the host cannot mirror (interpod/volume/service tables
  touched by impure runs or the scan fallback) are *invalidated* and
  resynced from the snapshot on the next wave instead of guessed at.

* **Scatter updates** — node add/remove/update inside the same padded
  node bucket ships ONLY the changed rows: one packed row buffer + a
  donated sharded scatter program (`_scatter_fn`) that updates the
  resident arrays in place.  A full rebuild happens only on topology
  change (padded node count, dtype/width, or field-set drift).

* **Donation** — the fold/scan programs donate their carry input
  (``donate_argnums``), so wave-to-wave commits mutate the resident
  buffers with zero realloc; the scatter program donates the arrays it
  updates.  ``stats`` counts every host->device byte so the O(pending
  pods) per-wave transfer claim is a measured number.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.ops.narrow import narrow_dtype
from kubernetes_tpu.trace.profile import device_wait

AXIS = "nodes"

#: carry leaf order — matches models/batch.BatchScheduler.initial_carry
CARRY_FIELDS = (
    "__res__", "port_mask", "class_count", "__last__",
    "ip_term_count", "ip_own_anti", "ip_rev_hard", "ip_rev_pref",
    "ip_rev_anti", "ip_spec_total",
    "vol_any", "vol_rw", "ebs_mask", "gce_mask",
    "svc_first_peer", "svc_peer_node_count", "svc_peer_total",
)

#: carry fields that invalidate together when a device fold the host
#: cannot mirror touches them (impure runs, the scan fallback)
CARRY_BLOCKS = {
    "ip": ("ip_term_count", "ip_own_anti", "ip_rev_hard", "ip_rev_pref",
           "ip_rev_anti", "ip_spec_total"),
    "vol": ("vol_any", "vol_rw", "ebs_mask", "gce_mask"),
    "svc": ("svc_first_peer", "svc_peer_node_count", "svc_peer_total"),
}

_PURE_CARRY = ("__res__", "port_mask", "class_count", "__last__")


def _pspecs():
    from jax.sharding import PartitionSpec as PSpec

    return PSpec


def carry_specs():
    """PartitionSpec per carry leaf (the single source the mesh programs
    and the resident placement share)."""
    PSpec = _pspecs()
    return (
        # stacked resources: node axis is axis 1
        PSpec(None, AXIS), PSpec(AXIS, None), PSpec(AXIS, None), PSpec(),
        # interpod count tables: replicated (domain-indexed, not node)
        PSpec(), PSpec(), PSpec(), PSpec(), PSpec(), PSpec(),
        # volume masks: node-axis sharded
        PSpec(AXIS, None), PSpec(AXIS, None), PSpec(AXIS, None),
        PSpec(AXIS, None),
        # service-group tables: replicated (small: groups x labels);
        # every shard applies identical commits with global indices
        PSpec(), PSpec(), PSpec(),
    )


#: static snapshot fields sharded along their first (node) axis
_STATIC_SHARDED_1D = frozenset((
    "alloc_mcpu", "alloc_mem", "alloc_gpu", "alloc_pods",
    "has_taints", "taint_bad", "mem_pressure", "zone_id",
    "ebs_bad", "gce_bad", "vz_zone", "vz_region", "vz_has",
))
#: static snapshot fields sharded along axis 0 with trailing vocab axes
_STATIC_SHARDED_2D = frozenset((
    "label_kv", "label_key", "numval", "taint_mask", "taint_count",
    "img_size",
))


def static_specs(keys) -> dict:
    """PartitionSpec per static snapshot field (node tables sharded,
    vocab/order tables replicated; nl_* are config-resolved node
    masks)."""
    PSpec = _pspecs()
    out = {}
    for k in keys:
        if k in _STATIC_SHARDED_1D or k.startswith("nl_"):
            out[k] = PSpec(AXIS)
        elif k in _STATIC_SHARDED_2D:
            out[k] = PSpec(AXIS, None)
        else:
            out[k] = PSpec()  # replicated vocab tables + global order
    return out


def host_static(config, snap) -> Dict[str, np.ndarray]:
    """The full static dict the mesh programs consume, as HOST arrays
    (snapshot fields + config-resolved node-label masks, with the
    selection order under its mesh-global name)."""
    from kubernetes_tpu.models.batch import BatchScheduler

    out = {f: np.asarray(getattr(snap, f))
           for f in BatchScheduler.STATIC_FIELDS}
    out.update(BatchScheduler.config_static(config, snap))
    out["name_desc_order_global"] = out.pop("name_desc_order")
    return out


def host_carry(snap, last_node_index: int) -> Dict[str, np.ndarray]:
    """The carry's seed values as HOST arrays, keyed by CARRY_FIELDS
    (__res__ is the stacked resource block, __last__ the round-robin
    counter)."""
    from kubernetes_tpu.snapshot.encode import RES_CARRY_FIELDS

    out = {"__res__": np.stack([np.asarray(getattr(snap, f))
                                for f in RES_CARRY_FIELDS]),
           "__last__": np.int64(last_node_index)}
    for f in CARRY_FIELDS:
        if f not in ("__res__", "__last__"):
            out[f] = np.asarray(getattr(snap, f))
    return out


def _eq(a: np.ndarray, b: np.ndarray) -> bool:
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if a.dtype.kind == "f":
        return bool(np.array_equal(a, b, equal_nan=True))
    return bool(np.array_equal(a, b))


def _node_axis(spec) -> Optional[int]:
    """Index of the sharded node axis in a PartitionSpec, None when the
    field is replicated."""
    for i, ent in enumerate(spec):
        if ent == AXIS:
            return i
    return None


def _scatter_fn(n_per_shard, names, axes, layout, arrays, buf):
    """Donated sharded row update: scatter `buf`'s packed rows into the
    resident arrays at the packed global node indices.  Collision-free
    by construction (the host dedups indices; off-shard entries fold a
    zero through commutative adds, never a racing set)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.models.pack import unpack as _unpack

    rows = _unpack(layout, buf)
    idx = rows["__idx__"]
    shard = jax.lax.axis_index(AXIS)
    offset = shard.astype(idx.dtype) * n_per_shard
    local = idx - offset
    valid = (idx >= 0) & (local >= 0) & (local < n_per_shard)
    safe = jnp.clip(local, 0, n_per_shard - 1)
    written = (
        jnp.zeros((n_per_shard,), jnp.int32)
        .at[safe].add(valid.astype(jnp.int32)) > 0
    )
    out = []
    for name, ax, arr in zip(names, axes, arrays):
        r = rows[name]  # (M, ...) with the node axis moved first
        a = jnp.moveaxis(arr, ax, 0)
        acc_dt = jnp.int32 if a.dtype == jnp.bool_ else a.dtype
        vexp = valid.reshape((valid.shape[0],) + (1,) * (r.ndim - 1))
        acc = (
            jnp.zeros(a.shape, acc_dt)
            .at[safe].add(jnp.where(vexp, r.astype(acc_dt), 0))
        )
        new = acc != 0 if a.dtype == jnp.bool_ else acc
        wexp = written.reshape((n_per_shard,) + (1,) * (a.ndim - 1))
        out.append(jnp.moveaxis(jnp.where(wexp, new, a), 0, ax))
    return tuple(out)


class ResidentClusterState:
    """Owns the device-resident sharded arrays + their host mirrors.

    One instance per MeshWaveScheduler.  ``sync`` is the per-wave entry:
    it returns (static dev dict, carry dev tuple) reusing resident
    buffers wherever the snapshot proves nothing changed, scattering
    changed rows, and rebuilding only on topology change.  The driver
    reports its commits through ``note_*`` so the mirrors stay exact.
    """

    #: changed-row fraction above which a field re-places wholesale
    #: instead of scattering (the packed-row shipment would approach the
    #: full table anyway)
    SCATTER_FRAC = 0.25
    #: the smallest row bucket a scatter pads to
    ROW_FLOOR = 64

    @classmethod
    def _row_bucket(cls, rows: int) -> int:
        from kubernetes_tpu.snapshot.pad import next_pow2

        return next_pow2(rows, floor=cls.ROW_FLOOR)

    def __init__(self, mesh):
        from kubernetes_tpu.analysis import races as _races

        self.mesh = mesh
        self._key = None  # topology signature (shapes/dtypes/field set)
        self._static: Dict[str, object] = {}
        self._carry: Optional[tuple] = None
        self._m_static: Dict[str, np.ndarray] = {}
        self._m_carry: Dict[str, np.ndarray] = {}
        self._last: int = 0
        self._valid = {b: True for b in CARRY_BLOCKS}
        self._scatter_jit: dict = {}
        self.stats = {
            "rebuilds": 0, "scatters": 0, "replaces": 0, "waves": 0,
            "h2d_bytes_total": 0, "wave_h2d_bytes": 0,
            "wave_table_bytes": 0,
        }
        # the resident mirrors are wave-driver-private state; tracking
        # them makes any cross-thread touch (a future async driver, a
        # stats scraper) a detector finding instead of a corrupt mirror
        _races.track(self, "parallel.ResidentClusterState")

    # -- accounting ----------------------------------------------------------

    def begin_wave(self) -> None:
        self.stats["waves"] += 1
        self.stats["wave_h2d_bytes"] = 0
        self.stats["wave_table_bytes"] = 0

    def count_h2d(self, nbytes: int, table: bool = False) -> None:
        self.stats["h2d_bytes_total"] += int(nbytes)
        self.stats["wave_h2d_bytes"] += int(nbytes)
        if table:
            self.stats["wave_table_bytes"] += int(nbytes)

    # -- sync ----------------------------------------------------------------

    def _placed_dtype(self, f: str, arr: np.ndarray) -> np.dtype:
        """Device-placement dtype for a field (ops/narrow):
        declared-narrow STATIC node tables place at their audited
        width; carry leaves stay full width (the device folds
        accumulate into them). The placed dtype is part of the topology
        signature, so a value outgrowing its narrow range rebuilds the
        table wider."""
        if f in CARRY_FIELDS:
            return arr.dtype
        return narrow_dtype(f, arr)

    def _placed(self, f: str, arr: np.ndarray) -> np.ndarray:
        dt = self._placed_dtype(f, arr)
        return arr.astype(dt, copy=False) if dt != arr.dtype else arr

    def _signature(self, hs: dict, hc: dict):
        return tuple(sorted(
            (name, a.shape, a.dtype.str,
             self._placed_dtype(name, a).str)
            for name, a in list(hs.items()) + list(hc.items())
            if isinstance(a, np.ndarray)
        ))

    def _alive(self) -> bool:
        if self._carry is None:
            return False
        for leaf in self._carry:
            if getattr(leaf, "is_deleted", lambda: False)():
                # a mid-wave exception stranded donated buffers
                return False
        return True

    def sync(self, config, snap, last_node_index: int,
             reuse: str = "auto"):
        """-> (static dev dict, carry dev tuple) for this wave.

        reuse: "auto"  — mirror-compare against the snapshot (daemon
                         path: trusts nothing, ships only deltas);
               "carry" — trust the resident carry outright (bench/soak
                         loops whose snapshot is the stale wave-0 view:
                         the resident carry IS the live truth there);
               "reship" — force a full re-placement (the r05-equivalent
                         baseline mode, kept for A/B measurement).
        """
        hs = host_static(config, snap)
        hc = host_carry(snap, last_node_index)
        key = self._signature(hs, hc)
        if reuse == "carry" and self._alive() and key == self._key:
            self._set_last(last_node_index)
            return dict(self._static), self._carry
        if reuse == "reship" or key != self._key or not self._alive():
            self._place_all(hs, hc, key)
            return dict(self._static), self._carry
        self._diff_sync(hs, hc)
        self._set_last(int(last_node_index))
        return dict(self._static), self._carry

    def _specs(self, static_keys):
        sspec = static_specs(static_keys)
        cspec = dict(zip(CARRY_FIELDS, carry_specs()))
        return sspec, cspec

    def _shardings(self, spec_by_name: dict) -> dict:
        from jax.sharding import NamedSharding

        return {k: NamedSharding(self.mesh, s)
                for k, s in spec_by_name.items()}

    def _place_all(self, hs: dict, hc: dict, key) -> None:
        import jax

        self.stats["rebuilds"] += 1
        sspec, cspec = self._specs(hs.keys())
        names = list(hs.keys()) + list(CARRY_FIELDS)
        # static tables place at their audited (possibly narrow) width;
        # mirrors below keep the full-width host arrays
        arrays = ([self._placed(n, hs[n]) for n in hs]
                  + [hc[f] for f in CARRY_FIELDS])
        shard = self._shardings(sspec)
        shard.update(self._shardings(cspec))
        placed = jax.device_put(arrays, [shard[n] for n in names])
        n_static = len(hs)
        self._static = dict(zip(hs.keys(), placed[:n_static]))
        self._carry = tuple(placed[n_static:])
        self._m_static = {k: np.array(v, copy=True) for k, v in hs.items()}
        self._m_carry = {
            f: (np.array(hc[f], copy=True)
                if isinstance(hc[f], np.ndarray) else hc[f])
            for f in CARRY_FIELDS if f != "__last__"
        }
        self._last = int(hc["__last__"])
        self._valid = {b: True for b in CARRY_BLOCKS}
        self._key = key
        for a in arrays:
            self.count_h2d(np.asarray(a).nbytes, table=True)

    def _block_of(self, field: str) -> Optional[str]:
        for b, members in CARRY_BLOCKS.items():
            if field in members:
                return b
        return None

    def _diff_sync(self, hs: dict, hc: dict) -> None:
        import jax

        sspec, cspec = self._specs(hs.keys())
        changed_static = [
            f for f in hs if not _eq(hs[f], self._m_static[f])
        ]
        changed_carry = []
        for f in CARRY_FIELDS:
            if f == "__last__":
                continue
            blk = self._block_of(f)
            if blk is not None and not self._valid[blk]:
                changed_carry.append(f)
            elif not _eq(hc[f], self._m_carry[f]):
                changed_carry.append(f)
        # breadcrumb for transfer forensics: WHAT forced bytes this wave
        self.stats["last_changed"] = tuple(changed_static + changed_carry)
        if not changed_static and not changed_carry:
            return
        scatter: List[Tuple[str, np.ndarray, object, int]] = []
        replace: List[Tuple[str, np.ndarray, object]] = []
        n_global = self._m_carry["port_mask"].shape[0]
        rows_union: Optional[np.ndarray] = None
        for f in changed_static + changed_carry:
            carry_f = f in CARRY_FIELDS
            spec = cspec[f] if carry_f else sspec[f]
            host = hc[f] if carry_f else hs[f]
            ax = _node_axis(spec)
            if ax is None or (carry_f and self._block_of(f) is not None
                              and not self._valid[self._block_of(f)]):
                # replicated, or an invalidated block: resync wholesale
                replace.append((f, host, spec))
                continue
            mirror = self._m_carry[f] if carry_f else self._m_static[f]
            diff = np.moveaxis(host, ax, 0) != np.moveaxis(mirror, ax, 0)
            if host.dtype.kind == "f":
                same_nan = (np.isnan(np.moveaxis(host, ax, 0))
                            & np.isnan(np.moveaxis(mirror, ax, 0)))
                diff = diff & ~same_nan
            rows = np.nonzero(
                diff.reshape(diff.shape[0], -1).any(axis=1))[0]
            scatter.append((f, host, spec, ax))
            rows_union = rows if rows_union is None else np.union1d(
                rows_union, rows)
        if rows_union is not None and (
            self._row_bucket(len(rows_union))
            > max(n_global * self.SCATTER_FRAC, self.ROW_FLOOR)
            or len(rows_union) > n_global * self.SCATTER_FRAC
        ):
            # (the bucket, not the rows alone: on a node axis that is
            # no power of two, 1,025 to 1,280 changed rows of 5,120
            # would scatter at the 2,048 bucket, which no warm-up
            # reaches, since a wave of 2,048 pods re-places)
            replace.extend((f, host, spec)
                           for f, host, spec, _ax in scatter)
            scatter = []
            rows_union = None
        if replace:
            self.stats["replaces"] += 1
            ships = [self._placed(f, h) for f, h, _s in replace]
            placed = jax.device_put(
                ships,
                [self._shardings({f: s})[f] for f, _h, s in replace],
            )
            for (f, host, _s), ship, dev in zip(replace, ships, placed):
                self._store(f, dev, host)
                self.count_h2d(ship.nbytes, table=True)
        if scatter:
            self._scatter(scatter, rows_union)

    def _store(self, f: str, dev, host: np.ndarray) -> None:
        if f in CARRY_FIELDS:
            i = CARRY_FIELDS.index(f)
            carry = list(self._carry)
            carry[i] = dev
            self._carry = tuple(carry)
            self._m_carry[f] = np.array(host, copy=True)
            blk = self._block_of(f)
            if blk is not None:
                self._valid[blk] = True
        else:
            self._static[f] = dev
            self._m_static[f] = np.array(host, copy=True)

    def _scatter(self, fields, rows: np.ndarray) -> None:
        """Ship ONLY the changed rows: one packed buffer + one donated
        sharded scatter dispatch updating every changed field."""
        import jax

        from kubernetes_tpu.models.pack import pack_arrays

        self.stats["scatters"] += 1
        M = self._row_bucket(len(rows))
        idx = np.full(M, -1, np.int64)
        idx[: len(rows)] = rows
        packed = {"__idx__": idx}
        names, axes, specs, arrays, hosts = [], [], [], [], []
        for f, host, spec, ax in fields:
            # scatter rows ship at the resident array's placed dtype
            # (identical to _placed_dtype(host) here — a width change
            # changes the signature and rebuilds before _diff_sync)
            r = np.moveaxis(host, ax, 0)[rows]
            pdt = self._placed_dtype(f, host)
            if pdt != r.dtype:
                r = r.astype(pdt)
            pad = np.zeros((M - len(rows),) + r.shape[1:], r.dtype)
            packed[f] = np.concatenate([r, pad]) if M > len(rows) else r
            names.append(f)
            axes.append(ax)
            specs.append(spec)
            arrays.append(self._carry[CARRY_FIELDS.index(f)]
                          if f in CARRY_FIELDS else self._static[f])
            hosts.append(host)
        layout, buf = pack_arrays(packed)
        n_per_shard = (self._m_carry["port_mask"].shape[0]
                       // self.mesh.devices.size)
        run = self._scatter_program(
            tuple(names), tuple(axes), tuple(specs), layout,
            tuple(a.shape for a in hosts), n_per_shard,
        )
        updated = run(tuple(arrays), buf)
        # donated dispatches drain before their aliased buffers can be
        # re-donated (see mesh.MeshWaveScheduler._fold)
        with device_wait():
            jax.block_until_ready(updated)
        for (f, host, _s, _ax), dev in zip(fields, updated):
            self._store(f, dev, host)
        self.count_h2d(buf.nbytes, table=True)

    def _scatter_program(self, names, axes, specs, layout, shapes,
                         n_per_shard, donate=True):
        """The pjit row-scatter program for one (field set, row bucket,
        shape) class — donated (in-place update of the resident
        arrays).  Shared with analysis/programs so the audited donation
        contract covers the exact dispatched program."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PSpec

        jkey = (names, axes, layout, shapes, n_per_shard, donate)
        run = self._scatter_jit.get(jkey)
        if run is None:
            body = jax.named_scope("scatter")(functools.partial(
                _scatter_fn, n_per_shard, names, axes, layout,
            ))
            arr_sh = tuple(NamedSharding(self.mesh, s) for s in specs)
            mesh_scatter = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(tuple(specs), PSpec()),
                out_specs=tuple(specs),
                check_vma=False,
            )
            mesh_scatter.__name__ = "mesh_scatter"
            run = jax.jit(
                mesh_scatter,
                in_shardings=(arr_sh, NamedSharding(self.mesh, PSpec())),
                out_shardings=arr_sh,
                donate_argnums=(0,) if donate else (),
            )
            self._scatter_jit[jkey] = run
        return run

    # -- mirror maintenance (the driver's commit reports) --------------------

    def _set_last(self, last: int) -> None:
        import jax

        if int(last) == self._last:
            return
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PSpec

        dev = jax.device_put(
            np.int64(last), NamedSharding(self.mesh, PSpec()))
        self._store_last(dev, int(last))
        self.count_h2d(8)

    def _store_last(self, dev, last: int) -> None:
        i = CARRY_FIELDS.index("__last__")
        carry = list(self._carry)
        carry[i] = dev
        self._carry = tuple(carry)
        self._last = int(last)

    def note_commit(self, pod: Dict[str, np.ndarray],
                    counts: np.ndarray) -> None:
        """Fold one run's commits into the pure-channel mirrors with the
        device fold's exact arithmetic."""
        from kubernetes_tpu.models.hosttab import commit_vector

        res = self._m_carry["__res__"]
        res += np.outer(commit_vector(pod), counts)
        touched = counts > 0
        pm = np.asarray(pod["port_mask"])
        if pm.any():
            port = self._m_carry["port_mask"]
            port[touched] |= pm[None, :]
        cls = int(pod["class_id"])
        cc = self._m_carry["class_count"]
        if cls < cc.shape[1]:
            cc[:, cls] += counts.astype(cc.dtype)
        self._last += int(counts.sum())

    def note_scan(self, pods: Sequence[Dict[str, np.ndarray]],
                  chosen: Sequence[int]) -> None:
        """Fold the scan fallback's per-pod commits (host-visible via
        the returned chosen ids) into the pure-channel mirrors."""
        from kubernetes_tpu.models.hosttab import commit_vector

        res = self._m_carry["__res__"]
        port = self._m_carry["port_mask"]
        cc = self._m_carry["class_count"]
        n = port.shape[0]
        for pod, c in zip(pods, chosen):
            self._last += 1 if 0 <= c < n else 0
            if not (0 <= c < n):
                continue
            res[:, c] += commit_vector(pod)
            pm = np.asarray(pod["port_mask"])
            if pm.any():
                port[c] |= pm
            cls = int(pod["class_id"])
            if cls < cc.shape[1]:
                cc[c, cls] += 1

    def invalidate(self, *blocks: str) -> None:
        """Mark carry blocks the host cannot mirror as unknown: the next
        wave resyncs them from the snapshot."""
        for b in blocks:
            if self._valid.get(b, False) and self._m_carry.get(
                    CARRY_BLOCKS[b][0]) is not None:
                self._valid[b] = False

    def set_carry(self, carry: tuple) -> None:
        """The driver threads the post-fold carry back in after every
        dispatch (donation deleted the previous leaves)."""
        self._carry = carry

    def finish_wave(self, carry: tuple, last: int) -> None:
        self._carry = carry
        self._last = int(last)

    def usage(self) -> np.ndarray:
        """The resource block at this instant (the grouped replay's
        `usage` input — exact, so the mesh group probe need not ship the
        carry's res block device->host)."""
        return np.array(self._m_carry["__res__"], copy=True)

    def invalidate_all(self) -> None:
        """Drop residency entirely (tests; provenance change)."""
        self._key = None
        self._carry = None
        self._static = {}
