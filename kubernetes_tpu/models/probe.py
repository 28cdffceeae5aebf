"""Wave probe: one device program that tabulates everything a run of
identical pods needs, so the host replay can reproduce the serial pick
sequence without 50k serial device steps.

For a run of identical pending pods (same encoded feature row — see
snapshot/encode.pod_feature_key) scheduled back-to-back, every
scheduling-relevant quantity is one of:

  * static during the run (node labels/taints/affinity matching, volume
    zone, image locality, host ports vs. the frozen mask, inter-pod
    state when the pod owns no affinity terms), or
  * a per-node function of j = how many of the run's pods have already
    been committed to that node (PodFitsResources, LeastRequested,
    BalancedResourceAllocation — the carry contribution of j identical
    commits is j * the pod's commit vector), or
  * a normalization over the live fit set / live counts that changes
    only on rare events (SelectorSpread's maxCount, the
    NodeAffinity/TaintToleration/InterPod normalizers) — recomputed by
    the replay when those events fire.

The probe evaluates the static parts and the j-tables in ONE jitted
program reusing the exact scan ops (models/batch.fit_mask and ops/*),
so every number the replay consumes is produced by the same kernels the
serial scan would have used.  Reference analogue: this is the hot loop
of generic_scheduler.go:72-135 factored into "what changes per pod" vs
"what doesn't" — a restructuring the serial Go scheduler never needed
because its per-pod cost was already CPU-bound.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.batch import (
    BALANCED_ALLOCATION,
    EQUAL,
    GENERAL_PREDICATES,
    IMAGE_LOCALITY,
    INTER_POD_AFFINITY,
    LEAST_REQUESTED,
    NODE_AFFINITY,
    NODE_LABEL_PRIORITY,
    SELECTOR_SPREAD,
    SERVICE_ANTI_AFFINITY,
    TAINT_TOLERATION,
    MATCH_INTER_POD_AFFINITY,
    SchedulerConfig,
    fit_mask,
    interpod_carry_tables,
    wants_ports,
    wants_resources,
)
from kubernetes_tpu.ops import interpod as IP
from kubernetes_tpu.ops import predicates as P
from kubernetes_tpu.ops import priorities as R
from kubernetes_tpu.trace.profile import device_wait


@dataclass
class RunTables:
    """Host-side tables for one run (all numpy; see models/replay.py)."""

    fit_static: np.ndarray  # bool[N]
    res_fit: np.ndarray  # bool[J, N]
    tab: np.ndarray  # i64[J, N] weighted LeastRequested+Balanced
    static_add: np.ndarray  # i64[N] Equal/ImageLocality/NodeLabel sum
    # SelectorSpread (None when not configured)
    w_spread: int
    spread_base: Optional[np.ndarray]  # i64[N]
    spread_selfmatch: bool
    has_selectors: bool
    # NodeAffinity preferred (unnormalized weight counts)
    w_na: int
    na_counts: Optional[np.ndarray]  # i64[N]
    # TaintToleration (unnormalized intolerable counts)
    w_tt: int
    tt_counts: Optional[np.ndarray]  # i64[N]
    # InterPodAffinity (unnormalized totals; static because the pod owns
    # no terms — the eligibility gate guarantees it)
    w_ip: int
    ip_totals: Optional[np.ndarray]  # i64[N]
    # zone blend (selector_spreading.go:221-228): zone ids are static
    # per run, so they ride host-side; the replay recomputes the
    # per-zone aggregation over the live fit set per pick. zone_id is
    # None on unzoned clusters (the plain float32 branch).
    zone_id: Optional[np.ndarray] = None  # i32[N]; 0 == no zone
    num_zones: int = 1
    # ServiceAntiAffinity (policy configs): per-pick renormalized spread
    # over values of a node label; counts/total grow with the run's own
    # member commits. None when not configured / run not a member.
    w_saa: int = 0
    saa_counts: Optional[np.ndarray] = None  # i64[N] base peer counts
    saa_total: int = 0  # base peer total (pre-run)
    saa_lbl_val: Optional[np.ndarray] = None  # i32[N]; -1 unlabeled
    saa_num_values: int = 0
    saa_member: bool = False  # run pods are peers of their own group
    # ServiceAffinity first-pick pin: when the run's group had NO first
    # peer at probe time, the first commit pins the unresolved config
    # labels to the picked node's values; rows are lbl_val per
    # unresolved label. None = no refinement (pinned already / fixed /
    # no group / predicate absent).
    sa_refine_rows: Optional[np.ndarray] = None  # i32[R, N]
    # the run's SA dynamics exceed what the tables model (a label left
    # unresolved by BOTH svc_fixed and the current first peer's node
    # can re-pin mid-run via the min-ord rule): route to the scan
    sa_bail: bool = False


def _probe_rows(config: SchedulerConfig, num_zones: int, num_values: int,
                J: int, static, carry, pod, views=None):
    """The probe body: -> (stk i64[N_STK_ROWS, N] header rows,
    tab i64[J, N] weighted LR+BA j-table). Callers that consume only
    `stk` (the grouped header probe, the device replay) leave `tab`
    dead and XLA eliminates it. `views` are the carry's inter-pod views
    (ops/interpod.Views) where the caller carries them (the device
    replay's run-slot loop); absent, they are gathered from the carry's
    tables here."""
    (
        res,
        port_mask,
        class_count,
        last_idx,
        ip_term_count,
        ip_own_anti,
        ip_rev_hard,
        ip_rev_pref,
        ip_rev_anti,
        ip_spec_total,
        vol_any,
        vol_rw,
        ebs_mask,
        gce_mask,
        svc_first_peer,
        svc_peer_node_count,
        svc_peer_total,
    ) = carry
    req_mcpu, req_mem, req_gpu, nz_mcpu, nz_mem, pod_count = res
    N = req_mcpu.shape[0]

    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)
    cnt_lt = own_lt = None
    if views is not None:
        cnt_lt, own_lt = views.cnt_lt, views.own_lt
    elif want_ip_pred or want_ip_prio:
        cnt_lt = interpod_carry_tables(static, ip_term_count, N)

    fit_static = jnp.broadcast_to(
        # a minimal config (e.g. PodFitsResources-only) leaves no
        # node-axis predicate here and the mask collapses to a scalar
        fit_mask(config, static, carry, pod, cnt_lt,
                 include_resources=False, own_lt=own_lt),
        (N,),
    )

    j = jnp.arange(J, dtype=jnp.int64)[:, None]  # (J, 1)
    if wants_resources(config):
        res_fit = P.pod_fits_resources(
            pod["req_mcpu"],
            pod["req_mem"],
            pod["req_gpu"],
            pod["zero_req"],
            static["alloc_mcpu"],
            static["alloc_mem"],
            static["alloc_gpu"],
            static["alloc_pods"],
            req_mcpu[None, :] + j * pod["commit_mcpu"],
            req_mem[None, :] + j * pod["commit_mem"],
            req_gpu[None, :] + j * pod["commit_gpu"],
            pod_count[None, :] + j,
        )
    else:
        res_fit = jnp.ones((J, N), bool)
    if wants_ports(config):
        # host-port self-conflict: once one copy holds the pod's host
        # ports on a node, no further copy fits there (predicates.go:574)
        has_ports = (pod["port_mask"] != 0).any()
        res_fit = res_fit & ((j == 0) | ~has_ports)

    nzj_cpu = nz_mcpu[None, :] + j * pod["nz_mcpu"]
    nzj_mem = nz_mem[None, :] + j * pod["nz_mem"]
    tab = jnp.zeros((J, N), jnp.int64)
    static_add = jnp.zeros((N,), jnp.int64)
    zeros = jnp.zeros((N,), jnp.int64)
    stk_rows = {"spread_base": zeros, "spread_selfmatch": zeros,
                "na_counts": zeros, "tt_counts": zeros, "ip_totals": zeros}
    for name, weight in config.priorities:
        if name in (LEAST_REQUESTED, BALANCED_ALLOCATION):
            score = (R.least_requested if name == LEAST_REQUESTED
                     else R.balanced_resource_allocation)(
                pod["nz_mcpu"], pod["nz_mem"], nzj_cpu, nzj_mem,
                static["alloc_mcpu"], static["alloc_mem"],
            )
            tab = tab + jnp.int64(weight) * score
        elif name == SELECTOR_SPREAD:
            # unmasked base counts; the replay applies the fit mask and
            # maxCount normalization per pick (ops/priorities.py:62)
            stk_rows["spread_base"] = R.spread_counts(
                class_count, pod["spread_match"])
            stk_rows["spread_selfmatch"] = jnp.broadcast_to(
                (pod["spread_match"][pod["class_id"]] > 0).astype(jnp.int64),
                (N,),
            )
        elif name == NODE_AFFINITY:
            stk_rows["na_counts"] = R.node_affinity_counts(
                pod["pref_valid"], pod["pref_weight"], pod["pref_ops"],
                pod["pref_key"], pod["pref_set"], pod["pref_numkey"],
                pod["pref_num"], static["label_kv"], static["label_key"],
                static["numval"], static["set_table"],
            )
        elif name == TAINT_TOLERATION:
            stk_rows["tt_counts"] = R.taint_intolerable_counts(
                static["taint_count"], pod["intolerable_prefer"]
            )
        elif name == INTER_POD_AFFINITY:
            stk_rows["ip_totals"] = IP.interpod_totals(
                cnt_lt,
                *(views[2:] if views is not None else (
                    IP.gather_lt(table, static["ip_u_topo"],
                                 static["ip_topo_dom"], static["ip_lt_u"],
                                 static["ip_lt_sign"])
                    for table in (ip_rev_hard, ip_rev_pref, ip_rev_anti))),
                static["ip_lt_spec"], pod["ip_match_spec"],
                pod["ip_fwd_lt"], pod["ip_fwd_w"],
                config.hard_pod_affinity_weight, N,
            )
        elif name == EQUAL:
            static_add = static_add + jnp.int64(weight) * R.equal(N)
        elif name == IMAGE_LOCALITY:
            static_add = static_add + jnp.int64(weight) * R.image_locality(
                static["img_size"], pod["img_count"]
            )
        elif isinstance(name, tuple) and name[0] == NODE_LABEL_PRIORITY:
            static_add = static_add + jnp.int64(weight) * R.node_label(
                static[f"nl_prio_{name[1]}"], name[2]
            )
        elif isinstance(name, tuple) and name[0] == SERVICE_ANTI_AFFINITY:
            pass  # per-pick renormalization: the replay consumes the
            # svc rows below (base counts/total + host lbl_val)
        else:
            raise ValueError(f"unknown priority {name!r}")
    # service-group state rows (zero when no SA/SAA config: G == 0).
    # row svc_counts: the run's group's per-node peer counts;
    # row svc_total: its peer total (broadcast);
    # row svc_pin: the group's first-peer order index (broadcast;
    # ORD_NONE means the run's first commit will pin)
    from kubernetes_tpu.snapshot.services import ORD_NONE as _ORD_NONE

    G = svc_first_peer.shape[0]
    if G:
        g = jnp.clip(pod["svc_group"], 0, G - 1)
        has_group = pod["svc_group"] >= 0
        svc_counts = jnp.where(
            has_group, svc_peer_node_count[g], 0
        ).astype(jnp.int64)
        svc_counts = jnp.broadcast_to(svc_counts, (N,))
        svc_total = jnp.broadcast_to(
            jnp.where(has_group, svc_peer_total[g], 0).astype(jnp.int64),
            (N,),
        )
        svc_pin = jnp.broadcast_to(
            jnp.where(
                has_group, svc_first_peer[g], jnp.int32(_ORD_NONE)
            ).astype(jnp.int64),
            (N,),
        )
    else:
        svc_counts = jnp.zeros((N,), jnp.int64)
        svc_total = jnp.zeros((N,), jnp.int64)
        svc_pin = jnp.full((N,), jnp.int64(_ORD_NONE))
    # A dispatch and a device->host transfer each have a fixed cost
    # that dwarfs these few KB, so the probe's entire product ships as
    # ONE i64 array:
    #   rows 0..N_STK_ROWS-1: the 1-D tables (fit_static, fit frontier,
    #     static_add, spread/na/tt/ip, svc counts/total/pin), and
    #   rows N_STK_ROWS+: the [J, N] j-table in the narrowest safe dtype
    #     (scores are bounded by 10 * the summed LR/BA weights),
    #     bitcast-packed into i64 words along the j axis.
    # res_fit itself never ships: per-node resource fit is monotone
    # non-increasing in j (commits only consume capacity, and the
    # host-port self-conflict kills j>0 outright), so its sum over j —
    # the fit frontier — reconstructs it host-side as j < frontier[n].
    frontier = res_fit.sum(0, dtype=jnp.int64)
    stk = jnp.stack([
        fit_static.astype(jnp.int64),
        frontier,
        static_add,
        stk_rows["spread_base"],
        stk_rows["spread_selfmatch"],
        stk_rows["na_counts"],
        stk_rows["tt_counts"],
        stk_rows["ip_totals"],
        svc_counts,
        svc_total,
        svc_pin,
    ])
    return stk, tab


@jax.named_scope("probe")
def _probe_fn(config: SchedulerConfig, num_zones: int, num_values: int, J: int,
              static, carry, pod):
    stk, tab = _probe_rows(config, num_zones, num_values, J, static, carry,
                           pod)
    N = stk.shape[1]
    dt = _tab_dtype(config)
    k = 8 // np.dtype(dt).itemsize  # J is pow2 >= 16, always divisible
    tabp = tab.astype(dt).reshape(J // k, k, N).swapaxes(1, 2)
    tabw = jax.lax.bitcast_convert_type(tabp, jnp.int64)  # (J//k, N)
    return {"packed": jnp.concatenate([stk, tabw], axis=0)}


@jax.named_scope("probe")
def _group_probe_fn(config: SchedulerConfig, num_zones: int, num_values: int,
                    G: int, layout, static, carry, group_buf):
    """Header-row probe for G stacked run representatives in one traced
    program: vmap of _probe_rows (J=1 — the host rebuilds the resource
    j-axis itself from the shipped resource block, see models/hosttab).
    Output is ONE array so the whole product crosses the device->host
    boundary in one transfer: rows [0, G*N_STK_ROWS) are the per-run
    headers, the final 6 rows are the live resource block (the carry's
    usage at probe time — the base the host j-tables start from)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    pods = _unpack_pod(layout, group_buf)

    def one(pod):
        stk, _tab = _probe_rows(config, num_zones, num_values, 1, static,
                                carry, pod)
        return stk

    stk = jax.vmap(one)(pods)  # (G, N_STK_ROWS, N)
    N = stk.shape[-1]
    return jnp.concatenate([stk.reshape(G * N_STK_ROWS, N), carry[0]],
                           axis=0)


N_STK_ROWS = 11  # header rows before the packed j-table words


def _tab_dtype(config: SchedulerConfig):
    """Narrowest dtype holding every possible j-table score: each
    configured LR/BA priority contributes weight * [0, 10]."""
    bound = 10 * sum(
        abs(w) for n, w in config.priorities
        if n in (LEAST_REQUESTED, BALANCED_ALLOCATION)
    )
    return (np.int8 if bound <= 127
            else np.int16 if bound <= 32767 else np.int32)


class WaveProbe:
    """Compiles/caches the probe program per (config, J); emits RunTables."""

    def __init__(self, config: Optional[SchedulerConfig] = None):
        self.config = config or SchedulerConfig()
        self._jitted = {}

    def _probe_partial(self, num_zones: int, num_values: int, J: int):
        return functools.partial(
            _probe_fn, self.config, num_zones, num_values, J)

    def _compiled(self, num_zones: int, num_values: int, J: int):
        key = (num_zones, num_values, J)
        fn = self._jitted.get(key)
        if fn is None:
            wave_probe = self._probe_partial(num_zones, num_values, J)
            wave_probe.__name__ = "wave_probe"  # the program: jit_wave_probe
            fn = jax.jit(wave_probe)
            self._jitted[key] = fn
        return fn

    def _compiled_fused(self, num_zones: int, num_values: int, J: int,
                        layout, apply_fn):
        """ONE program that (a) unpacks the NEXT run's pod row from its
        packed buffer, (b) folds the PREVIOUS run's commits into the
        carry via apply_fn, and (c) probes the next run against the
        updated carry. Every dispatch has a fixed cost, so fusing
        ship+apply+probe cuts a multi-template backlog's per-run cost
        to one dispatch + one transfer."""
        key = ("fused", num_zones, num_values, J, layout)
        fn = self._jitted.get(key)
        if fn is None:
            from kubernetes_tpu.models.pack import unpack as _unpack_pod

            probe_fn = self._probe_partial(num_zones, num_values, J)

            def probe_fused_prev(static, carry, prev_buf, counts,
                                 next_buf):
                # prev/next share the backlog's layout (vocab widths
                # are backlog-constant)
                if prev_buf is not None:
                    prev_pod = _unpack_pod(layout, prev_buf)
                    carry = apply_fn(static, carry, prev_pod, counts)
                next_pod = _unpack_pod(layout, next_buf)
                packed = probe_fn(static, carry, next_pod)
                return carry, packed

            def probe_fused_same(static, carry, buf, counts):
                # the dominant shape: a run re-probing ITSELF past the
                # table horizon folds its own previous counts — unpack
                # the one buffer once (and ship it once)
                pod = _unpack_pod(layout, buf)
                carry = apply_fn(static, carry, pod, counts)
                packed = probe_fn(static, carry, pod)
                return carry, packed

            def probe_fused_first(static, carry, next_buf):
                # variant without the apply fold (the backlog's first
                # probe): prev_buf=None burns a separate trace
                return probe_fused_prev(static, carry, None, None,
                                        next_buf)

            fn = {
                "prev": jax.jit(probe_fused_prev),
                "same": jax.jit(probe_fused_same),
                "first": jax.jit(probe_fused_first),
            }
            self._jitted[key] = fn
        return fn

    def probe_fused(self, static, carry, prev_buf, counts, next_buf,
                    num_zones: int, num_values: int, J: int,
                    rows: Optional[int], layout, apply_fn,
                    has_selectors: bool,
                    zone_id: Optional[np.ndarray] = None,
                    self_anti_veto: Optional[np.ndarray] = None,
                    svc_ctx: Optional[dict] = None):
        """-> (new_carry, RunTables). prev_buf/counts None on the
        backlog's first probe (nothing to fold yet). One fused
        apply+probe program and the one device->host transfer of its
        packed product."""
        fns = self._compiled_fused(num_zones, num_values, J, layout,
                                   apply_fn)
        if prev_buf is None:
            carry2, raw = fns["first"](static, carry, next_buf)
        elif prev_buf is next_buf:
            carry2, raw = fns["same"](static, carry, next_buf, counts)
        else:
            carry2, raw = fns["prev"](static, carry, prev_buf, counts,
                                      next_buf)
        if rows is None:
            rows = J
        rows = max(1, min(rows, J))
        with device_wait():
            arr = np.ascontiguousarray(jax.device_get(raw["packed"]))
        return carry2, tables_from_packed(
            self.config, arr, num_zones, J, rows,
            has_selectors=has_selectors, zone_id=zone_id,
            self_anti_veto=self_anti_veto, svc_ctx=svc_ctx,
        )

    def _compiled_group(self, num_zones: int, num_values: int, G: int,
                        layout, prev_key, apply_fn, apply_group_fn):
        """ONE program: fold the pending deferred apply (single-run or
        grouped — prev_key carries its kind+layout), then header-probe G
        stacked runs against the updated carry. The multi-template
        analogue of _compiled_fused: one dispatch + one transfer where
        the per-run loop paid one each."""
        key = ("group", num_zones, num_values, G, layout, prev_key)
        fn = self._jitted.get(key)
        if fn is None:
            from kubernetes_tpu.models.pack import unpack as _unpack_pod

            kind = prev_key[0] if prev_key else None
            prev_layout = prev_key[1] if prev_key else None

            def group_probe(static, carry, prev_buf, prev_counts,
                            group_buf):
                if kind == "single":
                    carry = apply_fn(static, carry,
                                     _unpack_pod(prev_layout, prev_buf),
                                     prev_counts)
                elif kind == "group":
                    carry = apply_group_fn(prev_layout, static, carry,
                                           prev_buf, prev_counts)
                out = _group_probe_fn(
                    self.config, num_zones, num_values, G, layout,
                    static, carry, group_buf,
                )
                return carry, out

            fn = jax.jit(group_probe)
            self._jitted[key] = fn
        return fn

    def probe_group(self, static, carry, prev, group_buf,
                    num_zones: int, num_values: int, G: int, layout,
                    apply_fn, apply_group_fn):
        """-> (new_carry, headers u64[G, N_STK_ROWS, N], usage i64[6, N]).
        `prev` is the deferred fold riding this dispatch: None or
        (kind, buf, layout, counts). `usage` is the carry's resource
        block at probe time — the host j-table base (models/hosttab)."""
        prev_key = None
        prev_buf = prev_counts = None
        if prev is not None:
            kind, prev_buf, prev_layout, prev_counts = prev
            prev_key = (kind, prev_layout)
        fn = self._compiled_group(num_zones, num_values, G, layout,
                                  prev_key, apply_fn, apply_group_fn)
        if prev_key is None:
            prev_buf = jnp.zeros(0, jnp.uint8)
            prev_counts = jnp.zeros(0, jnp.int64)
        carry2, raw = fn(static, carry, prev_buf,
                         jnp.asarray(prev_counts), group_buf)
        with device_wait():
            arr = np.ascontiguousarray(jax.device_get(raw))
        N = arr.shape[1]
        headers = arr[: G * N_STK_ROWS].reshape(G, N_STK_ROWS, N)
        usage = arr[G * N_STK_ROWS:]
        return carry2, headers, usage

    def probe(self, static, carry, pod, num_zones: int, num_values: int,
              J: int, rows: Optional[int] = None,
              has_selectors: Optional[bool] = None,
              zone_id: Optional[np.ndarray] = None,
              self_anti_veto: Optional[np.ndarray] = None) -> RunTables:
        """rows (<= J) bounds the j-depth the replay can need (the
        capacity bound from waveloop.pick_j, +2 so a node's fit observably
        reaches False before the table horizon). The full packed array
        still crosses the device->host boundary in ONE transfer (a
        dispatch and a transfer each have a fixed cost, so one fat
        transfer beats a slice dispatch + thin transfer); the clip to
        `rows` happens host-side and keeps the replay tables small."""
        if rows is None:
            rows = J
        rows = max(1, min(rows, J))
        raw = self._compiled(num_zones, num_values, J)(static, carry, pod)
        # ONE device->host transfer for the whole probe product
        with device_wait():
            arr = np.ascontiguousarray(jax.device_get(raw["packed"]))
        return tables_from_packed(
            self.config, arr, num_zones, J, rows,
            has_selectors=(bool(np.asarray(pod["has_selectors"]))
                           if has_selectors is None else has_selectors),
            zone_id=zone_id, self_anti_veto=self_anti_veto,
        )


def tables_from_packed(config: SchedulerConfig, arr: np.ndarray,
                       num_zones: int, J: int, rows: int,
                       has_selectors: bool,
                       zone_id: Optional[np.ndarray] = None,
                       self_anti_veto: Optional[np.ndarray] = None,
                       svc_ctx: Optional[dict] = None) -> RunTables:
    """Unpack the probe's packed product into RunTables (shared by the
    single-chip probe and the mesh probe, whose shard outputs
    concatenate into the identical global array).

    svc_ctx (SA/SAA policy configs; None otherwise) carries the
    host-side service context for the run:
      lbl_val_row i32[N], num_values, member (bool), sa_rows
      (i32[R, N] or None — candidate pin rows for unresolved SA
      labels), ord_node i32[ORD] (order index -> node row), w_saa."""
    stk = arr[:N_STK_ROWS]
    dt = _tab_dtype(config)
    k = 8 // np.dtype(dt).itemsize
    N = arr.shape[1]
    tab = (
        arr[N_STK_ROWS:].view(dt).reshape(J // k, N, k)
        .transpose(0, 2, 1).reshape(J, N)[:rows]
    )
    frontier = stk[1]
    res_fit = np.arange(rows, dtype=np.int64)[:, None] < frontier[None, :]
    return tables_from_stk(
        config, stk, res_fit, np.asarray(tab).astype(np.int64), num_zones,
        has_selectors=has_selectors, zone_id=zone_id,
        self_anti_veto=self_anti_veto, svc_ctx=svc_ctx,
    )


def tables_from_stk(config: SchedulerConfig, stk: np.ndarray,
                    res_fit: np.ndarray, tab: np.ndarray, num_zones: int,
                    has_selectors: bool,
                    zone_id: Optional[np.ndarray] = None,
                    self_anti_veto: Optional[np.ndarray] = None,
                    svc_ctx: Optional[dict] = None) -> RunTables:
    """Assemble RunTables from the probe's header rows plus a resource
    j-axis (res_fit + weighted LR/BA tab) supplied by the caller —
    either reconstructed from the packed single-run product
    (tables_from_packed) or rebuilt host-side from the live resource
    block by the grouped multi-run path (models/hosttab)."""
    N = stk.shape[1]
    rows = res_fit.shape[0]
    fit_static = stk[0].astype(bool)
    if self_anti_veto is not None and rows > 1:
        # hostname-topology hard anti-affinity against the run's own
        # labels: one committed copy excludes every further copy on
        # that node (wave.run_verdict computed where the term's
        # domain exists) — the same res_fit row shape as the
        # host-port self-conflict
        res_fit[1:, self_anti_veto] = False
    weights = {n if isinstance(n, str) else n[0]: w
               for n, w in config.priorities}
    w_spread = int(weights.get(SELECTOR_SPREAD, 0))
    w_na = int(weights.get(NODE_AFFINITY, 0))
    w_tt = int(weights.get(TAINT_TOLERATION, 0))
    w_ip = int(weights.get(INTER_POD_AFFINITY, 0))
    zid = None
    if (w_spread and zone_id is not None
            and np.any(np.asarray(zone_id) > 0)):
        zid = np.ascontiguousarray(zone_id, np.int32)
    w_saa = 0
    saa_counts = saa_lbl = sa_rows = None
    saa_total = saa_nv = 0
    saa_member = False
    sa_bail = False
    if svc_ctx is not None:
        from kubernetes_tpu.snapshot.services import ORD_NONE

        w_saa = int(svc_ctx.get("w_saa", 0))
        if w_saa:
            saa_counts = stk[8].astype(np.int64)
            saa_total = int(stk[9][0])
            saa_lbl = np.ascontiguousarray(
                svc_ctx["lbl_val_row"], np.int32
            )
            saa_nv = int(svc_ctx["num_values"])
            saa_member = bool(svc_ctx.get("member", False))
        pin_ord = int(stk[10][0])
        raw_rows = svc_ctx.get("sa_rows")
        if raw_rows is not None:
            raw_rows = np.ascontiguousarray(raw_rows, np.int32)
            if pin_ord == int(ORD_NONE):
                # unpinned: the first pick pins. Exact ONLY when every
                # node carries every unresolved label — then the pick
                # resolves them all and any later lower-ord commit must
                # carry identical values (the fit forces it), so the
                # min-ord re-pin can never change the requirement.
                if np.all(raw_rows >= 0):
                    sa_rows = raw_rows
                else:
                    sa_bail = True
            else:
                # pinned: static iff the peer's node resolves every
                # unresolved label (same fit-forces-match argument).
                # A peer on an unknown node (row < 0) fails every
                # candidate statically — no dynamics. A peer whose node
                # LACKS a label leaves it unresolved: a lower-ord
                # commit could re-pin it mid-run -> scan.
                ord_node = np.asarray(svc_ctx["ord_node"])
                peer_row = (int(ord_node[pin_ord])
                            if pin_ord < len(ord_node) else -1)
                if peer_row >= 0 and np.any(raw_rows[:, peer_row] < 0):
                    sa_bail = True
    return RunTables(
        zone_id=zid,
        num_zones=num_zones,
        w_saa=w_saa,
        saa_counts=saa_counts,
        saa_total=saa_total,
        saa_lbl_val=saa_lbl,
        saa_num_values=saa_nv,
        saa_member=saa_member,
        sa_refine_rows=sa_rows,
        sa_bail=sa_bail,
        fit_static=fit_static,
        res_fit=res_fit,
        tab=np.asarray(tab).astype(np.int64),
        static_add=stk[2],
        w_spread=w_spread,
        spread_base=stk[3] if w_spread else None,
        spread_selfmatch=bool(stk[4][0]) if w_spread else False,
        has_selectors=has_selectors,
        w_na=w_na,
        na_counts=stk[5] if w_na else None,
        w_tt=w_tt,
        tt_counts=stk[6] if w_tt else None,
        w_ip=w_ip,
        ip_totals=stk[7] if w_ip else None,
    )
