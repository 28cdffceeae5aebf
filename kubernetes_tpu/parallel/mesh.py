"""SPMD mesh implementation of the batch scheduler: resident pjit path.

Node-axis arrays are sharded P("nodes"); pod-batch arrays are replicated.
The scan/probe/fold bodies run inside shard_map so per-step collectives
(pmax/psum for the filtered-normalization maxes, all_gather for
selection) ride ICI.  Results are bit-identical to the single-chip
BatchScheduler: every reduction here computes exactly the same integers,
just distributed.

Round 7: the cluster state is DEVICE-RESIDENT across waves
(parallel/resident.ResidentClusterState).  Every program is pjit-shaped
— ``jax.jit`` with explicit ``in_shardings``/``out_shardings`` built
from the same PartitionSpecs the shard_map bodies declare — and the
commit folds DONATE their carry input (``donate_argnums``): wave-to-
wave commits mutate the resident sharded buffers in place, zero host
round trips and zero realloc, on every backend (the CPU client's
donation race that once kept CPU runs undonated did not reproduce on
jaxlib 0.9.0 — PR 22 re-ran the churn loop that showed it — so the
tests now execute the donated folds too; the auditor enforces the
donation contract on the lowered form).  Commit counts ship in
scatter form (touched
node ids + amounts, O(pending pods)) instead of dense O(nodes) rows;
steady-state waves ship no node table bytes at all (the jaxpr
auditor's donation/transfer contract and tests/test_resident.py
enforce both properties structurally).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from kubernetes_tpu.parallel.resident import (
    AXIS,
    CARRY_FIELDS,
    ResidentClusterState,
    carry_specs,
    host_carry,
    host_static,
    static_specs,
)

from kubernetes_tpu.models.batch import (
    CHECK_NODE_MEMORY_PRESSURE,
    INTER_POD_AFFINITY,
    MATCH_INTER_POD_AFFINITY,
    MAX_EBS_VOLUME_COUNT,
    MAX_GCE_PD_VOLUME_COUNT,
    NO_DISK_CONFLICT,
    NO_VOLUME_ZONE_CONFLICT,
    POD_TOLERATES_NODE_TAINTS,
    BatchScheduler,
    SchedulerConfig,
    wants_host,
    wants_ports,
    wants_resources,
    wants_selector,
)
from kubernetes_tpu.models.probe import N_STK_ROWS, tables_from_packed
from kubernetes_tpu.models.replay import replay_fast
from kubernetes_tpu.models.wave import (
    AFFINITY_COUNTERS,
    ANTI_COUNTERS,
    GROUP_COUNTERS,
    LOOP_COUNTERS,
    PATHS,
    WaveCounts,
    classify_runs,
    split_runs,
)
from kubernetes_tpu.models.waveloop import (
    Policy,
    Wave,
    gather_batch,
    host_group_cap,
    run_wave,
)
from kubernetes_tpu.ops import interpod as IP
from kubernetes_tpu.ops import predicates as P
from kubernetes_tpu.ops import select as S
from kubernetes_tpu.ops import priorities as R
from kubernetes_tpu.ops import services as SV
from kubernetes_tpu.ops import volumes as V
from kubernetes_tpu.snapshot.encode import ClusterSnapshot, PodBatch, service_config_labels
from kubernetes_tpu.snapshot.pad import next_pow2, pad_batch
from kubernetes_tpu.trace.profile import device_wait, fetch, phase_timer


def _pad_snapshot(snap: ClusterSnapshot, multiple: int) -> ClusterSnapshot:
    """Pad the node axis with never-fit dummy nodes (alloc all zero ->
    pod-count check fails) so N divides the mesh size. Dummy nodes never
    win selection because they are never in the fit mask."""
    n = len(snap.node_names)
    pad = (-n) % multiple
    if pad == 0:
        return snap
    import dataclasses

    def pad_arr(a: np.ndarray, fill=0):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths, constant_values=fill)

    fields = {}
    for f in dataclasses.fields(snap):
        v = getattr(snap, f.name)
        if f.name == "node_names":
            fields[f.name] = list(v) + [f"\x00pad-{i}" for i in range(pad)]
        elif f.name == "name_desc_order":
            # dummy names are never selected; order them after real nodes
            fields[f.name] = np.concatenate(
                [v, np.arange(n, n + pad, dtype=np.int32)]
            )
        elif f.name == "numval":
            fields[f.name] = np.pad(
                v, [(0, pad), (0, 0)], constant_values=np.nan
            )
        elif f.name == "ip_topo_dom":
            # node axis is axis 1; dummy nodes have no topology domains
            fields[f.name] = np.pad(
                v, [(0, 0), (0, pad)], constant_values=-1
            )
        elif f.name in ("svc_lbl_val", "svc_peer_node_count"):
            fields[f.name] = np.pad(v, [(0, 0), (0, pad)], constant_values=(-1 if f.name == "svc_lbl_val" else 0))
        elif f.name == "svc_node_ord":
            from kubernetes_tpu.snapshot.services import ORD_NONE
            fields[f.name] = np.pad(v, [(0, pad)], constant_values=int(ORD_NONE))
        elif f.name in ("svc_ord_node", "svc_first_peer", "svc_peer_total", "svc_labels", "svc_num_values", "key_ids"):
            fields[f.name] = v
        elif f.name in ("set_table", "noschedule_taints", "prefer_taints") or (
            f.name.startswith("ip_")
        ):
            fields[f.name] = v  # vocab/count tables: not node-axis
        elif isinstance(v, np.ndarray):
            fields[f.name] = pad_arr(v)
        else:
            fields[f.name] = v
    return dataclasses.replace(snap, **fields)


def _shard_fit(config, n_per_shard, n_global, static, carry, pod,
               include_resources=True):
    """Per-shard fit mask (the predicate section of the scan body,
    shared with the mesh wave probe). Returns (fit, cnt_lt, topo_local,
    offset); cnt_lt/topo_local are None unless interpod is configured."""
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

    shard = jax.lax.axis_index(AXIS)
    offset = shard.astype(jnp.int32) * n_per_shard

    # interpod count tables are replicated (small); queries use this
    # shard's node columns of the (replicated) topology-domain table
    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)
    cnt_lt = topo_local = None
    if want_ip_pred or want_ip_prio:
        dom_tab = static["ip_topo_dom"]
        if dom_tab.size:
            topo_local = jax.lax.dynamic_slice_in_dim(
                dom_tab, offset, n_per_shard, axis=1
            )
        else:
            # no interpod terms in the cluster: the incremental encoder
            # emits a (0, 0) domain table (the full encoder (0, N));
            # slicing either would trip — the empty per-shard window is
            # exact
            topo_local = jnp.zeros((dom_tab.shape[0], n_per_shard),
                                   dom_tab.dtype)
        cnt_lt = IP.expand_lt(
            IP.gather_counts(ip_term_count, static["ip_u_topo"], topo_local),
            static["ip_lt_u"],
            static["ip_lt_sign"],
            n_per_shard,
        )

    fit = ~pod["unschedulable"]
    if want_ip_prio:
        fit = fit & ~pod["ip_poison"]
    if NO_DISK_CONFLICT in config.predicates:
        fit = fit & V.no_disk_conflict(
            pod["vp_vol_rw"], pod["vp_vol_ro"], vol_any, vol_rw
        )
    if NO_VOLUME_ZONE_CONFLICT in config.predicates:
        fit = fit & V.volume_zone(
            pod["vp_vz_zone"], pod["vp_vz_region"], pod["vp_vz_fail"],
            static["vz_zone"], static["vz_region"], static["vz_has"],
        )
    if MAX_EBS_VOLUME_COUNT in config.predicates:
        fit = fit & V.max_pd_count(
            pod["vp_ebs"], pod["vp_ebs_bad"], pod["vp_has_ebs"],
            ebs_mask, static["ebs_bad"], config.max_ebs_volumes,
        )
    if MAX_GCE_PD_VOLUME_COUNT in config.predicates:
        fit = fit & V.max_pd_count(
            pod["vp_gce"], pod["vp_gce_bad"], pod["vp_has_gce"],
            gce_mask, static["gce_bad"], config.max_gce_pd_volumes,
        )
    if include_resources and wants_resources(config):
        fit = fit & P.pod_fits_resources(
            pod["req_mcpu"],
            pod["req_mem"],
            pod["req_gpu"],
            pod["zero_req"],
            static["alloc_mcpu"],
            static["alloc_mem"],
            static["alloc_gpu"],
            static["alloc_pods"],
            req_mcpu,
            req_mem,
            req_gpu,
            pod_count,
        )
    # host check against GLOBAL node ids
    local_ids = offset + jnp.arange(n_per_shard, dtype=jnp.int32)
    if wants_host(config):
        fit = fit & jnp.where(
            pod["host_req"] < 0, pod["host_req"] == -1, local_ids == pod["host_req"]
        )
    if wants_ports(config):
        fit = fit & P.pod_fits_host_ports(pod["port_mask"], port_mask)
    if wants_selector(config):
        fit = fit & P.match_node_selector(
            pod["ns_ops"],
            pod["ns_key"],
            pod["ns_set"],
            pod["ns_numkey"],
            pod["ns_num"],
            pod["aff_has_req"],
            pod["aff_term_valid"],
            pod["aff_ops"],
            pod["aff_key"],
            pod["aff_set"],
            pod["aff_numkey"],
            pod["aff_num"],
            static["label_kv"],
            static["label_key"],
            static["numval"],
            static["set_table"],
        )
    if POD_TOLERATES_NODE_TAINTS in config.predicates:
        fit = fit & P.pod_tolerates_node_taints(
            pod["tol_mask"],
            pod["has_tolerations"],
            static["taint_mask"],
            static["has_taints"],
            static["taint_bad"],
            static["noschedule_taints"],
        )
    if CHECK_NODE_MEMORY_PRESSURE in config.predicates:
        fit = fit & P.check_node_memory_pressure(pod["best_effort"], static["mem_pressure"])
    svc_labels = service_config_labels(config)
    for entry in config.predicates:
        if isinstance(entry, tuple) and entry[0] == "CheckNodeLabelPresence":
            for lbl in entry[1]:
                has = static[f"nl_pred_{lbl}"]
                fit = fit & (has if entry[2] else ~has)
        elif isinstance(entry, tuple) and entry[0] == "ServiceAffinity":
            # svc tables are replicated (small: groups x labels); evaluate
            # over the GLOBAL node axis and slice this shard's window
            ok_g = SV.service_affinity(
                svc_first_peer,
                static["svc_lbl_val"],
                static["svc_ord_node"],
                pod["svc_group"],
                pod["svc_fixed"],
                tuple(svc_labels.index(l) for l in entry[1]),
                n_global,
            )
            fit = fit & jax.lax.dynamic_slice_in_dim(ok_g, offset, n_per_shard)
    if want_ip_pred:
        own_lt = IP.gather_lt(
            ip_own_anti, static["ip_u_topo"], topo_local,
            static["ip_lt_u"], static["ip_lt_sign"],
        )
        fit = fit & IP.match_interpod(
            cnt_lt,
            own_lt,
            ip_spec_total,
            static["ip_lt_spec"],
            pod["ip_match_spec"],
            pod["ip_ha_lt"],
            pod["ip_ha_self"],
            pod["ip_hq_lt"],
            pod["ip_has_affinity"],
            pod["ip_has_anti"],
            pod["ip_sym_reject"],
            n_per_shard,
        )
    return fit, cnt_lt, topo_local, offset


def _mesh_scan_fn(config, num_zones, n_per_shard, n_global, num_values,
                  static, carry, pod):
    """Per-shard scan body. `static`/`carry` node arrays hold this shard's
    slice; `pod` is replicated. Mirrors models.batch._scan_fn with the
    normalization maxes and selection made global via collectives."""
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
    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)
    svc_labels = service_config_labels(config)

    fit, cnt_lt, topo_local, offset = _shard_fit(
        config, n_per_shard, n_global, static, carry, pod
    )

    score = jnp.zeros(req_mcpu.shape, jnp.int64)
    for name, weight in config.priorities:
        if name == "LeastRequestedPriority":
            s = R.least_requested(
                pod["nz_mcpu"], pod["nz_mem"], nz_mcpu, nz_mem,
                static["alloc_mcpu"], static["alloc_mem"],
            )
        elif name == "BalancedResourceAllocation":
            s = R.balanced_resource_allocation(
                pod["nz_mcpu"], pod["nz_mem"], nz_mcpu, nz_mem,
                static["alloc_mcpu"], static["alloc_mem"],
            )
        elif name == "SelectorSpreadPriority":
            s = _spread_sharded(
                pod["has_selectors"], pod["spread_match"], class_count,
                static["zone_id"], num_zones, fit,
            )
        elif name == "NodeAffinityPriority":
            counts = R.node_affinity_counts(
                pod["pref_valid"], pod["pref_weight"], pod["pref_ops"],
                pod["pref_key"], pod["pref_set"], pod["pref_numkey"],
                pod["pref_num"], static["label_kv"], static["label_key"],
                static["numval"], static["set_table"],
            )
            # int32 for the collective: s64 all-reduce max has no TPU lowering
            local_max = counts.max(where=fit, initial=0).astype(jnp.int32)
            max_count = jax.lax.pmax(local_max, AXIS).astype(jnp.int64)
            s = R.normalize_counts_up(counts, max_count)
        elif name == "TaintTolerationPriority":
            counts = R.taint_intolerable_counts(
                static["taint_count"], pod["intolerable_prefer"]
            )
            local_max = counts.max(where=fit, initial=0).astype(jnp.int32)
            max_count = jax.lax.pmax(local_max, AXIS).astype(jnp.int64)
            s = R.normalize_counts_down(counts, max_count)
        elif name == INTER_POD_AFFINITY:
            totals = IP.interpod_totals(
                cnt_lt,
                IP.gather_lt(
                    ip_rev_hard, static["ip_u_topo"], topo_local,
                    static["ip_lt_u"], static["ip_lt_sign"],
                ),
                IP.gather_lt(
                    ip_rev_pref, static["ip_u_topo"], topo_local,
                    static["ip_lt_u"], static["ip_lt_sign"],
                ),
                IP.gather_lt(
                    ip_rev_anti, static["ip_u_topo"], topo_local,
                    static["ip_lt_u"], static["ip_lt_sign"],
                ),
                static["ip_lt_spec"],
                pod["ip_match_spec"],
                pod["ip_fwd_lt"],
                pod["ip_fwd_w"],
                config.hard_pod_affinity_weight,
                n_per_shard,
            )
            # global min/max over fit nodes: gather the small vectors
            # (s64 all-reduce min/max has no TPU lowering; gather+reduce
            # computes the identical integers)
            totals_g = jax.lax.all_gather(totals, AXIS, tiled=True)
            fitp_g = jax.lax.all_gather(fit, AXIS, tiled=True)
            mx, mn = IP.interpod_minmax(totals_g, fitp_g)
            s = IP.interpod_normalize(totals, fit, mx, mn)
        elif name == "EqualPriority":
            s = jnp.ones(req_mcpu.shape, jnp.int64)
        elif name == "ImageLocalityPriority":
            # unnormalized: shards score their local nodes independently
            s = R.image_locality(static["img_size"], pod["img_count"])
        elif isinstance(name, tuple) and name[0] == "NodeLabelPriority":
            s = R.node_label(static[f"nl_prio_{name[1]}"], name[2])
        elif isinstance(name, tuple) and name[0] == "ServiceAntiAffinity":
            # the spread normalizer counts peers on the global filtered
            # node list: gather fit, score globally, slice local window
            fit_g_svc = jax.lax.all_gather(fit, AXIS, tiled=True)
            s_g = SV.service_anti_affinity(
                svc_peer_node_count,
                svc_peer_total,
                static["svc_lbl_val"][svc_labels.index(name[1])],
                pod["svc_group"],
                fit_g_svc,
                num_values,
                n_global,
            )
            s = jax.lax.dynamic_slice_in_dim(s_g, offset, n_per_shard)
        else:
            raise ValueError(name)
        score = score + jnp.int64(weight) * s

    # --- global selection: gather the small per-node vectors, pick once
    score_g = jax.lax.all_gather(score, AXIS, tiled=True)  # i64[N]
    fit_g = jax.lax.all_gather(fit, AXIS, tiled=True)  # bool[N]
    chosen, scheduled = S.select_host(
        score_g, fit_g, last_idx, static["name_desc_order_global"]
    )

    # --- commit locally if the chosen node lives on this shard
    local = chosen - offset
    mine = scheduled & (local >= 0) & (local < n_per_shard)
    safe = jnp.clip(local, 0, n_per_shard - 1)
    inc = mine.astype(jnp.int64)
    res = res.at[:, safe].add(
        jnp.stack(
            [
                pod["commit_mcpu"], pod["commit_mem"], pod["commit_gpu"],
                pod["nz_mcpu"], pod["nz_mem"], jnp.int64(1),
            ]
        )
        * inc
    )
    port_mask = port_mask.at[safe].set(
        jnp.where(mine, port_mask[safe] | pod["port_mask"], port_mask[safe])
    )
    class_count = class_count.at[safe, pod["class_id"]].add(inc)
    last_idx = last_idx + scheduled.astype(jnp.int64)  # global counter

    # interpod tables are replicated: every shard applies the identical
    # update using the GLOBAL chosen index and the global domain table
    if want_ip_pred or want_ip_prio:
        (
            ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref, ip_rev_anti,
            ip_spec_total,
        ) = IP.interpod_commit(
            ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref, ip_rev_anti,
            ip_spec_total,
            static["ip_topo_dom"],
            static["ip_u_topo"],
            static["ip_u_spec"],
            static["ip_lt_u"],
            pod["ip_match_spec"],
            pod["ip_own_hard"],
            pod["ip_own_pref"],
            pod["ip_own_anti_hard"],
            pod["ip_own_anti_pref"],
            chosen,
            scheduled,
        )

    if any(
        k in config.predicates
        for k in (NO_DISK_CONFLICT, MAX_EBS_VOLUME_COUNT, MAX_GCE_PD_VOLUME_COUNT)
    ):
        sel = jnp.where(mine, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        vol_any = vol_any.at[safe].set(
            vol_any[safe] | ((pod["vp_vol_rw"] | pod["vp_vol_ro"]) & sel)
        )
        vol_rw = vol_rw.at[safe].set(vol_rw[safe] | (pod["vp_vol_rw"] & sel))
        ebs_mask = ebs_mask.at[safe].set(ebs_mask[safe] | (pod["vp_ebs"] & sel))
        gce_mask = gce_mask.at[safe].set(gce_mask[safe] | (pod["vp_gce"] & sel))

    if svc_labels:
        svc_first_peer, svc_peer_node_count, svc_peer_total = SV.service_commit(
            svc_first_peer,
            svc_peer_node_count,
            svc_peer_total,
            static["svc_node_ord"],
            pod["svc_member"],
            chosen,
            scheduled,
        )

    carry = (
        res, port_mask, class_count, last_idx,
        ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref, ip_rev_anti,
        ip_spec_total,
        vol_any, vol_rw, ebs_mask, gce_mask,
        svc_first_peer, svc_peer_node_count, svc_peer_total,
    )
    return carry, chosen


def _spread_sharded(
    pod_has_selectors, pod_spread_match, class_count, zone_id, num_zones, fit_mask
):
    """selector_spread with the max/zone reductions made mesh-global;
    the arithmetic from there on is the single-chip scorer's own
    (`ops/priorities.spread_blend`), so that the two cannot differ by
    an ulp of a weight."""
    counts = R.spread_counts(class_count, pod_spread_match)
    counts = jnp.where(fit_mask, counts, 0)
    max_count = jax.lax.pmax(
        counts.max(where=fit_mask, initial=0).astype(jnp.int32), AXIS
    ).astype(jnp.int64)

    zcounts = jax.lax.psum(
        R.zone_sums(counts.astype(jnp.int32), zone_id, num_zones), AXIS
    ).astype(jnp.int64)
    have_zones = jax.lax.psum(
        jnp.any(fit_mask & (zone_id > 0)).astype(jnp.int32), AXIS) > 0
    max_zone = jnp.where(jnp.arange(num_zones) > 0, zcounts, 0).max(initial=0)

    return R.spread_blend(pod_has_selectors, counts, max_count, zcounts,
                          max_zone, have_zones, zone_id)


def _mesh_probe_rows(config, num_zones, num_values, J, n_per_shard,
                     n_global, static, carry, pod):
    """Per-shard probe body (models/probe._probe_rows, sharded):
    -> (stk [N_STK_ROWS, n_per_shard], tab [J, n_per_shard])."""
    (
        res, port_mask, class_count, last_idx,
        ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
        ip_rev_anti, ip_spec_total,
        vol_any, vol_rw, ebs_mask, gce_mask,
        svc_first_peer, svc_peer_node_count, svc_peer_total,
    ) = carry
    req_mcpu, req_mem, req_gpu, nz_mcpu, nz_mem, pod_count = res
    N = n_per_shard

    fit_static, cnt_lt, topo_local, offset = _shard_fit(
        config, n_per_shard, n_global, static, carry, pod,
        include_resources=False,
    )
    # minimal configs leave no node-axis predicate: scalar -> (N,)
    fit_static = jnp.broadcast_to(fit_static, (N,))

    j = jnp.arange(J, dtype=jnp.int64)[:, None]
    if wants_resources(config):
        res_fit = P.pod_fits_resources(
            pod["req_mcpu"], pod["req_mem"], pod["req_gpu"],
            pod["zero_req"],
            static["alloc_mcpu"], static["alloc_mem"],
            static["alloc_gpu"], static["alloc_pods"],
            req_mcpu[None, :] + j * pod["commit_mcpu"],
            req_mem[None, :] + j * pod["commit_mem"],
            req_gpu[None, :] + j * pod["commit_gpu"],
            pod_count[None, :] + j,
        )
    else:
        res_fit = jnp.ones((J, N), bool)
    if wants_ports(config):
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
        if name == "LeastRequestedPriority":
            tab = tab + jnp.int64(weight) * R.least_requested(
                pod["nz_mcpu"], pod["nz_mem"], nzj_cpu, nzj_mem,
                static["alloc_mcpu"], static["alloc_mem"],
            )
        elif name == "BalancedResourceAllocation":
            tab = tab + jnp.int64(weight) * R.balanced_resource_allocation(
                pod["nz_mcpu"], pod["nz_mem"], nzj_cpu, nzj_mem,
                static["alloc_mcpu"], static["alloc_mem"],
            )
        elif name == "SelectorSpreadPriority":
            stk_rows["spread_base"] = R.spread_counts(
                class_count, pod["spread_match"])
            stk_rows["spread_selfmatch"] = jnp.broadcast_to(
                (pod["spread_match"][pod["class_id"]] > 0).astype(jnp.int64),
                (N,),
            )
        elif name == "NodeAffinityPriority":
            stk_rows["na_counts"] = R.node_affinity_counts(
                pod["pref_valid"], pod["pref_weight"], pod["pref_ops"],
                pod["pref_key"], pod["pref_set"], pod["pref_numkey"],
                pod["pref_num"], static["label_kv"], static["label_key"],
                static["numval"], static["set_table"],
            )
        elif name == "TaintTolerationPriority":
            stk_rows["tt_counts"] = R.taint_intolerable_counts(
                static["taint_count"], pod["intolerable_prefer"]
            )
        elif name == INTER_POD_AFFINITY:
            stk_rows["ip_totals"] = IP.interpod_totals(
                cnt_lt,
                IP.gather_lt(ip_rev_hard, static["ip_u_topo"], topo_local,
                             static["ip_lt_u"], static["ip_lt_sign"]),
                IP.gather_lt(ip_rev_pref, static["ip_u_topo"], topo_local,
                             static["ip_lt_u"], static["ip_lt_sign"]),
                IP.gather_lt(ip_rev_anti, static["ip_u_topo"], topo_local,
                             static["ip_lt_u"], static["ip_lt_sign"]),
                static["ip_lt_spec"], pod["ip_match_spec"],
                pod["ip_fwd_lt"], pod["ip_fwd_w"],
                config.hard_pod_affinity_weight, N,
            )
        elif name == "EqualPriority":
            static_add = static_add + jnp.int64(weight) * R.equal(N)
        elif name == "ImageLocalityPriority":
            static_add = static_add + jnp.int64(weight) * R.image_locality(
                static["img_size"], pod["img_count"]
            )
        elif isinstance(name, tuple) and name[0] == "NodeLabelPriority":
            static_add = static_add + jnp.int64(weight) * R.node_label(
                static[f"nl_prio_{name[1]}"], name[2]
            )
        elif isinstance(name, tuple) and name[0] == "ServiceAntiAffinity":
            pass  # per-pick renormalization: the replay consumes the
            # svc rows emitted below
        else:
            raise ValueError(f"priority {name!r} is not mesh-wave-eligible")
    # service rows (the single-chip probe's svc_counts/svc_total/
    # svc_pin; see probe.N_STK_ROWS)
    from kubernetes_tpu.snapshot.services import ORD_NONE as _ORD_NONE

    G = svc_first_peer.shape[0]
    if G:
        g = jnp.clip(pod["svc_group"], 0, G - 1)
        has_group = pod["svc_group"] >= 0
        # the peer-count table is REPLICATED (G, N_global): emit this
        # shard's slice so the concatenated rows equal the single-chip
        # probe's global row
        counts_g = jnp.where(
            has_group, svc_peer_node_count[g], 0
        ).astype(jnp.int64)
        svc_counts = jax.lax.dynamic_slice_in_dim(
            counts_g, offset, n_per_shard
        )
        svc_total = jnp.broadcast_to(
            jnp.where(has_group, svc_peer_total[g], 0).astype(jnp.int64),
            (N,),
        )
        svc_pin = jnp.broadcast_to(
            jnp.where(has_group, svc_first_peer[g],
                      jnp.int32(_ORD_NONE)).astype(jnp.int64),
            (N,),
        )
    else:
        svc_counts = jnp.zeros((N,), jnp.int64)
        svc_total = jnp.zeros((N,), jnp.int64)
        svc_pin = jnp.full((N,), jnp.int64(_ORD_NONE))
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
def _mesh_probe_fn(config, num_zones, num_values, J, n_per_shard,
                   n_global, pod_layout, static, carry, pod_buf):
    """Per-shard wave probe (models/probe._probe_fn, sharded): this
    shard's slice of the packed table product. The out_spec concatenates
    shards along the node axis, so the host sees the same
    (probe.N_STK_ROWS + J-words, N) array the single-chip probe ships —
    replay and commit mapping are untouched. The pod row arrives as ONE
    packed replicated buffer (models/pack) instead of ~40 per-field
    transfers."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod
    from kubernetes_tpu.models.probe import _tab_dtype

    pod = _unpack_pod(pod_layout, pod_buf)
    stk, tab = _mesh_probe_rows(
        config, num_zones, num_values, J, n_per_shard, n_global, static,
        carry, pod,
    )
    N = n_per_shard
    dt = _tab_dtype(config)
    k = 8 // np.dtype(dt).itemsize
    tabp = tab.astype(dt).reshape(J // k, k, N).swapaxes(1, 2)
    tabw = jax.lax.bitcast_convert_type(tabp, jnp.int64)
    return jnp.concatenate([stk, tabw], axis=0)


@jax.named_scope("probe")
def _mesh_group_probe_fn(config, num_zones, num_values, G, n_per_shard,
                         n_global, pod_layout, static, carry, group_buf):
    """The grouped header probe, sharded: vmap of _mesh_probe_rows over
    G stacked run representatives (J=1 — the host rebuilds the resource
    j-axis against the resident state's exact host usage mirror,
    models/hosttab, so unlike the single-chip grouped probe NO resource
    block ships device->host). The run axis rides as a leading axis on
    every shard; the node axis stays sharded, and the out_spec
    concatenates shards into one (G*N_STK_ROWS, N) host-bound array."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod
    from kubernetes_tpu.models.probe import N_STK_ROWS

    pods = _unpack_pod(pod_layout, group_buf)

    def one(pod):
        stk, _tab = _mesh_probe_rows(
            config, num_zones, num_values, 1, n_per_shard, n_global,
            static, carry, pod,
        )
        return stk

    stk = jax.vmap(one)(pods)  # (G, N_STK_ROWS, n_per_shard)
    return stk.reshape(G * N_STK_ROWS, n_per_shard)


@jax.named_scope("fold")
def _mesh_apply_group_fn(config, pod_layout, n_global, static, carry,
                         group_buf, touch_idx, touch_cnt):
    """The grouped commit fold, sharded and donated: commits arrive in
    scatter form (per-run touched node ids + amounts, O(picks) bytes);
    node-axis tables take this shard's slice of the rebuilt per-run
    global counts [G, N]. Valid for PURE runs only
    (models/wave.run_pure): resource block, port masks, spread class
    counts, and the round-robin counter — the replicated ip/svc tables
    pass through untouched."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    pods = _unpack_pod(pod_layout, group_buf)
    counts_global = _group_counts_from_touch(n_global, touch_idx,
                                             touch_cnt)
    (res, port_mask, class_count, last_idx), rest = carry[:4], carry[4:]
    n_per_shard = port_mask.shape[0]
    shard = jax.lax.axis_index(AXIS)
    offset = shard.astype(jnp.int32) * n_per_shard
    counts = jax.lax.dynamic_slice_in_dim(
        counts_global, offset, n_per_shard, axis=1
    )  # (G, n_per_shard)
    commit = jnp.stack([
        pods["commit_mcpu"], pods["commit_mem"], pods["commit_gpu"],
        pods["nz_mcpu"], pods["nz_mem"],
        jnp.ones_like(pods["commit_mcpu"]),
    ])  # (6, G)
    # elementwise product + reduce instead of an s64 dot_general
    # (which has no TPU lowering); XLA fuses the reduction
    res = res + (commit[:, :, None] * counts[None, :, :]).sum(axis=1)
    touched = counts > 0
    add_bits = jnp.where(
        touched[:, :, None], pods["port_mask"][:, None, :],
        jnp.zeros_like(pods["port_mask"][:, None, :]),
    )
    port_mask = port_mask | jax.lax.reduce(
        add_bits, port_mask.dtype.type(0), jax.lax.bitwise_or, (0,)
    )
    class_count = class_count.at[:, pods["class_id"]].add(
        counts.T.astype(class_count.dtype)
    )
    last_idx = last_idx + counts_global.sum()
    return (res, port_mask, class_count, last_idx) + tuple(rest)


@jax.named_scope("apply")
def _mesh_apply_fn(config, pod_layout, n_global, static, carry, pod_buf,
                   touch_idx, touch_cnt):
    """The wave commit fold, sharded and donated: commits arrive in
    scatter form (touched node ids + amounts); node-axis tables take
    this shard's slice of the rebuilt global counts; the replicated
    interpod tables take the identical global fold on every shard (the
    pattern interpod_commit uses in the mesh scan)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    pod = _unpack_pod(pod_layout, pod_buf)
    counts_global = _counts_from_touch(n_global, touch_idx, touch_cnt)
    (
        res, port_mask, class_count, last_idx,
        ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
        ip_rev_anti, ip_spec_total,
        vol_any, vol_rw, ebs_mask, gce_mask,
        svc_first_peer, svc_peer_node_count, svc_peer_total,
    ) = carry
    n_per_shard = port_mask.shape[0]
    shard = jax.lax.axis_index(AXIS)
    offset = shard.astype(jnp.int32) * n_per_shard
    counts = jax.lax.dynamic_slice_in_dim(
        counts_global, offset, n_per_shard
    )
    k = counts_global.sum()
    commit = jnp.stack([
        pod["commit_mcpu"], pod["commit_mem"], pod["commit_gpu"],
        pod["nz_mcpu"], pod["nz_mem"], jnp.int64(1),
    ])
    res = res + commit[:, None] * counts[None, :]
    port_mask = jnp.where(
        (counts > 0)[:, None], port_mask | pod["port_mask"][None, :],
        port_mask,
    )
    class_count = class_count.at[:, pod["class_id"]].add(counts)
    last_idx = last_idx + k
    U = static["ip_u_topo"].shape[0]
    NG = counts_global.shape[0]
    if U and ip_term_count.shape[1]:
        dom = static["ip_topo_dom"][static["ip_u_topo"]]  # (U, NG)
        mu = pod["ip_match_spec"][static["ip_u_spec"]]
        add = jnp.where(
            dom >= 0,
            mu[:, None].astype(jnp.int64) * counts_global[None, :], 0,
        )
        ip_term_count = ip_term_count.at[
            jnp.arange(U)[:, None],
            jnp.clip(dom, 0, ip_term_count.shape[1] - 1),
        ].add(add.astype(ip_term_count.dtype))
    LT = static["ip_lt_u"].shape[0] if "ip_lt_u" in static else 0
    E = static["ip_lt_u"].shape[1] if LT else 0
    if LT and E and ip_own_anti.shape[2]:
        lt_u = static["ip_lt_u"]
        q = static["ip_u_topo"][jnp.clip(lt_u, 0, U - 1)]
        domq = static["ip_topo_dom"][q]  # (LT, E, NG)
        validq = (lt_u >= 0)[:, :, None] & (domq >= 0)
        sdq = jnp.clip(domq, 0, ip_own_anti.shape[2] - 1)
        lt_i = jnp.arange(LT)[:, None, None]
        e_i = jnp.arange(E)[None, :, None]
        c32 = jnp.where(validq, counts_global[None, None, :], 0).astype(
            jnp.int32
        )
        c64 = c32.astype(jnp.int64)
        ip_own_anti = ip_own_anti.at[lt_i, e_i, sdq].add(
            pod["ip_own_anti_hard"][:, None, None] * c32
        )
        ip_rev_hard = ip_rev_hard.at[lt_i, e_i, sdq].add(
            pod["ip_own_hard"][:, None, None] * c32
        )
        ip_rev_pref = ip_rev_pref.at[lt_i, e_i, sdq].add(
            pod["ip_own_pref"][:, None, None] * c64
        )
        ip_rev_anti = ip_rev_anti.at[lt_i, e_i, sdq].add(
            pod["ip_own_anti_pref"][:, None, None] * c64
        )
    if ip_spec_total.shape[0]:
        ip_spec_total = ip_spec_total + (
            pod["ip_match_spec"].astype(jnp.int64) * k
        ).astype(ip_spec_total.dtype)
    if svc_first_peer.shape[0]:
        # service tables are replicated: every shard applies the
        # identical GLOBAL fold
        from kubernetes_tpu.ops.services import service_commit_bulk

        (svc_first_peer, svc_peer_node_count,
         svc_peer_total) = service_commit_bulk(
            svc_first_peer, svc_peer_node_count, svc_peer_total,
            static["svc_node_ord"], pod["svc_member"], counts_global,
        )
    return (
        res, port_mask, class_count, last_idx,
        ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
        ip_rev_anti, ip_spec_total,
        vol_any, vol_rw, ebs_mask, gce_mask,
        svc_first_peer, svc_peer_node_count, svc_peer_total,
    )


def _static_specs(static: dict) -> dict:
    """PartitionSpec per static snapshot field (single-sourced in
    parallel/resident so placement and programs can never drift)."""
    return static_specs(static)


CARRY_SPECS = carry_specs()


def _ns_tree(mesh: Mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def empty_leaves(carry) -> tuple:
    """Indices of the carry's zero-size leaves (a backlog without
    inter-pod terms or services carries empty tables)."""
    return tuple(i for i, x in enumerate(carry) if np.size(x) == 0)


def _carry_out_shardings(mesh: Mesh, empty: tuple):
    """NamedShardings for a program's carry OUTPUT, with the zero-size
    leaves left unspecified. The TPU compiler (libtpu 0.0.34) aborts
    the process — `import_shardy_attrs.cc: Check failed:
    funcResultSharding.getNumOperands() == 1 (2 vs. 1)` — on any
    program with two or more zero-size int64 results that carry a
    declared sharding, which ip_rev_pref/ip_rev_anti are whenever the
    backlog has no inter-pod terms. An empty array has no bytes to
    place: the compiler gives those results the replicated sharding
    they had anyway (found compiling for a described v5e 2x2, PR 22;
    no CPU run can show it)."""
    return tuple(
        None if i in empty else NamedSharding(mesh, s)
        for i, s in enumerate(CARRY_SPECS)
    )


def _counts_from_touch(n_global, touch_idx, touch_cnt):
    """Dense i64[N] commit counts from the scatter-form shipment
    (touched node ids padded with -1 + per-node amounts): the per-wave
    host->device commit transfer is O(pending pods), not O(nodes)."""
    valid = touch_idx >= 0
    safe = jnp.clip(touch_idx, 0, n_global - 1)
    return jnp.zeros((n_global,), jnp.int64).at[safe].add(
        jnp.where(valid, touch_cnt, 0)
    )


def _group_counts_from_touch(n_global, touch_idx, touch_cnt):
    """Scatter-form -> dense i64[G, N] per-run commit counts."""
    G, M = touch_idx.shape
    valid = touch_idx >= 0
    safe = jnp.clip(touch_idx, 0, n_global - 1)
    g_i = jnp.arange(G, dtype=jnp.int64)[:, None]
    return jnp.zeros((G, n_global), jnp.int64).at[
        jnp.broadcast_to(g_i, (G, M)), safe
    ].add(jnp.where(valid, touch_cnt, 0))


class MeshBatchScheduler:
    """BatchScheduler over a jax.sharding.Mesh: node axis sharded, pods
    replicated. Intended shape: one shard per chip on a v5e slice, DCN
    untouched (the pod scan is sequential by construction)."""

    def __init__(self, mesh: Optional[Mesh] = None, config: Optional[SchedulerConfig] = None):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (AXIS,))
        self.mesh = mesh
        self.config = config or SchedulerConfig()
        self._jitted = {}

    def schedule(
        self, snap: ClusterSnapshot, batch: PodBatch, last_node_index: int = 0
    ):
        n_dev = self.mesh.devices.size
        if len(snap.node_names) == 0:
            sched = BatchScheduler(self.config)
            return (
                np.full(batch.num_pods, -1, np.int32),
                sched.initial_carry(snap, last_node_index),
            )
        snap = _pad_snapshot(snap, n_dev)
        n = len(snap.node_names)
        n_per_shard = n // n_dev

        static = host_static(self.config, snap)
        pods = {f: np.asarray(getattr(batch, f))
                for f in BatchScheduler.POD_FIELDS}
        num_zones = max(int(snap.zone_id.max()) + 1, 1)

        num_values = int(snap.svc_num_values)
        hc = host_carry(snap, last_node_index)
        carry = tuple(hc[f] for f in CARRY_FIELDS)
        final, chosen = self._exec(
            static, carry, pods, n, n_per_shard, num_zones, num_values,
            batch.num_pods,
        )
        return fetch(chosen), final

    def _jit_for(self, static, n, n_per_shard, num_zones, num_values,
                 num_pods, pods_keys, empty=()):
        """The pjit-shaped sharded-scan program for one shape class:
        explicit in/out shardings, carry deliberately UNDONATED (see the
        NB below — donation + lax.scan inside shard_map miscompiles on
        this jaxlib's CPU backend, so a scan flush re-allocates its
        carry); host numpy inputs are placed per in_shardings on call.
        `empty` names the carry's zero-size leaves
        (_carry_out_shardings). Shared with analysis/programs so the
        audited program IS the dispatched one."""
        key = (n, n_per_shard, num_pods, num_zones, num_values,
               tuple(sorted(static)), empty)
        run = self._jitted.get(key)
        if run is None:
            body = functools.partial(
                _mesh_scan_fn, self.config, num_zones, n_per_shard, n,
                num_values,
            )

            def spmd(static_, carry_, pods_):
                with jax.named_scope("scan"):
                    final, chosen = jax.lax.scan(
                        functools.partial(body, static_), carry_, pods_
                    )
                return final, chosen

            specs = (
                _static_specs(static), CARRY_SPECS,
                {k: PSpec() for k in pods_keys},
            )
            sharded = jax.shard_map(
                spmd,
                mesh=self.mesh,
                in_specs=specs,
                out_specs=(CARRY_SPECS, PSpec()),
                check_vma=False,
            )
            # NB: the scan does NOT donate its carry. On this jaxlib's
            # CPU backend, donation + lax.scan inside shard_map
            # miscompiles the ServiceAntiAffinity path (aliased carry
            # buffers corrupt the all_gather'd peer tables mid-scan;
            # reproduced and pinned by test_parallel's SAA tests — the
            # fold programs, whose bodies are scan-free, alias
            # correctly and keep their donation). The scan is the
            # fallback path, so the realloc cost is off the hot wave.
            def mesh_scan(static_, carry_, pods_):
                return sharded(static_, carry_, pods_)

            run = jax.jit(
                mesh_scan,
                in_shardings=_ns_tree(self.mesh, specs),
                out_shardings=(
                    _carry_out_shardings(self.mesh, empty),
                    NamedSharding(self.mesh, PSpec()),
                ),
            )
            self._jitted[key] = run
        return run

    def _exec(self, static, carry, pods, n, n_per_shard, num_zones,
              num_values, num_pods):
        """Run the sharded scan with an EXTERNAL carry (the mesh wave's
        fallback flush threads its resident carry through here)."""
        run = self._jit_for(static, n, n_per_shard, num_zones,
                            num_values, num_pods, tuple(pods),
                            empty=empty_leaves(carry))
        with self.mesh:
            final, chosen = run(static, carry, pods)
        return final, chosen

    def schedule_names(self, snap: ClusterSnapshot, batch: PodBatch):
        names = list(snap.node_names)
        chosen, _ = self.schedule(snap, batch)
        return [names[i] if i >= 0 else None for i in chosen]


#: a pod row's fields that the resident state's host mirrors fold
#: (`ResidentClusterState.note_commit` / `note_scan`)
_COMMIT_FIELDS = ("commit_mcpu", "commit_mem", "commit_gpu", "nz_mcpu",
                  "nz_mem", "port_mask", "class_id")


def _opaque_blocks(config) -> tuple:
    """Resident carry blocks this config's scan/impure folds can touch
    in ways the host mirrors cannot track (they resync from the next
    snapshot instead)."""
    blocks = []
    if MATCH_INTER_POD_AFFINITY in config.predicates or any(
        n == INTER_POD_AFFINITY for n, _ in config.priorities
    ):
        blocks.append("ip")
    if any(k in config.predicates for k in (
        NO_DISK_CONFLICT, MAX_EBS_VOLUME_COUNT, MAX_GCE_PD_VOLUME_COUNT,
    )):
        blocks.append("vol")
    if service_config_labels(config):
        blocks.append("svc")
    return tuple(blocks)


def _sparse_counts(counts: np.ndarray, floor: int = 64):
    """Dense i64[N] commit counts -> (idx i64[M], cnt i64[M]) scatter
    form, M pow2-bucketed (compile reuse) and padded with idx=-1: the
    commit shipment is O(touched nodes) <= O(picks), never O(N)."""
    ids = np.nonzero(counts)[0]
    M = next_pow2(max(len(ids), 1), floor)
    idx = np.full(M, -1, np.int64)
    cnt = np.zeros(M, np.int64)
    idx[: len(ids)] = ids
    cnt[: len(ids)] = counts[ids]
    return idx, cnt


def _sparse_group_counts(counts_mat: np.ndarray, floor: int = 64):
    """Dense i64[G, N] -> (idx i64[G, M], cnt i64[G, M]) scatter form
    with a shared pow2 M bucket."""
    G = counts_mat.shape[0]
    nz = [np.nonzero(row)[0] for row in counts_mat]
    width = max((len(i) for i in nz), default=0)
    M = next_pow2(max(width, 1), floor)
    idx = np.full((G, M), -1, np.int64)
    cnt = np.zeros((G, M), np.int64)
    for g, ids in enumerate(nz):
        idx[g, : len(ids)] = ids
        cnt[g, : len(ids)] = counts_mat[g, ids]
    return idx, cnt


class MeshWaveScheduler(WaveCounts):
    """The wave fast path over a device mesh, resident-state edition
    (the mesh side of `models/waveloop.run_wave`'s device seam):
    probe tables computed per shard against the DEVICE-RESIDENT sharded
    cluster state (node axis sharded, one shard per chip), the replay on
    the host exactly as single-chip, and the commit fold applied per
    shard through a donated pjit program whose scatter-form input is
    O(picks).  Ineligible pods flush through the sharded scan with the
    SAME resident carry, so the combined output is bit-identical to both
    the single-chip wave and the serial oracle.  Wave-to-wave the node
    tables never leave the device: ``resident`` holds them, its host
    mirrors prove freshness, and only deltas (node add/remove scatter,
    invalidated blocks) ever re-ship."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 config: Optional[SchedulerConfig] = None,
                 min_run: int = 16, max_j: int = 1024,
                 pod_floor: int = 64, replay=None):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (AXIS,))
        self.mesh = mesh
        self.config = config or SchedulerConfig()
        self.scan = MeshBatchScheduler(mesh, config=self.config)
        self.min_run = min_run
        self.max_j = max_j
        self.pod_floor = pod_floor
        self._replay = replay or replay_fast
        self._probe_jit = {}
        self._apply_jit = {}
        # the device-resident sharded cluster state (+ transfer stats)
        self.resident = ResidentClusterState(mesh)
        self._blocks = _opaque_blocks(self.config)
        # reuse mode when the caller passes none: "auto" mirror-compares
        # (the daemon), "carry" trusts the resident carry, "reship"
        # re-places per wave (the r05-equivalent A/B baseline)
        self.reuse_default = "auto"
        # per-wave device-dispatch tally (tests assert the grouped path
        # keeps this independent of the template count)
        self.dispatches: dict = {}
        # all waves, under WaveScheduler.stats' keys and meanings
        # (`pods_by_path` adds up to the pods handed in; a window's
        # numbers are diffs), plus what only a mesh has: the picks that
        # landed on each shard's nodes (they add up to the pods placed)
        # and the resident state's shipped bytes
        self.stats = {
            "waves": 0, "dispatches": 0, "dispatches_by_kind": {},
            "pods_by_path": dict.fromkeys(PATHS, 0), "pods_unplaced": 0,
            **dict.fromkeys(GROUP_COUNTERS, 0),
            "steps_by_kind": dict.fromkeys(PATHS, 0),
            **dict.fromkeys(LOOP_COUNTERS, 0),
            **dict.fromkeys(ANTI_COUNTERS, 0),
            **dict.fromkeys(AFFINITY_COUNTERS, 0), "scan_reasons": {},
            "picks_by_shard": [0] * int(mesh.devices.size),
            "h2d_bytes_total": 0,
        }

    # -- pjit programs (builders shared with analysis/programs) --------------

    def _pjit_program(self, cache, key, body, arg_specs, out_specs,
                      donate_carry=False, out_shardings=None):
        """One compile-cache slot for every mesh program: shard_map(body)
        wrapped pjit-shaped (jit with in/out shardings built from the
        SAME PartitionSpecs the shard_map declares), the carry (argnum
        1) donated when asked.  The four program families below differ
        only in body/specs/donation — one builder keeps their wrapping
        from drifting.  The folds pass their own `out_shardings`
        (_carry_out_shardings; their key names the empty leaves).
        key[0] is the family and names the program: jit_mesh_<family>
        in a trace and among the compiles."""
        run = cache.get(key)
        if run is None:
            program = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=arg_specs,
                out_specs=out_specs,
                check_vma=False,
            )
            program.__name__ = "mesh_" + key[0]
            run = jax.jit(
                program,
                in_shardings=_ns_tree(self.mesh, arg_specs),
                out_shardings=(
                    _ns_tree(self.mesh, out_specs)
                    if out_shardings is None else out_shardings),
                donate_argnums=(1,) if donate_carry else (),
            )
            cache[key] = run
        return run

    def _probe_program(self, static, n, n_per_shard, num_zones,
                       num_values, J, pod_layout):
        # out spec P(None, AXIS): shard slices concatenate along the
        # node axis into the same global packed array the single-chip
        # probe ships
        return self._pjit_program(
            self._probe_jit,
            ("probe", n, n_per_shard, num_zones, num_values, J,
             pod_layout, tuple(sorted(static))),
            functools.partial(_mesh_probe_fn, self.config, num_zones,
                              num_values, J, n_per_shard, n, pod_layout),
            (_static_specs(static), CARRY_SPECS, PSpec()),
            PSpec(None, AXIS),
        )

    def _group_probe_program(self, static, n, n_per_shard, num_zones,
                             num_values, G, pod_layout):
        return self._pjit_program(
            self._probe_jit,
            ("group_probe", n, n_per_shard, num_zones, num_values, G,
             pod_layout, tuple(sorted(static))),
            functools.partial(_mesh_group_probe_fn, self.config,
                              num_zones, num_values, G, n_per_shard, n,
                              pod_layout),
            (_static_specs(static), CARRY_SPECS, PSpec()),
            PSpec(None, AXIS),
        )

    def _apply_program(self, static, n, n_per_shard, pod_layout,
                       donate=True, empty=()):
        """The commit fold: with donation (the runtime form; tests and
        the auditor also lower the undonated one) the carry input
        aliases the output — resident buffers mutate in place;
        scatter-form counts ride replicated. Different idx/cnt bucket
        sizes compile per shape under this one wrapper (jit's shape
        cache keys them)."""
        return self._pjit_program(
            self._apply_jit,
            ("apply", n, n_per_shard, pod_layout, donate, empty,
             tuple(sorted(static))),
            functools.partial(_mesh_apply_fn, self.config, pod_layout,
                              n),
            (_static_specs(static), CARRY_SPECS, PSpec(), PSpec(),
             PSpec()),
            CARRY_SPECS,
            donate_carry=donate,
            out_shardings=_carry_out_shardings(self.mesh, empty),
        )

    def _apply_group_program(self, static, n, n_per_shard, pod_layout,
                             donate=True, empty=()):
        return self._pjit_program(
            self._apply_jit,
            ("apply_group", n, n_per_shard, pod_layout, donate, empty,
             tuple(sorted(static))),
            functools.partial(_mesh_apply_group_fn, self.config,
                              pod_layout, n),
            (_static_specs(static), CARRY_SPECS, PSpec(), PSpec(),
             PSpec()),
            CARRY_SPECS,
            donate_carry=donate,
            out_shardings=_carry_out_shardings(self.mesh, empty),
        )

    # -- backlog driver ------------------------------------------------------

    def schedule_backlog(
        self,
        snap: ClusterSnapshot,
        batch: PodBatch,
        rep_idx: np.ndarray,
        last_node_index: int = 0,
        reuse: Optional[str] = None,
    ):
        """Single-chip WaveScheduler.schedule_backlog semantics over the
        mesh: -> (chosen i32[P] node ids, final carry, lastNodeIndex).
        snap must already be padded to a mesh multiple.  `reuse` governs
        the resident state: "auto" mirror-compares against the snapshot
        and ships only deltas; "carry" trusts the resident carry
        outright (steady loops whose snapshot is the stale wave-0 view);
        "reship" re-places everything (the r05-equivalent baseline kept
        for A/B measurement)."""
        snap = _pad_snapshot(snap, self.mesh.devices.size)
        wave = Wave(self.config, snap, batch, rep_idx, int(last_node_index),
                    self.max_j, self._replay)
        reuse = reuse or self.reuse_default
        self.dispatches = {}
        self.stats["waves"] += 1
        self.resident.begin_wave()
        # "transfer": the mirror compare and whatever it ships (deltas
        # as row scatters, a changed replicated table whole)
        with phase_timer("transfer"):
            wave.static, wave.carry = self.resident.sync(
                self.config, snap, last_node_index, reuse=reuse
            )
        runs, policy = self.plan(wave, reuse)
        run_wave(self, wave, runs, policy)
        self._count_wave(wave, runs)
        return wave.out, wave.carry, wave.L_host

    def plan(self, wave: Wave, reuse: Optional[str] = None):
        """-> (the wave's classified runs, the policy that cuts them
        into steps): `WaveScheduler.plan` without gangs or device
        replays. The r05 dispatch shape (a full probe for a lone pure
        run) is kept under reuse="reship" as the A/B baseline."""
        runs = classify_runs(
            self.config, wave.snap, wave.batch, split_runs(wave.rep_idx),
            wave.num_values, self.min_run, zoned=wave.zoned,
        )
        return runs, Policy(
            host_group_cap(wave.N),
            lone_pure_grouped=(reuse or self.reuse_default) != "reship")

    def _count_wave(self, wave: Wave, runs) -> None:
        super()._count_wave(wave, runs)
        placed = wave.out[wave.out >= 0]
        shards = self.stats["picks_by_shard"]
        for shard, n in enumerate(np.bincount(
                placed // self._per_shard(wave),
                minlength=len(shards)).tolist()):
            shards[shard] += n
        self.stats["h2d_bytes_total"] = \
            self.resident.stats["h2d_bytes_total"]

    # -- the device seam (models/waveloop.run_wave) --------------------------

    #: the exact host usage mirror lets even a SINGLETON pure run ride
    #: the header-only probe (`waveloop.group_buffer`)
    group_floor = 1

    def _per_shard(self, wave: Wave) -> int:
        return wave.N // self.mesh.devices.size

    def place(self, buf):
        """Commit a packed pod/group buffer once per run: both the
        probe and the fold consume the SAME device copy (a host numpy
        arg would re-upload at every dispatch), and the shipment is
        counted once."""
        with phase_timer("transfer"):
            dev = jax.device_put(buf, NamedSharding(self.mesh, PSpec()))
            self.resident.count_h2d(buf.nbytes)
        return dev

    def scan_pending(self, wave: Wave, rows: np.ndarray):
        """The sharded scan over the resident carry, its commits noted
        on the resident state's host mirrors."""
        with phase_timer("transfer"):
            seg = gather_batch(wave.batch, wave.rep_idx[rows])
            segp = pad_batch(seg, next_pow2(len(rows), self.pod_floor))
            pods = {
                f: np.asarray(getattr(segp, f))
                for f in BatchScheduler.POD_FIELDS
            }
            self.resident.count_h2d(sum(v.nbytes for v in pods.values()))
            pods = jax.device_put(pods, NamedSharding(self.mesh, PSpec()))
        # "score", as on one chip: from the dispatch of the sharded
        # scan to the host's read of its picks
        with phase_timer("score"):
            self._count("scan")
            wave.carry, chosen = self.scan._exec(
                wave.static, wave.carry, pods, wave.N,
                self._per_shard(wave), wave.num_zones, wave.num_values,
                segp.num_pods,
            )
            self.resident.set_carry(wave.carry)
            with device_wait():
                chosen = np.asarray(chosen)[: len(rows)]
                last = int(jax.device_get(
                    wave.carry[BatchScheduler.LAST_IDX]))
        # host-visible pure-channel commits keep the mirrors exact;
        # the opaque feature blocks resync from the next snapshot
        segf = {f: np.asarray(getattr(seg, f)) for f in _COMMIT_FIELDS}
        self.resident.note_scan(
            [{k: v[i] for k, v in segf.items()} for i in range(len(rows))],
            chosen,
        )
        # invalidate only the blocks these pods can actually have
        # folded on device: a featureless scan wave (the daemon's
        # small mixed waves) must not force a next-wave resync
        inv = [block for block, names in (
            ("ip", ("ip_match_spec", "ip_own_hard", "ip_own_pref",
                    "ip_own_anti_hard", "ip_own_anti_pref")),
            ("vol", ("vp_vol_rw", "vp_vol_ro", "vp_ebs", "vp_gce")),
            ("svc", ("svc_member",)),
        ) if block in self._blocks
            and any(np.asarray(getattr(seg, f)).any() for f in names)]
        if inv:
            self.resident.invalidate(*inv)
        return chosen, last

    def probe_run(self, wave: Wave, run, layout, buf, J: int, rows: int):
        self._count("probe")
        program = self._probe_program(
            wave.static, wave.N, self._per_shard(wave), wave.num_zones,
            wave.num_values, J, layout)
        with phase_timer("probe"), self.mesh:
            raw = program(wave.static, wave.carry, buf)
            with device_wait():
                arr = np.ascontiguousarray(jax.device_get(raw))
        return tables_from_packed(self.config, arr, wave.num_zones, J, rows,
                                  **wave.table_context(run))

    def _fold(self, wave: Wave, program, buf, idx, cnt) -> None:
        """A donated commit fold, applied at once: the resident carry
        and its host mirrors, which the next grouped replay reads,
        never part. Commits ship in scatter form, O(picks)."""
        self._count("apply")
        self.resident.count_h2d(idx.nbytes + cnt.nbytes)
        with phase_timer("replay"), self.mesh:
            wave.carry = program(wave.static, wave.carry, buf, idx, cnt)
            # drain the donated fold before anything can re-donate its
            # aliased buffers (the fold is the last dispatch of its
            # run, so only fold-vs-host bookkeeping overlap is lost)
            with device_wait():
                jax.block_until_ready(wave.carry)
        self.resident.set_carry(wave.carry)

    def _note_commit(self, wave: Wave, run, counts) -> None:
        self.resident.note_commit(
            {f: np.asarray(getattr(wave.batch, f)[run.rep])
             for f in _COMMIT_FIELDS}, counts)

    def commit_run(self, wave: Wave, run, layout, buf, counts) -> None:
        self._fold(wave, self._apply_program(
            wave.static, wave.N, self._per_shard(wave), layout,
            empty=empty_leaves(wave.carry)), buf, *_sparse_counts(counts))
        self._note_commit(wave, run, counts)
        if self._blocks and not run.pure:
            # impure-but-eligible runs fold ip/svc tables on
            # device; those mirrors go opaque until resynced
            self.resident.invalidate(*self._blocks)

    def probe_group(self, wave: Wave, G_bucket: int, layout, buf):
        """-> (headers i64[G, N_STK_ROWS, N], usage): the grouped
        header probe for G stacked runs, ONE sharded dispatch and ONE
        device->host transfer (the resource block does not ship: the
        resident host mirror supplies the replay's usage exactly)."""
        self._count("group_probe")
        program = self._group_probe_program(
            wave.static, wave.N, self._per_shard(wave), wave.num_zones,
            wave.num_values, G_bucket, layout)
        with phase_timer("probe"), self.mesh:
            raw = program(wave.static, wave.carry, buf)
            with device_wait():
                arr = np.ascontiguousarray(jax.device_get(raw))
        wave.tallies["group_d2h_bytes"] += arr.nbytes
        # the mirror's copy is the host replay's input, booked with it
        with phase_timer("replay"):
            return (arr.reshape(G_bucket, N_STK_ROWS, wave.N),
                    self.resident.usage())

    def commit_group(self, wave: Wave, runs, G_bucket: int, layout, buf,
                     counts_mat) -> None:
        cm = np.zeros((G_bucket, wave.N), np.int64)
        cm[:len(runs)] = counts_mat
        self._fold(wave, self._apply_group_program(
            wave.static, wave.N, self._per_shard(wave), layout,
            empty=empty_leaves(wave.carry)), buf,
            *_sparse_group_counts(cm))
        for run, counts in zip(runs, counts_mat):
            if counts.any():
                self._note_commit(wave, run, counts)

    def finish(self, wave: Wave) -> None:
        self.resident.finish_wave(wave.carry, wave.L_host)
