"""Batched generic scheduler: the whole backlog as one device program.

The reference schedules 50k pods as 50k serial scheduleOne cycles
(scheduler.go:93), each a fresh O(nodes x predicates) CPU scan. Here the
backlog is a single jitted loop, one step a pod, whose carry is the
mutable slice of the cluster state (requested/nonzero resources, pod
counts, port masks, per-class pod counts, lastNodeIndex, the inter-pod
domain tables) and whose per-step body is:

    fit[N]    = AND of predicate masks          (ops.predicates)
    score[N]  = sum_i weight_i * priority_i[N]  (ops.priorities)
    chosen    = deterministic argmax w/ name-desc round-robin (ops.select)
    carry'    = carry + commit(pod, chosen)     (AssumePod analogue)

which is bit-identical to the serial loop because the commit threading
reproduces scheduler.go:122 AssumePod between cycles and the selection
reproduces selectHost exactly.

The 17-leaf carry is what callers hand in and get back (models/wave.py,
the probe, the folds, the encoder's kept tables). Inside the loop it
travels as `(carry, views)`: the views are the per-node expansions of
the five inter-pod tables (ops/interpod.Views), made once a dispatch
from the incoming carry, read by every step in place of a gather and
committed beside the tables; they are loop-local and dropped when the
scan returns.

The loop is a `lax.while_loop` over the pod axis, which is what
`lax.scan` lowers to, with its trip count an operand (`scan_backlog`'s
`count`): the pod axis is padded to a bucket so that a bucket is one
program, and the loop ends at the backlog's real length instead of
running the whole step on every padded row.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.ops import interpod as IP
from kubernetes_tpu.ops import predicates as P
from kubernetes_tpu.ops import priorities as R
from kubernetes_tpu.ops import select as S
from kubernetes_tpu.ops import services as SV
from kubernetes_tpu.ops import volumes as V
from kubernetes_tpu.snapshot.encode import (
    ClusterSnapshot,
    PodBatch,
    service_config_labels,
)

# predicate keys (factory/plugins.go registry names)
GENERAL_PREDICATES = "GeneralPredicates"
POD_TOLERATES_NODE_TAINTS = "PodToleratesNodeTaints"
CHECK_NODE_MEMORY_PRESSURE = "CheckNodeMemoryPressure"
MATCH_INTER_POD_AFFINITY = "MatchInterPodAffinity"
NO_DISK_CONFLICT = "NoDiskConflict"
NO_VOLUME_ZONE_CONFLICT = "NoVolumeZoneConflict"
MAX_EBS_VOLUME_COUNT = "MaxEBSVolumeCount"
MAX_GCE_PD_VOLUME_COUNT = "MaxGCEPDVolumeCount"
# GeneralPredicates components, individually addressable so a Policy file
# naming them resolves onto the device (plugins.go legacy keys)
POD_FITS_RESOURCES = "PodFitsResources"
POD_FITS_HOST_PORTS = "PodFitsHostPorts"
POD_FITS_PORTS = "PodFitsPorts"  # legacy alias (defaults.go:77)
HOST_NAME = "HostName"
MATCH_NODE_SELECTOR = "MatchNodeSelector"


def wants_resources(config: "SchedulerConfig") -> bool:
    return (GENERAL_PREDICATES in config.predicates
            or POD_FITS_RESOURCES in config.predicates)


def wants_host(config: "SchedulerConfig") -> bool:
    return (GENERAL_PREDICATES in config.predicates
            or HOST_NAME in config.predicates)


def wants_ports(config: "SchedulerConfig") -> bool:
    return (GENERAL_PREDICATES in config.predicates
            or POD_FITS_HOST_PORTS in config.predicates
            or POD_FITS_PORTS in config.predicates)


def wants_selector(config: "SchedulerConfig") -> bool:
    return (GENERAL_PREDICATES in config.predicates
            or MATCH_NODE_SELECTOR in config.predicates)

LEAST_REQUESTED = "LeastRequestedPriority"
BALANCED_ALLOCATION = "BalancedResourceAllocation"
SELECTOR_SPREAD = "SelectorSpreadPriority"
NODE_AFFINITY = "NodeAffinityPriority"
TAINT_TOLERATION = "TaintTolerationPriority"
INTER_POD_AFFINITY = "InterPodAffinityPriority"
EQUAL = "EqualPriority"
IMAGE_LOCALITY = "ImageLocalityPriority"
# config-parameterized entries (Policy args, api/types.go:60-94) are
# tuples: ("CheckNodeLabelPresence", (labels...), presence) as a predicate,
# (("NodeLabelPriority", label, presence), weight) as a priority
NODE_LABEL_PREDICATE = "CheckNodeLabelPresence"
NODE_LABEL_PRIORITY = "NodeLabelPriority"
SERVICE_AFFINITY = "ServiceAffinity"
SERVICE_ANTI_AFFINITY = "ServiceAntiAffinity"


@dataclass(frozen=True)
class SchedulerConfig:
    """Static (compile-time) algorithm configuration — the analogue of a
    resolved algorithm provider (defaults.go:55 init)."""

    # defaults.go:116 defaultPredicates (full set; order is irrelevant for
    # fit/no-fit — the masks AND together)
    predicates: Tuple[str, ...] = (
        NO_DISK_CONFLICT,
        NO_VOLUME_ZONE_CONFLICT,
        MAX_EBS_VOLUME_COUNT,
        MAX_GCE_PD_VOLUME_COUNT,
        GENERAL_PREDICATES,
        POD_TOLERATES_NODE_TAINTS,
        CHECK_NODE_MEMORY_PRESSURE,
        MATCH_INTER_POD_AFFINITY,
    )
    priorities: Tuple[Tuple[str, int], ...] = (
        (LEAST_REQUESTED, 1),
        (BALANCED_ALLOCATION, 1),
        (SELECTOR_SPREAD, 1),
        (NODE_AFFINITY, 1),
        (TAINT_TOLERATION, 1),
        (INTER_POD_AFFINITY, 1),
    )
    # --hard-pod-affinity-symmetric-weight (options.go:52)
    hard_pod_affinity_weight: int = 1
    # defaults.go:37-53 (KUBE_MAX_PD_VOLS overrides in the daemon shell)
    max_ebs_volumes: int = 39
    max_gce_pd_volumes: int = 16


def interpod_carry_tables(static, ip_term_count, num_nodes):
    """cnt_lt — the per-node expansion of the inter-pod term counts
    carried between steps. Shared by the scan body and the wave probe
    (models/probe.py)."""
    cnt_u = IP.gather_counts(
        ip_term_count, static["ip_u_topo"], static["ip_topo_dom"]
    )
    return IP.expand_lt(
        cnt_u, static["ip_lt_u"], static["ip_lt_sign"], num_nodes
    )


def wants_interpod(config: "SchedulerConfig") -> bool:
    """The config reads the inter-pod tables: the predicate or the
    priority."""
    return (MATCH_INTER_POD_AFFINITY in config.predicates
            or any(n == INTER_POD_AFFINITY for n, _ in config.priorities))


def interpod_views(config: "SchedulerConfig", static, carry):
    """The views of a carry's five inter-pod tables (ops/interpod.Views);
    None where the config has neither the predicate nor the priority."""
    if not wants_interpod(config):
        return None
    return IP.interpod_views(
        *carry[4:9], static["ip_topo_dom"], static["ip_u_topo"],
        static["ip_lt_u"], static["ip_lt_sign"], carry[0].shape[1],
    )


def fit_mask(
    config: "SchedulerConfig",
    static,
    carry,
    pod,
    cnt_lt,
    include_resources: bool = True,
    own_lt=None,
):
    """The full predicate AND for one pod against one carry state.

    `include_resources=False` drops the carry-dependent PodFitsResources
    term (the wave probe tabulates it separately over the commit count —
    models/probe.py); everything else is evaluated against the given
    carry exactly as the serial scan does. `own_lt` is the view of the
    carry's `ip_own_anti` where the caller carries it (the scan); absent,
    it is gathered here."""
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
    num_nodes = req_mcpu.shape[0]
    svc_labels = service_config_labels(config)
    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)

    fit = ~pod["unschedulable"]
    if want_ip_prio:
        # a bad assigned-pod annotation errors the priority for every pod
        fit = fit & ~pod["ip_poison"]
    if NO_DISK_CONFLICT in config.predicates:
        fit = fit & V.no_disk_conflict(
            pod["vp_vol_rw"], pod["vp_vol_ro"], vol_any, vol_rw
        )
    if NO_VOLUME_ZONE_CONFLICT in config.predicates:
        fit = fit & V.volume_zone(
            pod["vp_vz_zone"],
            pod["vp_vz_region"],
            pod["vp_vz_fail"],
            static["vz_zone"],
            static["vz_region"],
            static["vz_has"],
        )
    if MAX_EBS_VOLUME_COUNT in config.predicates:
        fit = fit & V.max_pd_count(
            pod["vp_ebs"],
            pod["vp_ebs_bad"],
            pod["vp_has_ebs"],
            ebs_mask,
            static["ebs_bad"],
            config.max_ebs_volumes,
        )
    if MAX_GCE_PD_VOLUME_COUNT in config.predicates:
        fit = fit & V.max_pd_count(
            pod["vp_gce"],
            pod["vp_gce_bad"],
            pod["vp_has_gce"],
            gce_mask,
            static["gce_bad"],
            config.max_gce_pd_volumes,
        )
    if wants_resources(config) and include_resources:
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
    if wants_host(config):
        fit = fit & P.pod_fits_host(pod["host_req"], static["alloc_mcpu"].shape[0])
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
        fit = fit & P.check_node_memory_pressure(
            pod["best_effort"], static["mem_pressure"]
        )
    for entry in config.predicates:
        if isinstance(entry, tuple) and entry[0] == NODE_LABEL_PREDICATE:
            # per-node static mask resolved host-side (predicates.go:552)
            for lbl in entry[1]:
                has = static[f"nl_pred_{lbl}"]
                fit = fit & (has if entry[2] else ~has)
        elif isinstance(entry, tuple) and entry[0] == SERVICE_AFFINITY:
            fit = fit & SV.service_affinity(
                svc_first_peer,
                static["svc_lbl_val"],
                static["svc_ord_node"],
                pod["svc_group"],
                pod["svc_fixed"],
                tuple(svc_labels.index(l) for l in entry[1]),
                num_nodes,
            )
    if want_ip_pred:
        if own_lt is None:
            own_lt = IP.gather_lt(
                ip_own_anti,
                static["ip_u_topo"],
                static["ip_topo_dom"],
                static["ip_lt_u"],
                static["ip_lt_sign"],
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
            num_nodes,
        )
    return fit


def evaluate_pod(config: SchedulerConfig, num_zones: int, num_values: int, static, carry, pod,
                 views=None):
    """Fit mask + weighted priority total for one pod against a frozen
    carry — Schedule() up to selectHost (generic_scheduler.go:72-115).
    Shared by the scan body and debug_evaluate (the conformance probe for
    ported reference test tables). `views` are the carry's inter-pod
    views where the caller carries them (the scan); absent, they are
    derived from the carry's tables here."""
    (
        # res: i64 (6, N) = [req_mcpu, req_mem, req_gpu, nz_mcpu, nz_mem,
        # pod_count] stacked so the per-step commit is ONE scatter (the
        # scan body is fusion-count-bound on TPU)
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
    num_nodes = req_mcpu.shape[0]
    svc_labels = service_config_labels(config)

    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)
    if views is None:
        views = interpod_views(config, static, carry)
    cnt_lt, own_lt = (None, None) if views is None else views[:2]

    fit = fit_mask(config, static, carry, pod, cnt_lt, include_resources=True,
                   own_lt=own_lt)

    score = jnp.zeros(req_mcpu.shape, jnp.int64)
    for name, weight in config.priorities:
        if name == LEAST_REQUESTED:
            s = R.least_requested(
                pod["nz_mcpu"],
                pod["nz_mem"],
                nz_mcpu,
                nz_mem,
                static["alloc_mcpu"],
                static["alloc_mem"],
            )
        elif name == BALANCED_ALLOCATION:
            s = R.balanced_resource_allocation(
                pod["nz_mcpu"],
                pod["nz_mem"],
                nz_mcpu,
                nz_mem,
                static["alloc_mcpu"],
                static["alloc_mem"],
            )
        elif name == SELECTOR_SPREAD:
            s = R.spread_score(
                pod["has_selectors"],
                R.spread_counts(class_count, pod["spread_match"]),
                static["zone_id"],
                num_zones,
                fit,
            )
        elif name == NODE_AFFINITY:
            s = R.node_affinity_preferred(
                pod["pref_valid"],
                pod["pref_weight"],
                pod["pref_ops"],
                pod["pref_key"],
                pod["pref_set"],
                pod["pref_numkey"],
                pod["pref_num"],
                static["label_kv"],
                static["label_key"],
                static["numval"],
                static["set_table"],
                fit,
            )
        elif name == TAINT_TOLERATION:
            s = R.taint_toleration(
                pod["intolerable_prefer"],
                static["taint_count"],
                fit,
            )
        elif name == INTER_POD_AFFINITY:
            s = IP.interpod_priority(
                cnt_lt,
                views.rev_hard_lt,
                views.rev_pref_lt,
                views.rev_anti_lt,
                static["ip_lt_spec"],
                pod["ip_match_spec"],
                pod["ip_fwd_lt"],
                pod["ip_fwd_w"],
                config.hard_pod_affinity_weight,
                fit,
                num_nodes,
            )
        elif name == EQUAL:
            s = R.equal(req_mcpu.shape[0])
        elif name == IMAGE_LOCALITY:
            s = R.image_locality(static["img_size"], pod["img_count"])
        elif isinstance(name, tuple) and name[0] == NODE_LABEL_PRIORITY:
            s = R.node_label(static[f"nl_prio_{name[1]}"], name[2])
        elif isinstance(name, tuple) and name[0] == SERVICE_ANTI_AFFINITY:
            s = SV.service_anti_affinity(
                svc_peer_node_count,
                svc_peer_total,
                static["svc_lbl_val"][svc_labels.index(name[1])],
                pod["svc_group"],
                fit,
                num_values,
                num_nodes,
            )
        else:
            raise ValueError(f"unknown priority {name!r}")
        score = score + jnp.int64(weight) * s

    return fit, score


def _scan_fn(config: SchedulerConfig, num_zones: int, num_values: int, static, dom_lt,
             loop, pod):
    """One step of the scan. `loop` is `(carry, views)` and `dom_lt` the
    domain ids the views were gathered at (ops/interpod.lt_domains),
    which no commit moves; with views None the step derives them from
    the carry's tables, as whoever holds a frozen carry does."""
    carry, views = loop
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
    svc_labels = service_config_labels(config)
    want_ip_pred = MATCH_INTER_POD_AFFINITY in config.predicates
    want_ip_prio = any(n == INTER_POD_AFFINITY for n, _ in config.priorities)

    fit, score = evaluate_pod(config, num_zones, num_values, static, carry, pod, views)

    chosen, scheduled = S.select_host(score, fit, last_idx, static["name_desc_order"])

    # commit (AssumePod): fold the pod into the carry where scheduled.
    # NodeInfo accounting uses container sums WITHOUT the init-container
    # max rule (node_info.go:158), hence commit_* not req_*.
    safe = jnp.maximum(chosen, 0)
    inc = scheduled.astype(jnp.int64)
    res = res.at[:, safe].add(
        jnp.stack(
            [
                pod["commit_mcpu"],
                pod["commit_mem"],
                pod["commit_gpu"],
                pod["nz_mcpu"],
                pod["nz_mem"],
                jnp.int64(1),
            ]
        )
        * inc
    )
    port_mask = port_mask.at[safe].set(
        jnp.where(scheduled, port_mask[safe] | pod["port_mask"], port_mask[safe])
    )
    class_count = class_count.at[safe, pod["class_id"]].add(inc)
    last_idx = last_idx + inc
    if views is not None:
        views = IP.interpod_commit_views(
            views,
            dom_lt,
            ip_own_anti.shape[2],
            static["ip_u_spec"],
            static["ip_lt_u"],
            static["ip_lt_sign"],
            pod["ip_match_spec"],
            pod["ip_own_hard"],
            pod["ip_own_pref"],
            pod["ip_own_anti_hard"],
            pod["ip_own_anti_pref"],
            chosen,
            scheduled,
        )
    if want_ip_pred or want_ip_prio:
        (
            ip_term_count,
            ip_own_anti,
            ip_rev_hard,
            ip_rev_pref,
            ip_rev_anti,
            ip_spec_total,
        ) = IP.interpod_commit(
            ip_term_count,
            ip_own_anti,
            ip_rev_hard,
            ip_rev_pref,
            ip_rev_anti,
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
        for k in (
            NO_DISK_CONFLICT,
            MAX_EBS_VOLUME_COUNT,
            MAX_GCE_PD_VOLUME_COUNT,
        )
    ):
        sel = jnp.where(scheduled, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        vol_any = vol_any.at[safe].set(vol_any[safe] | ((pod["vp_vol_rw"] | pod["vp_vol_ro"]) & sel))
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
    )
    return (carry, views), chosen


def _run_steps(step, loop, pods, count):
    """`step` over rows 0..count-1 of `pods` from `loop`, as `lax.scan`
    runs it over all of them -> (loop, chosen i32[P], the steps run).
    `count` is a traced int32, so the loop is a `while` and every count
    shares the bucket's program. `chosen` starts at -1 and a step writes
    its own row: past `count` it reads what a padded step (`pad_batch`'s
    unschedulable rows, which fit nowhere and commit nothing) answered
    when the loop still ran them."""
    num_pods = jax.tree.leaves(pods)[0].shape[0]
    count = jnp.minimum(count, jnp.int32(num_pods))

    def body(state):
        i, loop, chosen = state
        pod = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
            pods)
        loop, pick = step(loop, pod)
        return i + 1, loop, jax.lax.dynamic_update_index_in_dim(
            chosen, pick.astype(jnp.int32), i, 0)

    steps, loop, chosen = jax.lax.while_loop(
        lambda state: state[0] < count, body,
        (jnp.int32(0), loop, jnp.full((num_pods,), -1, jnp.int32)))
    return loop, chosen, steps


def scan_backlog(config: SchedulerConfig, num_zones: int, num_values: int, static, carry, pods,
                 count):
    """The backlog's scan -> (final carry, chosen[P], steps): the views
    made once from the incoming carry, the first `count` pods' steps
    (`_run_steps`; `count` an int32 scalar, `P` for all of them), and
    the carry alone handed back. `chosen[count:]` is -1, and `steps` is
    the loop's own counter where it stopped."""
    views = interpod_views(config, static, carry)
    dom_lt = None if views is None else IP.lt_domains(
        static["ip_u_topo"], static["ip_topo_dom"], static["ip_lt_u"])
    step = functools.partial(
        _scan_fn, config, num_zones, num_values, static, dom_lt)
    (final, _), chosen, steps = _run_steps(step, (carry, views), pods, count)
    return final, chosen, steps


class BatchScheduler:
    """Schedule a pending-pod backlog against a snapshot, bit-identically
    to the serial reference loop. One compile per (N, P, widths) shape."""

    # carry tuple index of selectHost's round-robin counter
    LAST_IDX = 3

    POD_FIELDS = [
        "req_mcpu",
        "req_mem",
        "req_gpu",
        "zero_req",
        "commit_mcpu",
        "commit_mem",
        "commit_gpu",
        "nz_mcpu",
        "nz_mem",
        "host_req",
        "port_mask",
        "ns_ops",
        "ns_key",
        "ns_set",
        "ns_numkey",
        "ns_num",
        "aff_has_req",
        "aff_term_valid",
        "aff_ops",
        "aff_key",
        "aff_set",
        "aff_numkey",
        "aff_num",
        "pref_valid",
        "pref_weight",
        "pref_ops",
        "pref_key",
        "pref_set",
        "pref_numkey",
        "pref_num",
        "tol_mask",
        "intolerable_prefer",
        "has_tolerations",
        "best_effort",
        "has_selectors",
        "spread_match",
        "class_id",
        "unschedulable",
        "ip_match_spec",
        "ip_ha_lt",
        "ip_ha_self",
        "ip_hq_lt",
        "ip_fwd_lt",
        "ip_fwd_w",
        "ip_own_hard",
        "ip_own_pref",
        "ip_own_anti_hard",
        "ip_own_anti_pref",
        "ip_has_affinity",
        "ip_has_anti",
        "ip_sym_reject",
        "ip_poison",
        "vp_vol_rw",
        "vp_vol_ro",
        "vp_ebs",
        "vp_gce",
        "vp_ebs_bad",
        "vp_gce_bad",
        "vp_has_ebs",
        "vp_has_gce",
        "vp_vz_zone",
        "vp_vz_region",
        "vp_vz_fail",
        "img_count",
        "svc_group",
        "svc_member",
        "svc_fixed",
    ]
    STATIC_FIELDS = [
        "alloc_mcpu",
        "alloc_mem",
        "alloc_gpu",
        "alloc_pods",
        "label_kv",
        "label_key",
        "numval",
        "taint_mask",
        "taint_count",
        "has_taints",
        "taint_bad",
        "mem_pressure",
        "zone_id",
        "name_desc_order",
        "set_table",
        "noschedule_taints",
        "prefer_taints",
        "ip_topo_dom",
        "ip_u_topo",
        "ip_u_spec",
        "ip_lt_spec",
        "ip_lt_u",
        "ip_lt_sign",
        "ebs_bad",
        "gce_bad",
        "vz_zone",
        "vz_region",
        "vz_has",
        "img_size",
        "svc_lbl_val",
        "svc_node_ord",
        "svc_ord_node",
    ]

    @classmethod
    def config_static(cls, config: "SchedulerConfig", snap: ClusterSnapshot):
        """Per-node static arrays for config-parameterized entries
        (NodeLabel predicates/priorities), resolved from the snapshot's
        host-side key vocab.  Returned as HOST arrays: every consumer
        feeds a jit boundary (which places them) or the mesh resident
        placement (which shards them) — the one resolution site serves
        both."""
        out = {}
        for entry in config.predicates:
            if isinstance(entry, tuple) and entry[0] == NODE_LABEL_PREDICATE:
                for lbl in entry[1]:
                    out[f"nl_pred_{lbl}"] = np.asarray(snap.node_has_key(lbl))
        for name, _w in config.priorities:
            if isinstance(name, tuple) and name[0] == NODE_LABEL_PRIORITY:
                out[f"nl_prio_{name[1]}"] = np.asarray(snap.node_has_key(name[1]))
        return out

    def __init__(self, config: Optional[SchedulerConfig] = None):
        self.config = config or SchedulerConfig()
        self._jitted = {}

    def _compiled(self, num_zones: int, num_values: int = 0):
        key = (num_zones, num_values)
        fn = self._jitted.get(key)
        if fn is None:
            config = self.config

            @jax.jit
            def batch_scan(static, carry, pods, count):
                with jax.named_scope("scan"):
                    return scan_backlog(
                        config, num_zones, num_values, static, carry, pods,
                        count)

            fn = batch_scan
            self._jitted[key] = fn
        return fn

    def initial_carry(self, snap: ClusterSnapshot, last_node_index: int = 0):
        from kubernetes_tpu.snapshot.encode import RES_CARRY_FIELDS

        return (
            jnp.stack(
                [jnp.asarray(getattr(snap, f)) for f in RES_CARRY_FIELDS]
            ),
            jnp.asarray(snap.port_mask),
            jnp.asarray(snap.class_count),
            # selectHost's persistent round-robin counter
            # (generic_scheduler.go:127 lastNodeIndex) — callers scheduling
            # successive waves thread the final value back in
            jnp.int64(last_node_index),
            jnp.asarray(snap.ip_term_count),
            jnp.asarray(snap.ip_own_anti),
            jnp.asarray(snap.ip_rev_hard),
            jnp.asarray(snap.ip_rev_pref),
            jnp.asarray(snap.ip_rev_anti),
            jnp.asarray(snap.ip_spec_total),
            jnp.asarray(snap.vol_any),
            jnp.asarray(snap.vol_rw),
            jnp.asarray(snap.ebs_mask),
            jnp.asarray(snap.gce_mask),
            jnp.asarray(snap.svc_first_peer),
            jnp.asarray(snap.svc_peer_node_count),
            jnp.asarray(snap.svc_peer_total),
        )

    def schedule(
        self, snap: ClusterSnapshot, batch: PodBatch, last_node_index: int = 0
    ):
        """Returns (chosen_node_index[P] int32 with -1 == unschedulable,
        final_carry). final_carry[LAST_IDX] is the post-wave lastNodeIndex.
        The whole batch is scheduled: the loop's trip count
        (`scan_backlog`'s `count`) is P here."""
        if snap.num_nodes == 0:
            # empty cluster: every pod fails with FitError in the reference
            return (
                np.full(batch.num_pods, -1, np.int32),
                self.initial_carry(snap, last_node_index),
            )
        static = {f: jnp.asarray(getattr(snap, f)) for f in self.STATIC_FIELDS}
        static.update(self.config_static(self.config, snap))
        pods = {f: jnp.asarray(getattr(batch, f)) for f in self.POD_FIELDS}
        num_zones = int(snap.zone_id.max()) + 1 if snap.zone_id.size else 1
        # num_zones must cover the vocab; zone ids are dense from encoding
        run = self._compiled(max(num_zones, 1), int(snap.svc_num_values))
        final, chosen, _steps = run(
            static, self.initial_carry(snap, last_node_index), pods,
            np.int32(batch.num_pods),
        )
        return np.asarray(chosen), final

    def schedule_names(self, snap: ClusterSnapshot, batch: PodBatch):
        """Like schedule() but returns node names (None == unschedulable)."""
        chosen, _ = self.schedule(snap, batch)
        return [snap.node_names[i] if i >= 0 else None for i in chosen]

    def debug_evaluate(self, snap: ClusterSnapshot, batch: PodBatch):
        """Per-(pod, node) fit and weighted score against the initial carry,
        with no commits between pods. This is how the reference unit tables
        (predicates_test.go / priorities_test.go) exercise each function:
        every case is evaluated against a frozen NodeInfo. Returns
        (fit[P, N] bool, score[P, N] int64) as numpy."""
        static = {f: jnp.asarray(getattr(snap, f)) for f in self.STATIC_FIELDS}
        static.update(self.config_static(self.config, snap))
        pods = {f: jnp.asarray(getattr(batch, f)) for f in self.POD_FIELDS}
        num_zones = max(int(snap.zone_id.max()) + 1 if snap.zone_id.size else 1, 1)
        carry = self.initial_carry(snap)
        fn = functools.partial(
            evaluate_pod, self.config, num_zones, int(snap.svc_num_values), static, carry
        )
        fit, score = jax.vmap(fn)(pods)
        return np.asarray(fit), np.asarray(score)
