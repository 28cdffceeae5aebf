"""Algorithm providers: DefaultProvider + the TPU provider.

Reference: plugin/pkg/scheduler/algorithmprovider/defaults/defaults.go
(init:55; defaultPredicates:116; defaultPriorities:162; legacy aliases
:60-81). The "TPUProvider" registers the same predicate/priority keys
but supplies an algorithm factory that runs the batched device program
(models/batch.py) instead of the per-pod host loop — the framework's
whole point.

Env knob parity: KUBE_MAX_PD_VOLS (defaults.go:41-53).
"""

from __future__ import annotations

import functools
import os

from kubernetes_tpu.oracle import predicates as preds
from kubernetes_tpu.oracle import priorities as prios
from kubernetes_tpu.oracle.scheduler import PriorityConfig
from kubernetes_tpu.scheduler import plugins

DEFAULT_PROVIDER_NAME = "DefaultProvider"
TPU_PROVIDER_NAME = "TPUProvider"

# deterministic predicate evaluation order (= defaults.go:116 table
# order; the reference's map iteration is random — SURVEY §7 hard-part 4)
CANONICAL_PREDICATE_ORDER = (
    "NoDiskConflict",
    "NoVolumeZoneConflict",
    "MaxEBSVolumeCount",
    "MaxGCEPDVolumeCount",
    "GeneralPredicates",
    "PodToleratesNodeTaints",
    "CheckNodeMemoryPressure",
    "MatchInterPodAffinity",
    # legacy/optional keys:
    "PodFitsPorts",
    "PodFitsHostPorts",
    "PodFitsResources",
    "HostName",
    "MatchNodeSelector",
)


def _max_pd_vols(default: int) -> int:
    v = os.environ.get("KUBE_MAX_PD_VOLS", "")
    try:
        return int(v) if v else default
    except ValueError:
        return default


def _register_all() -> None:
    # --- predicates (defaults.go:116-160 + legacy aliases) ---
    plugins.register_fit_predicate("NoDiskConflict", preds.no_disk_conflict)
    plugins.register_fit_predicate("NoVolumeZoneConflict", preds.volume_zone)
    plugins.register_fit_predicate_factory(
        "MaxEBSVolumeCount",
        lambda args: preds.max_pd_volume_count(
            "ebs", _max_pd_vols(preds.DEFAULT_MAX_EBS_VOLUMES)
        ),
    )
    plugins.register_fit_predicate_factory(
        "MaxGCEPDVolumeCount",
        lambda args: preds.max_pd_volume_count(
            "gce-pd", _max_pd_vols(preds.DEFAULT_MAX_GCE_PD_VOLUMES)
        ),
    )
    plugins.register_fit_predicate("GeneralPredicates", preds.general_predicates)
    plugins.register_fit_predicate(
        "PodToleratesNodeTaints", preds.pod_tolerates_node_taints
    )
    plugins.register_fit_predicate(
        "CheckNodeMemoryPressure", preds.check_node_memory_pressure
    )
    plugins.register_fit_predicate(
        "MatchInterPodAffinity", preds.inter_pod_affinity_matches
    )
    # legacy aliases (defaults.go:77 PodFitsPorts, etc.)
    plugins.register_fit_predicate("PodFitsPorts", preds.pod_fits_host_ports)
    plugins.register_fit_predicate("PodFitsHostPorts", preds.pod_fits_host_ports)
    plugins.register_fit_predicate("PodFitsResources", preds.pod_fits_resources)
    plugins.register_fit_predicate("HostName", preds.pod_fits_host)
    plugins.register_fit_predicate("MatchNodeSelector", preds.pod_selector_matches)

    # --- priorities (defaults.go:162-196) ---
    plugins.register_priority_function(
        "LeastRequestedPriority", prios.least_requested_priority
    )
    plugins.register_priority_function(
        "BalancedResourceAllocation", prios.balanced_resource_allocation
    )
    plugins.register_priority_function(
        "SelectorSpreadPriority", prios.selector_spread_priority
    )
    plugins.register_priority_function(
        "NodeAffinityPriority", prios.node_affinity_priority
    )
    plugins.register_priority_function(
        "TaintTolerationPriority", prios.taint_toleration_priority
    )
    plugins.register_priority_factory(
        "InterPodAffinityPriority",
        lambda args: PriorityConfig(
            functools.partial(
                prios.inter_pod_affinity_priority,
                hard_pod_affinity_weight=args.hard_pod_affinity_weight,
                # --failure-domains (options.go:52): empty/unset keeps the
                # built-in defaults
                failure_domains=tuple(args.failure_domains) or None,
            ),
            1,
            "InterPodAffinityPriority",
        ),
    )
    # legacy (defaults.go:60-81)
    plugins.register_priority_function("EqualPriority", prios.equal_priority, 1)
    plugins.register_priority_function(
        "ServiceSpreadingPriority", prios.selector_spread_priority
    )
    plugins.register_priority_function(
        "ImageLocalityPriority", prios.image_locality_priority
    )

    default_predicates = {
        "NoDiskConflict",
        "NoVolumeZoneConflict",
        "MaxEBSVolumeCount",
        "MaxGCEPDVolumeCount",
        "GeneralPredicates",
        "PodToleratesNodeTaints",
        "CheckNodeMemoryPressure",
        "MatchInterPodAffinity",
    }
    default_priorities = {
        "LeastRequestedPriority",
        "BalancedResourceAllocation",
        "SelectorSpreadPriority",
        "NodeAffinityPriority",
        "TaintTolerationPriority",
        "InterPodAffinityPriority",
    }
    plugins.register_algorithm_provider(
        DEFAULT_PROVIDER_NAME, default_predicates, default_priorities
    )
    plugins.register_algorithm_provider(
        TPU_PROVIDER_NAME,
        default_predicates,
        default_priorities,
        algorithm_factory=_tpu_algorithm_factory,
    )


def _build_mesh():
    """The daemon's device mesh, gated by KUBERNETES_TPU_MESH:
      auto (default) — shard the node axis when >1 device is visible;
      off            — single-chip even on a multi-chip host;
      force          — error out rather than silently run single-chip.
    Returns None for the single-chip path."""
    mode = os.environ.get("KUBERNETES_TPU_MESH", "auto").lower()
    if mode == "off":
        return None
    import jax

    devices = jax.devices()
    if len(devices) < 2:
        if mode == "force":
            raise RuntimeError(
                f"KUBERNETES_TPU_MESH=force but only {len(devices)} "
                "device(s) visible"
            )
        return None
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("nodes",))


def _tpu_algorithm_factory(factory_args):
    """Build the batched TPU ScheduleAlgorithm (lazy import keeps jax out
    of pure control-plane processes). Either driver gets the scheduler
    cache and the spread listers, so waves run off the incrementally
    maintained snapshot; on a multi-chip host the node axis shards
    across the device mesh (MeshWaveScheduler). Its decisions equal the
    single-chip driver's: asserted on 8 virtual CPU devices by the
    dryrun and tests/test_mesh_daemon.py, and on four v5e chips by the
    benchmark's mesh-20k.fill cell against its plain reference
    (PR 32)."""
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    return TPUScheduleAlgorithm(
        mesh=_build_mesh(),
        cache=factory_args.scheduler_cache,
        service_lister=factory_args.service_lister,
        controller_lister=factory_args.controller_lister,
        replica_set_lister=factory_args.replica_set_lister,
    )


_register_all()
