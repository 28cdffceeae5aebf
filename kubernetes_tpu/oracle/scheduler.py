"""Serial generic scheduler — the decision semantics the TPU path must match.

Reference: plugin/pkg/scheduler/generic_scheduler.go. One deliberate,
documented deviation (SURVEY.md §7 hard-part 4): the reference evaluates
predicates in Go map-iteration (i.e. random) order, which only affects WHICH
failure reason is reported, never fit/no-fit; we fix the canonical order to
the default-provider registration order below so reasons are deterministic.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.oracle import predicates as preds
from kubernetes_tpu.oracle import priorities as prios
from kubernetes_tpu.oracle.state import ClusterState, NodeInfo

# predicate: (pod, node_info, state) -> (fit, reason)
Predicate = Callable[[Pod, NodeInfo, ClusterState], Tuple[bool, Optional[str]]]
# priority: (pod, state) -> {node: score}
Priority = Callable[[Pod, ClusterState], Dict[str, int]]


@dataclass
class PriorityConfig:
    """algorithm/types.go:31 PriorityConfig."""

    function: Priority
    weight: int = 1
    name: str = ""


# defaults.go:116 defaultPredicates (canonical order, see module docstring).
DEFAULT_PREDICATE_ORDER: Tuple[Tuple[str, Predicate], ...] = (
    ("NoDiskConflict", preds.no_disk_conflict),
    ("NoVolumeZoneConflict", preds.volume_zone),
    (
        "MaxEBSVolumeCount",
        preds.max_pd_volume_count("ebs", preds.DEFAULT_MAX_EBS_VOLUMES),
    ),
    (
        "MaxGCEPDVolumeCount",
        preds.max_pd_volume_count("gce-pd", preds.DEFAULT_MAX_GCE_PD_VOLUMES),
    ),
    ("GeneralPredicates", preds.general_predicates),
    ("PodToleratesNodeTaints", preds.pod_tolerates_node_taints),
    ("CheckNodeMemoryPressure", preds.check_node_memory_pressure),
    ("MatchInterPodAffinity", preds.inter_pod_affinity_matches),
)

# defaults.go:162 defaultPriorities, all weight 1.
DEFAULT_PRIORITIES: Tuple[PriorityConfig, ...] = (
    PriorityConfig(prios.least_requested_priority, 1, "LeastRequestedPriority"),
    PriorityConfig(prios.balanced_resource_allocation, 1, "BalancedResourceAllocation"),
    PriorityConfig(prios.selector_spread_priority, 1, "SelectorSpreadPriority"),
    PriorityConfig(prios.node_affinity_priority, 1, "NodeAffinityPriority"),
    PriorityConfig(prios.taint_toleration_priority, 1, "TaintTolerationPriority"),
    PriorityConfig(prios.inter_pod_affinity_priority, 1, "InterPodAffinityPriority"),
)


class FitError(Exception):
    """generic_scheduler.go:40 FitError."""

    def __init__(self, pod: Pod, failed_predicates: Dict[str, str],
                 detail: Optional[str] = None):
        """`detail` is the per-node part of the message; a caller that
        fails many pods for the same reasons (one template on a full
        cluster) passes the first error's, so that a line per node is
        sorted and joined once and not once per pod."""
        self.pod = pod
        self.failed_predicates = failed_predicates
        if detail is None:
            detail = "\n".join(
                f"fit failure on node ({n}): {r}"
                for n, r in sorted(failed_predicates.items())
            )
        self.detail = detail
        super().__init__(
            f"pod ({pod.name}) failed to fit in any node\n" + detail
        )


def select_host(priority_list: List[Tuple[str, int]], last_node_index: int) -> str:
    """generic_scheduler.go:119 selectHost.

    sort.Reverse over HostPriorityList.Less (api/types.go:164-169) yields a
    strict total order: score descending, then host name DESCENDING. The
    winner among max-score ties is index lastNodeIndex % numTies.
    """
    if not priority_list:
        raise ValueError("empty priorityList")
    ordered = sorted(priority_list, key=lambda hp: (hp[1], hp[0]), reverse=True)
    max_score = ordered[0][1]
    num_ties = 0
    for _, score in ordered:
        if score < max_score:
            break
        num_ties += 1
    return ordered[last_node_index % num_ties][0]


def prioritize_nodes(
    pod: Pod,
    state: ClusterState,
    priority_configs: Sequence[PriorityConfig],
    filtered_nodes: Sequence[str],
) -> List[Tuple[str, int]]:
    """generic_scheduler.go:222 PrioritizeNodes.

    NOTE: each priority function sees ALL nodes in the state (the reference
    passes a FakeNodeLister over the FILTERED nodes for some functions and
    nodeNameToInfo for others; in practice every default priority iterates
    the lister's nodes = the filtered list). We therefore compute over the
    filtered node subset, like the reference does.
    """
    if not priority_configs:
        return [
            (name, 1)
            for name in filtered_nodes
        ]
    sub_state = _restrict_state(state, filtered_nodes)
    combined: Dict[str, int] = {name: 0 for name in filtered_nodes}
    for cfg in priority_configs:
        scores = cfg.function(pod, sub_state)
        for name in filtered_nodes:
            combined[name] += scores.get(name, 0) * cfg.weight
    return [(name, combined[name]) for name in filtered_nodes]


def _restrict_state(state: ClusterState, node_names: Sequence[str]) -> ClusterState:
    """Priorities see the filtered node list (FakeNodeLister(filteredNodes),
    generic_scheduler.go:109) but the full pod assignment for topology checks.
    We keep all node_infos for existing-pod node lookups and mark the subset.
    Simplest faithful model: a state whose node_infos are the filtered subset
    but which can still resolve other nodes for assigned pods.
    """
    sub = ClusterState(
        services=state.services,
        controllers=state.controllers,
        replica_sets=state.replica_sets,
        pvs=state.pvs,
        pvcs=state.pvcs,
    )
    sub.node_infos = {n: state.node_infos[n] for n in node_names}
    sub.full = state
    return sub


@dataclass
class GenericScheduler:
    """generic_scheduler.go:58 genericScheduler (host-side serial oracle)."""

    predicates: Sequence[Tuple[str, Predicate]] = DEFAULT_PREDICATE_ORDER
    priorities: Sequence[PriorityConfig] = DEFAULT_PRIORITIES
    last_node_index: int = 0

    def find_nodes_that_fit(
        self, pod: Pod, state: ClusterState
    ) -> Tuple[List[str], Dict[str, str]]:
        """generic_scheduler.go:139 findNodesThatFit."""
        fits: List[str] = []
        failed: Dict[str, str] = {}
        for name, info in state.node_infos.items():
            if info.node is None:
                continue
            ok = True
            for pname, predicate in self.predicates:
                fit, reason = predicate(pod, info, state)
                if not fit:
                    failed[name] = reason or pname
                    ok = False
                    break
            if ok:
                fits.append(name)
        return fits, failed

    def schedule(self, pod: Pod, state: ClusterState) -> str:
        """generic_scheduler.go:72 Schedule. Raises FitError if nothing fits."""
        if not state.node_infos:
            raise FitError(pod, {})
        fits, failed = self.find_nodes_that_fit(pod, state)
        if not fits:
            raise FitError(pod, failed)
        priority_list = prioritize_nodes(pod, state, self.priorities, fits)
        host = select_host(priority_list, self.last_node_index)
        self.last_node_index += 1
        return host

    def schedule_backlog(
        self, pods: Sequence[Pod], state: ClusterState, commit: bool = True
    ) -> List[Optional[str]]:
        """Serial scheduleOne over a backlog: schedule, assume, repeat —
        exactly what scheduler_perf drives (scheduler.go:93 + AssumePod).
        Returns the chosen node per pod (None where nothing fit)."""
        from kubernetes_tpu.oracle.priorities import PriorityError

        results: List[Optional[str]] = []
        for pod in pods:
            try:
                host = self.schedule(pod, state)
            except (FitError, PriorityError):
                results.append(None)
                continue
            results.append(host)
            if commit:
                import copy

                assumed = copy.copy(pod)
                assumed.spec = copy.copy(pod.spec)
                assumed.spec.node_name = host
                state.assign(assumed)
        return results
