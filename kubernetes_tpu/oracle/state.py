"""Cluster state as the oracle sees it.

Mirrors plugin/pkg/scheduler/schedulercache/node_info.go: per-node pod list
plus incrementally-maintained requested/nonzero resource sums. The oracle's
ClusterState is the Python analogue of the `GetNodeNameToInfoMap` snapshot
(cache.go:77) plus the auxiliary listers (services/RCs/RSs/PVs/PVCs).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

from kubernetes_tpu.api.types import (
    AFFINITY_ANNOTATION,
    Node,
    PersistentVolume,
    PersistentVolumeClaim,
    Pod,
    ReplicaSet,
    ReplicationController,
    Service,
    get_affinity,
    pod_nonzero_request,
    pod_resource_request,
)
from kubernetes_tpu.metrics import scheduler_pod_contribution_lookups_total


@dataclass
class NodeInfo:
    """node_info.go:32 NodeInfo — node + aggregated pod demand.

    requested_* excludes init containers (calculateResource, node_info.go:158);
    nonzero_* applies the 100m/200Mi per-container defaults.
    """

    node: Optional[Node] = None
    pods: List[Pod] = field(default_factory=list)
    requested_milli_cpu: int = 0
    requested_memory: int = 0
    requested_gpu: int = 0
    nonzero_milli_cpu: int = 0
    nonzero_memory: int = 0
    # (namespace, name) -> where add_pod put the pod in `pods`, so that
    # remove_pod need not walk the node's 20-110 pods, each a cache miss,
    # for every deleted pod. A hint only: `pods` is a public list and a
    # clone starts without hints; an entry that does not point at its pod
    # is ignored and the walk decides.
    _at: Dict[Tuple[str, str], int] = field(
        default_factory=dict, repr=False, compare=False)

    def add_pod(self, pod: Pod) -> None:
        c = pod_contribution(pod)
        self.requested_milli_cpu += c.cpu
        self.requested_memory += c.mem
        self.requested_gpu += c.gpu
        self.nonzero_milli_cpu += c.nonzero_cpu
        self.nonzero_memory += c.nonzero_mem
        self._at[_pod_key(pod)] = len(self.pods)
        self.pods.append(pod)

    def remove_pod(self, pod: Pod) -> None:
        key = _pod_key(pod)
        pods = self.pods
        i = self._at.pop(key, None)
        if i is None or i >= len(pods) or _pod_key(pods[i]) != key:
            for i, p in enumerate(pods):
                if _pod_key(p) == key:
                    break
            else:
                raise KeyError(f"no pod {key} on node")
        last = pods.pop()
        if i < len(pods):  # the last pod takes the place of the one gone
            pods[i] = last
            self._at[_pod_key(last)] = i
        c = pod_contribution(pod)
        self.requested_milli_cpu -= c.cpu
        self.requested_memory -= c.mem
        self.requested_gpu -= c.gpu
        self.nonzero_milli_cpu -= c.nonzero_cpu
        self.nonzero_memory -= c.nonzero_mem

    def clone(self) -> "NodeInfo":
        return NodeInfo(
            node=self.node,
            pods=list(self.pods),
            requested_milli_cpu=self.requested_milli_cpu,
            requested_memory=self.requested_memory,
            requested_gpu=self.requested_gpu,
            nonzero_milli_cpu=self.nonzero_milli_cpu,
            nonzero_memory=self.nonzero_memory,
        )


def _pod_key(pod: Pod) -> Tuple[str, str]:
    meta = pod.metadata
    return (meta.namespace, meta.name)


def _calculate_resource(pod: Pod) -> Tuple[int, int, int]:
    """node_info.go:158 calculateResource: containers only, no init max."""
    from kubernetes_tpu.api.resource import (
        resource_list_cpu_milli,
        resource_list_gpu,
        resource_list_memory,
    )

    cpu = sum(resource_list_cpu_milli(c.requests) for c in pod.spec.containers)
    mem = sum(resource_list_memory(c.requests) for c in pod.spec.containers)
    gpu = sum(resource_list_gpu(c.requests) for c in pod.spec.containers)
    return cpu, mem, gpu


def selector_canon(sel) -> object:
    """A LabelSelector's content, hashable: what two terms must agree in
    to count the same pods (snapshot/interpod.py keys its specs on it)."""
    if sel is None:
        return None
    return (
        tuple(sorted((sel.match_labels or {}).items())),
        tuple(
            (e.key, e.operator, tuple(e.values or ()))
            for e in (sel.match_expressions or ())
        ),
    )


class PodTerms(NamedTuple):
    """The inter-pod terms a pod owns, by the table that counts them
    (snapshot/interpod.py), parsed once per template. A term is
    (namespaces, selector, topology key): the namespaces as
    GetNamespacesFromPodAffinityTerm resolves them for the owner (a
    frozenset; empty for all), the selector as `selector_canon` has it.
    Hashable, so equal terms of two templates are one entry."""

    parsed: bool  # False: the annotation does not parse (it poisons)
    hard: tuple = ()  # required affinity: terms
    pref: tuple = ()  # preferred affinity: (term, weight)
    anti_hard: tuple = ()  # required anti-affinity: terms
    anti_pref: tuple = ()  # preferred anti-affinity: (term, weight)
    affinity: bool = False  # states podAffinity, with terms or without
    anti: bool = False  # states podAntiAffinity


def pod_terms(pod: Pod) -> Optional[PodTerms]:
    """The pod's PodTerms: None where it states no inter-pod affinity
    (has_pod_affinity is false), `parsed` false where it cannot be
    read."""
    try:
        aff = get_affinity(pod)
    except Exception:
        return PodTerms(False)
    if aff is None or (aff.pod_affinity is None
                       and aff.pod_anti_affinity is None):
        return None
    own = pod.metadata.namespace

    def term(t):
        # util/non_zero.go:96: nil is the owner's namespace, empty is all
        names = (own,) if t.namespaces is None else t.namespaces
        return (frozenset(names), selector_canon(t.label_selector),
                t.topology_key)

    def sides(side):
        if side is None:
            return (), ()
        return (
            tuple(term(t) for t in
                  side.required_during_scheduling_ignored_during_execution),
            tuple((term(wt.pod_affinity_term), wt.weight) for wt in
                  side.preferred_during_scheduling_ignored_during_execution),
        )

    return PodTerms(True, *sides(aff.pod_affinity),
                    *sides(aff.pod_anti_affinity),
                    aff.pod_affinity is not None,
                    aff.pod_anti_affinity is not None)


class PodContribution(NamedTuple):
    """What one assigned pod adds to its node: everything NodeInfo and
    the incremental snapshot (snapshot/incremental.py) take from it.
    Immutable, and shared by every pod of one template."""

    cpu: int  # _calculate_resource
    mem: int
    gpu: int
    nonzero_cpu: int  # pod_nonzero_request
    nonzero_mem: int
    host_ports: Tuple[int, ...]  # non-zero, in container order
    # the spread class: (namespace, frozenset(labels), deleting)
    class_key: Tuple[str, frozenset, bool]
    # the inter-pod terms it owns; None where has_pod_affinity is false
    terms: Optional[PodTerms]


#: contributions seen, under a structural key of exactly the fields
#: pod_contribution reads. Bounded: the oldest entry goes when a new one
#: would pass the bound, so a cluster of all-distinct pods pays the key
#: and a miss per pod and holds at most this many entries.
_CONTRIBUTIONS: Dict[tuple, PodContribution] = {}
_CONTRIBUTIONS_MAX = 4096
_contributions_lock = threading.Lock()  # guards insert + evict
_count_hit = scheduler_pod_contribution_lookups_total.child(result="hit")
_count_miss = scheduler_pod_contribution_lookups_total.child(result="miss")


def pod_contribution(pod: Pod) -> PodContribution:
    """The pod's PodContribution, derived once per template.

    The key is rebuilt from the pod's current fields on every call, so a
    pod mutated after a lookup (a `requests` dict edited in place) gets
    the answer for what it holds now, never a stale one. The name and
    the node are not in the key: neither is read."""
    meta = pod.metadata
    spec = pod.spec
    if spec.affinity is not None:
        # an Affinity object is not hashable; such pods are few (their
        # terms still come out equal, `PodTerms`, and share what the
        # incremental encoder keeps per distinct terms)
        _count_miss()
        return _derive_contribution(pod)
    key = (
        meta.namespace,
        tuple(meta.labels.items()),
        meta.deletion_timestamp is not None,
        meta.annotations.get(AFFINITY_ANNOTATION),
        tuple([
            (tuple(c.requests.items()),
             tuple([p.host_port for p in c.ports]) if c.ports else ())
            for c in spec.containers
        ]),
    )
    c = _CONTRIBUTIONS.get(key)
    if c is not None:
        _count_hit()
        return c
    _count_miss()
    c = _derive_contribution(pod)
    with _contributions_lock:
        if len(_CONTRIBUTIONS) >= _CONTRIBUTIONS_MAX:
            del _CONTRIBUTIONS[next(iter(_CONTRIBUTIONS))]
        _CONTRIBUTIONS[key] = c
    return c


def _derive_contribution(pod: Pod) -> PodContribution:
    meta = pod.metadata
    return PodContribution(
        *_calculate_resource(pod),
        *pod_nonzero_request(pod),
        tuple(
            p.host_port
            for c in pod.spec.containers
            for p in c.ports
            if p.host_port != 0
        ),
        (
            meta.namespace,
            frozenset(meta.labels.items()),
            meta.deletion_timestamp is not None,
        ),
        pod_terms(pod),
    )


@dataclass
class ClusterState:
    """The full decision input: node infos + auxiliary object listers."""

    node_infos: Dict[str, NodeInfo] = field(default_factory=dict)
    services: List[Service] = field(default_factory=list)
    controllers: List[ReplicationController] = field(default_factory=list)
    replica_sets: List[ReplicaSet] = field(default_factory=list)
    pvs: Dict[str, PersistentVolume] = field(default_factory=dict)
    pvcs: Dict[Tuple[str, str], PersistentVolumeClaim] = field(default_factory=dict)
    # When this state is a filtered view (priorities see only nodes that
    # passed predicates, generic_scheduler.go:109), `full` points at the
    # complete state so pod listers / GetNodeInfo still resolve everything,
    # matching the reference where nodeNameToInfo and podLister are global.
    full: Optional["ClusterState"] = None

    @classmethod
    def build(
        cls,
        nodes: List[Node],
        assigned_pods: List[Pod] = (),
        services: List[Service] = (),
        controllers: List[ReplicationController] = (),
        replica_sets: List[ReplicaSet] = (),
        pvs: List[PersistentVolume] = (),
        pvcs: List[PersistentVolumeClaim] = (),
    ) -> "ClusterState":
        st = cls(
            services=list(services),
            controllers=list(controllers),
            replica_sets=list(replica_sets),
            pvs={pv.metadata.name: pv for pv in pvs},
            pvcs={(p.metadata.namespace, p.metadata.name): p for p in pvcs},
        )
        for n in nodes:
            st.node_infos[n.name] = NodeInfo(node=n)
        for p in assigned_pods:
            st.assign(p)
        return st

    def assign(self, pod: Pod) -> None:
        """Add a pod with spec.node_name set (cache AddPod / AssumePod)."""
        name = pod.spec.node_name
        if not name:
            raise ValueError(f"pod {pod.name} has no node_name")
        self.node_infos.setdefault(name, NodeInfo()).add_pod(pod)

    def all_assigned_pods(self) -> List[Pod]:
        src = self.full if self.full is not None else self
        out: List[Pod] = []
        for info in src.node_infos.values():
            out.extend(info.pods)
        return out

    def get_node_info_any(self, name: str) -> Optional[NodeInfo]:
        """Resolve a node by name, looking through a filtered view if needed
        (the reference's schedulercache GetNodeInfo is always global)."""
        info = self.node_infos.get(name)
        if info is None and self.full is not None:
            info = self.full.node_infos.get(name)
        return info

    def nodes(self) -> List[Node]:
        return [i.node for i in self.node_infos.values() if i.node is not None]

    def get_node(self, name: str) -> Node:
        info = self.node_infos.get(name)
        if info is None or info.node is None:
            raise KeyError(f"node {name!r} not in cache")
        return info.node

    def clone(self) -> "ClusterState":
        st = ClusterState(
            services=list(self.services),
            controllers=list(self.controllers),
            replica_sets=list(self.replica_sets),
            pvs=dict(self.pvs),
            pvcs=dict(self.pvcs),
        )
        st.node_infos = {k: v.clone() for k, v in self.node_infos.items()}
        return st
