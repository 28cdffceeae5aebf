"""The plain reference for deployments whose pods carry inter-pod
affinity terms over a topology that couples nodes (`pods.shapes` with
the 1.3-era `scheduler.alpha.kubernetes.io/affinity` annotation on
zoned nodes): the serial generic scheduler with MatchInterPodAffinity
among its predicates and InterPodAffinityPriority among its priorities,
both written out, in straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider. A term is (selector, topologyKey); `held[u, node]` are the
live pods of template u on a node, `labels[u]` their labels, and two
nodes are co-located under a key when both carry it with equal,
non-empty values (util/non_zero.go:97-113). For a pod of template t:

    PodFitsResources        on the STATED requests, memory 0 where none
                            is stated (reference_antiaffinity.py's)
    MatchInterPodAffinity   predicates.go:754-947.
                            Required podAffinity (:800-849): for every
                            term, some live pod that the term selects is
                            co-located with the node; or, the escape
                            (:819-843), the term selects the pod's own
                            labels and no live pod ANYWHERE is selected
                            by it: the first pod of a collection goes
                            where the other predicates let it.
                            Required podAntiAffinity (:858-921), run only
                            for a pod that states a podAntiAffinity at
                            all, as upstream gates it: no live pod that
                            a term of the pod selects is co-located, and
                            no live pod whose own required anti term
                            selects this pod's labels is
    LeastRequestedPriority, BalancedResourceAllocation
                            on the NON-ZERO requests (100m, 200Mi),
                            reference_antiaffinity.py's own functions
    SelectorSpreadPriority  float32 with zoneWeighting 2/3 over the
                            nodes that fit, reference_zoned.py's
    InterPodAffinityPriority
                            interpod_affinity.go:86-216, over the nodes
                            that fit. A node's int64 total is the sum of
                              + weight x the live pods a preferred
                                podAffinity term of the pod selects,
                                co-located with the node
                              - weight x the same for its preferred
                                podAntiAffinity terms
                              + hardPodAffinitySymmetricWeight (1) for
                                every live pod, co-located with the
                                node, of which a REQUIRED podAffinity
                                term selects this pod's labels
                              + weight / - weight for every such live
                                pod's preferred podAffinity /
                                podAntiAffinity term that does.
                            maxCount and minCount start at 0 (Go's
                            ints), and the score is
                            int(10 * ((total - min) / (max - min))) in
                            float64, 0 where max == min

selectHost, the serial loop, the comparison and the stale-wave control
are benchmark/reference.py's (`decide`, `verify` know a cluster only by
its `ranking` and `bind`).

What it refuses, because nothing here scores it: a shape with a node
selector, a port or requests of its own; a term with an empty
topologyKey (upstream's OR over the default failure domains), with
`namespaces` stated (every pod here is in `default`, which a term that
states none means), or with a selector operator other than `In`.

`over_allocatable()` also counts the nodes on which a service lies
outside its zone: for a template whose required podAffinity term
selects its own labels, the live pods the term selects must all be
co-located (by induction: while one is live the next can only join
it), so every node that holds one outside the domain that holds most
is counted, and the comparison's `nodes_over_allocatable` holds the
guarantee on the cluster as read back.

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import deploy, reference, reference_antiaffinity, \
    reference_zoned
from benchmark.reference import decide, verify  # noqa: F401  (the interface)
from benchmark.reference_antiaffinity import (
    AFFINITY_ANNOTATION,
    DEFAULT_MEMORY,
    DEFAULT_MILLI_CPU,
    selects,
)

MAX_PRIORITY = reference.MAX_PRIORITY
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
#: componentconfig's default for --hard-pod-affinity-symmetric-weight
HARD_POD_AFFINITY_SYMMETRIC_WEIGHT = 1


def _term(term: dict):
    """-> (the selector as [(key, values)], all of which must hold; the
    topology key)"""
    if set(term) - {"labelSelector", "topologyKey"}:
        raise ValueError(f"this reference scores no "
                         f"{sorted(set(term) - {'labelSelector', 'topologyKey'})}"
                         f" of a term (every pod is in `default`): {term}")
    if not term.get("topologyKey"):
        raise ValueError("this reference scores no empty topologyKey (the "
                         f"OR over the default failure domains): {term}")
    selector = term.get("labelSelector") or {}
    wants = [(k, (v,)) for k, v in (selector.get("matchLabels") or
                                    {}).items()]
    for e in selector.get("matchExpressions") or []:
        if e["operator"] != "In":
            raise ValueError(f"selector operator {e['operator']!r}")
        wants.append((e["key"], tuple(e["values"])))
    if not wants:
        raise ValueError(f"a term that selects by no label: {term}")
    return wants, term["topologyKey"]


def terms_of(shape: dict):
    """What a shape's annotation states, as {"affinity", "anti":
    [(wants, key)], "pref_affinity", "pref_anti": [(weight, wants,
    key)], "states_anti": whether it states a podAntiAffinity at all};
    None where the shape states no annotation."""
    unknown = set(shape) - {"requests", "annotations"}
    if unknown:
        raise ValueError(f"this reference scores no {sorted(unknown)}")
    raw = (shape.get("annotations") or {}).get(AFFINITY_ANNOTATION)
    if raw is None:
        return None
    stated = json.loads(raw)
    if set(stated) - {"podAffinity", "podAntiAffinity"}:
        raise ValueError("this reference scores podAffinity and "
                         f"podAntiAffinity: {sorted(stated)}")
    read = {"states_anti": stated.get("podAntiAffinity") is not None}
    for kind, name in (("podAffinity", "affinity"),
                       ("podAntiAffinity", "anti")):
        group = stated.get(kind) or {}
        if set(group) - {REQUIRED, PREFERRED}:
            raise ValueError(f"this reference scores no {sorted(group)}")
        read[name] = [_term(t) for t in group.get(REQUIRED) or []]
        read["pref_" + name] = [
            (int(w["weight"]),) + _term(w["podAffinityTerm"])
            for w in group.get(PREFERRED) or [] if int(w["weight"])]
    return read


class Cluster(reference_zoned.Cluster):
    """Zoned nodes of a deployment, each template's terms, and what is
    bound to the nodes (`peers[u, node]`: the live pods of template u)."""

    #: what benchmark/control_podaffinity.py switches, on a copy, to see
    #: whether the comparison tells the difference
    required_holds = True
    preferred_holds = True
    hard_weight = HARD_POD_AFFINITY_SYMMETRIC_WEIGHT
    #: the precision InterPodAffinityPriority is normalised in
    #: (upstream's float64)
    normal = np.float64
    nonzero_defaults = True

    # the resource priorities on the non-zero requests: the functions
    # of the hostname reference, which read `nz_*` off the cluster
    _scored_totals = reference_antiaffinity.Cluster._scored_totals
    _least_requested = reference_antiaffinity.Cluster._least_requested
    _balanced = reference_antiaffinity.Cluster._balanced

    def __init__(self, cfg: dict):
        stated = dict(cfg["pods"]["requests"])
        # the zoned reference builds the nodes, the zones and the
        # tallies, and refuses to look at the shapes; what it fits with
        # is the stated requests, memory 0 where none is stated
        super().__init__({**cfg, "pods": {
            **{k: v for k, v in cfg["pods"].items() if k != "shapes"},
            "requests": {"cpu": stated.get("cpu", "0"),
                         "memory": stated.get("memory", "0")}}})
        self.cfg = cfg
        n, T = len(self.names), self.templates
        shapes = [deploy.template_shape(cfg, t) for t in range(T)]
        if any(s["requests"] != stated for s in shapes):
            raise ValueError("this reference scores one request shape")
        labels = [deploy.template_labels(cfg, u) for u in range(T)]
        node_labels = [deploy.node_labels(cfg, i) for i in range(n)]
        self._doms: dict = {}

        def dom(key: str):
            """The nodes' domain numbers under a topology key, -1 where
            a node lacks the label or carries it empty."""
            if key not in self._doms:
                values = [l.get(key) or None for l in node_labels]
                ids = {v: i for i, v in enumerate(
                    sorted({v for v in values if v is not None}))}
                self._doms[key] = np.array(
                    [ids.get(v, -1) for v in values], np.int64)
            return self._doms[key]

        def compiled(wants, key):
            # which templates' pods the selector selects, and where the
            # nodes lie under the key
            return (np.array([selects(wants, labels[u]) for u in range(T)],
                             np.int64), dom(key))

        self.terms = []
        for s in shapes:
            read = terms_of(s) or {"affinity": [], "anti": [],
                                   "pref_affinity": [], "pref_anti": [],
                                   "states_anti": False}
            self.terms.append({
                "states_anti": read["states_anti"],
                "affinity": [compiled(*t) for t in read["affinity"]],
                "anti": [compiled(*t) for t in read["anti"]],
                "pref_affinity": [(w,) + compiled(wants, key) for
                                  w, wants, key in read["pref_affinity"]],
                "pref_anti": [(w,) + compiled(wants, key) for
                              w, wants, key in read["pref_anti"]]})
        self.nz_pod_cpu = self.pod_cpu if "cpu" in stated \
            else DEFAULT_MILLI_CPU
        self.nz_pod_mem = self.pod_mem if "memory" in stated \
            else DEFAULT_MEMORY
        self.nz_cpu = np.zeros(n, np.int64)
        self.nz_mem = np.zeros(n, np.int64)
        self.incoming = 0

    def bind(self, template: int, node: int) -> None:
        super().bind(template, node)
        self.nz_cpu[node] += self.nz_pod_cpu
        self.nz_mem[node] += self.nz_pod_mem

    # -- co-location ----------------------------------------------------------

    @staticmethod
    def _by_domain(on_node, dom):
        """Pods counted a node -> a domain's pods, dealt to its nodes; 0
        on a node outside every domain (NodesHaveSameTopologyKey needs
        the label on both)."""
        valid = dom >= 0
        if not valid.any():
            return np.zeros(len(dom), np.int64)
        sums = np.bincount(dom[valid], weights=on_node[valid]
                           ).astype(np.int64)
        return np.where(valid, sums[np.maximum(dom, 0)], 0)

    def _selected(self, mask, dom):
        """The live pods a term selects, co-located with each node."""
        return self._by_domain(mask @ self.peers, dom)

    def _owners(self, template: int, kind: str):
        """(weight or 1, u, dom) for every template u of which a term of
        `kind` selects the incoming template's labels."""
        for u, terms in enumerate(self.terms):
            for term in terms[kind]:
                if term[-2][template]:
                    yield (term[0] if len(term) == 3 else 1), u, term[-1]

    def over_allocatable(self) -> int:
        """Nodes over allocatable, or holding a pod of a service outside
        the service's zone (the module's docstring)."""
        over = ((self.req_cpu > self.cap_cpu) | (self.req_mem > self.cap_mem)
                | (self.pods > self.cap_pods))
        astray = np.zeros(len(self.names), bool)
        for t, terms in enumerate(self.terms):
            for mask, dom in terms["affinity"]:
                if not mask[t]:
                    continue  # not a collection that selects itself
                on_node = mask @ self.peers
                held = (on_node > 0) & (dom >= 0)
                if not held.any():
                    continue
                sums = np.bincount(dom[held], weights=on_node[held])
                astray |= held & (dom != int(sums.argmax()))
        return int(np.count_nonzero(over | astray))

    # -- one scheduling cycle -------------------------------------------------

    def fits(self):
        fit = super().fits()
        mine = self.terms[self.incoming]
        if self.required_holds:
            for mask, dom in mine["affinity"]:
                on_node = mask @ self.peers
                ok = self._by_domain(on_node, dom) > 0
                if mask[self.incoming] and not on_node.any():
                    ok[:] = True  # the first pod of its collection
                fit &= ok
        if mine["states_anti"]:
            for mask, dom in mine["anti"]:
                fit &= self._selected(mask, dom) == 0
            for _one, u, dom in self._owners(self.incoming, "anti"):
                fit &= self._by_domain(self.peers[u], dom) == 0
        return fit

    def _inter_pod_affinity(self, fit):
        """CalculateInterPodAffinityPriority over the nodes that fit."""
        n = len(self.names)
        total = np.zeros(n, np.int64)
        mine = self.terms[self.incoming]
        if self.preferred_holds:
            for w, mask, dom in mine["pref_affinity"]:
                total += w * self._selected(mask, dom)
            for w, mask, dom in mine["pref_anti"]:
                total -= w * self._selected(mask, dom)
            for w, u, dom in self._owners(self.incoming, "pref_affinity"):
                total += w * self._by_domain(self.peers[u], dom)
            for w, u, dom in self._owners(self.incoming, "pref_anti"):
                total -= w * self._by_domain(self.peers[u], dom)
        if self.hard_weight > 0:
            for _one, u, dom in self._owners(self.incoming, "affinity"):
                total += self.hard_weight * self._by_domain(self.peers[u],
                                                            dom)
        most = max(int(total[fit].max(initial=0)), 0)
        least = min(int(total[fit].min(initial=0)), 0)
        if most - least <= 0:
            return np.zeros(n, np.int64)
        real = self.normal
        share = (total - least).astype(real) / real(most - least)
        return np.where(fit, (real(MAX_PRIORITY) * share).astype(np.int64), 0)

    def ranking(self, template: int):
        self.incoming = template
        fit = self.fits()
        if not fit.any():
            return np.empty(0, np.int64)
        total = (self._least_requested() + self._balanced()
                 + self._spread(template, fit)
                 + self._inter_pod_affinity(fit))
        best = total[fit].max()
        top = fit & (total == best)
        return self.desc[top[self.desc]]
