"""The plain reference for a deployment whose templates differ in kind:
pods without an annotation beside pods that carry a required
podAffinity, a required podAntiAffinity, a preferred podAffinity or a
preferred podAntiAffinity term (`pods.shapes`, the 1.3-era
`scheduler.alpha.kubernetes.io/affinity` annotation on some shapes and
none on others), all with stated cpu AND memory requests: the serial
generic scheduler with MatchInterPodAffinity among its predicates and
InterPodAffinityPriority among its priorities, both written out for all
four kinds of term at once, in straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider. A term is (selector, topologyKey); `peers[u, node]` are the
live pods of template u on a node, and two nodes are co-located under a
key when both carry it with equal, non-empty values. For a pod of
template t, whatever its shape:

    PodFitsResources        on the stated requests (cpu and memory)
    MatchInterPodAffinity   predicates.go:754-947.
                            Required podAffinity (:800-849): for every
                            term of the pod, some live pod the term
                            selects is co-located with the node, or
                            (the escape, :819-843) the term selects the
                            pod's own labels and no live pod anywhere
                            is selected by it.
                            Required podAntiAffinity (:858-921), for a
                            pod that states a podAntiAffinity at all, as
                            upstream gates it: no live pod that a
                            required term of the pod selects is
                            co-located, and no live pod whose own
                            required anti term selects this pod's labels
                            is. A pod without an annotation passes
    LeastRequestedPriority, BalancedResourceAllocation
                            on the stated requests: a container that
                            states both counts them, not the non-zero
                            defaults (priorities.go:55-60)
    SelectorSpreadPriority  float32 with zoneWeighting 2/3 over the
                            nodes that fit, benchmark/reference_zoned.py's
    InterPodAffinityPriority
                            interpod_affinity.go:86-216, over the nodes
                            that fit. A node's int64 total is the sum of
                              + weight x the live pods a preferred
                                podAffinity term of the pod selects,
                                co-located with the node
                              - weight x the same for its preferred
                                podAntiAffinity terms
                              + weight / - weight for every live pod,
                                co-located with the node, of which a
                                preferred podAffinity / podAntiAffinity
                                term selects this pod's labels
                              + hardPodAffinitySymmetricWeight (1) for
                                every such live pod of which a REQUIRED
                                podAffinity term does.
                            maxCount and minCount start at 0 (Go's
                            ints): with a preferred podAffinity term in
                            the cluster the maximum lies above 0, with
                            a preferred podAntiAffinity term the minimum
                            below it, and one pod may meet both. The
                            score is int(10 * ((total - min) /
                            (max - min))) in float64, 0 where max == min

The nodes, the tallies, the zones and the spread score are
benchmark/reference_zoned.py's; the reading of a shape's annotation, the
co-location sums and the resource priorities are
benchmark/reference_podaffinity.py's and
benchmark/reference_antiaffinity.py's (plain references like this one:
`terms_of` yields four lists a template, each empty where the
annotation, or the pod, states no such term); selectHost, the serial
loop, the comparison and the stale-wave control are
benchmark/reference.py's (`decide`, `verify` know a cluster only by its
`ranking` and `bind`). What differs from the two term references is
written out here: the predicate and the priority with one switch a kind
of term, so that benchmark/control_mixed.py can leave each out in turn,
the memory the resource priorities count, and the read-back guarantees.

What it refuses, because nothing here scores it: a shape with a node
selector, a port or requests of its own; a term with an empty
topologyKey, with `namespaces` stated, or with a selector operator other
than `In` (reference_podaffinity.py's refusals).

`over_allocatable()` counts a node over allocatable, a node that holds
two live pods of which one's required anti-affinity term selects the
other under the term's key (`anti_affinity`), and a node that holds a
pod of a self-selecting required podAffinity term outside the domain
that holds most of its collection (`zone_affinity`: none can be, on one
zone), so the comparison's `nodes_over_allocatable` holds the two
guarantees on the cluster as read back.

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference, reference_podaffinity
from benchmark.reference import decide, verify  # noqa: F401  (the interface)

MAX_PRIORITY = reference.MAX_PRIORITY


class Cluster(reference_podaffinity.Cluster):
    """Zoned nodes of a deployment, each template's terms of four kinds
    (none for a template without an annotation), and what is bound to
    the nodes (`peers[u, node]`: the live pods of template u)."""

    #: what benchmark/control_mixed.py switches, on a copy, to see
    #: whether the comparison tells the difference: one switch a kind of
    #: term, both directions of it (the pod's own terms and those of
    #: bound pods that select it)
    affinity_holds = True
    anti_holds = True
    pref_affinity_holds = True
    pref_anti_holds = True
    #: bytes of memory a pod counts in the two resource priorities where
    #: that is not what it states (the control: upstream's non-zero
    #: default); None for the stated request
    memory_scored = None

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        stated = cfg["pods"]["requests"]
        if "cpu" not in stated or "memory" not in stated:
            raise ValueError("this reference scores stated cpu and memory "
                             f"requests: {stated}")

    # -- read back ------------------------------------------------------------

    def nodes_with_two(self):
        """bool[N]: the node holds two live pods of which one's required
        anti-affinity term selects the other, under the term's key."""
        together = np.zeros(len(self.names), bool)
        for t, terms in enumerate(self.terms):
            for mask, dom in terms["anti"]:
                # the pods the term selects in each node's domain, less
                # the one that looks
                others = self._selected(mask, dom) - mask[t]
                together |= (self.peers[t] > 0) & (dom >= 0) & (others > 0)
        return together

    def nodes_astray(self):
        """bool[N]: the node holds a pod of a collection whose required
        podAffinity term selects itself, outside the domain that holds
        most of the collection (reference_podaffinity.py's count)."""
        astray = np.zeros(len(self.names), bool)
        for t, terms in enumerate(self.terms):
            for mask, dom in terms["affinity"]:
                if not mask[t]:
                    continue  # not a collection that selects itself
                on_node = mask @ self.peers
                held = (on_node > 0) & (dom >= 0)
                if held.any():
                    sums = np.bincount(dom[held], weights=on_node[held])
                    astray |= held & (dom != int(sums.argmax()))
        return astray

    def over_allocatable(self) -> int:
        """Nodes over allocatable, holding two pods of an anti-affine
        collection, or astray from their collection's zone."""
        over = ((self.req_cpu > self.cap_cpu) | (self.req_mem > self.cap_mem)
                | (self.pods > self.cap_pods))
        return int(np.count_nonzero(over | self.nodes_with_two()
                                    | self.nodes_astray()))

    # -- one scheduling cycle -------------------------------------------------

    def _scored_totals(self):
        """What the two resource priorities count on a node with the
        pod on it: the stated requests (one request shape, so a node's
        memory is its pods' times the pod's)."""
        if self.memory_scored is None:
            return self.req_cpu + self.pod_cpu, self.req_mem + self.pod_mem
        return (self.req_cpu + self.pod_cpu,
                (self.pods + 1) * np.int64(self.memory_scored))

    def fits(self):
        """PodFitsResources and MatchInterPodAffinity for a pod of
        `self.incoming`."""
        fit = reference.Cluster.fits(self)
        mine = self.terms[self.incoming]
        if self.affinity_holds:
            for mask, dom in mine["affinity"]:
                on_node = mask @ self.peers
                ok = self._by_domain(on_node, dom) > 0
                if mask[self.incoming] and not on_node.any():
                    ok[:] = True  # the first pod of its collection
                fit &= ok
        if self.anti_holds and mine["states_anti"]:
            for mask, dom in mine["anti"]:
                fit &= self._selected(mask, dom) == 0
            for _one, u, dom in self._owners(self.incoming, "anti"):
                fit &= self._by_domain(self.peers[u], dom) == 0
        return fit

    def _inter_pod_affinity(self, fit):
        """CalculateInterPodAffinityPriority over the nodes that fit,
        all four kinds of term in one int64 total a node."""
        n = len(self.names)
        total = np.zeros(n, np.int64)
        mine = self.terms[self.incoming]
        for kind, sign, holds in (
                ("pref_affinity", 1, self.pref_affinity_holds),
                ("pref_anti", -1, self.pref_anti_holds)):
            if not holds:
                continue
            for w, mask, dom in mine[kind]:
                total += sign * w * self._selected(mask, dom)
            for w, u, dom in self._owners(self.incoming, kind):
                total += sign * w * self._by_domain(self.peers[u], dom)
        if self.affinity_holds and self.hard_weight > 0:
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
