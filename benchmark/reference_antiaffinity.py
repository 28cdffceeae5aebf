"""The plain reference for deployments whose pods carry a required
inter-pod anti-affinity term on the node's own name (`pods.shapes` with
the 1.3-era `scheduler.alpha.kubernetes.io/affinity` annotation): the
serial generic scheduler with MatchInterPodAffinity among its
predicates, in straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider, as benchmark/reference.py does for pods without terms. With
`term[t]` the label selector of template t's one required
podAntiAffinity term, `labels[u]` template u's pod labels, and
`held[u, node]` the live pods of template u on a node:

    PodFitsResources        pods + 1 <= cap_pods, req_cpu + cpu <=
                            cap_cpu, req_mem + mem <= cap_mem, on the
                            STATED requests: a container that states no
                            memory counts 0 (predicates.go getResourceRequest)
    MatchInterPodAffinity   predicates.go:754-947, both directions: the
                            node holds no live pod whose labels match
                            the incoming pod's term, and none whose own
                            term matches the incoming pod's labels. The
                            topology key is kubernetes.io/hostname and
                            every node's value is its own, so a term's
                            domain is one node
    LeastRequestedPriority  ((cap - (nz + r)) * 10) / cap for cpu and
                            memory, int64 with truncating division,
                            their sum halved the same way, on the
                            NON-ZERO requests (priorities.go:55-60
                            getNonZeroRequests: 100m and 200Mi where a
                            container states none), the node's tally of
                            them and the pod's
    BalancedResourceAllocation
                            int(10 - |cpuFraction - memFraction| * 10)
                            in float64 on the same non-zero totals, 0
                            where either fraction reaches 1
    SelectorSpreadPriority  float32 over the template's own
                            controller's pods on the nodes that fit, on
                            nodes without zones, as reference.py
    InterPodAffinityPriority
                            interpod_affinity.go:86-216: a node's count
                            moves by a preferred term's weight and by a
                            hard AFFINITY term of a bound pod; these
                            pods have neither, so every count is 0,
                            max == min, and every node scores 0
                            (`_inter_pod_affinity`, written out)

selectHost takes the best total, host name descending, round-robin
among ties by a counter that steps once per scheduled pod. One pod at a
time, each commit seen by the next. The cluster's tallies, the serial
loop, the comparison and the stale-wave control are
benchmark/reference.py's: its `Cluster` is extended here, and `decide`
and `verify` know a cluster only by its `ranking` and `bind`.

What it refuses, because nothing here scores it: a shape with a node
selector, a port or requests of its own; a podAffinity term, a
preferred term, a topology key other than the node's own name, a
selector operator other than `In`, nodes whose hostname labels repeat.

`over_allocatable()` also counts a node that holds two live pods of
which one's term matches the other's labels, so the comparison's
`nodes_over_allocatable` holds the anti-affinity guarantee on the
cluster as read back.

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import deploy, reference
from benchmark.reference import decide, verify  # noqa: F401  (the interface)

MAX_PRIORITY = reference.MAX_PRIORITY
AFFINITY_ANNOTATION = "scheduler.alpha.kubernetes.io/affinity"
HOSTNAME = "kubernetes.io/hostname"
#: priorities.go:55-60: what a container that states no request counts
#: in the two resource priorities (and 0 in PodFitsResources)
DEFAULT_MILLI_CPU = 100
DEFAULT_MEMORY = 200 * 1024 * 1024
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def term_of(shape: dict):
    """The label selector of the one required hostname anti-affinity
    term a shape's annotation states, as [(key, values)] (all must
    hold); None where the shape states no annotation."""
    unknown = set(shape) - {"requests", "annotations"}
    if unknown:
        raise ValueError(f"this reference scores no {sorted(unknown)}")
    raw = (shape.get("annotations") or {}).get(AFFINITY_ANNOTATION)
    if raw is None:
        return None
    affinity = json.loads(raw)
    anti = affinity.pop("podAntiAffinity", None) or {}
    terms = anti.pop(REQUIRED, None) or []
    if affinity or anti or len(terms) != 1:
        raise ValueError(
            "this reference scores one required podAntiAffinity term; "
            f"the annotation also states {sorted(affinity) + sorted(anti)}"
            f" and {len(terms)} required term(s)")
    term = terms[0]
    if term.get("topologyKey") != HOSTNAME or term.get("namespaces"):
        raise ValueError("this reference knows one topology, the node's "
                         f"own name in the pod's namespace: {term}")
    selector = term.get("labelSelector") or {}
    wants = [(k, (v,)) for k, v in (selector.get("matchLabels") or
                                    {}).items()]
    for e in selector.get("matchExpressions") or []:
        if e["operator"] != "In":
            raise ValueError(f"selector operator {e['operator']!r}")
        wants.append((e["key"], tuple(e["values"])))
    if not wants:
        raise ValueError(f"a term that selects by no label: {term}")
    return wants


def selects(wants, labels: dict) -> bool:
    """LabelSelector semantics: every requirement holds. A template
    without a term (`wants` None) selects nothing."""
    return wants is not None and all(labels.get(k) in vs
                                     for k, vs in wants)


class Cluster(reference.Cluster):
    """Nodes of a deployment, each template's term, and what is bound
    to the nodes (`peers[u, node]`: the live pods of template u)."""

    #: the two things benchmark/control_antiaffinity.py switches off, on
    #: a copy, to see whether the comparison tells the difference
    term_holds = True
    nonzero_defaults = True

    def __init__(self, cfg: dict):
        stated = dict(cfg["pods"]["requests"])
        # the one-shape reference builds the nodes and the tallies, and
        # refuses to look at the shapes; what it fits with is the
        # stated requests, memory 0 where none is stated
        super().__init__({**cfg, "pods": {
            **{k: v for k, v in cfg["pods"].items() if k != "shapes"},
            "requests": {"cpu": stated.get("cpu", "0"),
                         "memory": stated.get("memory", "0")}}})
        self.cfg = cfg
        n = len(self.names)
        hosts = {deploy.node_labels(cfg, i).get(HOSTNAME)
                 for i in range(n)}
        if len(hosts) != n or None in hosts:
            raise ValueError("every node is its own topology domain here: "
                             f"{HOSTNAME} must differ node by node")
        shapes = [deploy.template_shape(cfg, t)
                  for t in range(self.templates)]
        if any(s["requests"] != stated for s in shapes):
            raise ValueError("this reference scores one request shape")
        labels = [deploy.template_labels(cfg, u)
                  for u in range(self.templates)]
        terms = [term_of(s) for s in shapes]
        # match[t, u]: template t's term selects template u's pods
        self.match = np.array(
            [[selects(terms[t], labels[u]) for u in range(self.templates)]
             for t in range(self.templates)], np.int64)
        # the default stands where a request is not stated, not where
        # it is stated as 0
        self.nz_pod_cpu = self.pod_cpu if "cpu" in stated \
            else DEFAULT_MILLI_CPU
        self.nz_pod_mem = self.pod_mem if "memory" in stated \
            else DEFAULT_MEMORY
        # the node's tally of its pods' non-zero requests
        # (schedulercache NodeInfo.NonZeroRequest)
        self.nz_cpu = np.zeros(n, np.int64)
        self.nz_mem = np.zeros(n, np.int64)
        self.incoming = 0

    def bind(self, template: int, node: int) -> None:
        super().bind(template, node)
        self.nz_cpu[node] += self.nz_pod_cpu
        self.nz_mem[node] += self.nz_pod_mem

    def excluded(self, template: int):
        """MatchInterPodAffinity's two directions for a pod of
        `template`, node by node."""
        mine = self.match[template] @ self.peers > 0
        theirs = self.match[:, template] @ self.peers > 0
        return mine | theirs

    def over_allocatable(self) -> int:
        """Nodes over allocatable, or holding two live pods of which
        one's term selects the other."""
        selected = self.match @ self.peers - np.diag(self.match)[:, None]
        together = ((self.peers > 0) & (selected > 0)).any(axis=0)
        over = ((self.req_cpu > self.cap_cpu) | (self.req_mem > self.cap_mem)
                | (self.pods > self.cap_pods))
        return int(np.count_nonzero(over | together))

    # -- one scheduling cycle -------------------------------------------------

    def fits(self):
        fit = super().fits()
        if self.term_holds:
            fit &= ~self.excluded(self.incoming)
        return fit

    def _scored_totals(self):
        """What the two resource priorities count on a node with the
        pod on it."""
        if self.nonzero_defaults:
            return (self.nz_cpu + self.nz_pod_cpu,
                    self.nz_mem + self.nz_pod_mem)
        return self.req_cpu + self.pod_cpu, self.req_mem + self.pod_mem

    def _least_requested(self):
        def score(total, cap):
            s = ((cap - total) * 10) // np.maximum(cap, 1)
            return np.where((cap == 0) | (total > cap), 0, s)

        total_cpu, total_mem = self._scored_totals()
        return (score(total_cpu, self.cap_cpu)
                + score(total_mem, self.cap_mem)) // 2

    def _balanced(self):
        total_cpu, total_mem = self._scored_totals()
        cpu = np.where(self.cap_cpu != 0, total_cpu.astype(np.float64)
                       / np.maximum(self.cap_cpu, 1), 1.0)
        mem = np.where(self.cap_mem != 0, total_mem.astype(np.float64)
                       / np.maximum(self.cap_mem, 1), 1.0)
        s = (10.0 - np.abs(cpu - mem) * 10.0).astype(np.int64)
        return np.where((cpu >= 1) | (mem >= 1), 0, s)

    def _inter_pod_affinity(self):
        """interpod_affinity.go:86-216 on pods whose only terms are
        required anti-affinity ones: no term adds to or takes from a
        node's count, so max == min and every node scores 0."""
        counts = np.zeros(len(self.names), np.float64)
        spread = counts.max(initial=0.0) - counts.min(initial=0.0)
        if spread > 0:
            return (MAX_PRIORITY * (counts - counts.min()) / spread
                    ).astype(np.int64)
        return np.zeros(len(self.names), np.int64)

    def ranking(self, template: int):
        self.incoming = template
        fit = self.fits()
        if not fit.any():
            return np.empty(0, np.int64)
        total = (self._least_requested() + self._balanced()
                 + self._spread(template, fit) + self._inter_pod_affinity())
        best = total[fit].max()
        top = fit & (total == best)
        return self.desc[top[self.desc]]
