"""The plain reference: the serial generic scheduler for the shapes the
benchmark's deployments use, in straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider, for pods that carry only cpu and memory requests and labels,
on Ready nodes with no taints: PodFitsResources filters;
LeastRequestedPriority (int64), BalancedResourceAllocation (float64)
and SelectorSpreadPriority (float32) score, on nodes that carry no
zone label; selectHost takes the best score, host name descending,
round-robin among ties by a counter that steps once per scheduled pod.
The default provider's other priorities give every node the same score
here and are left out. One pod at a time, each commit seen by the
next.

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP. A deployment names its reference (`reference` in its
file); one with zoned nodes brings a reference of its own.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import deploy

MAX_PRIORITY = 10


class Cluster:
    """Nodes of a deployment and what is bound to them."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        n = cfg["nodes"]["count"]
        self.names = [deploy.node_name(cfg, i) for i in range(n)]
        self.index = {name: i for i, name in enumerate(self.names)}
        # selectHost's order among equal scores: host name descending
        self.desc = np.array(sorted(range(n), key=self.names.__getitem__,
                                    reverse=True))
        if cfg["nodes"].get("zones"):
            raise ValueError("this reference scores no zones")
        alloc = cfg["nodes"]["allocatable"]
        self.cap_cpu = np.full(n, deploy.milli_cpu(alloc["cpu"]), np.int64)
        self.cap_mem = np.full(n, deploy.mem_bytes(alloc["memory"]),
                               np.int64)
        self.cap_pods = np.full(n, int(alloc["pods"]), np.int64)
        req = cfg["pods"]["requests"]
        self.pod_cpu = deploy.milli_cpu(req["cpu"])
        self.pod_mem = deploy.mem_bytes(req["memory"])
        self.selecting = int(cfg["controllers"]["count"]) > 0
        self.templates = deploy.num_templates(cfg)
        self.req_cpu = np.zeros(n, np.int64)
        self.req_mem = np.zeros(n, np.int64)
        self.pods = np.zeros(n, np.int64)
        # peers[t, node]: bound pods that template t's controller selects
        self.peers = np.zeros((self.templates, n), np.int64)

    def bind(self, template: int, node: int) -> None:
        self.req_cpu[node] += self.pod_cpu
        self.req_mem[node] += self.pod_mem
        self.pods[node] += 1
        self.peers[template, node] += 1

    def over_allocatable(self) -> int:
        return int(np.count_nonzero(
            (self.req_cpu > self.cap_cpu) | (self.req_mem > self.cap_mem)
            | (self.pods > self.cap_pods)))

    # -- one scheduling cycle -------------------------------------------------

    def fits(self):
        return ((self.pods + 1 <= self.cap_pods)
                & (self.req_cpu + self.pod_cpu <= self.cap_cpu)
                & (self.req_mem + self.pod_mem <= self.cap_mem))

    def _least_requested(self):
        def score(total, cap):
            s = ((cap - total) * 10) // np.maximum(cap, 1)
            return np.where((cap == 0) | (total > cap), 0, s)

        cpu = score(self.req_cpu + self.pod_cpu, self.cap_cpu)
        mem = score(self.req_mem + self.pod_mem, self.cap_mem)
        return (cpu + mem) // 2

    def _balanced(self):
        cpu = np.where(self.cap_cpu != 0,
                       (self.req_cpu + self.pod_cpu) / self.cap_cpu, 1.0)
        mem = np.where(self.cap_mem != 0,
                       (self.req_mem + self.pod_mem) / self.cap_mem, 1.0)
        s = (10.0 - np.abs(cpu - mem) * 10.0).astype(np.int64)
        return np.where((cpu >= 1) | (mem >= 1), 0, s)

    def _spread(self, template: int, fit):
        """selector_spreading.go:84, over the nodes that fit, in
        float32 as upstream computes it."""
        n = len(self.names)
        if not self.selecting:
            return np.full(n, MAX_PRIORITY, np.int64)
        counts = self.peers[template]
        max_count = int(counts[fit].max(initial=0))
        if max_count == 0:
            return np.full(n, MAX_PRIORITY, np.int64)
        f32 = np.float32
        share = (max_count - counts).astype(f32) / f32(max_count)
        return (f32(MAX_PRIORITY) * share).astype(np.int64)

    def ranking(self, template: int):
        """-> the nodes that share the best score, host name
        descending; empty where nothing fits."""
        fit = self.fits()
        if not fit.any():
            return np.empty(0, np.int64)
        total = (self._least_requested() + self._balanced()
                 + self._spread(template, fit))
        best = total[fit].max()
        top = fit & (total == best)
        return self.desc[top[self.desc]]


def decide(cluster: Cluster, backlog, last_node_index: int,
           stale: int = 1) -> list:
    """Schedule `backlog` (template numbers) serially, committing each
    pick. -> node index per pod, None where nothing fit. With
    `stale` > 1 the picks of that many pods are committed together,
    after the last: the batch that does not thread its commitments,
    which breaks the guarantee that decisions are the serial ones
    (the control)."""
    picks, held = [], []
    for t in backlog:
        ties = cluster.ranking(t)
        if len(ties) == 0:
            picks.append(None)
            continue
        node = int(ties[last_node_index % len(ties)])
        last_node_index += 1
        picks.append(node)
        held.append((t, node))
        if len(held) >= stale:
            for pair in held:
                cluster.bind(*pair)
            held = []
    for pair in held:
        cluster.bind(*pair)
    return picks


def _merge(r, m, a, n):
    """x = r (mod m) and x = a (mod n) -> (r', lcm), or None."""
    g = math.gcd(m, n)
    if (a - r) % g:
        return None
    lcm = m // g * n
    k = ((a - r) // g * pow(m // g, -1, n // g)) % (n // g) if n // g > 1 \
        else 0
    return (r + m * k) % lcm, lcm


def verify(cluster: Cluster, backlog, picks) -> dict:
    """Hold the picks a scheduler made for `backlog`, in order, against
    the reference. Each pick has to be one of the reference's best
    nodes, and its place among them has to be the round-robin counter's:
    the counter's value is the scheduler's own, so the picks are held
    to there being ONE starting value that explains all of them.
    The cluster follows the picks as made. -> {"mismatches", "checked",
    "counter": (residue, modulus)}"""
    residue, modulus = 0, 1
    mismatches = checked = scheduled = 0
    for t, node in zip(backlog, picks):
        ties = cluster.ranking(t)
        checked += 1
        if node is None:
            mismatches += len(ties) != 0
            continue
        where = np.flatnonzero(ties == node)
        if len(where) == 0:
            mismatches += 1
        else:
            merged = _merge(residue, modulus,
                            (int(where[0]) - scheduled) % len(ties),
                            len(ties))
            if merged is None:
                mismatches += 1
            else:
                residue, modulus = merged
        scheduled += 1
        cluster.bind(t, node)
    return {"mismatches": mismatches, "checked": checked,
            "counter": (residue, modulus)}
