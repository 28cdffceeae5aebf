"""The plain reference for deployments whose nodes carry a zone: the
serial generic scheduler with upstream's zone-weighted spreading, in
straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider, as benchmark/reference.py does for unzoned nodes, and
plugin/pkg/scheduler/algorithm/priorities/selector_spreading.go
(CalculateSpreadPriority) for SelectorSpreadPriority. Over the nodes
that fit, with `count` the bound pods the pod's controller selects:

    maxNode  = the largest count on one node
    zoneCount[z] = the sum of count over the fitting nodes of zone z
    maxZone  = the largest zoneCount
    node  = 10 * (maxNode - count) / maxNode      (10 where maxNode is 0)
    zone  = 10 * (maxZone - zoneCount[z]) / maxZone
    score = int(node * (1 - 2/3) + (2/3) * zone)  for a node with a zone
    score = int(node)                             for a node with none

every step in float32, the result truncated toward zero. PodFitsResources
filters; LeastRequestedPriority (int64) and BalancedResourceAllocation
(float64) score beside it; selectHost takes the best total, host name
descending, round-robin among ties by a counter that steps once per
scheduled pod. One pod at a time, each commit seen by the next. All of
that but the spread score is benchmark/reference.py's own: its
`Cluster` (fit, the two resource scorers, the ranking) is extended here
with the zones, and its serial loop, comparison and stale-wave control
(`decide`, `verify`) know a cluster only by its `ranking`.

Departures from upstream, each because the deployments here cannot
tell the difference:

  * zones are dealt by the deployment file (`nodes.zones`, round-robin
    by index, as benchmark/deploy.py labels the nodes); upstream reads
    the zone and region labels into one key, and the deployments carry
    no region;
  * where no fitting node of any zone holds a selected pod, upstream
    divides 0 by 0, and Go's int(NaN) on amd64 is the smallest int64
    for every zoned node alike; the reference gives them that same
    number, so the other two scorers decide, as they do upstream;
  * the default provider's other priorities (node affinity, taints,
    inter-pod affinity, node labels) give every node the same score
    on these pods and nodes, and are left out;
  * a deployment with no controllers has no selectors: every node
    scores 10, zones or not (selector_spreading.go: the counts map
    stays empty).

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference
from benchmark.reference import decide, verify  # noqa: F401  (the interface)

MAX_PRIORITY = reference.MAX_PRIORITY
#: selector_spreading.go:38
ZONE_WEIGHTING = 2.0 / 3.0
#: Go's int(NaN) on amd64
INT_OF_NAN = np.iinfo(np.int64).min


class Cluster(reference.Cluster):
    """Nodes of a deployment, their zones, and what is bound to them."""

    #: the precision SelectorSpreadPriority is computed in. Upstream's,
    #: and the deployment's guarantee, is float32; a test puts float16
    #: here to show that the comparison tells the difference.
    real = np.float32

    def __init__(self, cfg: dict):
        # the unzoned reference builds everything but the zones, which
        # it refuses to look at
        super().__init__({**cfg, "nodes": {**cfg["nodes"], "zones": []}})
        self.cfg = cfg
        zones = cfg["nodes"].get("zones") or []
        n = len(self.names)
        # zone number per node, dealt as deploy.node_labels deals the
        # letters; -1 where the deployment has no zones
        self.zone = (np.arange(n) % len(zones) if zones
                     else np.full(n, -1)).astype(np.int64)
        self.num_zones = len(zones)

    def _spread(self, template: int, fit):
        """CalculateSpreadPriority over the nodes that fit, in
        `self.real` (float32 as upstream computes it)."""
        n = len(self.names)
        if not self.selecting:
            return np.full(n, MAX_PRIORITY, np.int64)
        real = self.real
        counts = self.peers[template]
        max_node = int(counts[fit].max(initial=0))
        score = np.full(n, real(MAX_PRIORITY))
        if max_node > 0:
            score = real(MAX_PRIORITY) * (
                (max_node - counts).astype(real) / real(max_node))
        if not self.num_zones:
            return score.astype(np.int64)
        by_zone = np.bincount(self.zone[fit], weights=counts[fit],
                              minlength=self.num_zones).astype(np.int64)
        max_zone = int(by_zone.max())
        if max_zone == 0:
            return np.full(n, INT_OF_NAN, np.int64)
        zone_score = real(MAX_PRIORITY) * (
            (max_zone - by_zone[self.zone]).astype(real) / real(max_zone))
        blended = (score * real(1.0 - ZONE_WEIGHTING)
                   + real(ZONE_WEIGHTING) * zone_score)
        return blended.astype(np.int64)
