"""The plain reference for deployments whose templates ask for different
resources (`pods.shapes`): the serial generic scheduler scoring each
pod with its own template's requests, in straightforward numpy.

It follows plugin/pkg/scheduler/generic_scheduler.go with the default
provider, as benchmark/reference.py does for one request shape. With
`cpu[t]`, `mem[t]` the requests `deploy.template_shape(cfg, t)` gives
template t, and `req`, `cap` a node's committed requests and its
allocatable:

    PodFitsResources        pods + 1 <= cap_pods, req_cpu + cpu[t] <=
                            cap_cpu, req_mem + mem[t] <= cap_mem
    LeastRequestedPriority  ((cap - (req + r[t])) * 10) / cap for cpu
                            and memory, int64 with truncating division,
                            then their sum halved the same way
    BalancedResourceAllocation
                            int(10 - |cpuFraction - memFraction| * 10),
                            the fractions (req + r[t]) / cap in float64
                            (`Cluster.real`), 0 where either reaches 1
    SelectorSpreadPriority  float32 over the template's own controller's
                            pods on nodes without zones, as reference.py

selectHost takes the best total, host name descending, round-robin
among ties by a counter that steps once per scheduled pod. One pod at a
time, and a bound pod commits ITS template's requests, so the next pod,
of whatever template, is scored on what the ones before it took. All of
that but the per-template requests is benchmark/reference.py's own: its
`Cluster` is extended here, and its serial loop, comparison and
stale-wave control (`decide`, `verify`) know a cluster only by its
`ranking` and `bind`.

Departures from upstream, each because the deployments here cannot
tell the difference:

  * a shape is read for its `requests` only; one that states an
    annotation, a node selector or a port is refused, because nothing
    here scores them;
  * every shape states cpu and memory, so upstream's defaults for a
    container without requests (100m, 200Mi in the two resource
    priorities) never apply, and the requests PodFitsResources counts
    are the ones the priorities count;
  * the default provider's other priorities (node affinity, taints,
    inter-pod affinity, node labels) give every node the same score on
    these pods and nodes, and are left out, as in reference.py;
  * nodes carry no zone: a deployment with zones and shapes brings a
    reference that scores both.

It imports nothing of the program and takes nothing the program made:
its input is the deployment file and pod->node pairs read back over
plain HTTP.
"""

from __future__ import annotations

import numpy as np

from benchmark import deploy, reference
from benchmark.reference import decide, verify  # noqa: F401  (the interface)

MAX_PRIORITY = reference.MAX_PRIORITY


class Cluster(reference.Cluster):
    """Nodes of a deployment, each template's requests, and what is
    bound to the nodes."""

    #: the precision BalancedResourceAllocation's fractions are computed
    #: in: upstream's float64. benchmark/control_precision.py puts
    #: float32 here to see whether the comparison tells the difference.
    real = np.float64

    def __init__(self, cfg: dict):
        # the one-shape reference builds the nodes and the tallies, and
        # refuses to look at the shapes
        super().__init__({**cfg, "pods": {
            k: v for k, v in cfg["pods"].items() if k != "shapes"}})
        self.cfg = cfg
        shapes = [deploy.template_shape(cfg, t)
                  for t in range(self.templates)]
        for t, shape in enumerate(shapes):
            if set(shape) != {"requests"}:
                raise ValueError(
                    f"this reference scores requests only; template {t} "
                    f"also states {sorted(set(shape) - {'requests'})}")
        self.shape_cpu = np.array(
            [deploy.milli_cpu(s["requests"]["cpu"]) for s in shapes],
            np.int64)
        self.shape_mem = np.array(
            [deploy.mem_bytes(s["requests"]["memory"]) for s in shapes],
            np.int64)
        # what a pod is fitted and scored with: what it asks for.
        # benchmark/control_shapes.py puts one shape for all here to
        # see whether the comparison tells the difference.
        self.scored_cpu, self.scored_mem = self.shape_cpu, self.shape_mem

    def bind(self, template: int, node: int) -> None:
        # the base class reads the pod in hand as `pod_cpu`, `pod_mem`
        self.pod_cpu = int(self.shape_cpu[template])
        self.pod_mem = int(self.shape_mem[template])
        super().bind(template, node)

    def ranking(self, template: int):
        self.pod_cpu = int(self.scored_cpu[template])
        self.pod_mem = int(self.scored_mem[template])
        return super().ranking(template)

    def _balanced(self):
        real = self.real
        total_cpu = (self.req_cpu + self.pod_cpu).astype(real)
        total_mem = (self.req_mem + self.pod_mem).astype(real)
        cpu = np.where(self.cap_cpu != 0,
                       total_cpu / np.maximum(self.cap_cpu, 1).astype(real),
                       real(1))
        mem = np.where(self.cap_mem != 0,
                       total_mem / np.maximum(self.cap_mem, 1).astype(real),
                       real(1))
        s = (real(10) - np.abs(cpu - mem) * real(10)).astype(np.int64)
        return np.where((cpu >= 1) | (mem >= 1), 0, s)
