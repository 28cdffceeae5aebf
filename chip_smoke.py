"""The quickest proof that the scheduling path still starts on the chip.

    python3 chip_smoke.py          # one TPU chip, every phase
    python3 chip_smoke.py --mesh   # all local chips (>= 2): mesh path only

Drives the system through the entry points its users start it with, on
whatever accelerator JAX finds — and refuses to run on anything that is
not a TPU (no flag makes a CPU run pass; tests call the phase functions
directly at small sizes). ONE process holds the chip: this one, with
the scheduler daemon inside it, exactly as `harness.perf --separate`
runs it. The apiserver and the pod creator are child processes that
never import jax.

Phases, in this order so the 1,024-node programs compile once:
  a. native engines present; device reported
  b. raw path at the north-star size (BASELINE.json config 5): 5,000
     nodes / 50,000 pause pods through TPUScheduleAlgorithm
  c. oracle identity on the chip: 128 density pods on 1,000 nodes, and
     a mixed 512-pod backlog on 256 zoned nodes that executes scan,
     grouped probe, apply and zreplay — equal to the serial oracle
  d. served path at full size (scheduler_perf density test B): 1,000
     nodes / 30,000 pods, apiserver process (TLV wire) + creator
     process + the daemon here; bindings read back over HTTP

Every phase raises on failure and nothing catches it: any failed phase
is a non-zero exit with no result line. The per-phase lines are a
smoke's, not measurements — they go into no record. The last line of
stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"


def require_tpu() -> dict:
    """-> the device as JAX reports it; SystemExit unless it is a TPU.
    Unconditional: the smoke exists to prove the CHIP path."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (got {device}); this script "
            "proves the chip path and never falls back")
    return device


def result_line(device: dict) -> str:
    return json.dumps({"ok": True, "device": device})


class CompileCounter:
    """Counts XLA programs built in this process and how many of them
    came out of the persistent cache, from jax.monitoring events — a
    second run in the same checkout shows the cache working."""

    def __init__(self):
        from jax import monitoring

        self.built = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.built += 1

    def _event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self):
        return self.built, self.hits


def report(phase: str, what: str, seconds: float, counter, before,
           device: dict) -> None:
    built = counter.built - before[0]
    hits = counter.hits - before[1]
    print(f"[{phase}] {what}; {seconds:.1f}s; programs {built} "
          f"(compiled {built - hits}, from cache {hits}); device "
          f"{device['platform']}/{device['kind']} x{device['count']}",
          flush=True)


# -- clusters -----------------------------------------------------------------


def _node(name: str, labels=None):
    from kubernetes_tpu.api.types import (
        Node,
        NodeCondition,
        NodeStatus,
        ObjectMeta,
    )

    # perf/util.go:88-118 node shape: 4 CPU / 32Gi / 110 pods
    return Node(
        metadata=ObjectMeta(name=name, labels=labels or {}),
        status=NodeStatus(
            allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
            conditions=[NodeCondition("Ready", "True")],
        ),
    )


def _pod(name: str, cpu: str = "100m", mem: str = "500Mi", labels=None,
         annotations=None):
    from kubernetes_tpu.api.types import Container, ObjectMeta, Pod, PodSpec

    # perf/util.go:120-141 pause pod: 100m / 500Mi
    return Pod(
        metadata=ObjectMeta(name=name, labels=labels or {},
                            annotations=annotations or {}),
        spec=PodSpec(containers=[
            Container(name="pause", image="kubernetes/pause:go",
                      requests={"cpu": cpu, "memory": mem})]),
    )


def density_cluster(num_nodes: int, num_pods: int):
    """scheduler_perf shapes, named as harness.creator names them."""
    from kubernetes_tpu.oracle import ClusterState

    nodes = [_node(f"node-{i:05d}") for i in range(num_nodes)]
    pods = [_pod(f"sched-perf-pod-{i:06d}", labels={"name": "sched-perf"})
            for i in range(num_pods)]
    return ClusterState.build(nodes), pods


def mixed_cluster(num_nodes: int, num_pods: int, min_run: int = 16):
    """A zoned cluster and a backlog that takes every single-chip
    program: 8 adjacent request templates, the first of which a term
    selects (a device replay of its own, then the grouped probe), one
    soft anti-affinity template and one service-backed run (the term
    owner and the zoned spread run side by side: the grouped device
    replay, which carries the grouped fold), a lone plain run (a probe
    of its own, its fold settled apart before the scan), and singletons
    below min_run (the scan)."""
    from kubernetes_tpu.api.types import (
        AFFINITY_ANNOTATION,
        ObjectMeta,
        Service,
        ServiceSpec,
    )
    from kubernetes_tpu.oracle import ClusterState

    nodes = [
        _node(f"znode-{i:04d}", labels={
            "kubernetes.io/hostname": f"znode-{i:04d}",
            ZONE_LABEL: "abc"[i % 3],
        })
        for i in range(num_nodes)
    ]
    singles = min(2 * (min_run - 1), max(num_pods // 16, 2))
    per_run = (num_pods - singles) // 11
    assert per_run >= min_run, "backlog too small to form template runs"
    pods = []
    for t in range(8):
        pods += [_pod(f"tpl{t}-{i:04d}", cpu=f"{100 + 25 * t}m",
                      mem="200Mi", labels={"app": f"tpl-{t}"})
                 for i in range(per_run)]
    soft_anti = json.dumps({"podAntiAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 5,
            "podAffinityTerm": {
                "labelSelector": {"matchLabels": {"app": "tpl-0"}},
                "topologyKey": "kubernetes.io/hostname",
            },
        }],
    }})
    pods += [_pod(f"anti-{i:04d}", mem="200Mi", labels={"app": "anti"},
                  annotations={AFFINITY_ANNOTATION: soft_anti})
             for i in range(per_run)]
    spread = num_pods - singles - 10 * per_run
    pods += [_pod(f"svc-{i:04d}", mem="200Mi",
                  labels={"app": "svc-backed"})
             for i in range(spread)]
    pods += [_pod(f"lone-{i:04d}", cpu="350m", mem="200Mi",
                  labels={"app": "lone"})
             for i in range(per_run)]
    # distinct requests, fewer than min_run of each: never a run
    pods += [_pod(f"single-{i:04d}", cpu=f"{300 + i}m", mem="200Mi",
                  labels={"app": "single"})
             for i in range(singles)]
    state = ClusterState.build(nodes, services=[Service(
        metadata=ObjectMeta(name="svc"),
        spec=ServiceSpec(selector={"app": "svc-backed"}),
    )])
    return state, pods


def _check_capacity(hosts, pods, state) -> collections.Counter:
    """Every pod placed and no node over its allocatable; -> pods per
    node."""
    from kubernetes_tpu.api.resource import (
        resource_list_cpu_milli,
        resource_list_memory,
    )

    placed = sum(1 for h in hosts if h is not None)
    assert placed == len(pods), f"only {placed}/{len(pods)} pods placed"
    count = collections.Counter(hosts)
    cpu = collections.Counter()
    mem = collections.Counter()
    for p, h in zip(pods, hosts):
        req = p.spec.containers[0].requests
        cpu[h] += resource_list_cpu_milli(req)
        mem[h] += resource_list_memory(req)
    for node in state.nodes():
        alloc = node.status.allocatable
        name = node.metadata.name
        assert count[name] <= int(alloc["pods"]), (name, count[name])
        assert cpu[name] <= resource_list_cpu_milli(alloc), (name, cpu[name])
        assert mem[name] <= resource_list_memory(alloc), (name, mem[name])
    return count


# -- phases -------------------------------------------------------------------


def phase_engines() -> tuple:
    """a. The three native engines built and loaded (the pure-Python
    degradation is for tests; a smoke that ran it would prove the
    wrong program)."""
    from kubernetes_tpu.api import resource
    from kubernetes_tpu.models import replay
    from kubernetes_tpu.native.build import ensure_all
    from kubernetes_tpu.runtime import tlv

    paths = ensure_all()
    assert all(paths), f"native engine(s) not built: {paths}"
    assert replay._load_lib() is not None, "replay engine did not load"
    assert resource._kquantity is not None, "_kquantity did not load"
    assert tlv._ktlv is not None, "_ktlv did not load"
    return paths


def phase_raw(num_nodes: int, num_pods: int) -> collections.Counter:
    """b. The raw tensor path: one schedule_backlog call over the whole
    backlog; all placed, no node over allocatable, and a warm second
    call decides identically. -> pods per node."""
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    state, pods = density_cluster(num_nodes, num_pods)
    algo = TPUScheduleAlgorithm()
    cold = algo.schedule_backlog(pods, state)
    count = _check_capacity(cold, pods, state)
    algo._last_node_index = 0  # selectHost's round-robin counter
    warm = algo.schedule_backlog(pods, state)
    assert warm == cold, "warm second call decided differently"
    return count


def phase_oracle(density_nodes: int, density_pods: int,
                 mixed_nodes: int, mixed_pods: int) -> dict:
    """c. Device decisions equal the serial oracle's. -> the mixed
    wave's dispatch tally."""
    from kubernetes_tpu.oracle import GenericScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    state, pods = density_cluster(density_nodes, density_pods)
    got = TPUScheduleAlgorithm().schedule_backlog(pods, state)
    want = GenericScheduler().schedule_backlog(pods, state)
    assert got == want, _first_diff("density", got, want)

    state, pods = mixed_cluster(mixed_nodes, mixed_pods)
    algo = TPUScheduleAlgorithm()
    got = algo.schedule_backlog(pods, state)
    want = GenericScheduler().schedule_backlog(pods, state)
    assert got == want, _first_diff("mixed", got, want)
    ran = dict(algo._wave.dispatches)
    for program in ("scan", "group_probe", "probe", "apply", "zreplay",
                    "zreplay_group"):
        assert ran.get(program, 0) >= 1, (
            f"mixed backlog never dispatched {program!r}: {ran}")
    return ran


def _first_diff(label, got, want) -> str:
    bad = [(i, g, w) for i, (g, w) in enumerate(zip(got, want)) if g != w]
    return (f"{label}: device and oracle disagree on {len(bad)}/"
            f"{len(want)} pods; first {bad[:5]}")


def phase_served(num_nodes: int, num_pods: int,
                 raw_count: collections.Counter) -> dict:
    """d. The served path: apiserver process over the TLV wire, creator
    process, scheduler daemon in THIS process. Every pod bound — read
    back from the apiserver over plain HTTP, not from the daemon's
    informer — no node over 110 pods, and the pods-per-node histogram
    equal to the raw path's on the same input. -> the harness stats."""
    from kubernetes_tpu.client.rest import RESTClient
    from kubernetes_tpu.client.transport import HTTPTransport
    from kubernetes_tpu.harness.perf import schedule_pods_separate

    def read_back(url: str) -> collections.Counter:
        client = RESTClient(HTTPTransport(url, timeout=180.0,
                                          user="system:kube-scheduler"))
        items, _rv = client.pods().list()
        assert len(items) == num_pods, (len(items), num_pods)
        unbound = [p.metadata.name for p in items if not p.spec.node_name]
        assert not unbound, f"{len(unbound)} pods unbound: {unbound[:5]}"
        return collections.Counter(p.spec.node_name for p in items)

    stats = schedule_pods_separate(num_nodes, num_pods, "TPUProvider",
                                   out=sys.stderr, check=read_back)
    count = stats.pop("check")
    assert max(count.values()) <= 110, max(count.values())
    assert sorted(count.values()) == sorted(raw_count.values()), (
        "served pods-per-node histogram differs from the raw path's: "
        f"{sorted(collections.Counter(count.values()).items())} vs "
        f"{sorted(collections.Counter(raw_count.values()).items())}")
    return stats


def mesh_backlog(num_nodes: int, num_pods: int, min_run: int = 16):
    """The mesh phase's input: 8 request templates in repeating blocks
    (grouped and single sharded probes + folds) with a sub-min_run
    pair of odd pods after every 8th block (the sharded scan)."""
    from kubernetes_tpu.oracle import ClusterState

    nodes = [_node(f"node-{i:05d}") for i in range(num_nodes)]
    block = max(min_run, num_pods // 100)
    pods = []
    b = 0
    while len(pods) < num_pods:
        t = b % 8
        n = min(block, num_pods - len(pods))
        pods += [_pod(f"m{b:04d}-{i:05d}", cpu=f"{100 + 25 * t}m",
                      labels={"app": f"tpl-{t}"}) for i in range(n)]
        b += 1
        if b % 8 == 0 and len(pods) + 2 <= num_pods:
            pods += [_pod(f"odd{b:04d}-{i}", cpu=f"{310 + b + i}m",
                          labels={"app": "odd"}) for i in range(2)]
    return ClusterState.build(nodes), pods


def phase_mesh(num_nodes: int, num_pods: int, devices=None) -> dict:
    """--mesh: the node axis sharded over ALL local devices (what
    KUBERNETES_TPU_MESH=auto gives a daemon on a multi-chip host; a
    test passes a subset), the folds donated as they always are,
    decisions equal to the single-chip driver's on the same input, and
    the resident node tables really spread over distinct devices.
    -> the mesh wave's dispatch tally and the three calls' seconds (a
    smoke's, on the host clock: where the phase's time went, not a
    measurement)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    devices = list(devices or jax.devices())
    assert len(devices) >= 2, (
        f"--mesh needs >= 2 devices, JAX sees {len(devices)}")
    state, pods = mesh_backlog(num_nodes, num_pods)
    algo = TPUScheduleAlgorithm(mesh=Mesh(np.array(devices), ("nodes",)))
    t0 = time.time()
    got = algo.schedule_backlog(pods, state)
    cold = time.time() - t0
    _check_capacity(got, pods, state)

    resident = algo._mesh_sched.resident
    for label, arr, axis in (
            ("alloc_mcpu", resident._static["alloc_mcpu"], 0),
            ("carry resources", resident._carry[0], 1)):
        shards = arr.addressable_shards
        on = {s.device for s in shards}
        assert len(on) == len(devices), (
            f"{label}: shards sit on {len(on)} device(s), not "
            f"{len(devices)} — the node axis is not really sharded")
        assert all(s.data.shape[axis] * len(devices) == arr.shape[axis]
                   for s in shards), f"{label}: uneven or replicated"

    # a warm second call over the donated resident buffers: the same
    # decisions, and its seconds apart from the cold call's compiles
    algo._last_node_index = 0
    t0 = time.time()
    again = algo.schedule_backlog(pods, state)
    warm = time.time() - t0
    assert again == got, _first_diff("mesh warm vs cold", again, got)

    t0 = time.time()
    want = TPUScheduleAlgorithm().schedule_backlog(pods, state)
    single = time.time() - t0
    assert got == want, _first_diff("mesh vs single-chip", got, want)
    return {"dispatches": dict(algo._mesh_sched.dispatches),
            "seconds": {"mesh_cold": round(cold, 1),
                        "mesh_warm": round(warm, 1),
                        "single_chip_cold": round(single, 1)}}


# -- entry --------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run ONLY the multi-chip mesh phase and what it "
                         "is compared with, over all local devices")
    args = ap.parse_args(argv)

    import kubernetes_tpu  # noqa: F401  (x64 + compile cache, before jax)

    device = require_tpu()
    counter = CompileCounter()
    print(f"compile cache: {os.environ.get('JAX_COMPILATION_CACHE_DIR')}",
          flush=True)

    def run(phase, what, fn, *a):
        before, t0 = counter.snapshot(), time.time()
        out = fn(*a)
        report(phase, what(out) if callable(what) else what,
               time.time() - t0, counter, before, device)
        return out

    run("a", lambda p: "native engines " + ", ".join(
        os.path.basename(x) for x in p), phase_engines)
    if args.mesh:
        run("mesh", lambda d: (
            f"20,000 nodes / 50,000 mixed pods sharded over "
            f"{device['count']} devices == single-chip, warm rerun "
            f"identical; folds donated; {d}"), phase_mesh, 20_000, 50_000)
        print(result_line(device), flush=True)
        return

    # the default phases test the SINGLE-CHIP daemon on any machine:
    # `auto` would put phase d on the mesh wherever >1 chip is visible
    os.environ["KUBERNETES_TPU_MESH"] = "off"
    run("b", "raw path 5,000 nodes / 50,000 pods: all placed, within "
        "allocatable, warm rerun identical", phase_raw, 5000, 50_000)
    run("c", lambda d: (
        "oracle identity: 128 density pods / 1,000 nodes; 512 mixed "
        f"pods / 256 zoned nodes, dispatches {d}"),
        phase_oracle, 1000, 128, 256, 512)
    raw_count = run("d-ref", "raw path 1,000 nodes / 30,000 pods (the "
                    "served phase's reference histogram)",
                    phase_raw, 1000, 30_000)
    run("d", lambda s: (
        "served path 1,000 nodes / 30,000 pods: every pod bound (HTTP "
        "read-back), 30 per node as the raw path; daemon set-up "
        f"{s['ready_seconds']}s apart from the scheduling window "
        f"(creation {s['creation_seconds']}s, creation->all-bound "
        f"{s['pipeline_seconds']}s)"),
        phase_served, 1000, 30_000, raw_count)
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main()
