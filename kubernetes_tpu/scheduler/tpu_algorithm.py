"""The TPU ScheduleAlgorithm: ClusterState -> device program -> hosts.

Bridges the event-driven shell (SchedulerCache snapshots) to the batched
tensor program (models/batch.BatchScheduler): encode the snapshot
columnar (snapshot/encode.py), run the scan program, map chosen node
ids back to names. Decisions are bit-identical to the serial oracle
(tests/test_conformance.py), so the shell can treat this exactly like
the host GenericScheduler — schedule() for one pod, schedule_backlog()
for a whole FIFO wave in one dispatch.
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional, Sequence

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.oracle.scheduler import FitError
from kubernetes_tpu.oracle.state import ClusterState
from kubernetes_tpu.trace import profile as trace_profile

log = logging.getLogger(__name__)

#: after this long the re-warm starts no further pod bucket of the scan
#: and hands the loop back for a wave (`TPUScheduleAlgorithm._rewarm`):
#: from the compile cache all seven buckets take seconds; where they
#: compile, 20-45 s each on the chip, two or three fit and the last one
#: started ends at 105 s at most, behind a wave that built its own
#: bucket in 45 s: half of the 300 s the benchmark's load generator
#: gives a prefill step
REWARM_SLICE_S = 60.0

#: node slots a device on a mesh: the sharded node axis is a multiple
#: of this times the devices (lanes of 128; a cluster that outgrows its
#: bucket compiles the mesh programs anew, as one that doubles does on
#: a single chip)
MESH_SLOTS_PER_SHARD = 256


def _eager_scan_warm() -> bool:
    """KUBERNETES_TPU_WARM_SCAN=1: compile the scan-path programs during
    the run-phase warmup instead of waiting for 5s of daemon idleness.
    Off by default — a cold compile cache pays ~20 s per scan program
    before the loop opens, and the idle-deferred scan warm exists
    exactly for that."""
    import os

    return os.environ.get(
        "KUBERNETES_TPU_WARM_SCAN", "").strip().lower() in (
        "1", "true", "on", "yes")


def interpod_widths(snap, batch) -> Optional[tuple]:
    """The widths of the inter-pod tables that the wave programs are
    traced per, beyond the node and pod axes: topology combos, term
    classes, specs, logical terms and their expansion, the domain axis
    (the snapshot's `ip_*` tables) and the pods' own term lists (hard
    affinity, hard anti-affinity, preferred). None where neither the
    cluster nor the wave has ever carried a term: every table is then
    zero-width and the start-up warm-up's programs serve."""
    import numpy as np

    terms, expansion = (tuple(np.shape(snap.ip_lt_u)) + (0, 0))[:2]
    units, specs = len(snap.ip_u_topo), len(snap.ip_spec_total)
    if not (units or specs or terms):
        return None
    return (np.shape(snap.ip_topo_dom)[0], units, specs, terms, expansion,
            np.shape(snap.ip_term_count)[-1], batch.ip_ha_lt.shape[1],
            batch.ip_hq_lt.shape[1], batch.ip_fwd_lt.shape[1])


#: `interpod_widths`' entries by name, for the span and the log
WIDTH_NAMES = ("combos", "classes", "specs", "terms", "expansion", "domains",
               "own_affinity", "own_anti", "own_preferred")


def _ids_to_names(chosen, node_names, n_real) -> List[Optional[str]]:
    """Device node ids -> names; -1 and padded ids mean unschedulable."""
    return [
        node_names[i] if 0 <= i < n_real else None
        for i in (int(c) for c in chosen)
    ]


class TPUScheduleAlgorithm:
    def __init__(self, mesh=None, min_run: int = 16, cache=None,
                 service_lister=None, controller_lister=None,
                 replica_set_lister=None, config=None, replay=None,
                 profile=None):
        """config: a models/batch SchedulerConfig overriding the default
        provider — the device end of a resolved Policy file
        (factory.go:266 CreateFromConfig). replay overrides the wave
        replay engine (testing seam; also disables the device replay).
        profile picks the wave driver: "greedy" (default; bit-identical
        to the serial oracle) or "optimizing" (the joint-packing
        profile, scheduler/optimizer); None reads
        KUBERNETES_TPU_PROFILE."""
        # compile-vs-execute attribution: listening before any program
        # compiles means the first jit of every shape lands in
        # scheduler_xla_compile_seconds, not in a phase histogram
        trace_profile.install_compile_listener()
        from kubernetes_tpu.scheduler.optimizer import (
            PROFILE_OPTIMIZING,
            active_profile,
        )

        self._profile = active_profile(profile)
        self._opt = None
        self._mesh_sched = None
        self._inc = None
        if mesh is not None and self._profile == PROFILE_OPTIMIZING:
            # the optimizing profile is single-chip for now; the mesh
            # path keeps the greedy driver (its resident-state grouped
            # machinery) rather than silently changing semantics
            log.warning("KUBERNETES_TPU_PROFILE=optimizing is not "
                        "supported on the mesh driver; using greedy")
            self._profile = "greedy"
        if mesh is not None:
            from kubernetes_tpu.parallel.mesh import MeshWaveScheduler

            # `_wave` is the wave driver of either build (its config,
            # floors, stats and per-wave dispatches); `_mesh_sched` is
            # set when it is the sharded one
            self._wave = self._mesh_sched = MeshWaveScheduler(
                mesh, config=config, min_run=min_run
            )
        else:
            from kubernetes_tpu.models.wave import WaveScheduler

            self._wave = WaveScheduler(config=config, min_run=min_run,
                                       replay=replay)
        self._sched = self._wave.scan
        if cache is not None:
            # daemon mode: maintain the snapshot incrementally from
            # cache deltas instead of re-encoding the cluster per wave
            # (both drivers: the mesh resident state additionally
            # content-compares the view against its host mirrors, so an
            # unchanged incremental view ships zero node-table bytes)
            self._inc = self._new_encoder()
            cache.add_listener(self._inc.on_cache_event)
        # the re-warm at inter-pod widths first seen (`_rewarm`): the
        # daemon's own encoder (a warm-up swaps `_inc`), a pod of every
        # template seen pending (by feature key) and the kind of step a
        # run of it makes (`WaveScheduler.run_kinds`, once terms are
        # live), whether its pods take the scan as a rule, the widths
        # the scan and the widths the run programs are already warmed
        # at, the widths being warmed, whether their run programs, which
        # of the scan's pod buckets and which of the grouped device
        # replay's run-slot buckets are still to warm, and the last
        # wave's widths
        self._live_inc = self._inc
        self._templates: dict = {}
        self._template_kinds: dict = {}
        self._warmed_widths: set = set()
        self._warmed_run_widths: set = set()
        self._scan_bound = False
        self._rewarm_widths = None
        self._rewarm_runs = False
        self._rewarm_left: List[int] = []
        self._rewarm_slots: List[int] = []
        self._last_widths = None
        self._service_lister = service_lister
        self._controller_lister = controller_lister
        self._replica_set_lister = replica_set_lister
        # selectHost's round-robin counter persists across waves, like the
        # reference's genericScheduler.lastNodeIndex persists across pods
        self._last_node_index = 0
        # serializes warmup against real waves (the scheduler loop itself
        # is single-threaded; warmup runs on a server thread)
        self._sched_lock = threading.Lock()

    def _new_encoder(self):
        """An incremental encoder for this build's driver. On a mesh
        the node axis grows by MESH_SLOTS_PER_SHARD slots a device, so
        that every shard holds real nodes; the single-chip driver keeps
        the doubling its programs' shapes were compiled for."""
        from kubernetes_tpu.snapshot.incremental import IncrementalEncoder

        step = None
        if self._mesh_sched is not None:
            step = MESH_SLOTS_PER_SHARD * self._mesh_sched.mesh.devices.size
        return IncrementalEncoder(config=self._wave.config, slot_step=step)

    def _dedup(self, pods: Sequence[Pod]):
        """Template-created pods (RC/RS/Job) are identical up to their
        name: encode one representative per distinct feature key.
        -> (representatives, each pod's representative, their keys)"""
        import numpy as np

        from kubernetes_tpu.snapshot.encode import pod_feature_key

        reps: List[Pod] = []
        rep_of_key = {}
        rep_idx = np.empty(len(pods), np.int64)
        for i, p in enumerate(pods):
            k = pod_feature_key(p)
            r = rep_of_key.get(k)
            if r is None:
                r = len(reps)
                rep_of_key[k] = r
                reps.append(p)
            rep_idx[i] = r
        return reps, rep_idx, list(rep_of_key)

    def warmup(self, num_nodes: int, phase: str = "all",
               nodes: Optional[Sequence] = None) -> None:
        """Compile the wave programs for an `num_nodes`-sized cluster
        before the first real pod arrives (server.py runs this in the
        background while informers sync): a cold XLA compile
        otherwise lands on the first scheduling cycle.
        Uses a synthetic cluster shaped like the one it will serve, as
        far as that is known before a pod arrives: the caller's own
        `nodes` where it has them (their labels and zones set the label
        and zone widths; unlabeled synthetic ones otherwise), and pods
        that carry the selectors of the daemon's ReplicationControllers
        (label-only `app: warm` pods where there are none), one of each
        already bound, so the spread-class axis is as wide as the
        controllers' templates make it. The per-bucket backlogs deal the
        templates in turn, as replication managers replacing replicas
        do: with one controller that is one run (the probe path), with
        hundreds every run has length 1 and each bucket warms the scan
        program at the cluster's real shapes.

        phase "run" warms only the run path (probe+replay+apply — what
        every template-created backlog hits); phase "scan" warms the
        heterogeneous-pod scan path. The caller (server.py) runs "run"
        first and defers "scan" until the daemon is idle, so the loop
        opens for business after the template-path slice instead of the
        whole program set.

        The mesh driver warms through the same backlogs (its sharded
        header probe and folds for the runs, its sharded scan at every
        pod bucket for the lone pods), and one more thing with them:
        the warm-up's encoder never hears of a backlog's picks, so the
        nodes the backlog before filled differ from the resident
        state's mirrors, and the row scatter that ships a wave's churn
        compiles here at every row bucket up to the wave cap. The
        single-chip driver's own row scatter is warmed apart
        (`_warm_row_scatter`).

        What is warmed when, on one chip: here, before the loop opens,
        every program at the cluster's node, label, zone and
        spread-class widths with ZERO-WIDTH inter-pod tables (the
        run's probe, replay and fold, the grouped programs at two run
        slots, the scan at every pod bucket, nine row-scatter bucket
        pairs); behind the first wave that shows a set of inter-pod
        widths (`_rewarm`, with KUBERNETES_TPU_WARM_SCAN on: the terms
        are the pods' annotations, which nothing here can know), at
        those widths: on a cluster whose pods take the scan as a rule
        (`_after_live_wave` says by what evidence) the run programs on
        the kinds of run the templates seen pending make (a run alone,
        its like side by side, the grouped programs at their smallest
        run-slot buckets) and the scan again at every pod
        bucket; on a cluster whose pods do not, and whose runs take the
        device replay (owners of terms the run tables hold), the same
        run programs and `jit_zreplay_group` at every run-slot bucket a
        wave can fill, since there a wave is ONE group of as many run
        slots as it has runs. Still warmed by nobody, and compiled
        where first met: the grouped device replay at its larger
        run-slot bucket (over 32 runs) and the grouped header probe over
        16 on a cluster that does use the scan, whose stretches cut a
        wave's groups short; the spread-class axis while
        it grows as controllers' pods first appear; the probes and the
        fold at inter-pod widths where the host replays the runs
        (`replay=`); and the transfers' unpack programs, one a set of
        tables shipped (a benchmark mix's prefill steps meet those:
        PERF.md section 7)."""
        from kubernetes_tpu.api.types import (
            Container,
            Node,
            NodeCondition,
            NodeStatus,
            ObjectMeta,
            Pod as PodT,
            PodSpec,
        )
        from kubernetes_tpu.oracle.state import ClusterState as CS

        nodes = list(nodes) if nodes else [
            Node(
                metadata=ObjectMeta(name=f"warm-{i:05d}"),
                status=NodeStatus(
                    allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
                    conditions=[NodeCondition("Ready", "True")],
                ),
            )
            for i in range(max(num_nodes, 1))
        ]
        lister = self._controller_lister
        controllers = list(lister.list()) if lister is not None else []
        templates = [dict(rc.spec.selector) for rc in controllers
                     if rc.spec.selector] or [{"app": "warm"}]

        def pod(name, cpu, turn=0):
            return PodT(
                metadata=ObjectMeta(
                    name=name, labels=templates[turn % len(templates)]),
                spec=PodSpec(containers=[
                    Container(image="warm", requests={"cpu": cpu})
                ]),
            )

        bound = []
        if controllers:
            for t in range(len(templates)):
                p = pod(f"wbound-{t}", "100m", t)
                p.spec.node_name = nodes[t % len(nodes)].metadata.name
                bound.append(p)
        state = CS.build(nodes, bound, controllers=controllers)
        shared = []  # the mesh driver's one encoder for every backlog

        def warm(backlog):
            self._warm_one(backlog, state, nodes, bound, shared)

        # an eligible run (probe+replay+apply programs); the lone pods
        # distinct only in their requests (below min_run => the scan
        # program) warm in phase "scan" — differing by resources keeps
        # every vocab width, and therefore every compiled shape,
        # identical to the run's
        if phase in ("all", "run"):
            warm([pod(f"w{i}", "100m")
                  for i in range(max(self._wave.min_run, 2))])
            # two adjacent template runs warm the GROUPED programs
            # (header probe + grouped fold) — the multi-template
            # backlog shape every RC/RS burst mix hits
            n = max(self._wave.min_run, 2)
            warm([pod(f"wg{i}", "100m") for i in range(n)]
                 + [pod(f"wh{i}", "150m") for i in range(n)])
            # every pod-axis pow2 bucket a daemon wave can land in:
            # burst-adaptive gathering produces waves anywhere in
            # [pod_floor, wave cap], and each bucket is its own compiled
            # shape. Left cold, those compiles land MID-STORM — measured
            # ~4.5s of trace + compile-cache-read CPU interleaved with
            # the first minutes of a 30k-pod create burst, all of it
            # removable by compiling here, before the loop opens.
            buckets = self._pod_buckets()
            for bucket in buckets:
                warm([pod(f"wb{bucket}-{i}", "100m", i)
                      for i in range(bucket)])
            if _eager_scan_warm():
                # sub-min_run trickle waves hit the SCAN program, whose
                # warm normally waits for 5s of sustained idleness — a
                # window a continuous-arrival storm never opens, so the
                # scan compiles landed mid-storm (~2s of trace CPU
                # interleaved with creation). Opt-in because a cold
                # compile cache pays tens of seconds here before the
                # loop opens; the wire bench and soak harness set it.
                for k in (2, buckets[-1]):
                    warm([pod(f"wsb{k}-{i}", f"{200 + i}m")
                          for i in range(k)])
            if bound and self._mesh_sched is None and self._inc is not None:
                def bind(name, node):
                    p = pod(name, "100m")
                    p.spec.node_name = node.metadata.name
                    return p

                self._warm_row_scatter(
                    [pod(f"wr{i}", "100m")
                     for i in range(max(self._wave.min_run, 2))],
                    state, nodes, bound, bind)
        if phase in ("all", "scan"):
            warm([pod("w-scan", "200m"), pod("w-scan2", "300m")])

    def _pod_buckets(self) -> List[int]:
        """Every pod-axis pow2 bucket a daemon wave can land in, smallest
        first: each is a compiled shape of its own."""
        from kubernetes_tpu.scheduler.core import WAVE_CAP

        buckets, bucket = [], max(self._wave.pod_floor, self._wave.min_run, 2)
        while bucket <= WAVE_CAP:
            buckets.append(bucket)
            bucket *= 2
        return buckets

    def _slot_buckets(self) -> List[int]:
        """Every run-slot bucket a grouped device replay can land in,
        smallest first (`waveloop.DEVICE_SLOT_BUCKETS`, as far as a wave
        of WAVE_CAP pods in runs of `min_run` reaches): each is a
        compiled shape of its own."""
        from kubernetes_tpu.models.waveloop import DEVICE_SLOT_BUCKETS
        from kubernetes_tpu.scheduler.core import WAVE_CAP

        most = WAVE_CAP // max(self._wave.min_run, 2)
        buckets: List[int] = []
        for bucket in DEVICE_SLOT_BUCKETS:
            buckets.append(bucket)
            if bucket >= most:
                break
        return buckets

    def _warm_row_scatter(self, backlog, state, nodes, bound, bind) -> None:
        """The single-chip driver ships a table of which few rows
        changed since the wave before as a row scatter, a program per
        power of two of rows (WaveScheduler._to_dev_many). A wave's
        churn touches most nodes, so a storm meets the small buckets
        only in a lull, and compiled them there (two programs inside a
        measured window in one full-size rehearsal of five; PERF.md,
        PR 39). Here one encoder serves a row of small waves, and
        between them hears of 1, 2, 4, ... newly bound pods of a
        controller on as many nodes, up to the share of the node slots
        at which the driver ships the table whole (`bind(name, node)`
        makes one): the spread counts' rows change, and every bucket is
        met."""
        from kubernetes_tpu.models.wave import WaveScheduler

        inc = self._warm_encoder(nodes, bound)
        self._warm_one(backlog, state, nodes, bound, [inc])
        most = int(WaveScheduler.SCATTER_FRAC * len(inc.node_names))
        rows, serial = 1, 0
        while rows <= min(most, len(nodes)):
            for node in nodes[:rows]:
                inc.on_cache_event("pod_add", bind(f"wrow-{serial}", node))
                serial += 1
            self._warm_one(backlog, state, nodes, bound, [inc])
            rows *= 2

    def _warm_encoder(self, nodes, bound):
        """A throwaway encoder that holds the warm-up's synthetic
        cluster, fed through the cache-event seam as the daemon's is."""
        inc = self._new_encoder()
        for n in nodes:
            inc.on_cache_event("node_set", n)
        for p in bound:
            inc.on_cache_event("pod_add", p)
        return inc

    def _warm_one(self, backlog, state, nodes, bound, shared) -> None:
        with self._sched_lock:
            self._warm_one_locked(backlog, state, nodes, bound, shared)

    def _warm_one_locked(self, backlog, state, nodes, bound, shared) -> None:
        """`_warm_one` under a caller's `_sched_lock` (the re-warm runs
        inside a wave's own call)."""
        saved_last, saved_inc = self._last_node_index, self._inc
        try:
            if saved_inc is not None:
                # daemon mode schedules off the incremental view, whose
                # static-array shapes (empty-vocab widths) differ from
                # the full encoder's padded ones — warming the wrong
                # program would leave the cold compile on the first
                # real wave. Feed a throwaway encoder the synthetic
                # cluster through the same cache-event seam. It never
                # hears of a warm backlog's picks, so its view is the
                # synthetic cluster every time. The mesh driver's
                # resident state goes by content: one such encoder
                # (`shared`, the warm-up's) serves all of a
                # warm-up's backlogs (feeding 20,000 nodes and
                # encoding 1,250 templates' rows a dozen times was
                # 200 s of a set-up at that size). The single-chip driver's
                # device cache goes by provenance (`source`,
                # `keep`): each of its backlogs gets an encoder of
                # its own, as before.
                inc = shared[0] if shared else None
                if inc is None:
                    inc = self._warm_encoder(nodes, bound)
                    if self._mesh_sched is not None:
                        shared.append(inc)
                self._inc = inc
            else:
                self._inc = None  # compile via the full-encode path
            self._schedule_locked(backlog, state)
        finally:
            self._inc = saved_inc
            self._last_node_index = saved_last

    def schedule_backlog(
        self, pods: Sequence[Pod], state: ClusterState,
        gangs: Optional[Sequence[dict]] = None,
    ) -> List[Optional[str]]:
        """`gangs` marks all-or-nothing spans of the backlog (the gang
        director's layout): [{"start", "length", "score_by_name":
        {node_name: int} | None}]. The single-chip wave driver enforces
        them in-program (no partial binds, no carry pollution); the
        mesh path schedules normally and relies on the caller's
        post-hoc all-or-nothing check before binding."""
        if not pods:
            return []
        # the lock serializes real waves against the background
        # warmup's counter save/restore
        with self._sched_lock:
            return self._schedule_locked(pods, state, gangs=gangs)

    def _schedule_locked(self, pods, state, gangs=None):
        if self._mesh_sched is not None:
            return self._schedule_backlog_mesh(pods, state)
        return self._schedule_backlog_locked(pods, state, gangs=gangs)

    def _schedule_backlog_locked(
        self, pods: Sequence[Pod], state: ClusterState,
        gangs: Optional[Sequence[dict]] = None,
    ) -> List[Optional[str]]:
        from kubernetes_tpu.models.wave import count_encoder
        from kubernetes_tpu.parallel.mesh import _pad_snapshot
        from kubernetes_tpu.snapshot.encode import SnapshotEncoder
        from kubernetes_tpu.snapshot.pad import next_pow2

        with trace_profile.phase_timer("encode"):
            reps, rep_idx, keys = self._dedup(pods)
            snap = batch = None
            keep = reship = frozenset()
            source = "full"
            fallback = rebuilds = None
            if self._inc is not None:
                def ls(l):
                    return l.list() if l is not None else ()

                snap, batch, keep = self._inc.wave_view(
                    reps,
                    services=ls(self._service_lister),
                    controllers=ls(self._controller_lister),
                    replica_sets=ls(self._replica_set_lister),
                    keys=keys,
                )
                if snap is not None:
                    # identify the ENCODER INSTANCE, not just the kind: a
                    # warmup's throwaway incremental encoder and the real
                    # one must never satisfy each other's `keep` (their
                    # vocab bit/slot assignments are encoder-local)
                    source = self._inc.source_token
                    reship = self._inc.reship
                fallback = self._inc.fallback
                rebuilds = self._inc.take_rebuilds()
            count_encoder(self._wave.stats,
                          "full" if snap is None else "incremental", fallback,
                          rebuilds)
            if snap is None:
                # from-scratch encode (no daemon cache, or a scope gate
                # hit: volumes / SA-SAA config)
                enc = SnapshotEncoder(state, reps, config=self._wave.config)
                snap = enc.encode_nodes()
                batch = enc.encode_pods()
                n_real = snap.num_nodes
                if n_real == 0:
                    # empty cluster: every pod fails with FitError
                    return [None] * len(pods)
                n_bucket = next_pow2(n_real, 64)
                if n_bucket > n_real:
                    snap = _pad_snapshot(snap, n_bucket)
        wave_gangs = None
        if gangs:
            # resolve per-node-NAME score rows (the heterogeneity
            # throughput term) into snapshot node order; padded nodes
            # score 0 and can never be picked (fit_static is False)
            name_to_id = {
                nm: i for i, nm in enumerate(snap.node_names) if nm
            }
            wave_gangs = []
            for g in gangs:
                add = None
                by_name = g.get("score_by_name")
                if by_name:
                    import numpy as _np

                    add = _np.zeros(len(snap.node_names), _np.int64)
                    for nm, v in by_name.items():
                        i = name_to_id.get(nm)
                        if i is not None:
                            add[i] = int(v)
                wave_gangs.append({
                    "start": g["start"], "length": g["length"],
                    "score_add": add,
                })
        driver = self._wave
        if self._profile == "optimizing":
            if self._opt is None:
                from kubernetes_tpu.scheduler.optimizer.profile import (
                    OptimizingWaveDriver,
                )

                self._opt = OptimizingWaveDriver(self._wave)
            driver = self._opt
        scanned = self._wave.stats["pods_by_path"]["scan"]
        chosen, _final, last = driver.schedule_backlog(
            snap, batch, rep_idx, last_node_index=self._last_node_index,
            keep=keep, source=source, gangs=wave_gangs, reship=reship,
        )
        scanned = self._wave.stats["pods_by_path"]["scan"] - scanned
        self._last_node_index = last
        names = snap.node_names
        hosts = [
            (names[i] or None) if 0 <= i < len(names) else None
            for i in (int(c) for c in chosen)
        ]
        if source != "full":
            self._last_widths = interpod_widths(snap, batch)
            if self._inc is self._live_inc and _eager_scan_warm():
                self._after_live_wave(reps, keys, state, snap, batch, scanned)
        return hosts

    def _after_live_wave(self, reps, keys, state, snap, batch,
                         scanned: int) -> None:
        """Behind a wave of the daemon's own (never a warm-up's): keep a
        pod of every template seen pending and, once terms are live,
        the kind of step a run of it makes; and warm the scan and the
        run programs where the wave's inter-pod widths are new, or
        buckets are still left: the scan and the run programs on a
        cluster whose pods take the scan as a rule, the run programs
        with the grouped device replay at every run-slot bucket on one
        whose pods do not and whose runs are that replay's (a wave is
        then one group, as many run slots as it has runs: each bucket
        would compile where a wave first fills it). The evidence for
        the first, since a warm wave costs its whole bucket of steps (2 ms a
        step with ten logical terms on the chip: 30 s for the seven
        buckets from the compile cache; PERF.md, PR 45): a template that
        `run_verdict` refuses whatever its run's length (an own
        required podAffinity term, a preferred term on its own copies,
        a zone-coupled anti-affinity term, the policy), or a wave whose
        scan decided more pods than the smallest bucket holds. Where
        every term is the run tables' (a hostname anti-affinity term)
        the scan meets these widths only through the run a wave's end
        cuts short, in its smallest bucket, which that wave builds, and
        none of the scan's buckets is warmed."""
        widths = self._last_widths
        if len(self._templates) + len(reps) > 8192:
            # as PendingRows.MAX_ROWS bounds rows
            self._templates.clear()
            self._template_kinds.clear()
        if widths is not None:
            fresh = [i for i, k in enumerate(keys)
                     if k not in self._template_kinds]
            self._template_kinds.update(
                (keys[i], kind) for i, kind in zip(
                    fresh, self._wave.run_kinds(snap, batch, fresh)))
        if widths is not None and widths not in self._warmed_widths:
            self._scan_bound = (
                self._scan_bound or scanned > self._wave.pod_floor
                or any(self._template_kinds[k] == "scan" for k in keys))
            if self._scan_bound:
                # widths newer than the ones being warmed take their
                # place: what was left of those serves a cluster that
                # is gone
                self._warmed_widths.add(widths)
                self._rewarm_widths = widths
                self._rewarm_left = self._pod_buckets()
        if widths is not None and widths not in self._warmed_run_widths:
            grouped = not self._scan_bound and "device" in \
                self._template_kinds.values()
            if self._scan_bound or grouped:
                self._warmed_run_widths.add(widths)
                self._rewarm_widths = widths
                self._rewarm_runs = True
                self._rewarm_slots = self._slot_buckets() if grouped else []
        self._templates.update(zip(keys, reps))
        if self._rewarm_runs or self._rewarm_left or self._rewarm_slots:
            self._rewarm(state)

    def _rewarm(self, state) -> None:
        """Warm the wave programs at the inter-pod widths a wave has just
        shown, before the loop decides its next wave: the run programs
        on the kinds of run the templates seen pending make, then
        `jit_batch_scan` (and the transfers round it) for every pod
        bucket from `pod_floor` to the wave cap, smallest first, then
        `jit_zreplay_group` for every run-slot bucket (whichever of the
        two `_after_live_wave` asked for).
        `warmup` cannot: it runs before a pod arrives and knows the
        controllers' selectors, not their pods' annotations, so its
        programs have zero-width inter-pod tables; and every wave
        program is traced per width of those tables (the scan per pod
        bucket besides), so each would compile where a later wave first
        meets it, 20-45 s on the chip, inside a measured window or a
        check batch (PERF.md, PRs 28, 35, 45 and 49).

        Through `_warm_one`'s seam: a throwaway encoder fed the nodes
        and bound pods of the wave's own snapshot of the scheduler
        cache, so that its vocabularies, and so its widths, are the live
        ones. The run programs by ONE backlog (`_warm_runs`), the scan
        by backlogs of a pod of every template seen pending, dealt in
        turn (runs of length 1), the grouped device replay by backlogs
        of a bucket's runs each (`_warm_group`). The live encoder,
        `_last_node_index` and the driver's device mirrors are as they
        were afterwards.
        Where the warm view's widths are not the live ones (a term only
        deleted pods carried) that is counted (`rewarm_mismatches`) and
        logged. It starts no further warm wave after REWARM_SLICE_S and
        goes on behind the next wave. Counted in `stats` (`rewarms`,
        `rewarm_seconds`, `rewarm_programs`) and as the span
        `scheduler.rewarm` (`steps`: the steps its run backlog made, by
        kind; `buckets`, `slots`: the scan's pod buckets and the
        replay's run-slot buckets it warmed); its time on the timeline
        is the warm waves' own phases
        (`encode`, `transfer`, `probe`, `replay`, `score`)."""
        import time

        from kubernetes_tpu.models.wave import count_group
        from kubernetes_tpu.trace import spans as trace_span

        widths = self._rewarm_widths
        began, built = time.time(), trace_profile.compile_count()
        nodes = [info.node for info in state.node_infos.values()
                 if info.node is not None]
        bound = state.all_assigned_pods()
        templates = list(self._templates.values())
        inc = self._warm_encoder(nodes, bound)
        wave = self._wave
        mirrors = wave._dev, wave._dev_source
        wave._dev, wave._dev_source = {}, None
        buckets, slots, steps, waves, off = [], [], {}, 0, 0

        def warm(backlog):
            nonlocal waves, off
            self._warm_one_locked(backlog, state, nodes, bound, [inc])
            waves += 1
            off += self._last_widths != widths

        try:
            if self._rewarm_runs:
                self._rewarm_runs = False
                backlog = self._warm_runs()
                if backlog:
                    ran = dict(wave.stats["steps_by_kind"])
                    warm(backlog)
                    steps = {kind: n - ran[kind] for kind, n in
                             wave.stats["steps_by_kind"].items()
                             if n > ran[kind]}
            # a warm wave at the least; then the loop's turn once the
            # slice is spent, and the rest behind its next wave
            while (self._rewarm_left or self._rewarm_slots) and (
                    not waves or time.time() - began < REWARM_SLICE_S):
                if self._rewarm_left:
                    bucket = self._rewarm_left.pop(0)
                    warm([templates[i % len(templates)]
                          for i in range(bucket)])
                    buckets.append(bucket)
                else:
                    slots.append(self._rewarm_slots.pop(0))
                    warm(self._warm_group(slots[-1]))
        finally:
            wave._dev, wave._dev_source = mirrors
            self._last_widths = widths
        ended = time.time()
        counted = {"rewarms": 1, "rewarm_seconds": ended - began,
                   "rewarm_programs": trace_profile.compile_count() - built}
        if off:
            counted["rewarm_mismatches"] = off
            log.warning("re-warm: %d of %d warm waves had other inter-pod "
                        "widths than the live %s", off, waves,
                        dict(zip(WIDTH_NAMES, widths)))
        count_group(wave.stats, counted)
        left = len(self._rewarm_left) + len(self._rewarm_slots)
        log.info("re-warmed at %s: the runs' steps %s, the scan's pod "
                 "buckets %s, the replay's run-slot buckets %s, in %.1fs "
                 "(%d programs; %d buckets left)",
                 dict(zip(WIDTH_NAMES, widths)), steps, buckets, slots,
                 ended - began, counted["rewarm_programs"], left)
        trace_span.record_span(
            "scheduler.rewarm", trace_span.new_trace_id(), began, ended,
            buckets=buckets, slots=slots, steps=steps, left=left,
            programs=counted["rewarm_programs"],
            **dict(zip(WIDTH_NAMES, widths)))

    def _warm_runs(self) -> List[Pod]:
        """The re-warm's one backlog of runs: `min_run` pods in a row of
        every template seen pending whose runs the run machinery takes
        (`_template_kinds`), dealt so that the plan holds every kind of
        step they make on a live wave: each run alone between two scan
        stretches (a `single`: the device replay of one run, or the
        probe and its fold, with the veto where the template has one);
        then all of them side by side, like kinds together (a
        `group_device` or `group_host` of what groups, a probe that
        carries the fold before it for what does not); then, of each
        kind that groups, a group one run over the grouped header
        probe's smallest run-slot bucket, which is its next bucket and
        still the device replay's first (a live wave's neighbours are
        seldom more: the grouped programs are traced a bucket). A scan
        stretch is one pod of a template that is neither neighbour's: a
        run too short for anything else; the templates take turns at it,
        the scan's own first, and those it never reached close the
        backlog, because a wave's programs are traced per width of its
        pods' own term lists and of the spread classes too: a warm wave
        has to hold every template a live one does. Empty where no
        template's runs leave the scan: its buckets are then all there
        is to warm."""
        by_kind: dict = {}
        for key, pod in self._templates.items():
            by_kind.setdefault(self._template_kinds.get(key), []).append(pod)
        runs = [pod for kind in ("device", "pure", "single")
                for pod in by_kind.get(kind, ())]
        if not runs:
            return []
        row = max(self._wave.min_run, 2)
        lone = [pod for kind in ("scan", None, "device", "pure", "single")
                for pod in by_kind.get(kind, ())]
        backlog: List[Pod] = []

        def stretch(nxt):
            """One pod between the backlog's last run and `nxt`'s."""
            for i, pod in enumerate(lone):
                if pod is not backlog[-1] and pod is not nxt:
                    lone.append(lone.pop(i))  # the next turn is another's
                    backlog.append(pod)
                    return

        for pod, nxt in zip(runs, runs[1:] + runs[:1]):
            backlog += [pod] * row
            stretch(nxt)
        for pod in runs:
            backlog += [pod] * row
        for kind in ("device", "pure"):
            pair = by_kind.get(kind, [])[:2]
            if len(pair) == 2:
                stretch(pair[0])
                for i in range(self._wave.group_floor + 1):
                    backlog += [pair[i % 2]] * row
        held = {id(pod) for pod in backlog}
        return backlog + [pod for pod in lone if id(pod) not in held]

    def _warm_group(self, slots: int) -> List[Pod]:
        """A backlog that is one grouped device replay of `slots` runs,
        which is its run-slot bucket: `min_run` pods in a row of the
        templates whose runs take that replay, dealt in turn (two
        neighbours never alike: one run), and behind them a pod of
        every other template seen pending, for the widths a live wave's
        batch has. One template alone makes no group: one run."""
        device = [pod for key, pod in self._templates.items()
                  if self._template_kinds.get(key) == "device"]
        row = max(self._wave.min_run, 2)
        runs = slots if len(device) > 1 else len(device)
        return [pod for i in range(runs)
                for pod in [device[i % len(device)]] * row] + [
            pod for key, pod in self._templates.items()
            if self._template_kinds.get(key) != "device"]

    def _schedule_backlog_mesh(
        self, pods: Sequence[Pod], state: ClusterState
    ) -> List[Optional[str]]:
        """Mesh daemon path: the sharded WAVE driver (probe tables per
        shard, host replay, per-shard donated commit fold) against the
        DEVICE-RESIDENT sharded cluster state, with the sharded scan as
        the in-carry fallback.  With a cache the incremental encoder
        supplies the per-wave view; either way the resident state
        content-compares the snapshot against its host mirrors and ships
        only deltas — steady-state waves upload O(pending pods)."""
        from kubernetes_tpu.parallel.mesh import _pad_snapshot
        from kubernetes_tpu.snapshot.encode import SnapshotEncoder
        from kubernetes_tpu.snapshot.pad import next_pow2

        with trace_profile.phase_timer("encode"):
            reps, rep_idx, keys = self._dedup(pods)
            snap = batch = None
            # the incremental view comes in its encoder's own bucket,
            # a multiple of the mesh (`_new_encoder`)
            bucket = 1
            if self._inc is not None:
                def ls(l):
                    return l.list() if l is not None else ()

                snap, batch, _keep = self._inc.wave_view(
                    reps,
                    services=ls(self._service_lister),
                    controllers=ls(self._controller_lister),
                    replica_sets=ls(self._replica_set_lister),
                    keys=keys,
                )
            if snap is None:
                enc = SnapshotEncoder(
                    state, reps, config=self._mesh_sched.config
                )
                snap = enc.encode_nodes()
                batch = enc.encode_pods()
                # bucket the node axis for compile reuse (pow2, floor 64)
                bucket = next_pow2(snap.num_nodes, 64)
            n_real = snap.num_nodes
            if n_real == 0:
                return [None] * len(pods)
            # then to a mesh multiple so the shard math sees the final N
            # here and node ids map back to THIS snapshot's names
            snap = _pad_snapshot(snap, bucket)
            snap = _pad_snapshot(snap, self._mesh_sched.mesh.devices.size)
        chosen, _final, last = self._mesh_sched.schedule_backlog(
            snap, batch, rep_idx, last_node_index=self._last_node_index
        )
        self._last_node_index = last
        return _ids_to_names(chosen, snap.node_names, n_real)

    def schedule(self, pod: Pod, state: ClusterState) -> str:
        host = self.schedule_backlog([pod], state)[0]
        if host is None:
            raise FitError(pod, {})
        return host
