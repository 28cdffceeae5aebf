"""The wave loop: a wave's plan as a value, and the one loop that runs it.

A wave is a FIFO backlog cut into maximal runs of identical pods, each
classified once into a `Run` (`models/wave.classify_runs`). `next_step`
cuts the runs into the wave's dispatch shape, a `Step` at a time, from
the runs and a `Policy` alone: it touches no device and no snapshot, so
a plan can be printed (`plan_steps`, `format_plan`), tested and built
ahead of its dispatch. `run_wave` executes the steps over a *device
seam*: the few operations in which the single-chip driver
(`models/wave.WaveScheduler`) and the mesh driver
(`parallel/mesh.MeshWaveScheduler`) really differ. What both share
lives here once: the grouping loop, the hand-off to the scan, the table
path of a single run and the grouped host replay.

The seam is the driver handed to `run_wave`. `wave` is the `Wave` being
run; each operation advances `wave.carry` itself:

  scan_pending(wave, rows) -> (chosen i32[len(rows)], lastNodeIndex)
      the serial scan over backlog positions `rows`
  place(buf) -> buf
      a packed pod row or group buffer on its way to the device
  group_floor
      the least run bucket of a group buffer (`group_buffer`)
  probe_run(wave, run, layout, buf, J, rows) -> RunTables
  commit_run(wave, run, layout, buf, counts i64[N])
      one chip defers the fold to the next dispatch, the mesh applies
      it at once and notes it on the resident state's host mirror
  probe_group(wave, G_bucket, layout, buf) -> (headers, usage i64[6, N])
  commit_group(wave, runs, G_bucket, layout, buf, counts i64[G, N])
  finish(wave)
      settle what is deferred | hand the carry back to the resident state

and, only for a driver whose classification sets `Run.device`:

  replay_run_device(wave, run, done0)
  replay_group_device(wave, runs) -> None | (g, picks done of run g)

This module imports no jax, no driver and nothing of `parallel` or
`scheduler` (tests/test_timeline.py holds the arrows).
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field, fields, replace as dc_replace
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.models import hosttab
from kubernetes_tpu.models.batch import (
    BatchScheduler,
    SchedulerConfig,
    wants_resources,
)
from kubernetes_tpu.models.pack import pack_arrays
from kubernetes_tpu.models.probe import RunTables, tables_from_stk
from kubernetes_tpu.models.replay import ReplayResult
from kubernetes_tpu.snapshot.encode import ClusterSnapshot, PodBatch
from kubernetes_tpu.snapshot.pad import next_pow2
from kubernetes_tpu.trace.profile import phase_timer

#: which path decided a pod, in the order of `stats["pods_by_path"]`,
#: and the kinds of a `Step`: the serial scan program (`flush`), a run's
#: own probe or device replay (`run_single`), a grouped header probe
#: replayed on the host (`run_group_host`), a grouped device replay
PATHS = ("scan", "single", "group_host", "group_device")
_SCAN, _SINGLE, _GROUP_HOST, _GROUP_DEVICE = range(len(PATHS))


# -- the plan ----------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class Run:
    """A maximal span of identical pods and all that `classify_runs`
    found of it: made once, changed by nothing."""

    rep: int  # the pods' row of the wave's batch
    start: int  # first backlog position
    length: int
    #: the run machinery takes it (else the scan does: short, or refused)
    eligible: bool = False
    #: why a run long enough for the run machinery goes to the scan all
    #: the same (one of `models/wave.SCAN_REASONS`)
    refused: Optional[str] = None
    #: bool[N], nodes where one committed copy excludes every further
    #: one (`models/wave.run_verdict`)
    veto: Optional[np.ndarray] = None
    svc_ctx: Optional[dict] = None  # `models/wave.svc_run_context`
    #: probe, picks and fold in one device program: zoned spread runs,
    #: and runs the grouped header probe cannot take (a veto, or not
    #: `pure`); only the single-chip driver's classification sets it
    device: bool = False
    #: a grouped probe's host adjustments cover its commits (`run_pure`)
    pure: bool = False
    #: the all-or-nothing span this run is, where the driver takes it
    #: atomically: {"start", "length", "score_add"}
    gang: Optional[dict] = None

    @property
    def stop(self) -> int:
        return self.start + self.length


class Policy(NamedTuple):
    """What cuts a wave's runs into steps, besides the runs."""

    #: how many runs one grouped header probe may carry (`host_group_cap`)
    host_cap: int
    #: the mesh's resident modes: the exact host usage mirror rebuilds
    #: the j-table (models/hosttab), so even a LONE pure run takes the
    #: header-only probe, and the full [J, N] probe with its O(J*N)
    #: device->host shipment drops out of the steady-state wave
    lone_pure_grouped: bool = False


class Step(NamedTuple):
    kind: str  # one of PATHS
    runs: Tuple[Run, ...]


#: the grouped device replay's bounds: runs a dispatch, pods a run, and
#: how many pick slots its [G bucket, K bucket] buffer may hold for each
#: pick the group makes
DEVICE_GROUP_RUNS = 128
DEVICE_RUN_PODS = 1 << 16
DEVICE_SLOTS_PER_PICK = 8
#: the run-slot buckets of the grouped device replay. A bucket is a
#: program of its own (at 2,048 node slots 15-19 s to compile on the
#: chip and 3.3 s to trace and load from the compile cache: PERF.md,
#: PR 52), where a padded run slot costs bytes of operands alone (a veto
#: row, a pod row, a row of picks back: the run loop ends at the real
#: run count). So the ladder is two steps, and where a wave is one
#: group (`classify_runs`) the daemon warms both behind the first wave
#: that shows the widths they are traced per
DEVICE_SLOT_BUCKETS = (32, DEVICE_GROUP_RUNS)

#: pick-buffer length floors of the zoned device replay: one run per
#: dispatch pads to 256; the grouped form keeps K picks PER RUN SLOT, so
#: its padding costs G times over and the floor is lower. The buckets
#: bound the compiled shapes; the pick loop ends at a run's real length
ZREPLAY_K_FLOOR = 256
ZREPLAY_GROUP_K_FLOOR = 64


def replay_k_bucket(length: int, floor: int) -> int:
    """The compiled pick-buffer length for a device-replayed run (or a
    group's longest run) of `length` pods."""
    return next_pow2(min(length, DEVICE_RUN_PODS), floor=floor)


def replay_g_bucket(runs: int) -> int:
    """The compiled run-slot count for a device-replayed group of `runs`
    (<= DEVICE_GROUP_RUNS) runs."""
    return next(b for b in DEVICE_SLOT_BUCKETS if runs <= b)


def host_group_cap(num_nodes: int) -> int:
    """How many runs one grouped header probe may carry: bounds the
    device->host shipment (N_STK_ROWS i64 rows per run) to ~32 MB, so
    one transfer stays cheap next to the dispatch it rides with."""
    return max(8, min(256, (1 << 25) // max(num_nodes * 96, 1)))


def next_step(runs: Sequence[Run], idx: int, policy: Policy) -> Step:
    """The step that starts at `runs[idx]`. A wave's plan is this,
    iterated from 0 (`plan_steps`); a group that stops early at its run
    `g` finishes that run as a `single` and re-plans from `g + 1`
    (`run_wave`)."""
    first, end = runs[idx], idx + 1
    if not first.eligible:
        while end < len(runs) and not runs[end].eligible:
            end += 1
        return Step("scan", tuple(runs[idx:end]))
    if first.device:
        # device-path runs group freely (each probe runs against the
        # live in-program carry: no purity needed), bounded by what the
        # shared K bucket still wastes: the picks come back as
        # [G bucket, K bucket] whatever the runs' lengths (the pick
        # loop itself ends at each run's)
        kind, picks, longest = "group_device", first.length, first.length
        while (end < len(runs) and end - idx < DEVICE_GROUP_RUNS
               and first.length <= DEVICE_RUN_PODS):
            nxt = runs[end]
            if not nxt.device or nxt.length > DEVICE_RUN_PODS:
                break
            k_bucket = replay_k_bucket(max(longest, nxt.length),
                                       ZREPLAY_GROUP_K_FLOOR)
            if (end - idx + 1) * k_bucket \
                    > DEVICE_SLOTS_PER_PICK * (picks + nxt.length):
                break
            picks += nxt.length
            longest = max(longest, nxt.length)
            end += 1
    else:
        kind = "group_host"
        while (first.pure and end < len(runs)
               and end - idx < policy.host_cap
               and runs[end].pure and not runs[end].device):
            end += 1
    if end - idx >= 2 or (kind == "group_host" and first.pure
                          and policy.lone_pure_grouped):
        return Step(kind, tuple(runs[idx:end]))
    return Step("single", (first,))


def plan_steps(runs: Sequence[Run], policy: Policy) -> List[Step]:
    """A wave's whole plan: what `run_wave` runs while no group stops
    early and no run hands its rest to the scan."""
    steps: List[Step] = []
    idx = 0
    while idx < len(runs):
        steps.append(next_step(runs, idx, policy))
        idx += len(steps[-1].runs)
    return steps


def format_plan(steps: Sequence[Step]) -> str:
    """`group_host[3 runs, 120 pods] scan[2 runs, 2 pods]`."""
    return " ".join(
        f"{s.kind}[{len(s.runs)} runs, {sum(r.length for r in s.runs)} pods]"
        for s in steps)


# -- a wave's state ----------------------------------------------------------


@dataclass
class Wave:
    """What one wave reads and writes while it runs: its inputs, the
    device's state as the driver placed it, the answer so far."""

    config: SchedulerConfig
    snap: ClusterSnapshot
    batch: PodBatch  # one row per unique pod
    rep_idx: np.ndarray  # backlog position -> row of `batch`
    #: lastNodeIndex, tracked host-side (the replay computes it
    #: exactly) so the fast path never blocks on the device carry
    L_host: int
    max_j: int
    replay: Callable[[RunTables, int, int], ReplayResult]
    static: Optional[dict] = None
    carry: Optional[tuple] = None
    #: one chip's deferred commit: ("single", buf, layout, counts[N]) or
    #: ("group", buf, layout, counts[G, N]), at most one. A run's (or
    #: group's) apply rides the NEXT probe's dispatch: each dispatch has
    #: a fixed cost, so deferring halves the per-run dispatch count for
    #: multi-template backlogs
    fold: Optional[tuple] = None
    #: backlog positions the fast paths left to the scan
    pending: List[int] = field(default_factory=list)
    #: what the wave's steps did, under the names of `models/wave`'s
    #: GROUP_, ZREPLAY_ and ANTI_COUNTERS and `scan_flushes`; into the
    #: driver's `stats` with the wave's other tallies at its END: a read
    #: in the middle of a wave never finds picks ahead of the pods
    #: decided (one chip run's `anti_run_share.fill` read 101.4)
    tallies: Counter = field(default_factory=Counter)
    #: the `Step`s the loop ran, by kind (PATHS): a group that stopped
    #: early and the `run_single` that finished its run are one each;
    #: `stats["steps_by_kind"]` at the wave's end, like `via`
    steps: Counter = field(default_factory=Counter)

    def __post_init__(self):
        zone_id = np.asarray(self.snap.zone_id)
        self.N = self.snap.num_nodes
        self.perm = np.asarray(self.snap.name_desc_order).astype(np.int64)
        self.zoned = bool(np.any(zone_id > 0))
        self.num_zones = max(
            int(zone_id.max()) + 1 if zone_id.size else 1, 1)
        self.num_values = int(self.snap.svc_num_values)
        #: node ids, -1 == unschedulable
        self.out = np.full(len(self.rep_idx), -1, np.int32)
        #: the path that decided each position (an index into PATHS): a
        #: fast path marks its span when it takes it, and what it hands
        #: on to `pending` is marked again by the scan that decides it
        self.via = np.full(len(self.rep_idx), _SCAN, np.int8)

    def write(self, at: int, chosen: np.ndarray) -> None:
        """Picks in permuted node space into `out` from position `at`."""
        self.out[at:at + len(chosen)] = np.where(
            chosen >= 0, self.perm[chosen], -1).astype(np.int32)

    def pod_row(self, rep: int) -> dict:
        return {f: np.asarray(getattr(self.batch, f)[rep])
                for f in BatchScheduler.POD_FIELDS}

    def table_context(self, run: Run) -> dict:
        """The host's side of a run's tables, as keywords of
        `models/probe.tables_from_packed`."""
        return dict(
            has_selectors=bool(self.batch.has_selectors[run.rep]),
            zone_id=np.asarray(self.snap.zone_id) if self.zoned else None,
            self_anti_veto=run.veto, svc_ctx=run.svc_ctx)

    def zone_perm(self) -> np.ndarray:
        """The nodes' zone ids in the replay's node order."""
        return np.ascontiguousarray(
            np.asarray(self.snap.zone_id)[self.perm], np.int32)


# -- what the executors share ------------------------------------------------


def pick_j(config: SchedulerConfig, max_j: int, snap: ClusterSnapshot,
           batch: PodBatch, rep: int, K: int) -> Tuple[int, int]:
    """-> (J, rows). J is the compiled table depth (pow2-bucketed
    for compile reuse); rows <= J is the replay's table horizon —
    the capacity bound +2, so the most capacious node's fit
    observably goes False inside the table instead of tripping the
    horizon bail (which would force a full re-probe of the
    remaining run). The probe ships the full packed J-table in one
    transfer and clips to `rows` host-side (transfer is latency-
    bound, not bandwidth-bound); `rows` exists to bound the replay
    and keep the host tables small. Computed from the run-start
    snapshot only — commits monotonically shrink every node's
    remaining capacity, so this stays an upper bound for the whole
    backlog (no device sync)."""
    alloc_pods = np.asarray(snap.alloc_pods)
    if not alloc_pods.size:
        return 16, 16
    if not wants_resources(config):
        # no PodFitsResources: nothing enforces the capacity bound,
        # res_fit never goes False, and clipping rows below J would
        # horizon-bail (and re-probe) every `rows` picks
        J = next_pow2(min(K + 1, max_j), floor=128)
        return J, J
    cap = np.maximum(alloc_pods - np.asarray(snap.pod_count), 0)
    # the commit vector shrinks cpu/mem headroom too (a fit at j
    # implies j*commit + request <= alloc); use whichever bound is
    # tightest so the table stays small
    for commit, alloc, used in (
        (int(batch.commit_mcpu[rep]), snap.alloc_mcpu, snap.req_mcpu),
        (int(batch.commit_mem[rep]), snap.alloc_mem, snap.req_mem),
    ):
        if commit > 0:
            room = np.maximum(np.asarray(alloc) - np.asarray(used), 0)
            cap = np.minimum(cap, room // commit + 1)
    depth = min(K, int(cap.max()) + 1) + 1
    # floor 128: one probe program serves every wave size (a small
    # K would otherwise compile J=16/32/64 variants for nothing)
    J = next_pow2(min(depth, max_j), floor=128)
    return J, min(depth, J)


def gather_batch(batch: PodBatch, rows: np.ndarray) -> PodBatch:
    """Materialize per-position rows from the unique-representative
    batch (fancy-index every pod-axis array)."""
    moved = {}
    for f in fields(batch):
        v = getattr(batch, f.name)
        if f.name == "pod_keys":
            moved[f.name] = [v[r] for r in rows]
        elif isinstance(v, np.ndarray) and v.ndim >= 1 \
                and v.shape[0] == batch.num_pods:
            moved[f.name] = v[rows]
    return dc_replace(batch, **moved)


def group_buffer(batch: PodBatch, reps, floor: int = 8):
    """Pack a group's run representatives (padded to a pow2 run bucket
    by repeating the LAST rep — padded slots schedule nothing and their
    commit counts stay zero) into ONE stacked buffer:
    -> (G_bucket, layout, uint8 host buffer). The padding rule is part
    of the host_group_replay / grouped-fold contract. The mesh passes
    floor=1: its exact host usage mirror lets even a SINGLETON pure run
    ride the header-only probe, so padding the run bucket to 8 would
    octuple the header shipment for nothing."""
    G_bucket = next_pow2(len(reps), floor=floor)
    reps = list(reps) + [reps[-1]] * (G_bucket - len(reps))
    seg = gather_batch(batch, np.asarray(reps, np.int64))
    layout, buf = pack_arrays({
        f: np.asarray(getattr(seg, f))
        for f in BatchScheduler.POD_FIELDS
    })
    return G_bucket, layout, buf


def gang_score_add(tables: RunTables, add: np.ndarray) -> RunTables:
    """Fold a per-node additive score row (the heterogeneity-aware
    throughput term: weight x normalized throughput of the gang's
    workload class on each node's accelerator type) into a run's
    tables. static_add is the per-node static score sum the replay
    reads per pick, so the adjustment is exact — the pick sequence
    maximizes the combined score including the term."""
    return dc_replace(tables, static_add=tables.static_add + add)


#: RunTables' node-axis fields, by the axis the nodes lie on
_NODE_AXIS = {
    0: ("fit_static", "static_add", "spread_base", "zone_id", "na_counts",
        "tt_counts", "ip_totals", "saa_counts", "saa_lbl_val"),
    1: ("res_fit", "tab", "sa_refine_rows"),
}


def permute_tables(t: RunTables, perm: np.ndarray) -> RunTables:
    """`t` with its nodes in the order `perm` (the replay's: names
    descending, selectHost's tie order)."""
    moved = {}
    for axis, names in _NODE_AXIS.items():
        for name in names:
            a = getattr(t, name)
            if a is not None:
                moved[name] = a[perm] if axis == 0 else a[:, perm]
    return dc_replace(t, **moved)


def host_group_replay(wave: Wave, runs: Sequence[Run],
                      headers: np.ndarray, usage: np.ndarray):
    """FIFO host replay of a group of runs from ONE grouped probe.

    headers: i64[G, N_STK_ROWS, N] probed against the pre-group carry;
    usage: the carry's resource block i64[6, N] at probe time.  Each
    run's j-axis is rebuilt from the LIVE usage (prior runs' commits
    folded in — models/hosttab), its spread base is advanced by the
    prior runs' class commits, and port-conflicting nodes are vetoed —
    exactly the adjustments a fresh per-run probe would have baked in,
    so decisions are bit-identical to the serial per-run sequence
    (tests/test_wave.py fuzz).

    Writes the picks into `wave.out` and advances `wave.L_host`;
    returns (counts_mat i64[G, N] node-order commits per run, n_full
    runs completely replayed, partial_done picks of run n_full when it
    stopped early (0 otherwise)).

    A run with a `gang` is ALL-OR-NOTHING: unless every member gets a
    node, the gang is parked — no member binds (out stays -1), no
    commit folds, and the replay continues with the NEXT run against
    the same state, so a parked gang can never pollute the runs behind
    it. A gang's optional `score_add` (i64[N]) is its heterogeneity-
    aware throughput term, folded into the run's static score row."""
    config, snap, batch = wave.config, wave.snap, wave.batch
    G = len(runs)
    N = usage.shape[1]
    usage = usage.astype(np.int64, copy=True)
    alloc = {
        f: np.asarray(getattr(snap, f)).astype(np.int64)
        for f in ("alloc_mcpu", "alloc_mem", "alloc_gpu", "alloc_pods")
    }
    zone_arr = np.asarray(snap.zone_id) if wave.zoned else None
    counts_mat = np.zeros((G, N), np.int64)
    class_acc: dict = {}  # class id -> accumulated commit counts [N]
    port_kills: list = []  # (port row, touched mask) of committed runs
    n_full = 0
    partial_done = 0
    for r, run in enumerate(runs):
        rep, K = run.rep, run.length
        pod = {
            f: np.asarray(getattr(batch, f))[rep]
            for f in ("req_mcpu", "req_mem", "req_gpu", "zero_req",
                      "commit_mcpu", "commit_mem", "commit_gpu",
                      "nz_mcpu", "nz_mem", "port_mask", "class_id",
                      "spread_match")
        }
        _J, rows = pick_j(config, wave.max_j, snap, batch, rep, K)
        stk = headers[r].copy()
        # cross-run host-port conflicts: a prior run's commit holds its
        # ports on the touched nodes; overlapping wants can't land there
        for port_row, touched in port_kills:
            if np.any(port_row & pod["port_mask"]):
                stk[0] = np.where(touched, 0, stk[0])
        # spread base advance: prior commits of class c add
        # spread_match[c] matches per committed copy on that node
        spread_match = np.asarray(pod["spread_match"])
        for cls, cnts in class_acc.items():
            m = int(spread_match[cls]) if cls < spread_match.shape[0] else 0
            if m:
                stk[3] = stk[3] + m * cnts
        res_fit, tab = hosttab.resource_tables(config, pod, alloc, usage,
                                               rows)
        tables = tables_from_stk(
            config, stk, res_fit, tab, wave.num_zones,
            has_selectors=bool(batch.has_selectors[rep]),
            zone_id=zone_arr,
        )
        gang = run.gang
        if gang is not None and gang.get("score_add") is not None:
            tables = gang_score_add(tables, gang["score_add"])
        res: ReplayResult = wave.replay(
            permute_tables(tables, wave.perm), K, wave.L_host)
        if gang is not None and (res.n_done == 0
                                 or bool((res.chosen < 0).any())):
            # unfit member: park — no binds, no folds, round-robin
            # counter untouched; the NEXT run replays against the same
            # usage/spread/port state a never-attempted gang leaves.
            # (A gang TABLE-HORIZON partial — n_done < K with every
            # pick valid — is NOT unfit: it falls through to the
            # normal partial path below, so the loop re-probes and
            # continues the gang through run_single, whose gang
            # failure path erases the whole span before any bind.)
            n_full += 1
            continue
        if res.n_done == 0:
            break  # no progress through tables: the loop re-probes
        wave.write(run.start, res.chosen)
        counts = np.zeros(N, np.int64)
        counts[wave.perm] = res.counts
        counts_mat[r] = counts
        wave.L_host = res.last_node_index
        # fold this run's commits into the host-tracked channels
        usage += np.outer(hosttab.commit_vector(pod), counts)
        if np.any(pod["port_mask"]):
            port_kills.append((pod["port_mask"], counts > 0))
        cls = int(pod["class_id"])
        prev = class_acc.get(cls)
        class_acc[cls] = counts if prev is None else prev + counts
        if res.n_done < K:
            partial_done = res.n_done
            break  # table horizon: the loop re-probes the remainder
        n_full += 1
    return counts_mat, n_full, partial_done


# -- the loop ----------------------------------------------------------------


def run_wave(dev, wave: Wave, runs: Sequence[Run], policy: Policy) -> None:
    """Decide every pod of the wave: `wave.out`, `wave.carry` and
    `wave.L_host` are the answer, `wave.via` and `wave.tallies` what the
    driver counts at the wave's end."""
    idx = 0
    while idx < len(runs):
        step = next_step(runs, idx, policy)
        wave.steps[step.kind] += 1
        if step.kind == "scan":
            for run in step.runs:
                wave.pending.extend(range(run.start, run.stop))
            idx += len(step.runs)
            continue
        flush(dev, wave)
        if step.kind == "single":
            stopped = (0, 0)  # a group that stops before its first pick
        elif step.kind == "group_host":
            stopped = run_group_host(dev, wave, step.runs)
        else:
            for run in step.runs:
                wave.via[run.start:run.stop] = _GROUP_DEVICE
            stopped = dev.replay_group_device(wave, step.runs)
            # the run a group broke off at is `run_single`'s to count
            whole = len(step.runs) if stopped is None else stopped[0]
            for run in step.runs[:whole]:
                count_veto(wave, run)
        if stopped is None:
            idx += len(step.runs)
        else:
            g, done = stopped
            if step.kind != "single":
                wave.steps["single"] += 1  # the run the group broke off at
            run_single(dev, wave, step.runs[g], done)
            idx += g + 1
    flush(dev, wave)
    dev.finish(wave)


def flush(dev, wave: Wave) -> None:
    """The scan decides what the fast paths left in `pending`, in FIFO
    order before the next of them runs: one dispatch of the scan and one
    wait for its picks a call that finds pods pending (`scan_flushes`)."""
    if not wave.pending:
        return
    rows = np.asarray(wave.pending, np.int64)
    wave.tallies["scan_flushes"] += 1
    wave.via[rows] = _SCAN
    wave.out[rows], wave.L_host = dev.scan_pending(wave, rows)
    wave.pending.clear()


def run_single(dev, wave: Wave, run: Run, done0: int = 0) -> None:
    """One run by itself from its pod `done0` on: a probe (or the
    single-run device replay) for every table horizon it meets."""
    wave.via[run.start + done0:run.stop] = _SINGLE
    if run.device:
        dev.replay_run_device(wave, run, done0)
    else:
        _run_tables(dev, wave, run, done0)
    count_veto(wave, run)


def count_veto(wave: Wave, run: Run) -> None:
    """A run the run machinery has decided, into `anti_runs` and
    `anti_picks` where it carries a self-anti veto: once a run, all its
    picks so far (a group's before it broke off too); what it left to
    `pending` is the scan's, decided later."""
    if run.veto is not None:
        wave.tallies["anti_runs"] += 1
        wave.tallies["anti_picks"] += int(np.count_nonzero(
            wave.out[run.start:run.stop] >= 0))


def _run_tables(dev, wave: Wave, run: Run, done0: int) -> None:
    """The table path of a single run: probe, host replay, commit —
    one device round trip per re-probe."""
    snap, batch, rep = wave.snap, wave.batch, run.rep
    layout, buf = pack_arrays(wave.pod_row(rep))
    buf = dev.place(buf)
    score_add = run.gang.get("score_add") if run.gang is not None else None
    done = done0
    while done < run.length:
        K = run.length - done
        J, rows = pick_j(wave.config, wave.max_j, snap, batch, rep, K)
        tables = dev.probe_run(wave, run, layout, buf, J, rows)
        if run.veto is not None and done == done0:
            # the run's first probe: the nodes it finds unfit (a slot
            # without allocatable is padding or a node gone)
            real = np.asarray(snap.alloc_pods) > 0
            wave.tallies["anti_nodes_excluded"] += int(np.count_nonzero(
                real & ~(tables.fit_static & tables.res_fit[0])))
        if tables.sa_bail:
            # ServiceAffinity dynamics the tables can't express (mid-run
            # re-pin hazard): scan the rest of the run (a gang here
            # schedules via the scan; the director's post-hoc check
            # still guards its binds)
            break
        if score_add is not None:
            tables = gang_score_add(tables, score_add)
        with phase_timer("replay"):
            res: ReplayResult = wave.replay(
                permute_tables(tables, wave.perm), K, wave.L_host)
        if run.gang is not None and (
                res.n_done == 0 or bool((res.chosen < 0).any())):
            # all-or-nothing: park the gang — no member binds and THIS
            # segment folds nothing. Erase the whole span: earlier
            # horizon segments (rare — the +2 table-depth rule makes
            # resource-bounded runs fit-bail inside the table) may have
            # written picks and folded counts; the picks are discarded
            # here and the folded counts remain only as conservative
            # in-wave phantom usage — no binds happen, so the next wave
            # starts from clean cluster state.
            wave.out[run.start:run.stop] = -1
            return
        # (a gang table-horizon partial, n_done < K with all picks
        # valid, goes on: write + commit + re-probe, the same
        # transactional continuation any run gets)
        if res.n_done == 0:
            break  # no progress possible through tables
        wave.write(run.start + done, res.chosen)
        counts = np.zeros(wave.N, np.int64)
        counts[wave.perm] = res.counts
        dev.commit_run(wave, run, layout, buf, counts)
        # the fold adds counts.sum() == res.scheduled to the device's
        # last_idx; mirror it host-side
        wave.L_host = res.last_node_index
        done += res.n_done
    wave.pending.extend(range(run.start + done, run.stop))


def run_group_host(dev, wave: Wave, runs: Sequence[Run]):
    """K pure runs, ONE probe dispatch + ONE fold: the grouped header
    probe ships every run's static channels; the host rebuilds each
    run's j-axis against the accumulating usage (models/hosttab) and
    replays them in FIFO order. -> None, or (g, picks done of run g)
    where the group stopped early: that run costs a probe of its own."""
    G = len(runs)
    for run in runs:
        wave.via[run.start:run.stop] = _GROUP_HOST
    G_bucket, layout, buf = group_buffer(
        wave.batch, [run.rep for run in runs], floor=dev.group_floor)
    buf = dev.place(buf)
    headers, usage = dev.probe_group(wave, G_bucket, layout, buf)
    with phase_timer("replay"):
        counts_mat, n_full, partial_done = host_group_replay(
            wave, runs, headers[:G], usage)
    if counts_mat.any():
        dev.commit_group(wave, runs, G_bucket, layout, buf, counts_mat)
    wave.tallies["group_runs"] += G
    if n_full == G:
        return None
    wave.tallies["group_reprobes"] += 1
    return n_full, partial_done
