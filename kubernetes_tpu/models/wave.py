"""Wave backlog driver: runs of identical pods bypass the serial scan.

The serial scan (models/batch.py) is bit-identical to the reference's
scheduleOne loop but fundamentally serial: 50k pods = 50k sequential
device steps, which no per-step optimization can bring under the
50k-pods-in-1s target. This driver splits the FIFO backlog into maximal
runs of consecutive *identical* pods (equal snapshot/encode
pod_feature_key — exactly what an RC/RS/Job template emits), and for
each eligible run:

  1. probes the frozen carry once on device (models/probe.py) —
     static fit + score tables over the per-node commit count, and
  2. replays the pick sequence on the host (models/replay.py, C engine
     in native/replay.c) in O(log N) per pod, reproducing selectHost's
     exact round-robin tie rule, then
  3. applies the run's commits to the carry in one device scatter
     (the AssumePod fold of j identical pods is linear in the counts).

Ineligible pods (own inter-pod terms, volumes, service-affinity
membership — anything whose commit feeds back into its own run's
decisions in ways the tables can't express) fall back to the serial
scan, threading the same carry, so the combined output is bit-identical
to scanning the whole backlog. Eligibility is per-run and conservative;
tests/test_wave.py fuzzes equivalence.

This module classifies the runs (`classify_runs` -> `waveloop.Run`)
and holds the single-chip side of the device seam (`WaveScheduler`);
how a wave's runs are cut into dispatches and the loop that runs them
are `models/waveloop`'s, shared with the mesh driver.

Reference hot loop this replaces: generic_scheduler.go:72-135 +
scheduler.go:122 AssumePod, iterated per pod.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.batch import (
    BALANCED_ALLOCATION,
    EQUAL,
    IMAGE_LOCALITY,
    INTER_POD_AFFINITY,
    LEAST_REQUESTED,
    MATCH_INTER_POD_AFFINITY,
    NODE_AFFINITY,
    NODE_LABEL_PRIORITY,
    SELECTOR_SPREAD,
    SERVICE_ANTI_AFFINITY,
    TAINT_TOLERATION,
    BatchScheduler,
    SchedulerConfig,
)
# `hosttab` stays a name of this module: the benchmark's controls reach
# `resource_tables` through it (tests/benchmark/test_benchmark_shapes.py)
from kubernetes_tpu.models import hosttab  # noqa: F401
from kubernetes_tpu.models.pack import Packer, pack_arrays, unpack
from kubernetes_tpu.models.probe import WaveProbe
from kubernetes_tpu.models.replay import replay_fast
# the plan, the loop and what its executors share live in
# models/waveloop; the names below are part of this module's surface
from kubernetes_tpu.models.waveloop import (  # noqa: F401
    PATHS,
    ZREPLAY_GROUP_K_FLOOR,
    ZREPLAY_K_FLOOR,
    Policy,
    Run,
    Wave,
    gather_batch,
    group_buffer,
    host_group_cap,
    host_group_replay,
    pick_j,
    replay_g_bucket,
    replay_k_bucket,
    run_wave,
)
from kubernetes_tpu.models.zreplay import ZReplay
from kubernetes_tpu.ops import interpod as IP
from kubernetes_tpu.ops.narrow import narrow_dtype
from kubernetes_tpu.snapshot.encode import (
    ClusterSnapshot,
    PodBatch,
    service_config_labels,
)
from kubernetes_tpu.snapshot.pad import next_pow2, pad_batch
from kubernetes_tpu.trace.profile import (
    count_wave,
    count_wave_group,
    count_wave_reasons,
    count_wave_encoder,
    device_wait,
    phase_timer,
)

_WAVE_PRIORITIES = {
    LEAST_REQUESTED,
    BALANCED_ALLOCATION,
    SELECTOR_SPREAD,
    NODE_AFFINITY,
    TAINT_TOLERATION,
    INTER_POD_AFFINITY,
    EQUAL,
    IMAGE_LOCALITY,
}


def config_eligible(config: SchedulerConfig) -> bool:
    total_w = 0
    n_saa = 0
    for name, w in config.priorities:
        if isinstance(name, tuple):
            if name[0] == SERVICE_ANTI_AFFINITY:
                # per-pick renormalization handled by the spec replay;
                # the tables carry ONE term's counts
                n_saa += 1
                if n_saa > 1:
                    return False
            elif name[0] != NODE_LABEL_PRIORITY:
                return False
        elif name not in _WAVE_PRIORITIES:
            return False
        total_w += abs(w)
    # replay score range guard (C engine buckets by score value)
    return total_w * 10 < (1 << 20)


def _lt_pernode_dom(snap: ClusterSnapshot, lt: int):
    """For logical term lt: the per-node domain row when the term has
    exactly one expansion entry (an explicit topology key) AND distinct
    nodes never share a domain (each valid node is its own domain —
    hostname-like). Returns i32[N] (-1 where the key is missing) or
    None when the term's domains couple nodes."""
    lt_u = np.asarray(snap.ip_lt_u)
    if lt_u.ndim != 2 or not lt_u.size:
        return None
    entries = lt_u[lt]
    valid = entries[entries >= 0]
    if len(valid) != 1:
        return None  # empty-key OR expansion: zone/region coupling
    q = int(np.asarray(snap.ip_u_topo)[valid[0]])
    dom = np.asarray(snap.ip_topo_dom)[q]
    live = dom[dom >= 0]
    if len(np.unique(live)) != len(live):
        return None  # two nodes share a domain: commits couple them
    return dom


def run_pure(config: SchedulerConfig, batch: PodBatch, i: int,
             *, svc_free: bool = None) -> bool:
    """True when row i's commits touch ONLY the carry channels a grouped
    probe can account for without a re-probe: the resource block
    (models/hosttab rebuilds the j-axis from the shipped usage), host
    port masks and spread class counts (exact host-side deltas).
    Impure-but-eligible runs — inter-pod term owners / spec matchers,
    service members — get no grouped header probe: their commits mutate
    carry tables (ip reverse tables, svc peer counts) that later runs'
    probed headers can't be adjusted for host-side. They take a probe
    of their own a run, or, term owners and spec matchers on one chip,
    the device replay, whose every probe reads the live carry
    (`classify_runs`).  svc_free is the hoistable
    per-config invariant (no ServiceAffinity/ServiceAntiAffinity
    labels)."""
    if svc_free is None:
        svc_free = not service_config_labels(config)
    if not svc_free:
        # SA pin ordinals and SAA peer counts are per-probe state
        return False
    b = batch
    want_ip = MATCH_INTER_POD_AFFINITY in config.predicates or any(
        n == INTER_POD_AFFINITY for n, _ in config.priorities
    )
    if want_ip:
        if b.ip_match_spec.size and np.any(b.ip_match_spec[i]):
            return False  # commits grow other pods' term counts
        for rows in (b.ip_ha_lt, b.ip_hq_lt, b.ip_fwd_lt):
            if rows.size and np.any(rows[i] >= 0):
                return False  # own terms fold into the reverse tables
    return True


#: why `run_verdict` leaves a run to the serial scan, the keys of
#: `stats["scan_reasons"]`: the policy (`config_eligible`), a required
#: podAffinity term of the pod's own, a preferred term that selects the
#: pod's own labels, a required anti-affinity term that selects them
#: over a topology whose domains hold more than one node, a volume
SCAN_REASONS = ("config", "hard_affinity", "self_preferred", "zone_anti",
                "volumes")


def run_verdict(config: SchedulerConfig, batch: PodBatch, i: int,
                snap: ClusterSnapshot, *, config_ok: bool = None):
    """-> (reason, self_anti_veto) for pod row i's run. `reason` is None
    for an eligible run, else the one of SCAN_REASONS it is refused for
    (`classify_runs` keeps it, and a wave's end counts the refused
    runs' pods under it in `stats["scan_reasons"]`). Eligible means its
    commits don't feed back into its own fit/score except through the
    channels the tables model (resources, ports-self, spread counts,
    and — via the returned veto — hostname-topology hard anti-affinity
    against itself, the one-per-node pattern: self_anti_veto is then
    bool[N] marking nodes where one committed copy excludes every
    further copy).
    config_ok is a hoistable per-backlog invariant."""
    if config_ok is None:
        config_ok = config_eligible(config)
    if not config_ok:
        return "config", None
    b = batch
    # own inter-pod terms: the run stays eligible as long as none of
    # them feed back into the run's OWN fit/score in a way the tables
    # can't express. A term whose spec doesn't match the pod's own
    # labels never reacts to the run's commits (the carry fold in
    # _apply_fn records it exactly for later pods). A hard ANTI term
    # that DOES self-match is expressible when its topology is
    # hostname-like: each commit kills only its own node's fit
    # (generalizing the host-port self-conflict row of res_fit).
    # The three refusals below are what podaffinity-2k's pods meet
    # (PR 45: `hard_affinity` first, so that is the reason its runs
    # are counted under; its preferred hostname term alone would read
    # `self_preferred`), and `interpod_scan_share.fill` is their
    # measure: it falls when the tables learn a term (ROADMAP M8 / D3).
    if b.ip_ha_lt.size and np.any(b.ip_ha_lt[i] >= 0):
        # own hard AFFINITY: the first-pod bootstrap + domain growth
        # feedback (predicates.go:819-843) is not table-expressible
        return "hard_affinity", None
    lt_spec = np.asarray(snap.ip_lt_spec) if snap.ip_lt_spec is not None \
        else np.zeros(0, np.int32)
    ms = b.ip_match_spec[i] if b.ip_match_spec.size else None

    def self_match(lt: int) -> bool:
        return bool(ms is not None and ms[lt_spec[lt]])

    if b.ip_fwd_lt.size:
        for lt in b.ip_fwd_lt[i]:
            if lt >= 0 and self_match(int(lt)):
                # preferred term scoring its own copies: the slope in j
                # isn't in the tables (yet)
                return "self_preferred", None
    veto = None
    if b.ip_hq_lt.size:
        for lt in b.ip_hq_lt[i]:
            if lt < 0 or not self_match(int(lt)):
                continue
            dom = _lt_pernode_dom(snap, int(lt))
            if dom is None:
                return "zone_anti", None  # zone-coupled self anti-affinity
            v = dom >= 0  # nodes where the term can ever co-locate
            veto = v if veto is None else (veto | v)
    # volume commits conflict with the run's own copies
    if np.any(b.vp_vol_rw[i]) or np.any(b.vp_vol_ro[i]):
        return "volumes", None
    if np.any(b.vp_ebs[i]) or np.any(b.vp_gce[i]):
        return "volumes", None
    if b.vp_has_ebs[i] or b.vp_has_gce[i] or b.vp_ebs_bad[i] or b.vp_gce_bad[i]:
        return "volumes", None
    # (service-member runs stay eligible: the replay models the
    # ServiceAffinity first-pick pin and the per-pick ServiceAntiAffinity
    # renormalization from the probe's svc rows; the apply fold records
    # the commits for later pods. Zoned selector-spread runs likewise:
    # the probe carries the node->zone map and the replay recomputes the
    # 2/3 blend per pick — the coupling is linear in per-zone counts,
    # exactly table shape.)
    return None, veto


#: what `stats` counts of the grouped header probe, in both drivers
GROUP_COUNTERS = ("group_runs", "group_d2h_bytes", "group_reprobes")
#: what `stats` counts of the device replay (the single-chip driver's
#: alone: the mesh has none): the pick steps `jit_zreplay_group`'s and
#: `jit_zreplay_run`'s loops ran, the run-slot iterations the group's
#: outer loop ran, and the pick steps that evaluated the carried score
#: again (a node picked twice since the last evaluation, or one that
#: left the fit set holding a normaliser's extreme), by the programs'
#: own counters, and the pods they placed
ZREPLAY_COUNTERS = ("zreplay_steps", "zreplay_slots", "zreplay_rescores",
                    "zreplay_picks")
#: what `stats` counts of the scan's loop (`scan_rows`: the loop's `flush`,
#: and the optimizing profile's remainder): the steps `jit_batch_scan` ran, by the loop's
#: own counter where it stopped, and the steps its pod buckets hold,
#: which a loop over the padded axis would have run. `scan_steps` over
#: `pods_by_path["scan"]` is 1.0 while the loop ends at a wave's real
#: count; `scan_bucket_steps` over `scan_steps` is the padding it skips
SCAN_COUNTERS = ("scan_steps", "scan_bucket_steps")
#: what `stats` counts of the wave loop itself, in both drivers, at the
#: wave's end with `pods_by_path`: `steps_by_kind` ({kind: the `Step`s
#: `waveloop.run_wave` ran}, a dict beside this tuple) and the calls of
#: `waveloop.flush` that found pods pending: each is one dispatch of the
#: scan and one wait for its picks, so `pods_by_path["scan"]` over it is
#: the pods a scan dispatch decides
LOOP_COUNTERS = ("scan_flushes",)
#: what `stats` counts of the runs that carry a self-anti veto (pods
#: whose required hostname anti-affinity term selects their own labels;
#: `run_verdict`), which the device replay decides a group a dispatch on
#: one chip and `waveloop.run_single` one probe a run elsewhere
#: (`waveloop.count_veto`): the runs, the pods they placed, and summed
#: over the runs the real nodes the run's FIRST probe found unfit, by
#: the replay program's own count of the nodes that fit or on the tables
#: a host probe shipped (where nothing but the terms of bound pods
#: excludes a node, how much of the cluster they have taken from a run
#: before it starts); counted at the wave's end, with `pods_by_path`
ANTI_COUNTERS = ("anti_runs", "anti_picks", "anti_nodes_excluded")
#: what `stats` counts of the runs whose pod owns a required podAffinity
#: term (every run of a wave, whatever its length; `run_verdict` sends
#: those of `min_run` pods and more to the scan as `hard_affinity`): the
#: runs, and summed over them the real nodes the term keeps the pod off
#: on the snapshot the wave began with (`affinity_nodes_excluded`:
#: counted on the kept inter-pod tables on the host, `anti_nodes_excluded`'s
#: twin); counted at the wave's end by `count_runs`, in both drivers
AFFINITY_COUNTERS = ("affinity_runs", "affinity_nodes_excluded")
#: what `stats` counts of the daemon's re-warm of the scan at inter-pod
#: widths first seen (scheduler/tpu_algorithm.TPUScheduleAlgorithm._rewarm;
#: the single-chip driver's alone): the times it held the loop, their
#: seconds, the programs built in them, and the warm waves whose widths
#: were not the live ones
REWARM_COUNTERS = ("rewarms", "rewarm_seconds", "rewarm_programs",
                   "rewarm_mismatches")
#: which encoder made a wave's snapshot (counted where the scheduler
#: chooses, scheduler/tpu_algorithm), and by which scope gate of the
#: incremental one a wave went to the from-scratch encoder
#: (snapshot/incremental.IncrementalEncoder.fallback)
ENCODERS = ("incremental", "full")


def count_group(stats: dict, counted: dict) -> None:
    """Some of `GROUP_COUNTERS`, `ZREPLAY_COUNTERS`, `SCAN_COUNTERS`,
    `LOOP_COUNTERS`, `ANTI_COUNTERS`, `AFFINITY_COUNTERS` or
    `REWARM_COUNTERS` into a
    driver's cumulative `stats`, and into the process-wide totals on
    /debug/traces."""
    for key, n in counted.items():
        stats[key] += n
    count_wave_group(counted)


def count_encoder(stats: dict, encoder: str,
                  fallback: Optional[str] = None,
                  rebuilds: Optional[Dict[str, int]] = None) -> None:
    """A wave's snapshot was made by `encoder` (one of ENCODERS), sent
    there by the scope gate `fallback` if by any; before it the
    incremental encoder rebuilt its inter-pod tables whole `rebuilds`
    times, by reason (what its deltas do not cover:
    snapshot/interpod.InterPodTables). Into a driver's cumulative
    `stats` and the process-wide totals on /debug/traces."""
    stats["waves_by_encoder"][encoder] += 1
    if fallback:
        by_reason = stats["encoder_fallbacks"]
        by_reason[fallback] = by_reason.get(fallback, 0) + 1
    for reason, n in (rebuilds or {}).items():
        by_reason = stats["interpod_rebuilds"]
        by_reason[reason] = by_reason.get(reason, 0) + n
    count_wave_encoder(encoder, fallback, rebuilds)


def svc_run_context(config: SchedulerConfig, snap: ClusterSnapshot,
                    batch: PodBatch, rep: int, num_values: int):
    """The host-side service context for one run (SA/SAA policy
    configs): what probe.tables_from_packed needs to model the
    ServiceAffinity first-pick pin and the ServiceAntiAffinity per-pick
    renormalization in the replay. None when the config has no service
    terms. Shared by the single-chip and mesh wave drivers."""
    svc_labels = service_config_labels(config)
    if not svc_labels:
        return None
    sa_rows_idx: List[int] = []
    saa_li, w_saa = -1, 0
    for e in config.predicates:
        if isinstance(e, tuple) and e[0] == "ServiceAffinity":
            sa_rows_idx.extend(svc_labels.index(l) for l in e[1])
    for nm, w in config.priorities:
        if isinstance(nm, tuple) and nm[0] == "ServiceAntiAffinity":
            saa_li = svc_labels.index(nm[1])
            w_saa = int(w)
    lbl_val = np.asarray(snap.svc_lbl_val)
    g = int(batch.svc_group[rep])
    ctx = {"w_saa": w_saa}
    if w_saa:
        ctx["lbl_val_row"] = lbl_val[saa_li]
        ctx["num_values"] = num_values
        ctx["member"] = bool(
            g >= 0 and batch.svc_member.shape[1]
            and batch.svc_member[rep, g]
        )
    if sa_rows_idx and g >= 0:
        unres = [
            li for li in sa_rows_idx
            if int(batch.svc_fixed[rep, li]) < 0
        ]
        if unres:
            ctx["sa_rows"] = lbl_val[unres]
            # pin-staleness analysis needs the ord -> node row map
            ctx["ord_node"] = np.asarray(snap.svc_ord_node)
    return ctx


def split_runs(rep_idx: np.ndarray,
               boundaries: Sequence[int] = ()) -> List[Tuple[int, int, int]]:
    """Maximal runs of consecutive equal representative rows:
    -> [(rep, start, length)]. Shared by the single-chip and mesh
    drivers. `boundaries` forces additional run breaks at those
    backlog positions — a gang span must be ITS OWN run even when the
    neighbouring pods share its template, so the all-or-nothing commit
    decision covers exactly the gang's members."""
    runs: List[Tuple[int, int, int]] = []
    cuts = frozenset(boundaries)
    i, P = 0, len(rep_idx)
    while i < P:
        r = rep_idx[i]
        s = i
        while i < P and rep_idx[i] == r and (i == s or i not in cuts):
            i += 1
        runs.append((int(r), s, i - s))
    return runs


def classify_runs(config: SchedulerConfig, snap: ClusterSnapshot,
                  batch: PodBatch, spans, num_values: int, min_run: int,
                  *, device_zoned: bool = False, zoned: bool = False,
                  gangs: Sequence[dict] = ()) -> List[Run]:
    """Classify every span of `split_runs` once, into a finished `Run`:
    eligibility, the self-anti veto, the service context, the
    device-replay route, commit purity (whether a grouped probe's host
    adjustments can cover its commits) and the gang it is. Shared by
    the single-chip and mesh wave drivers — the classification IS the
    dispatch-shape contract: `waveloop.next_step` cuts a wave's steps
    from these `Run`s and a `Policy` and from nothing else, and nothing
    changes a `Run` once it is made, so the two drivers cannot drift."""
    config_ok = config_eligible(config)
    svc_free = not service_config_labels(config)
    gang_by_start = {int(g["start"]): g for g in gangs}
    runs: List[Run] = []
    for rep, start, length in spans:
        gang = gang_by_start.get(start)
        # `refused`: why a run long enough for the run machinery goes
        # to the scan all the same (None for an eligible run and for
        # one that is merely short)
        eligible, veto, refused = False, None, None
        # a gang span takes the run machinery at ANY length (typical
        # gangs are 2-16 pods, under the default min_run): the probe/
        # replay path is where the all-or-nothing commit is enforced
        if length >= min_run or gang is not None:
            refused, veto = run_verdict(
                config, batch, rep, snap, config_ok=config_ok,
            )
            eligible = refused is None
        if not (gang is not None and eligible and length == gang["length"]):
            # a span the driver can't take atomically (mixed member
            # templates or ineligible features) schedules plainly; the
            # director's post-hoc check guards the binds
            gang = None
        svc_ctx = svc_run_context(
            config, snap, batch, rep, num_values
        ) if eligible else None
        pure = bool(
            eligible and veto is None and svc_ctx is None
            and run_pure(config, batch, rep, svc_free=svc_free))
        runs.append(Run(
            rep, start, length, eligible=eligible, refused=refused,
            veto=veto, svc_ctx=svc_ctx,
            # the device replay takes what the host's routes decide
            # badly: the zone blend (the C engine cannot bucket it) and a
            # run the grouped header probe cannot take (a veto, an owner
            # of a term, a matcher of a spec: a probe of its own a run on
            # the host, grouped freely in the program). An atomic gang
            # takes the host probe/replay path only (the device replay
            # folds commits in-program and cannot discard a partial gang)
            device=bool(
                eligible and device_zoned and gang is None
                and svc_ctx is None
                and (zoned and bool(batch.has_selectors[rep])
                     or not pure)),
            pure=pure,
            gang=gang,
        ))
    return runs


def count_runs(stats: dict, snap: ClusterSnapshot, batch: PodBatch,
               runs: Sequence[Run]) -> None:
    """What a wave's classification says of its runs, into a driver's
    cumulative `stats` and the process-wide totals on /debug/traces, at
    the wave's end beside `pods_by_path`: `scan_reasons` ({reason: the
    pods of the runs `run_verdict` refused}) and AFFINITY_COUNTERS."""
    reasons: Dict[str, int] = {}
    for run in runs:
        if run.refused is not None:
            reasons[run.refused] = reasons.get(run.refused, 0) + run.length
    if reasons:
        tally = stats["scan_reasons"]
        for reason, n in reasons.items():
            tally[reason] = tally.get(reason, 0) + n
        count_wave_reasons(reasons)
    owners = (np.asarray(batch.ip_ha_lt) >= 0).any(axis=1)
    if not owners.any():
        return
    excluded: Dict[int, int] = {}  # by pod row: a wave's runs repeat it
    owned = nodes = 0
    for run in runs:
        rep = run.rep
        if owners[rep]:
            if rep not in excluded:
                excluded[rep] = affinity_nodes_excluded(snap, batch, rep)
            owned += 1
            nodes += excluded[rep]
    count_group(stats, {"affinity_runs": owned,
                        "affinity_nodes_excluded": nodes})


def affinity_nodes_excluded(snap: ClusterSnapshot, batch: PodBatch,
                            rep: int) -> int:
    """The real nodes (allocatable pods > 0) that pod row `rep`'s
    required podAffinity terms keep it off on the wave's snapshot: for
    some term no bound pod that matches lies in the node's domain, and
    the first-pod escape does not hold (ops/interpod.match_interpod's
    hard-affinity half, in numpy on the kept tables: no device read)."""
    lt_u = np.asarray(snap.ip_lt_u)
    sign = np.asarray(snap.ip_lt_sign)
    u_topo = np.asarray(snap.ip_u_topo)
    topo_dom = np.asarray(snap.ip_topo_dom)
    term_count = np.asarray(snap.ip_term_count)
    lt_spec = np.asarray(snap.ip_lt_spec)
    spec_total = np.asarray(snap.ip_spec_total)
    out = np.zeros(topo_dom.shape[1], bool)
    for lt, own in zip(np.asarray(batch.ip_ha_lt)[rep],
                       np.asarray(batch.ip_ha_self)[rep]):
        if lt < 0:
            continue
        if own and spec_total[lt_spec[lt]] == 0:
            continue  # the first pod of its collection goes anywhere
        cnt = np.zeros(topo_dom.shape[1], np.int64)
        for u, sg in zip(lt_u[lt], sign[lt]):
            if u >= 0:
                dom = topo_dom[u_topo[u]]
                cnt += np.where(dom >= 0, int(sg) * term_count[
                    u, np.clip(dom, 0, term_count.shape[1] - 1)], 0)
        out |= cnt <= 0
    return int(np.count_nonzero(out & (np.asarray(snap.alloc_pods) > 0)))


class WaveCounts:
    """The tallies both wave drivers keep: `dispatches`, the device
    programs of the wave in hand by kind, and `stats`, all waves."""

    dispatches: dict
    stats: dict

    def _count(self, key: str) -> None:
        self.dispatches[key] = self.dispatches.get(key, 0) + 1
        self.stats["dispatches"] += 1
        by_kind = self.stats["dispatches_by_kind"]
        by_kind[key] = by_kind.get(key, 0) + 1

    def _count_wave(self, wave: Wave, runs: Sequence[Run]) -> None:
        """A finished wave into the cumulative tallies, here and on
        /debug/traces (trace/profile.wave_totals): the pods each path
        decided, what its steps tallied and what its classification
        says of its runs, all at once (`Wave.tallies` says why)."""
        pods = dict(zip(PATHS, np.bincount(wave.via, minlength=len(PATHS))
                        .tolist()))
        unplaced = int(np.count_nonzero(wave.out < 0))
        steps = {kind: wave.steps[kind] for kind in PATHS}
        for path in PATHS:
            self.stats["pods_by_path"][path] += pods[path]
            self.stats["steps_by_kind"][path] += steps[path]
        self.stats["pods_unplaced"] += unplaced
        count_wave(pods, self.dispatches, unplaced, steps)
        if wave.tallies:
            count_group(self.stats, dict(wave.tallies))
        count_runs(self.stats, wave.snap, wave.batch, runs)


class WaveScheduler(WaveCounts):
    """Schedules an encoded backlog (unique rows + per-position rep
    index) bit-identically to the serial scan, fast-pathing runs: the
    single-chip side of `waveloop.run_wave`'s device seam."""

    LAST_IDX = BatchScheduler.LAST_IDX

    def __init__(self, config: Optional[SchedulerConfig] = None,
                 min_run: int = 16, max_j: int = 1024, pod_floor: int = 64,
                 replay=None):
        self.config = config or SchedulerConfig()
        self.scan = BatchScheduler(self.config)
        self.probe = WaveProbe(self.config)
        self.min_run = min_run
        self.max_j = max_j
        self.pod_floor = pod_floor
        self._replay = replay or replay_fast
        self._apply_packed_jit: dict = {}
        self._apply_group_jit: dict = {}
        self._zreplay = ZReplay(self.config, self._apply_fn,
                                self._apply_group_fn)
        # per-wave device-dispatch tally (tests assert the grouped path
        # keeps this independent of the template count)
        self.dispatches: dict = {}
        # two kinds of run replay ON DEVICE (`classify_runs` sets
        # `Run.device`): zoned selector-spread runs — the zone blend
        # couples whole zones per commit, which the C engine can't
        # bucket and numpy pays ~0.4ms/pick for — and runs the grouped
        # header probe cannot take (a self-anti veto, an owner of a
        # term, a matcher of a spec), which the host decides one probe
        # round trip a run and the device program a group a dispatch.
        # Opt out (e.g. for differential testing of the host path) via
        # replay=.
        self._device_zoned = replay is None
        self._packer = Packer()
        # device-resident snapshot fields across waves (the mesh path's
        # resident/mirror design, single-chip): field ->
        # (host shape, host dtype, device array, full-width host MIRROR).
        # The caller's `keep` set says which host fields are unchanged
        # since the previous wave; fields NOT in keep are still reused
        # when the mirror proves the content unchanged, scatter-updated
        # when only a few rows moved, and re-shipped otherwise — so a
        # quiet wave ships zero table bytes even without incremental
        # provenance. `_dev_source` guards against reuse across snapshot
        # provenances: arrays from a from-scratch encoder (fresh vocab
        # bit/slot assignments) must never satisfy a `keep` computed by
        # the incremental encoder.
        self._dev: dict = {}
        self._dev_source: Optional[str] = None
        self._row_set_jit: dict = {}
        # per-wave/total table-shipment accounting
        self.stats = {
            "waves": 0, "table_ships": 0, "table_reuses": 0,
            "table_scatters": 0, "wave_table_bytes": 0,
            "table_bytes_total": 0,
            # bytes a reuse/scatter AVOIDED shipping (what the
            # pre-resident driver re-shipped every wave) — the bench's
            # steady-state byte-reduction numerator
            "table_bytes_reused": 0,
            # device programs launched, all waves: the cumulative total
            # beside the per-wave `dispatches` dict, which _wave_setup
            # empties (a window's launches are a diff of this)
            "dispatches": 0,
            # the same launches by kind (`_count`'s keys), and the pods
            # each path decided (PATHS), all waves: a window's shares
            # are diffs of these. They add up to the pods handed to
            # schedule_backlog; `pods_unplaced` are those among them
            # that fitted nowhere (-1 in its answer)
            "dispatches_by_kind": {},
            "pods_by_path": dict.fromkeys(PATHS, 0),
            "pods_unplaced": 0,
            # the grouped header probe replayed on the host
            # (`waveloop.run_group_host`), all waves: the runs that went
            # through it, the bytes its probes fetched from the device
            # (every run slot's header rows and the resource block), and
            # the groups that stopped before their last run and sent one
            # to `run_single`, which costs a probe of its own
            **dict.fromkeys(GROUP_COUNTERS, 0),
            # the grouped device replay (`replay_group_device`), all waves
            **dict.fromkeys(ZREPLAY_COUNTERS, 0),
            # the scan's loop (`scan_rows`), all waves
            **dict.fromkeys(SCAN_COUNTERS, 0),
            # the wave loop's steps by kind and its flushes of the scan
            "steps_by_kind": dict.fromkeys(PATHS, 0),
            **dict.fromkeys(LOOP_COUNTERS, 0),
            # the runs with a self-anti veto (`count_veto`), all waves
            **dict.fromkeys(ANTI_COUNTERS, 0),
            # the runs whose pod owns a required podAffinity term, and
            # why runs of `min_run` pods went to the scan (`count_runs`)
            **dict.fromkeys(AFFINITY_COUNTERS, 0),
            "scan_reasons": {},
            # the daemon's re-warm of the scan (the scheduler counts)
            **dict.fromkeys(REWARM_COUNTERS, 0),
            # the encoder behind each wave's snapshot, and the scope
            # gates that sent waves to the from-scratch one
            # (`count_encoder`; the scheduler counts, the driver keeps)
            "waves_by_encoder": dict.fromkeys(ENCODERS, 0),
            "encoder_fallbacks": {},
            # whole rebuilds of the kept inter-pod tables, by reason
            "interpod_rebuilds": {},
        }

    # fraction of changed rows above which a scatter-row update loses
    # to wholesale re-ship (mirrors parallel/resident.SCATTER_FRAC)
    SCATTER_FRAC = 0.25

    @staticmethod
    def _rows_neq(mirror, host):
        """Per-row changed mask, NaN-aware (numval uses NaN fills)."""
        neq = mirror != host
        if mirror.dtype.kind == "f":
            neq &= ~(np.isnan(mirror) & np.isnan(host))
        if neq.ndim == 1:
            return neq
        if neq.size == 0:
            return np.zeros(neq.shape[0], bool)
        return neq.reshape(neq.shape[0], -1).any(axis=1)

    def _row_set(self, dtype, tail, bucket):
        key = (np.dtype(dtype).str, tail, bucket)
        fn = self._row_set_jit.get(key)
        if fn is None:
            def row_set(a, r, v):
                return a.at[r].set(v)

            fn = jax.jit(row_set, donate_argnums=0)
            self._row_set_jit[key] = fn
        return fn

    def _to_dev_many(self, snap, fields, keep: frozenset, extra=None,
                     reship: frozenset = frozenset()):
        """Device copies for `fields` (+ `extra` host arrays), shipping
        every miss in ONE batched device_put: each individual transfer
        has a fixed cost, so per-field puts dominate a cold wave. Placed
        copies may ride a narrowed dtype (ops/narrow); mirrors
        keep full width, and a narrow-range overflow changes the
        placement dtype, which misses the cache and rebuilds wider.
        `reship` names fields whose producer says they moved as one:
        each goes whole with the batch, compared with nothing, so the
        set of tables shipped is the same every such wave (a set is one
        `jit_pack_unpack` layout, built where it is first met)."""
        out = {}
        missing = {}
        scatters = []
        for f in fields:
            host = getattr(snap, f)
            host_np = np.asarray(host)
            place_dt = narrow_dtype(f, host_np)
            ent = self._dev.get(f)
            if (
                ent is not None
                and f not in reship
                and ent[2] is not None
                and ent[0] == host_np.shape
                and ent[1] == host_np.dtype
                and ent[2].dtype == place_dt
            ):
                if f in keep:
                    out[f] = ent[2]
                    self.stats["table_reuses"] += 1
                    self.stats["table_bytes_reused"] += ent[2].nbytes
                    continue
                neq = self._rows_neq(ent[3], host_np)
                changed = np.nonzero(neq)[0]
                if changed.size == 0:
                    out[f] = ent[2]
                    self.stats["table_reuses"] += 1
                    self.stats["table_bytes_reused"] += ent[2].nbytes
                    continue
                if (host_np.ndim >= 1 and changed.size
                        <= self.SCATTER_FRAC * host_np.shape[0]):
                    scatters.append((f, host_np, place_dt, changed))
                    continue
            missing[f] = host_np.astype(place_dt) \
                if place_dt != host_np.dtype else host_np
            self._dev[f] = (host_np.shape, host_np.dtype, None,
                            host_np.copy())
        for f, host_np, place_dt, changed in scatters:
            # pad the row count to a pow2 bucket (stable jit cache);
            # duplicate rows re-set identical values, which is safe
            bucket = 1
            while bucket < changed.size:
                bucket *= 2
            rows = np.full(bucket, changed[0], np.int32)
            rows[: changed.size] = changed
            vals = np.ascontiguousarray(
                host_np[rows].astype(place_dt, copy=False))
            put = self._packer.ship(
                {"__rows__": rows, "__vals__": vals})
            ent = self._dev[f]
            fn = self._row_set(place_dt, host_np.shape[1:], bucket)
            arr = fn(ent[2], put["__rows__"], put["__vals__"])
            mirror = ent[3]
            mirror[changed] = host_np[changed]
            self._dev[f] = (ent[0], ent[1], arr, mirror)
            out[f] = arr
            self.stats["table_scatters"] += 1
            self.stats["wave_table_bytes"] += rows.nbytes + vals.nbytes
            self.stats["table_bytes_total"] += rows.nbytes + vals.nbytes
            self.stats["table_bytes_reused"] += max(
                0, arr.nbytes - rows.nbytes - vals.nbytes)
            self._count("table_scatter")
        if extra:
            missing.update(extra)
        if missing:
            put = self._packer.ship(missing)
            for f, arr in put.items():
                if extra and f in extra:
                    out[f] = arr
                    continue
                ent = self._dev[f]
                self._dev[f] = (ent[0], ent[1], arr, ent[3])
                out[f] = arr
                self.stats["table_ships"] += 1
                self.stats["wave_table_bytes"] += missing[f].nbytes
                self.stats["table_bytes_total"] += missing[f].nbytes
        return out

    # -- carry commit of a whole run -----------------------------------------

    @jax.named_scope("apply")
    def _apply_fn(self, static, carry, pod, counts, picks=None):
        """Fold j identical commits per node into the carry — the exact
        sum of the scan's per-step commit section over the run. `picks`
        are the same commits one by one, (nodes i32[K], placed bool[K]),
        where the caller holds them (the device replay): the inter-pod
        tables then take an update a pick
        (ops/interpod.interpod_commit_picks) and not one a node."""
        (
            res, port_mask, class_count, last_idx,
            ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
            ip_rev_anti, ip_spec_total,
            vol_any, vol_rw, ebs_mask, gce_mask,
            svc_first_peer, svc_peer_node_count, svc_peer_total,
        ) = carry
        k = counts.sum()
        commit = jnp.stack([
            pod["commit_mcpu"], pod["commit_mem"], pod["commit_gpu"],
            pod["nz_mcpu"], pod["nz_mem"], jnp.int64(1),
        ])
        res = res + commit[:, None] * counts[None, :]
        port_mask = jnp.where(
            (counts > 0)[:, None], port_mask | pod["port_mask"][None, :],
            port_mask,
        )
        class_count = class_count.at[:, pod["class_id"]].add(counts)
        last_idx = last_idx + k
        U = static["ip_u_topo"].shape[0]
        if picks is not None:
            (ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
             ip_rev_anti) = IP.interpod_commit_picks(
                ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
                ip_rev_anti, static["ip_topo_dom"], static["ip_u_topo"],
                static["ip_u_spec"], static["ip_lt_u"],
                pod["ip_match_spec"], pod["ip_own_hard"],
                pod["ip_own_pref"], pod["ip_own_anti_hard"],
                pod["ip_own_anti_pref"], *picks)
        elif U and ip_term_count.shape[1]:
            # term_count[u, dom(u, n)] += match_spec[spec(u)] * counts[n]
            # — interpod_commit is linear in the commit count
            dom = static["ip_topo_dom"][static["ip_u_topo"]]  # (U, N)
            mu = pod["ip_match_spec"][static["ip_u_spec"]]  # (U,)
            add = jnp.where(
                dom >= 0, mu[:, None].astype(jnp.int64) * counts[None, :], 0
            )
            ip_term_count = ip_term_count.at[
                jnp.arange(U)[:, None],
                jnp.clip(dom, 0, ip_term_count.shape[1] - 1),
            ].add(add.astype(ip_term_count.dtype))
        LT = static["ip_lt_u"].shape[0] if "ip_lt_u" in static else 0
        E = static["ip_lt_u"].shape[1] if LT else 0
        if picks is None and LT and E and ip_own_anti.shape[2]:
            # the run's OWN terms, folded per node with multiplicity
            # counts[n] — ops/interpod.interpod_commit vectorized over N
            # (run_verdict guarantees these terms never feed back into
            # this run's own fit/score; later pods need the exact state)
            lt_u = static["ip_lt_u"]  # (LT, E)
            q = static["ip_u_topo"][jnp.clip(lt_u, 0, U - 1)]
            domq = static["ip_topo_dom"][q]  # (LT, E, N)
            validq = (lt_u >= 0)[:, :, None] & (domq >= 0)
            sdq = jnp.clip(domq, 0, ip_own_anti.shape[2] - 1)
            lt_i = jnp.arange(LT)[:, None, None]
            e_i = jnp.arange(E)[None, :, None]
            c32 = jnp.where(validq, counts[None, None, :], 0).astype(
                jnp.int32
            )
            c64 = c32.astype(jnp.int64)
            ip_own_anti = ip_own_anti.at[lt_i, e_i, sdq].add(
                pod["ip_own_anti_hard"][:, None, None] * c32
            )
            ip_rev_hard = ip_rev_hard.at[lt_i, e_i, sdq].add(
                pod["ip_own_hard"][:, None, None] * c32
            )
            ip_rev_pref = ip_rev_pref.at[lt_i, e_i, sdq].add(
                pod["ip_own_pref"][:, None, None] * c64
            )
            ip_rev_anti = ip_rev_anti.at[lt_i, e_i, sdq].add(
                pod["ip_own_anti_pref"][:, None, None] * c64
            )
        if ip_spec_total.shape[0]:
            ip_spec_total = ip_spec_total + (
                pod["ip_match_spec"].astype(jnp.int64) * k
            ).astype(ip_spec_total.dtype)
        if svc_first_peer.shape[0]:
            from kubernetes_tpu.ops.services import service_commit_bulk

            (svc_first_peer, svc_peer_node_count,
             svc_peer_total) = service_commit_bulk(
                svc_first_peer, svc_peer_node_count, svc_peer_total,
                static["svc_node_ord"], pod["svc_member"], counts,
            )
        return (
            res, port_mask, class_count, last_idx,
            ip_term_count, ip_own_anti, ip_rev_hard, ip_rev_pref,
            ip_rev_anti, ip_spec_total,
            vol_any, vol_rw, ebs_mask, gce_mask,
            svc_first_peer, svc_peer_node_count, svc_peer_total,
        )

    _CARRY_FIELDS = (
        "port_mask", "class_count", "ip_term_count", "ip_own_anti",
        "ip_rev_hard", "ip_rev_pref", "ip_rev_anti", "ip_spec_total",
        "vol_any", "vol_rw", "ebs_mask", "gce_mask",
        "svc_first_peer", "svc_peer_node_count", "svc_peer_total",
    )

    def _carry_from(self, dev: dict):
        """BatchScheduler.initial_carry from the batched device dict:
        the resource block ships as ONE stacked array and the (usually
        empty) ip/vol/svc blocks reuse their device copies when
        unchanged."""
        return (dev["__res__"], dev["port_mask"], dev["class_count"],
                dev["__lidx__"]) + tuple(
            dev[f] for f in self._CARRY_FIELDS[2:]
        )

    def _apply_packed(self, static, carry, buf, layout, counts):
        """The commit fold from a PACKED pod-row buffer — the settle
        path when no further probe will carry the fold for free."""
        fn = self._apply_packed_jit.get(layout)
        if fn is None:
            def wave_apply_packed(static_, carry_, buf_, counts_):
                pod = unpack(layout, buf_)
                return self._apply_fn(static_, carry_, pod, counts_)

            fn = jax.jit(wave_apply_packed)
            self._apply_packed_jit[layout] = fn
        # carry-fold commit (async dispatch: the timer sees the enqueue
        # plus whatever the device makes it wait for)
        with phase_timer("replay"):
            self._count("apply")
            return fn(static, carry, buf, jnp.asarray(counts))

    @jax.named_scope("fold")
    def _apply_group_fn(self, layout, static, carry, buf, counts):
        """Fold a whole GROUP of runs' commits (counts i64[G, N], one
        row per stacked pod in `buf`) into the carry in one scatter.
        Valid only for PURE runs (run_pure): the resource block, port
        masks, spread class counts, and the round-robin counter are the
        only carry channels their commits touch — the ip/vol/svc blocks
        pass through untouched, exactly as G zero-commit _apply_fn
        folds would have left them."""
        pods = unpack(layout, buf)
        (res, port_mask, class_count, last_idx), rest = (
            carry[:4], carry[4:]
        )
        commit = jnp.stack([
            pods["commit_mcpu"], pods["commit_mem"], pods["commit_gpu"],
            pods["nz_mcpu"], pods["nz_mem"],
            jnp.ones_like(pods["commit_mcpu"]),
        ])  # (6, G)
        # elementwise product + reduce instead of an s64 dot_general
        # (which has no TPU lowering); XLA fuses the reduction
        res = res + (commit[:, :, None] * counts[None, :, :]).sum(axis=1)
        touched = counts > 0  # (G, N)
        add_bits = jnp.where(
            touched[:, :, None], pods["port_mask"][:, None, :],
            jnp.zeros_like(pods["port_mask"][:, None, :]),
        )  # (G, N, W)
        port_mask = port_mask | jax.lax.reduce(
            add_bits, port_mask.dtype.type(0), jax.lax.bitwise_or, (0,)
        )
        class_count = class_count.at[:, pods["class_id"]].add(
            counts.T.astype(class_count.dtype)
        )
        last_idx = last_idx + counts.sum()
        return (res, port_mask, class_count, last_idx) + tuple(rest)

    def _apply_group_packed(self, static, carry, buf, layout, counts):
        """Standalone dispatch of the grouped fold (the settle path)."""
        fn = self._apply_group_jit.get(layout)
        if fn is None:
            def wave_apply_group(static_, carry_, buf_, counts_):
                return self._apply_group_fn(layout, static_, carry_,
                                            buf_, counts_)

            fn = jax.jit(wave_apply_group)
            self._apply_group_jit[layout] = fn
        with phase_timer("replay"):
            self._count("apply")
            return fn(static, carry, buf, jnp.asarray(counts))

    def scan_rows(self, static, carry, batch: PodBatch, reps: np.ndarray,
                  num_zones: int, num_values: int):
        """`jit_batch_scan` over rows `reps` of `batch` in their order,
        padded to their pod bucket (one program a bucket) with the loop
        ending at their count -> (carry, chosen i32[len(reps)],
        lastNodeIndex). Counts the dispatch and `SCAN_COUNTERS`."""
        n = len(reps)
        seg = pad_batch(gather_batch(batch, reps),
                        next_pow2(n, self.pod_floor))
        pods = self._packer.ship({
            f: np.asarray(getattr(seg, f))
            for f in BatchScheduler.POD_FIELDS
        })
        run = self.scan._compiled(num_zones, num_values)
        # "score": the fused predicate+priority scan program — the
        # asarray/int reads force the dispatch so the timer covers
        # compute, not just enqueue. The count goes in as np.int32
        # everywhere: another dtype is another program
        with phase_timer("score"):
            self._count("scan")
            carry, chosen, steps = run(static, carry, pods, np.int32(n))
            with device_wait():
                chosen = np.asarray(chosen)[:n]
                last = int(carry[self.LAST_IDX])
                steps = int(steps)
        count_group(self.stats, {"scan_steps": steps,
                                 "scan_bucket_steps": seg.num_pods})
        return carry, chosen, last

    # -- backlog -------------------------------------------------------------

    def _wave_setup(self, snap: ClusterSnapshot, keep: frozenset,
                    source: str, last_node_index: int,
                    reship: frozenset = frozenset()):
        """Per-wave device placement shared by the greedy driver and
        the optimizing profile (scheduler/optimizer/profile.py):
        -> (static, carry, num_zones, num_values). Resets the per-wave
        dispatch tally and the device field cache on a snapshot
        producer change."""
        if source != self._dev_source:
            self._dev.clear()
            self._dev_source = source
        self.dispatches = {}
        self.stats["waves"] += 1
        self.stats["wave_table_bytes"] = 0
        # all of it host-to-device placement: the stack of the resource
        # rows and config_static's masks as much as Packer.ship inside
        # _to_dev_many, which opens the same phase
        with phase_timer("transfer"):
            res_host = np.stack([
                np.asarray(snap.req_mcpu), np.asarray(snap.req_mem),
                np.asarray(snap.req_gpu), np.asarray(snap.nz_mcpu),
                np.asarray(snap.nz_mem), np.asarray(snap.pod_count),
            ])
            dev = self._to_dev_many(
                snap,
                tuple(BatchScheduler.STATIC_FIELDS) + self._CARRY_FIELDS,
                keep,
                extra={"__res__": res_host,
                       "__lidx__": np.int64(last_node_index)},
                reship=reship,
            )
            static = {f: dev[f] for f in BatchScheduler.STATIC_FIELDS}
            # config-resolved node masks are HOST arrays: place them
            # once per wave (a numpy leaf in `static` would re-upload
            # at every per-run probe/apply dispatch)
            static.update({
                k: jnp.asarray(v)
                for k, v in BatchScheduler.config_static(
                    self.config, snap).items()
            })
        num_zones = max(
            int(snap.zone_id.max()) + 1 if snap.zone_id.size else 1, 1
        )
        num_values = int(snap.svc_num_values)
        return static, self._carry_from(dev), num_zones, num_values

    def schedule_backlog(
        self,
        snap: ClusterSnapshot,
        batch: PodBatch,
        rep_idx: np.ndarray,
        last_node_index: int = 0,
        keep: frozenset = frozenset(),
        source: str = "full",
        gangs: Optional[Sequence[dict]] = None,
        reship: frozenset = frozenset(),
    ) -> Tuple[np.ndarray, tuple, int]:
        """-> (chosen i32[P] node ids with -1 == unschedulable,
        final carry, final lastNodeIndex). snap may be node-padded;
        batch holds one row per unique pod; rep_idx maps backlog
        position -> row. `keep` (from the incremental encoder) names
        snapshot fields unchanged since the previous wave — their
        device copies are reused instead of re-shipped; `reship` names
        those that moved as one and ship whole (`_to_dev_many`). `source`
        identifies the snapshot's producer; a producer change drops the
        device cache (ids/bit positions are producer-relative).

        `gangs` marks all-or-nothing spans of the backlog:
        [{"start", "length", "score_add": i64[N] | None}]. Each span
        becomes its own run (split_runs boundaries) riding the SAME
        grouped probe/replay machinery as any template run — a gang
        costs no extra dispatches — but its commits fold only when
        every member gets a node; otherwise the whole span stays -1
        (parked) and later runs/singletons replay against untouched
        state. Spans the run machinery cannot take atomically (mixed
        member templates, ineligible features -> the serial scan)
        schedule plainly; the caller (scheduler/gang.GangDirector)
        applies an unconditional post-hoc all-or-nothing check over
        the returned hosts before anything binds. None/[] = no gangs,
        and the wave is bit-identical to the pre-gang driver."""
        wave = Wave(self.config, snap, batch, rep_idx, int(last_node_index),
                    self.max_j, self._replay)
        wave.static, wave.carry, _zones, _values = self._wave_setup(
            snap, keep, source, last_node_index, reship)
        runs, policy = self.plan(wave, gangs)
        run_wave(self, wave, runs, policy)
        self._count_wave(wave, runs)
        return wave.out, wave.carry, wave.L_host

    def plan(self, wave: Wave, gangs: Optional[Sequence[dict]] = None
             ) -> Tuple[List[Run], Policy]:
        """The wave's runs, classified, and the policy that cuts them
        into steps (`waveloop.plan_steps` prints the plan): host work
        on the wave's inputs alone, no device. Gang spans force their
        own run boundaries so all-or-nothing covers exactly the gang's
        members."""
        gangs = list(gangs or ())
        cuts = [int(g["start"]) + off
                for g in gangs for off in (0, int(g["length"]))]
        runs = classify_runs(
            self.config, wave.snap, wave.batch,
            split_runs(wave.rep_idx, cuts), wave.num_values, self.min_run,
            device_zoned=self._device_zoned, zoned=wave.zoned, gangs=gangs,
        )
        return runs, Policy(host_group_cap(wave.N))

    def run_kinds(self, snap: ClusterSnapshot, batch: PodBatch,
                  reps: Sequence[int]) -> List[str]:
        """What a run of `min_run` pods of each pod row `reps` is to the
        plan: "scan" (the scan's, whatever its length), "device" (a
        device replay's, alone or grouped with its like: a zoned spread
        run, a veto, a term owner, a spec matcher), "pure" (a grouped
        header probe's with its like, else a probe's of its own) or
        "single" (a probe's of its own whatever its neighbours: what
        "device" names where the host replays, `replay=`, and a member
        of a service under a ServiceAffinity policy). It says which
        runs `waveloop.next_step`
        puts into one step, and so which programs they meet (the
        daemon's re-warm deals its warm runs by it)."""
        runs = classify_runs(
            self.config, snap, batch,
            [(int(rep), 0, self.min_run) for rep in reps],
            int(snap.svc_num_values), self.min_run,
            device_zoned=self._device_zoned,
            zoned=bool(np.any(np.asarray(snap.zone_id) > 0)))
        return ["scan" if not run.eligible else "device" if run.device
                else "pure" if run.pure else "single" for run in runs]

    # -- the device seam (models/waveloop.run_wave) --------------------------

    group_floor = 8

    def place(self, buf):
        return buf  # the program that reads it ships it

    def scan_pending(self, wave: Wave, rows: np.ndarray):
        self._settle(wave)
        wave.carry, chosen, last = self.scan_rows(
            wave.static, wave.carry, wave.batch, wave.rep_idx[rows],
            wave.num_zones, wave.num_values)
        return chosen, last

    def _settle(self, wave: Wave) -> None:
        """The waiting fold in a dispatch of its own: no further probe
        will carry it for free."""
        if wave.fold is not None:
            kind, buf, layout, counts = wave.fold
            wave.fold = None
            apply = self._apply_packed if kind == "single" \
                else self._apply_group_packed
            wave.carry = apply(wave.static, wave.carry, buf, layout, counts)

    finish = _settle  # the wave's last fold

    def _take_fold(self, wave: Wave, layout):
        """-> (buf, counts) of the waiting fold where a single run's
        dispatch can carry it (a run's own, or one of its layout), else
        (None, None) with the fold settled apart."""
        if wave.fold is not None and wave.fold[0] == "single" \
                and wave.fold[2] == layout:
            _kind, buf, _layout, counts = wave.fold
            wave.fold = None
            return buf, counts
        self._settle(wave)
        return None, None

    def probe_run(self, wave: Wave, run: Run, layout, buf, J: int,
                  rows: int):
        prev_buf, prev_counts = self._take_fold(wave, layout)
        with phase_timer("probe"):
            self._count("probe")
            wave.carry, tables = self.probe.probe_fused(
                wave.static, wave.carry, prev_buf, prev_counts, buf,
                wave.num_zones, wave.num_values, J, rows, layout,
                self._apply_fn, **wave.table_context(run),
            )
        return tables

    def commit_run(self, wave: Wave, run: Run, layout, buf, counts) -> None:
        wave.fold = ("single", buf, layout, counts)  # rides the next probe

    def probe_group(self, wave: Wave, G_bucket: int, layout, buf):
        prev, wave.fold = wave.fold, None
        with phase_timer("probe"):
            self._count("group_probe")
            wave.carry, headers, usage = self.probe.probe_group(
                wave.static, wave.carry, prev, buf, wave.num_zones,
                wave.num_values, G_bucket, layout, self._apply_fn,
                self._apply_group_fn,
            )
        wave.tallies["group_d2h_bytes"] += headers.nbytes + usage.nbytes
        return headers, usage

    def commit_group(self, wave: Wave, runs, G_bucket: int, layout, buf,
                     counts_mat) -> None:
        cm = np.zeros((G_bucket, counts_mat.shape[1]), np.int64)
        cm[:len(runs)] = counts_mat
        wave.fold = ("group", buf, layout, cm)

    def _count_excluded(self, wave: Wave, runs: Sequence[Run], fits) -> None:
        """`anti_nodes_excluded` for the vetoed ones of `runs`, whose
        first probes found `fits` nodes fit: the program's own count of
        them, no second read."""
        fits = [int(fit) for run, fit in zip(runs, fits)
                if run.veto is not None]
        if fits:
            real = int(np.count_nonzero(np.asarray(wave.snap.alloc_pods) > 0))
            wave.tallies["anti_nodes_excluded"] += real * len(fits) - sum(fits)

    def replay_run_device(self, wave: Wave, run: Run, done0: int) -> None:
        """A device run (`Run.device`) by itself: probe + pick sequence +
        commit fold in one device dispatch (models/zreplay) for every
        table horizon it meets; the run's commits are folded in the
        program."""
        layout, buf = pack_arrays(wave.pod_row(run.rep))
        zone_perm = wave.zone_perm()
        veto = np.zeros(wave.N, bool) if run.veto is None \
            else np.asarray(run.veto)
        veto_perm = np.ascontiguousarray(veto[wave.perm])
        done = done0
        while done < run.length:
            K = run.length - done
            J, rows = pick_j(self.config, self.max_j, wave.snap,
                             wave.batch, run.rep, K)
            K_bucket = replay_k_bucket(K, ZREPLAY_K_FLOOR)
            prev_buf, prev_counts = self._take_fold(wave, layout)
            with phase_timer("replay"):
                self._count("zreplay")
                wave.carry, chosen, _counts, L, n_done = self._zreplay.run(
                    wave.static, wave.carry, prev_buf, prev_counts, buf,
                    layout, wave.num_zones, wave.num_values, J, K_bucket,
                    zone_perm, veto_perm,
                    bool(wave.batch.has_selectors[run.rep]), rows,
                    min(K, K_bucket), wave.L_host,
                )
                with device_wait():
                    chosen = np.asarray(chosen)
                    n_done = int(n_done)
                    L = int(L)
                    steps, rescores = np.asarray(self._zreplay.run_ran)
                    fit = int(self._zreplay.run_fit)
            if done == 0:  # the run's first probe (a group's otherwise)
                self._count_excluded(wave, [run], [fit])
            wave.tallies.update({
                "zreplay_steps": int(steps),
                "zreplay_rescores": int(rescores),
                "zreplay_picks": int((chosen >= 0).sum())})
            if n_done == 0:
                break  # no progress through tables: the scan's
            wave.write(run.start + done, chosen[:n_done])
            wave.L_host = L
            done += n_done
        wave.pending.extend(range(run.start + done, run.stop))

    def replay_group_device(self, wave: Wave, runs: Sequence[Run]):
        """K device runs, ONE fused device dispatch: probe + pick loop +
        commit fold per run inside one outer loop
        (models/zreplay.run_group), carry threaded run to run.
        -> None, or (g, picks done of run g) where it stopped early."""
        G = len(runs)
        # a floor no less than the runs is the bucket
        G_bucket, glayout, gbuf = group_buffer(
            wave.batch, [run.rep for run in runs], floor=replay_g_bucket(G))
        K_bucket = replay_k_bucket(max(run.length for run in runs),
                                   ZREPLAY_GROUP_K_FLOOR)
        zone_perm = wave.zone_perm()
        vetos = np.zeros((G_bucket, wave.N), bool)
        has_sels = np.zeros(G_bucket, bool)
        rows_arr = np.ones(G_bucket, np.int64)
        k_reals = np.zeros(G_bucket, np.int32)
        J_g = 128
        for i, run in enumerate(runs):
            Jr, rows_arr[i] = pick_j(self.config, self.max_j, wave.snap,
                                     wave.batch, run.rep, run.length)
            J_g = max(J_g, Jr)
            k_reals[i] = min(run.length, K_bucket)
            has_sels[i] = bool(wave.batch.has_selectors[run.rep])
            if run.veto is not None:
                vetos[i] = np.asarray(run.veto)[wave.perm]
        prev, wave.fold = wave.fold, None
        with phase_timer("replay"):
            self._count("zreplay_group")
            wave.carry, chosen, n_done, L = self._zreplay.run_group(
                wave.static, wave.carry, prev, gbuf, glayout,
                wave.num_zones, wave.num_values, J_g, K_bucket, G_bucket,
                zone_perm, vetos, has_sels, rows_arr, k_reals, G,
                wave.L_host,
            )
            with device_wait():
                chosen = np.asarray(chosen)
                n_done = np.asarray(n_done)
                wave.L_host = int(L)
                steps, slots, rescores = np.asarray(
                    self._zreplay.group_ran)
                fits = np.asarray(self._zreplay.group_fit)
        self._count_excluded(wave, runs[:int(slots)], fits)
        wave.tallies.update({
            "zreplay_steps": int(steps), "zreplay_slots": int(slots),
            "zreplay_rescores": int(rescores),
            "zreplay_picks": int((chosen >= 0).sum())})
        for i, run in enumerate(runs):
            nd = int(n_done[i])
            wave.write(run.start, chosen[i, :nd])
            if nd < run.length:
                return i, nd
        return None
