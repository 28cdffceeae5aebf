"""Device replay: wave pick sequences as ONE device dispatch.

The host replays (replay.py's C engine and numpy spec) assume scores
decompose into per-node functions of that node's commit count. The
ZONE-blended SelectorSpread breaks that: every commit re-weights a whole
zone, so the C engine can't bucket and the numpy spec pays ~0.4 ms per
pick — a zoned 50k-pod north-star took ~20 s. Here the whole pick
sequence runs ON DEVICE instead: probe + the run's pick steps + the
commit fold in one jitted program, one dispatch, one small transfer out.
K (and the group's G) only size the compiled buffers: the pick loop ends
at the run's real length and the run loop at the group's real run
count, both read from the program's input. Each step
scores with the combined score exactly as models/replay._scores gives it
(same float32/float64 formulas, same NaN -> minInt64 quirk, same
selectHost round-robin in name-desc order) — differentially tested
against the host spec replay and the oracle by tests/test_wave.py — but
evaluates at every step only the float32 spread term, which every commit
moves: the rest of the score, emulated int64 and float64 on the chip, is
carried from step to step and evaluated again when a pick spends it
(_replay_run).

Two entry points share the same probe+replay body:

  * ZReplay.run — one run per dispatch (the original shape), and
  * ZReplay.run_group — up to G runs per dispatch: an OUTER loop carries
    the live device carry across runs, so each run's probe sees every
    earlier run's commits and a 500-template zoned backlog costs ONE
    device round trip instead of 500. A run that trips its table
    horizon ends the loop (n_done reports how far each run got) and the
    host driver resumes from there — output stays bit-identical to the
    serial per-run sequence. On a cluster with inter-pod terms the loop
    carries the tables' per-node views beside the carry (gathered once
    a dispatch, advanced by each run's picks: _run_slots), and a run's
    fold writes its picks into the tables, not every node.

Scope (`models/wave.classify_runs` sets `Run.device`): runs whose
cross-node coupling is the zone blend (the common zoned-cluster case),
and, whatever the zoning, runs the grouped header probe cannot take: a
self-anti veto, an owner of a term, a matcher of a spec, which the host
decides one probe round trip a run and this program a group a dispatch
(`num_zones == 1` blends nothing). ServiceAffinity/ServiceAntiAffinity
dynamics stay on the host spec replay (policy-config scale is smaller).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models.batch import (
    BALANCED_ALLOCATION,
    INTER_POD_AFFINITY,
    LEAST_REQUESTED,
    NODE_AFFINITY,
    SELECTOR_SPREAD,
    TAINT_TOLERATION,
    SchedulerConfig,
    interpod_carry_tables,
    wants_interpod,
)
from kubernetes_tpu.models.probe import N_STK_ROWS, _probe_rows
from kubernetes_tpu.ops import interpod as IP


def _weights(config: SchedulerConfig):
    w = {n if isinstance(n, str) else n[0]: wt
         for n, wt in config.priorities}
    return (int(w.get(SELECTOR_SPREAD, 0)), int(w.get(NODE_AFFINITY, 0)),
            int(w.get(TAINT_TOLERATION, 0)),
            int(w.get(INTER_POD_AFFINITY, 0)))


def _round_robin(L, n, N):
    """selectHost's L % n as int32, for the count L >= 0 (int64) of pods
    placed so far and 1 <= n <= N ties (int32). An int64 remainder is
    some 1,800 scalar instructions of emulated long division on the
    chip, at every pick; under 2^16 nodes the same remainder comes from
    L's two 32-bit halves, hi * 2^32 + lo, reduced mod n one by one
    (every product stays under n * (n - 1) < 2^32)."""
    if N >= 1 << 16:
        return (L % n).astype(jnp.int32)
    n = n.astype(jnp.uint32)
    hi = (L >> 32).astype(jnp.uint32)
    lo = L.astype(jnp.uint32)  # the low half: the conversion wraps
    two32 = (jnp.uint32(0xFFFFFFFF) % n + 1) % n  # 2^32 mod n
    return (((hi % n) * two32 + lo % n) % n).astype(jnp.int32)


def _name_desc_tables(static):
    """What a replay program needs of the name-desc permutation, made
    ONCE a program and outside its run loop: the permutation, its
    inverse (a run's commit counts go back to node order by a gather;
    a scatter by `perm` is one serial update a node on the chip) and
    the two allocatable rows LeastRequested / BalancedResourceAllocation
    read, permuted."""
    perm = static["name_desc_order"].astype(jnp.int32)
    N = perm.shape[0]
    inv = jnp.zeros((N,), jnp.int32).at[perm].set(
        jnp.arange(N, dtype=jnp.int32), unique_indices=True)
    return perm, inv, (static["alloc_mcpu"][perm],
                       static["alloc_mem"][perm])


#: the picks one step of `_advance_views` takes at once
VIEW_PICKS = 64


def _carried_views(config, static, carry):
    """The carry's five inter-pod views and the node axis' domain ids
    they are gathered at (ops/interpod.Views, lt_domains), made ONCE a
    dispatch: a gather costs by its index vectors, LT x N of them a
    table, so the four owned-term tables are read by one gather
    (gather_lt_many). (None, None) where the cluster has no terms or
    the config reads none: nothing to carry, and the program is the one
    it was."""
    if not (static["ip_lt_u"].shape[0] and wants_interpod(config)):
        return None, None
    where = (static["ip_u_topo"], static["ip_topo_dom"], static["ip_lt_u"])
    views = IP.Views(
        interpod_carry_tables(static, carry[4], carry[0].shape[1]),
        *IP.gather_lt_many(carry[5:9], *where, static["ip_lt_sign"]))
    return views, IP.lt_domains(*where)


def _advance_views(static, width, dom_lt, views, pod, nodes, placed, ran):
    """The views after one pod's picks `nodes` (i32[K], where `placed`;
    none past the first `ran`): each pick's increment
    (ops/interpod.interpod_commit_views), VIEW_PICKS of them summed at a
    time, which is what gathering the tables anew behind the fold would
    read, bit for bit: integer sums in the views' own dtypes."""
    C = min(nodes.shape[0], VIEW_PICKS)  # both powers of two
    zero = jax.tree.map(jnp.zeros_like, views)

    def increment(node, ok):
        return IP.interpod_commit_views(
            zero, dom_lt, width, static["ip_u_spec"], static["ip_lt_u"],
            static["ip_lt_sign"], pod["ip_match_spec"], pod["ip_own_hard"],
            pod["ip_own_pref"], pod["ip_own_anti_hard"],
            pod["ip_own_anti_pref"], node, ok)

    def some(c, views):
        inc = jax.vmap(increment)(
            jax.lax.dynamic_slice_in_dim(nodes, c * C, C),
            jax.lax.dynamic_slice_in_dim(placed, c * C, C))
        return IP.Views(*(v + d.sum(axis=0) for v, d in zip(views, inc)))

    return jax.lax.fori_loop(0, (ran + C - 1) // C, some, views)


def _fold_run(apply_fn, static, carry, pod, perm, inv, j, picks, by_picks):
    """A run's commits into the carry -> (carry', counts i64[N] in node
    order, the picks as (nodes i32[K], placed bool[K]) or None). `j` are
    the commit counts in name-desc order, `picks` the same commits one
    by one. `by_picks` (terms live): the fold takes the picks beside the
    counts, so the inter-pod tables pay an update a pick and not one a
    node."""
    counts = j[inv].astype(jnp.int64)
    if not by_picks:
        return apply_fn(static, carry, pod, counts), counts, None
    at = (perm[jnp.maximum(picks, 0)], picks >= 0)
    return apply_fn(static, carry, pod, counts, at), counts, at


def _replay_run(config, num_zones, num_values, J, K, static, carry, pod,
                perm, alloc, zone_id, veto, has_selectors, rows_dyn,
                k_real, L0, views=None):
    """Probe `pod` against the live carry (and its inter-pod `views`,
    where the caller carries them), then one pick step per pod of
    the run: the loop ends at k_real (<= K) or at a table-horizon bail.

    A step scores in emulated 64-bit only what its pick changed. The
    score is `base` (everything but the spread term: int64 and float64,
    a function of the commit counts j and the fit mask alone) plus the
    float32 spread term (which moves with every commit through the zone
    sums). So the picks run in EPOCHS: an epoch evaluates `cur` = base at
    j and `nxt` = base at j + 1 for all nodes, and its steps carry
    `cur`: a pick of node m moves cur[m] to nxt[m] and marks m used. An
    epoch ends when nxt[m] was spent already (m picked twice in it) or
    m left the fit set while it HELD an extreme that NodeAffinity /
    TaintToleration / InterPodAffinity normalise by (`holds_extreme`:
    base depends on the fit set through those extremes alone, and
    within a run the fit set only shrinks), and the next one evaluates
    again. Same functions on the same inputs as a step that evaluated
    all of it: the same integers. Where picks spread over the nodes a
    run is one epoch, with the self-anti veto too (every pick of such a
    run takes its node out of the fit set); where they pile on one node
    an epoch is two steps, which costs what the per-step evaluation
    did.

    A step takes its pick as a MASK over the nodes and makes every
    update a select on it (the commit count, the fit bit, the zone sums,
    `cur`): on the chip a read or a write of one element at a traced
    index is an op of its own with a gap behind it, and a step that
    kept its books at node m made some twenty of them.

    zone_id/veto/alloc are PERMUTED to name-desc order already. Returns
    (j i32[N] permuted-space commit counts, chosen i32[K] permuted-space
    ids, -1 past the last step, L, n_done, bailed, the steps run, the
    epochs after the first: the rescores, and how many nodes fit before
    the first pick: what the cluster left the run)."""
    stk, _tab = _probe_rows(config, num_zones, num_values, J, static,
                            carry, pod, views)
    N = perm.shape[0]
    # ONE gather by the permutation: the header rows and, behind them,
    # the two usage rows LR/BA read (a gather costs by its indices, not
    # by the rows it moves)
    stk = jnp.concatenate([stk, carry[0][3:5]])[:, perm]
    fit_static = stk[0] != 0
    frontier = stk[1]
    static_add = stk[2]
    spread_base = stk[3]
    selfmatch = stk[4][0] > 0
    na_counts = stk[5]
    tt_counts = stk[6]
    ip_totals = stk[7]
    nz_cpu0 = stk[N_STK_ROWS]
    nz_mem0 = stk[N_STK_ROWS + 1]
    alloc_cpu, alloc_mem = alloc
    # LR/BA scores are computed directly (int math, exactly the
    # j-table's contents — R.least_requested/balanced mirror): cheaper
    # on TPU than a variable-row gather from the packed table
    from kubernetes_tpu.ops import priorities as R

    w_lr = w_ba = 0
    for name, wt in config.priorities:
        if name == LEAST_REQUESTED:
            w_lr += int(wt)
        elif name == BALANCED_ALLOCATION:
            w_ba += int(wt)
    # the veto (hostname self-anti): one committed copy per node
    frontier = jnp.where(veto, jnp.minimum(frontier, 1), frontier)
    w_spread, w_na, w_tt, w_ip = _weights(config)

    fit0 = fit_static & (0 < frontier)
    # which nodes lie in which zone, and which zones are zones (0 is
    # "no zone"): made once a run, read at every step
    in_zone = zone_id[None, :] == jnp.arange(
        num_zones, dtype=zone_id.dtype)[:, None]
    is_zone = np.arange(num_zones) > 0

    def zone_sums(counts):
        """ops/priorities.zone_sums' masked reduction (a scatter-add by
        zone_id is one serial update a node on the chip), over the
        run's own membership."""
        return jnp.where(in_zone, counts[None, :], 0).sum(axis=1)

    def base_pair(j, fit):
        """-> (i64[N], i64[N]): the score without its spread term, all
        nodes, at the commit counts j and at j + 1 (one evaluation over
        both: the program holds the division chains once)."""
        score = static_add
        # The na/tt/ip normalizers keep the host's EXACT float64
        # expression shapes (replay._scores): integer-division rewrites
        # are NOT equivalent under double rounding — TaintToleration's
        # (1.0 - c/mx)*10.0 truncates to 0 where (10*(mx-c))//mx gives 1
        # (e.g. mx=20, c=18), a divergence an adversarial review repro
        # caught. float64 is emulated on TPU, which is why it is
        # evaluated when the fit set changes and not at every step.
        if w_na:
            mx = jnp.maximum(na_counts.max(where=fit, initial=0), 0)
            f = jnp.where(
                mx > 0,
                10.0 * (na_counts.astype(jnp.float64)
                        / mx.astype(jnp.float64)),
                jnp.float64(0.0),
            )
            score = score + w_na * f.astype(jnp.int64)
        if w_tt:
            mx = jnp.maximum(tt_counts.max(where=fit, initial=0), 0)
            f = jnp.where(
                mx > 0,
                (1.0 - tt_counts.astype(jnp.float64)
                 / mx.astype(jnp.float64)) * 10.0,
                jnp.float64(10.0),
            )
            score = score + w_tt * f.astype(jnp.int64)
        if w_ip:
            big = jnp.int64(2**62)
            mx = jnp.maximum(
                ip_totals.max(where=fit, initial=-big), 0
            )
            mn = jnp.minimum(
                ip_totals.min(where=fit, initial=big), 0
            )
            rng = mx - mn
            f = jnp.where(
                rng > 0,
                10.0 * ((ip_totals - mn).astype(jnp.float64)
                        / rng.astype(jnp.float64)),
                jnp.float64(0.0),
            )
            score = score + w_ip * jnp.where(
                fit, f.astype(jnp.int64), 0
            )
        if not (w_lr or w_ba):
            return score, score
        # both commit counts side by side on the node axis (a [2, N]
        # stack would take the chip's tiles a quarter full)
        def twice(x):
            return jnp.concatenate([x, x])

        jj = jnp.concatenate([j, j + 1])
        usage = (pod["nz_mcpu"], pod["nz_mem"],
                 twice(nz_cpu0) + jj * pod["nz_mcpu"],
                 twice(nz_mem0) + jj * pod["nz_mem"],
                 twice(alloc_cpu), twice(alloc_mem))
        lrba = jnp.zeros((2 * N,), jnp.int64)
        if w_lr:
            lrba = lrba + jnp.int64(w_lr) * R.least_requested(*usage)
        if w_ba:
            lrba = lrba + jnp.int64(w_ba) * \
                R.balanced_resource_allocation(*usage)
        return score + lrba[:N], score + lrba[N:]

    def holds_extreme(fit):
        """-> bool[N]: the nodes whose leaving `fit` may move one of the
        extremes base_pair normalises by, which are all it reads of the
        fit set: a node that holds the NodeAffinity / TaintToleration
        maximum or the inter-pod totals' maximum or minimum, where the
        clip at 0 does not hold it in place anyway. While none of them
        leaves, the extremes and their holders stay what they were.
        Two nodes that tie for an extreme both hold it: never
        optimistic."""
        holds = jnp.zeros((N,), bool)
        if w_na:
            mx = na_counts.max(where=fit, initial=0)
            holds = holds | ((mx > 0) & (na_counts == mx))
        if w_tt:
            mx = tt_counts.max(where=fit, initial=0)
            holds = holds | ((mx > 0) & (tt_counts == mx))
        if w_ip:
            big = jnp.int64(2**62)
            mx = ip_totals.max(where=fit, initial=-big)
            mn = ip_totals.min(where=fit, initial=big)
            holds = holds | ((mx > 0) & (ip_totals == mx)) \
                | ((mn < 0) & (ip_totals == mn))
        return holds

    def counted(j, fit):
        """What SelectorSpread counts on each node that fits."""
        return jnp.where(
            fit, spread_base + jnp.where(selfmatch, j, 0).astype(jnp.int64),
            0)

    def spread(j, fit, zc):
        """The weighted SelectorSpread term (float32 as upstream): it
        moves with every commit, through M and the zone sums."""
        cm = counted(j, fit)
        M = jnp.maximum(cm.max(where=fit, initial=0), 0)
        f = jnp.where(
            M > 0,
            jnp.float32(10.0) * ((M - cm).astype(jnp.float32)
                                 / M.astype(jnp.float32)),
            jnp.float32(10.0),
        )
        zoned = num_zones > 1
        if zoned:
            # zc is maintained INCREMENTALLY in the loop state (a
            # full scatter-add per step serializes on TPU)
            have_zones = (fit & (zone_id > 0)).any()
            max_zone = jnp.where(is_zone, zc, 0).max(initial=0)
            # a zone's score once a zone, then dealt to its nodes: the
            # same float32 values as the expression over zc[zone_id]
            zone_score = (jnp.float32(10.0) * (
                (max_zone - zc).astype(jnp.float32)
                / max_zone.astype(jnp.float32)
            ))[zone_id]
            blended = (f * jnp.float32(1.0 / 3.0)
                       + jnp.float32(2.0 / 3.0) * zone_score)
            f = jnp.where(have_zones & (zone_id > 0), blended, f)
        f = jnp.where(has_selectors, f, jnp.float32(10.0))
        nan = jnp.isnan(f)
        fi = jnp.where(nan, jnp.float32(0), f).astype(jnp.int64)
        return w_spread * jnp.where(nan, jnp.int64(-(2**63)), fi)

    def more(state):
        i, stopped = state[0], state[6]
        return (i < k_real) & ~stopped

    def epoch(state):
        """Evaluate the carried score at j and at j + 1, then pick
        until a pick spends the evaluation or the run ends."""
        i0 = state[0]
        cur0, nxt = base_pair(state[1].astype(jnp.int64), state[2])
        spends = holds_extreme(state[2])

        def step(state):
            (i, j, fit, zc, L, n_done, stopped, chosen, cur, used,
             _stale) = state
            sched = fit.any()
            score = cur + spread(j, fit, zc) if w_spread else cur
            smax = jnp.where(fit, score, jnp.int64(-(2**63))).max()
            ties = fit & (score == smax)
            num_ties = jnp.maximum(ties.sum(dtype=jnp.int32), 1)
            r = _round_robin(L, num_ties, N)
            tie_rank = jnp.cumsum(ties.astype(jnp.int32)) - 1
            # the pick as a mask: one node where any fits, none where
            # none does (then nothing below moves)
            pick = ties & (tie_rank == r)
            m = jnp.argmax(pick).astype(jnp.int32)
            j_new = j + pick.astype(jnp.int32)
            fit_new = jnp.where(
                pick, fit_static & (j_new < frontier), fit)
            # the zone sums move by what the picked node counted before
            # and counts now
            zc = zc + zone_sums(jnp.where(
                pick, counted(j_new, fit_new) - counted(j, fit), 0))
            L = L + sched.astype(jnp.int64)
            # the bail ends the loop: at most one ever fires
            bail = (pick & (j_new >= rows_dyn)).any()
            n_done = jnp.where(bail, i + 1, n_done)
            chosen = chosen.at[i].set(jnp.where(sched, m, jnp.int32(-1)))
            # the picked node moved to its next commit count, where the
            # carried score is nxt's: unless that was spent already (the
            # node picked before in this epoch) or the node left the fit
            # set holding one of the normalisers' extremes — then the
            # epoch ends
            stale = (pick & (used | (spends & (fit_new != fit)))).any()
            return (i + 1, j_new, fit_new, zc, L, n_done, stopped | bail,
                    chosen, jnp.where(pick, nxt, cur), used | pick, stale)

        def fresh(state):
            return more(state) & ~state[10]

        out = jax.lax.while_loop(
            fresh, step,
            (*state[:8], cur0, jnp.zeros((N,), bool), jnp.bool_(False)),
        )
        # a run's first epoch is no rescore
        return (*out[:8], state[8] + (i0 > 0).astype(jnp.int32))

    zc0 = zone_sums(counted(jnp.int32(0), fit0))
    k_real = k_real.astype(jnp.int32)
    steps, j, _fit, _zc, L, n_done, stopped, chosen, rescores = \
        jax.lax.while_loop(more, epoch, (
            jnp.int32(0), jnp.zeros((N,), jnp.int32), fit0, zc0,
            jnp.int64(L0), k_real, jnp.bool_(False),
            jnp.full((K,), -1, jnp.int32), jnp.int32(0),
        ))
    return (j, chosen, L, n_done, stopped, steps, rescores,
            fit0.sum(dtype=jnp.int32))


@jax.named_scope("zreplay")
def _zreplay_fn(config, num_zones, num_values, J, K, layout, apply_fn,
                fold_prev, static, carry, prev_buf, prev_counts,
                pod_buf, zone_id, veto, has_selectors, rows_dyn, k_real,
                L0):
    """probe + device replay of k_real (<= K) picks + commit fold, one
    program.

    zone_id/veto are PERMUTED to name-desc order already; probe rows are
    permuted inside. Returns (carry', chosen[K] permuted-space ids,
    counts[N] node-order, L', n_done, ran i32[2]: the pick steps the
    loop ran and those that evaluated the carried score again, fit i32:
    the nodes that fit at the probe)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    if fold_prev:
        prev_pod = _unpack_pod(layout, prev_buf)
        carry = apply_fn(static, carry, prev_pod, prev_counts)
    pod = _unpack_pod(layout, pod_buf)
    perm, inv, alloc = _name_desc_tables(static)
    views, _dom_lt = _carried_views(config, static, carry)
    j, chosen, L, n_done, _stopped, steps, rescores, fit = _replay_run(
        config, num_zones, num_values, J, K, static, carry, pod, perm,
        alloc, zone_id, veto, has_selectors, rows_dyn, k_real, L0, views,
    )
    # permuted j -> node-order counts; fold THIS run's commits
    carry, counts, _at = _fold_run(
        apply_fn, static, carry, pod, perm, inv, j, chosen,
        views is not None)
    return (carry, chosen, counts, L, n_done, jnp.stack([steps, rescores]),
            fit)


def _run_slots(config, num_zones, num_values, J, K, G, apply_fn, static,
               carry, pods, zone_id, vetos, has_sels, rows_arr, k_reals,
               runs, L0):
    """The run-slot loop of the grouped replay: probe + replay + fold
    for each of the first `runs` rows of `pods` (every field with a
    leading G axis), the carry and its inter-pod views threaded from
    slot to slot -> (carry', views' or None, chosen[G, K], n_done[G],
    L', ran i32[3], fits i32[G]: the nodes that fit at each slot's
    probe, 0 for a slot the loop never entered)."""
    perm, inv, alloc = _name_desc_tables(static)
    views, dom_lt = _carried_views(config, static, carry)

    def run_body(state):
        (g, carry, views, L, _bailed, chosen, n_done, steps,
         rescores, fits) = state
        pod = {f: v[g] for f, v in pods.items()}
        j, picks, L, done, bailed, ran, rescored, fit = _replay_run(
            config, num_zones, num_values, J, K, static, carry, pod,
            perm, alloc, zone_id, vetos[g], has_sels[g], rows_arr[g],
            k_reals[g], L, views,
        )
        carry, _counts, at = _fold_run(
            apply_fn, static, carry, pod, perm, inv, j, picks,
            views is not None)
        if views is not None:
            views = _advance_views(static, carry[5].shape[2], dom_lt,
                                   views, pod, *at, ran)
        return (g + 1, carry, views, L, bailed, chosen.at[g].set(picks),
                n_done.at[g].set(done), steps + ran,
                rescores + rescored, fits.at[g].set(fit))

    def more(state):
        g, bailed = state[0], state[4]
        return (g < runs) & ~bailed

    (slots, carry, views, L, _bailed, chosen, n_done, steps,
     rescores, fits) = jax.lax.while_loop(
        more, run_body,
        (jnp.int32(0), carry, views, jnp.int64(L0), jnp.bool_(False),
         jnp.full((G, K), -1, jnp.int32), jnp.zeros((G,), jnp.int32),
         jnp.int32(0), jnp.int32(0), jnp.zeros((G,), jnp.int32)),
    )
    return (carry, views, chosen, n_done, L,
            jnp.stack([steps, slots, rescores]), fits)


@jax.named_scope("zreplay")
def _zreplay_group_fn(config, num_zones, num_values, J, K, G, layout,
                      apply_fn, prev_kind, prev_layout, apply_group_fn,
                      static, carry, prev_buf, prev_counts, group_buf,
                      zone_id, vetos, has_sels, rows_arr, k_reals, runs,
                      L0):
    """The group's `runs` (<= G) runs — probe + replay + fold each — in
    ONE device program: an outer loop threads the carry run to run, so
    every probe sees the earlier runs' commits exactly as the serial
    per-run loop would. A table-horizon bail ends the loop (the runs
    behind it, like the slots past `runs`, keep n_done == 0 and picks of
    -1); the host resumes from there. Returns (carry', chosen[G, K],
    n_done[G], L', ran i32[3]: the pick steps and the run-slot
    iterations the two loops ran, and the steps that evaluated the
    carried score again, fits i32[G]: the nodes that fit at each run
    slot's probe)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    if prev_kind == "single":
        carry = apply_fn(static, carry,
                         _unpack_pod(prev_layout, prev_buf), prev_counts)
    elif prev_kind == "group":
        carry = apply_group_fn(prev_layout, static, carry, prev_buf,
                               prev_counts)
    pods = _unpack_pod(layout, group_buf)  # each field: leading G axis
    carry, _views, chosen, n_done, L, ran, fits = _run_slots(
        config, num_zones, num_values, J, K, G, apply_fn, static, carry,
        pods, zone_id, vetos, has_sels, rows_arr, k_reals, runs, L0)
    return carry, chosen, n_done, L, ran, fits


#: the deferred fold's operands where none rides the dispatch (host
#: arrays: an argument's transfer, where `jnp.zeros` was a program of
#: its own to launch before every dispatch)
_NO_BUF = np.zeros(0, np.uint8)
_NO_COUNTS = np.zeros(0, np.int64)


class ZReplay:
    """Compile cache for the fused probe+replay+fold programs. A
    dispatch hands its host-made operands over as numpy values: they
    ride the call's own transfer, and no conversion program runs
    before it."""

    def __init__(self, config: SchedulerConfig, apply_fn,
                 apply_group_fn=None):
        self.config = config
        self.apply_fn = apply_fn
        self.apply_group_fn = apply_group_fn
        self._jitted = {}
        #: i32[3] on the device: the pick steps and run-slot iterations
        #: the last run_group dispatch ran and the steps that rescored
        #: (its return stays the four values its callers unpack)
        self.group_ran = None
        #: i32[2] on the device: the last run dispatch's pick steps and
        #: those that rescored
        self.run_ran = None
        #: i32[G] / i32 on the device: the nodes that fit at each run
        #: slot's probe of the last run_group dispatch, at the probe of
        #: the last run dispatch
        self.group_fit = None
        self.run_fit = None

    def run(self, static, carry, prev_buf, prev_counts, pod_buf, layout,
            num_zones, num_values, J, K_bucket, zone_id_perm, veto_perm,
            has_selectors, rows, k_real, L0):
        fold_prev = prev_buf is not None
        key = (num_zones, num_values, J, K_bucket, layout, fold_prev)
        fn = self._jitted.get(key)
        if fn is None:
            zreplay_run = functools.partial(
                _zreplay_fn, self.config, num_zones, num_values, J,
                K_bucket, layout, self.apply_fn, fold_prev,
            )
            zreplay_run.__name__ = "zreplay_run"  # jit_zreplay_run
            fn = jax.jit(zreplay_run)
            self._jitted[key] = fn
        if not fold_prev:
            prev_buf, prev_counts = _NO_BUF, _NO_COUNTS
        carry, chosen, counts, L, n_done, self.run_ran, self.run_fit = fn(
            static, carry, prev_buf, prev_counts, pod_buf,
            zone_id_perm, veto_perm,
            np.bool_(has_selectors), np.int64(rows), np.int32(k_real),
            np.int64(L0),
        )
        return carry, chosen, counts, L, n_done

    def run_group(self, static, carry, prev, group_buf, layout,
                  num_zones, num_values, J, K_bucket, G,
                  zone_id_perm, vetos_perm, has_sels, rows_arr, k_reals,
                  runs, L0):
        """-> (carry', chosen i32[G, K_bucket] permuted-space,
        n_done i32[G], L'), `runs` (<= G) being the group's real run
        count. `prev` is a deferred fold riding this dispatch: None or
        (kind, buf, layout, counts)."""
        prev_kind = prev_layout = None
        prev_buf = prev_counts = None
        if prev is not None:
            prev_kind, prev_buf, prev_layout, prev_counts = prev
        key = ("group", num_zones, num_values, J, K_bucket, G, layout,
               prev_kind, prev_layout)
        fn = self._jitted.get(key)
        if fn is None:
            zreplay_group = functools.partial(
                _zreplay_group_fn, self.config, num_zones, num_values,
                J, K_bucket, G, layout, self.apply_fn, prev_kind,
                prev_layout, self.apply_group_fn,
            )
            zreplay_group.__name__ = "zreplay_group"  # jit_zreplay_group
            fn = jax.jit(zreplay_group)
            self._jitted[key] = fn
        if prev_kind is None:
            prev_buf, prev_counts = _NO_BUF, _NO_COUNTS
        carry, chosen, n_done, L, self.group_ran, self.group_fit = fn(
            static, carry, prev_buf, prev_counts, group_buf,
            zone_id_perm, vetos_perm, has_sels, rows_arr, k_reals,
            np.int32(runs), np.int64(L0),
        )
        return carry, chosen, n_done, L
