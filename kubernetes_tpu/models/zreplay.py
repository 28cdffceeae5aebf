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
reassembles the combined score exactly as models/replay._scores (same
float32/float64 formulas, same NaN -> minInt64 quirk, same selectHost
round-robin in name-desc order) — differentially tested against the
host spec replay and the oracle by tests/test_wave.py.

Two entry points share the same probe+replay body:

  * ZReplay.run — one run per dispatch (the original shape), and
  * ZReplay.run_group — up to G runs per dispatch: an OUTER loop carries
    the live device carry across runs, so each run's probe sees every
    earlier run's commits and a 500-template zoned backlog costs ONE
    device round trip instead of 500. A run that trips its table
    horizon ends the loop (n_done reports how far each run got) and the
    host driver resumes from there — output stays bit-identical to the
    serial per-run sequence.

Scope: runs whose only cross-node coupling is the zone blend (the
common zoned-cluster case). ServiceAffinity/ServiceAntiAffinity
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
)
from kubernetes_tpu.models.probe import _probe_rows


def _weights(config: SchedulerConfig):
    w = {n if isinstance(n, str) else n[0]: wt
         for n, wt in config.priorities}
    return (int(w.get(SELECTOR_SPREAD, 0)), int(w.get(NODE_AFFINITY, 0)),
            int(w.get(TAINT_TOLERATION, 0)),
            int(w.get(INTER_POD_AFFINITY, 0)))


def _replay_run(config, num_zones, num_values, J, K, static, carry, pod,
                zone_id, veto, has_selectors, rows_dyn, k_real, L0):
    """Probe `pod` against the live carry, then one pick step per pod of
    the run: the loop ends at k_real (<= K) or at a table-horizon bail.

    zone_id/veto are PERMUTED to name-desc order already. Returns
    (j i64[N] permuted-space commit counts, chosen i32[K] permuted-space
    ids, -1 past the last step, L, n_done, bailed, the steps run)."""
    stk, _tab = _probe_rows(config, num_zones, num_values, J, static,
                            carry, pod)
    perm = static["name_desc_order"].astype(jnp.int32)
    N = perm.shape[0]
    stk = stk[:, perm]
    fit_static = stk[0] != 0
    frontier = stk[1]
    static_add = stk[2]
    spread_base = stk[3]
    selfmatch = stk[4][0] > 0
    na_counts = stk[5]
    tt_counts = stk[6]
    ip_totals = stk[7]
    # LR/BA scores are recomputed directly per step (int math, exactly
    # the j-table's contents — R.least_requested/balanced mirror):
    # cheaper on TPU than a variable-row gather from the packed table
    from kubernetes_tpu.ops import priorities as R

    w_lr = w_ba = 0
    for name, wt in config.priorities:
        if name == LEAST_REQUESTED:
            w_lr += int(wt)
        elif name == BALANCED_ALLOCATION:
            w_ba += int(wt)
    res = carry[0]  # (6, N) node-order
    nz_cpu0 = res[3][perm]
    nz_mem0 = res[4][perm]
    alloc_cpu = static["alloc_mcpu"][perm]
    alloc_mem = static["alloc_mem"][perm]
    # the veto (hostname self-anti): one committed copy per node
    frontier = jnp.where(veto, jnp.minimum(frontier, 1), frontier)
    w_spread, w_na, w_tt, w_ip = _weights(config)

    fit0 = fit_static & (0 < frontier)

    def scores(j, fit, zc):
        score = static_add
        if w_lr or w_ba:
            nzj_cpu = nz_cpu0 + j * pod["nz_mcpu"]
            nzj_mem = nz_mem0 + j * pod["nz_mem"]
            if w_lr:
                score = score + jnp.int64(w_lr) * R.least_requested(
                    pod["nz_mcpu"], pod["nz_mem"], nzj_cpu, nzj_mem,
                    alloc_cpu, alloc_mem,
                )
            if w_ba:
                score = score + jnp.int64(w_ba) * \
                    R.balanced_resource_allocation(
                        pod["nz_mcpu"], pod["nz_mem"], nzj_cpu, nzj_mem,
                        alloc_cpu, alloc_mem,
                    )
        if w_spread:
            c = spread_base + jnp.where(selfmatch, j, 0)
            M = jnp.maximum(c.max(where=fit, initial=0), 0)
            cm = jnp.where(fit, c, 0)
            f = jnp.where(
                M > 0,
                jnp.float32(10.0) * ((M - cm).astype(jnp.float32)
                                     / M.astype(jnp.float32)),
                jnp.float32(10.0),
            )
            zoned = num_zones > 1
            if zoned:
                # zc is maintained INCREMENTALLY in the scan state (a
                # full scatter-add per step serializes on TPU)
                have_zones = (fit & (zone_id > 0)).any()
                max_zone = jnp.where(
                    jnp.arange(num_zones) > 0, zc, 0
                ).max(initial=0)
                zone_score = jnp.float32(10.0) * (
                    (max_zone - zc[zone_id]).astype(jnp.float32)
                    / max_zone.astype(jnp.float32)
                )
                blended = (f * jnp.float32(1.0 / 3.0)
                           + jnp.float32(2.0 / 3.0) * zone_score)
                f = jnp.where(have_zones & (zone_id > 0), blended, f)
            f = jnp.where(has_selectors, f, jnp.float32(10.0))
            nan = jnp.isnan(f)
            fi = jnp.where(nan, jnp.float32(0), f).astype(jnp.int64)
            score = score + w_spread * jnp.where(
                nan, jnp.int64(-(2**63)), fi
            )
        # The na/tt/ip normalizers keep the host's EXACT float64
        # expression shapes (replay._scores): integer-division rewrites
        # are NOT equivalent under double rounding — TaintToleration's
        # (1.0 - c/mx)*10.0 truncates to 0 where (10*(mx-c))//mx gives 1
        # (e.g. mx=20, c=18), a divergence an adversarial review repro
        # caught. float64 is emulated on TPU but measured negligible
        # here; the scan's cost was the per-step zone scatter.
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
        return score

    def step(state):
        i, j, fit, zc, L, n_done, stopped, chosen = state
        sched = fit.any()
        score = scores(j, fit, zc)
        smax = jnp.where(fit, score, jnp.int64(-(2**63))).max()
        ties = fit & (score == smax)
        num_ties = jnp.maximum(ties.sum(), 1)
        r = (L % num_ties).astype(jnp.int32)
        tie_rank = jnp.cumsum(ties.astype(jnp.int32)) - 1
        m = jnp.argmax(ties & (tie_rank == r)).astype(jnp.int32)
        # zone-count bookkeeping around the commit (only column m moves)
        sm = jnp.where(selfmatch, jnp.int64(1), jnp.int64(0))
        c_old_m = spread_base[m] + sm * j[m]
        contrib_old = jnp.where(fit[m], c_old_m, 0)
        j = j.at[m].add(jnp.where(sched, 1, 0))
        L = L + sched.astype(jnp.int64)
        jm = j[m]
        # the bail ends the loop: at most one ever fires
        bail = sched & (jm >= rows_dyn)
        n_done = jnp.where(bail, i + 1, n_done)
        new_fit_m = fit_static[m] & (jm < frontier[m])
        fit = fit.at[m].set(jnp.where(sched, new_fit_m, fit[m]))
        c_new_m = spread_base[m] + sm * jm
        contrib_new = jnp.where(fit[m], c_new_m, 0)
        zc = zc.at[zone_id[m]].add(
            jnp.where(sched, contrib_new - contrib_old, 0)
        )
        chosen = chosen.at[i].set(jnp.where(sched, m, jnp.int32(-1)))
        return i + 1, j, fit, zc, L, n_done, stopped | bail, chosen

    def more(state):
        i, stopped = state[0], state[6]
        return (i < k_real) & ~stopped

    zc0 = jnp.zeros((num_zones,), jnp.int64).at[zone_id].add(
        jnp.where(fit0, spread_base, 0)
    )
    k_real = k_real.astype(jnp.int32)
    state0 = (
        jnp.int32(0), jnp.zeros((N,), jnp.int64), fit0, zc0,
        jnp.int64(L0), k_real, jnp.bool_(False),
        jnp.full((K,), -1, jnp.int32),
    )
    steps, j, _fit, _zc, L, n_done, stopped, chosen = jax.lax.while_loop(
        more, step, state0
    )
    return j, chosen, L, n_done, stopped, steps


@jax.named_scope("zreplay")
def _zreplay_fn(config, num_zones, num_values, J, K, layout, apply_fn,
                fold_prev, static, carry, prev_buf, prev_counts,
                pod_buf, zone_id, veto, has_selectors, rows_dyn, k_real,
                L0):
    """probe + device replay of k_real (<= K) picks + commit fold, one
    program.

    zone_id/veto are PERMUTED to name-desc order already; probe rows are
    permuted inside. Returns (carry', chosen[K] permuted-space ids,
    counts[N] node-order, L', n_done)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    if fold_prev:
        prev_pod = _unpack_pod(layout, prev_buf)
        carry = apply_fn(static, carry, prev_pod, prev_counts)
    pod = _unpack_pod(layout, pod_buf)
    perm = static["name_desc_order"].astype(jnp.int32)
    N = perm.shape[0]
    j, chosen, L, n_done, _stopped, _steps = _replay_run(
        config, num_zones, num_values, J, K, static, carry, pod,
        zone_id, veto, has_selectors, rows_dyn, k_real, L0,
    )
    # permuted j -> node-order counts; fold THIS run's commits
    counts = jnp.zeros((N,), jnp.int64).at[perm].set(j)
    carry = apply_fn(static, carry, pod, counts)
    return carry, chosen, counts, L, n_done


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
    n_done[G], L', ran i32[2]: the pick steps and the run-slot
    iterations the two loops ran)."""
    from kubernetes_tpu.models.pack import unpack as _unpack_pod

    if prev_kind == "single":
        carry = apply_fn(static, carry,
                         _unpack_pod(prev_layout, prev_buf), prev_counts)
    elif prev_kind == "group":
        carry = apply_group_fn(prev_layout, static, carry, prev_buf,
                               prev_counts)
    pods = _unpack_pod(layout, group_buf)  # each field: leading G axis
    perm = static["name_desc_order"].astype(jnp.int32)
    N = perm.shape[0]

    def run_body(state):
        g, carry, L, _bailed, chosen, n_done, steps = state
        pod = {f: v[g] for f, v in pods.items()}
        j, picks, L, done, bailed, ran = _replay_run(
            config, num_zones, num_values, J, K, static, carry, pod,
            zone_id, vetos[g], has_sels[g], rows_arr[g], k_reals[g], L,
        )
        counts = jnp.zeros((N,), jnp.int64).at[perm].set(j)
        carry = apply_fn(static, carry, pod, counts)
        return (g + 1, carry, L, bailed, chosen.at[g].set(picks),
                n_done.at[g].set(done), steps + ran)

    def more(state):
        g, bailed = state[0], state[3]
        return (g < runs) & ~bailed

    slots, carry, L, _bailed, chosen, n_done, steps = jax.lax.while_loop(
        more, run_body,
        (jnp.int32(0), carry, jnp.int64(L0), jnp.bool_(False),
         jnp.full((G, K), -1, jnp.int32), jnp.zeros((G,), jnp.int32),
         jnp.int32(0)),
    )
    return carry, chosen, n_done, L, jnp.stack([steps, slots])


class ZReplay:
    """Compile cache for the fused probe+replay+fold programs."""

    def __init__(self, config: SchedulerConfig, apply_fn,
                 apply_group_fn=None):
        self.config = config
        self.apply_fn = apply_fn
        self.apply_group_fn = apply_group_fn
        self._jitted = {}
        #: i32[2] on the device: the pick steps and run-slot iterations
        #: the last run_group dispatch ran (its return stays the four
        #: values its callers unpack)
        self.group_ran = None

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
            prev_buf = jnp.zeros(0, jnp.uint8)
            prev_counts = jnp.zeros(0, jnp.int64)
        return fn(
            static, carry, prev_buf, prev_counts, pod_buf,
            jnp.asarray(zone_id_perm), jnp.asarray(veto_perm),
            jnp.asarray(bool(has_selectors)),
            jnp.asarray(np.int64(rows)), jnp.asarray(np.int32(k_real)),
            np.int64(L0),
        )

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
            prev_buf = jnp.zeros(0, jnp.uint8)
            prev_counts = jnp.zeros(0, jnp.int64)
        carry, chosen, n_done, L, self.group_ran = fn(
            static, carry, prev_buf, jnp.asarray(prev_counts), group_buf,
            jnp.asarray(zone_id_perm), jnp.asarray(vetos_perm),
            jnp.asarray(has_sels), jnp.asarray(rows_arr),
            jnp.asarray(k_reals), np.int32(runs), np.int64(L0),
        )
        return carry, chosen, n_done, L
