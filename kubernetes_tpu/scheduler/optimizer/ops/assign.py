"""Device joint-assignment solver: the wave as a [pods x nodes] tensor.

The greedy wave driver decides pods one at a time (bit-identical to the
serial oracle). The optimizing profile instead treats a whole wave's
optimizer-eligible slots as ONE assignment problem over the same
feasibility and score tables the probe already produces:

  * ``fit`` bool[P, N] — the probe's static fit mask per slot (every
    configured predicate except resources, which the solver enforces
    itself from the request/commit vectors),
  * ``score`` i64[P, N] — the probed j=0 priority score per slot,
  * ``req``/``commit`` i64[P, 4] and ``cap`` i64[N, 4] — the exact
    integer resource math of ops/predicates.pod_fits_resources
    (mcpu, mem bytes, devices, pod slots; ``check`` masks the rows a
    zero-request pod skips, preserving the predicate's order quirk).

Two programs, each ONE dispatch per wave (the transfer contract is
audited in analysis/programs.py):

``auction``: Bertsekas-style auction rounds as a lax.scan. Per round
every unassigned slot bids its top-utility node (price-adjusted score;
epsilon scaling halves the increment each round down to 1), the highest
composite bid per node wins a seat, prices rise by the winning bid.
Priority tiers occupy the high bits of the bid key, so a contested node
always goes to the higher tier first. A deterministic (slot + node) % N
tie rotation spreads equal-score bids across nodes instead of
stampeding column 0 (argmax's first-index rule would otherwise
serialize a whole template onto one node per round).

``beam``: top-K beam over slots in solve order (small waves): each step
expands every beam by its top-C feasible nodes plus an explicit skip
branch, keeps the K best partial assignments by accumulated score with
a large per-skip penalty, so the beam maximizes placements first and
score second.

Integer-only math (no f64, no dot_general) and scatter-free by
construction — the winner resolution is a one-hot max over the bid
matrix, not a scatter — declared as such in the program registry.
Neither program is trusted for validity: the host re-validates every
proposed placement against the serial predicates before commit
(scheduler/optimizer/profile.py).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from kubernetes_tpu.trace.profile import fetch

#: resource rows of the req/commit/cap tables, in order
RES_ROWS = 4  # mcpu, mem bytes, devices, pod slots

#: "no utility" sentinel; far below any real price-adjusted score
_NEG = np.int64(-1) << 60

#: beam skip penalty: one skipped slot outweighs any score difference,
#: so the beam maximizes placement count before score
_SKIP_PENALTY = np.int64(1) << 40


def _auction_assign_fn(rounds, fit, score, req, commit, check, cap,
                       prio, order, eps0):
    """fit bool[P, N], score i64[P, N], req/commit i64[P, 4],
    check bool[P, 4], cap i64[N, 4] (free capacity at wave start),
    prio i32[P], order i32[P] (FIFO rank, tiebreak), eps0 i64 scalar
    -> owner i32[P] (node id per slot, -1 unassigned)."""
    import jax
    import jax.numpy as jnp

    P, N = score.shape
    neg = jnp.int64(_NEG)
    # order-preserving tie rotation: equal scores resolve to distinct
    # nodes per slot, so a template's slots fan out in one round
    rot = (
        jnp.arange(P, dtype=jnp.int64)[:, None]
        + jnp.arange(N, dtype=jnp.int64)[None, :]
    ) % jnp.int64(max(N, 1))
    score_tb = score * jnp.int64(N) + rot
    n_ids = jnp.arange(N, dtype=jnp.int32)
    p_ids = jnp.arange(P, dtype=jnp.int64)

    def round_fn(carry, t):
        price, owner, used = carry
        unassigned = owner < 0
        # exact resource feasibility at the CURRENT tentative usage
        fits_res = jnp.all(
            jnp.where(
                check[:, None, :],
                used[None, :, :] + req[:, None, :] <= cap[None, :, :],
                True,
            ),
            axis=2,
        )  # [P, N]
        feas = fit & fits_res & unassigned[:, None]
        util = jnp.where(feas, score_tb - price[None, :], neg)
        v1 = util.max(axis=1)
        n1 = util.argmax(axis=1)  # the slot's bid target
        mask1 = n_ids[None, :] == n1[:, None].astype(jnp.int32)
        v2 = jnp.where(mask1, neg, util).max(axis=1)
        # epsilon scaling; the shift amount clamps at 62 — a >=64-bit
        # int64 shift is implementation-defined, and long auctions
        # (rounds > 64 when P >> N) would otherwise see eps snap back
        # to eps0 mid-run on backends that wrap the shift mod 64
        eps = jnp.maximum(jnp.int64(1),
                          eps0 >> jnp.minimum(t, jnp.int64(62)))
        bid = jnp.where(v2 > neg, v1 - v2, jnp.int64(0)) + eps
        valid = v1 > neg
        # composite winner key: priority tier, then bid, then FIFO rank
        key = (
            jnp.clip(prio.astype(jnp.int64), 0, (1 << 14) - 1)
            * (jnp.int64(1) << 48)
            + jnp.clip(bid, 0, (jnp.int64(1) << 31) - 1)
            * (jnp.int64(1) << 16)
            + jnp.clip(jnp.int64(P) - order.astype(jnp.int64), 0,
                       (1 << 16) - 1)
        )
        key = jnp.where(valid, key, neg)
        # per-node winner via one-hot max (scatter-free): a slot bids on
        # exactly one node, so it can win at most one seat per round
        keyed = jnp.where(mask1 & valid[:, None], key[:, None], neg)
        win_key = keyed.max(axis=0)  # [N]
        win_p = keyed.argmax(axis=0)  # [N]
        win_valid = win_key > neg
        won = win_valid[n1] & (win_p[n1] == p_ids)
        owner = jnp.where(won & unassigned, n1.astype(owner.dtype),
                          owner)
        used = used + jnp.where(win_valid[:, None], commit[win_p],
                                jnp.int64(0))
        price = price + jnp.where(win_valid,
                                  jnp.clip(bid[win_p], 1, None),
                                  jnp.int64(0))
        return (price, owner, used), None

    price0 = jnp.zeros((N,), jnp.int64)
    owner0 = jnp.full((P,), -1, jnp.int32)
    used0 = jnp.zeros((N, RES_ROWS), jnp.int64)
    (_price, owner, _used), _ = jax.lax.scan(
        round_fn, (price0, owner0, used0),
        jnp.arange(rounds, dtype=jnp.int64),
    )
    return owner


def _beam_assign_fn(K, C, fit, score, req, commit, check, cap):
    """Top-K beam over slots in solve order (arrays arrive pre-permuted
    by priority/demand): -> owner i32[P]. One lax.scan over P steps;
    each step expands K beams by their top-C feasible nodes plus a skip
    branch and keeps the K best by accumulated score."""
    import jax
    import jax.numpy as jnp

    P, N = score.shape
    neg = jnp.int64(_NEG)
    C_eff = min(C, N)

    def step(carry, p):
        used, acc, choice = carry  # [K,N,4], [K], [K,P]
        req_p = jnp.take(req, p, axis=0)
        check_p = jnp.take(check, p, axis=0)
        fits_res = jnp.all(
            jnp.where(
                check_p[None, None, :],
                used + req_p[None, None, :] <= cap[None, :, :],
                True,
            ),
            axis=2,
        )  # [K, N]
        feas = jnp.take(fit, p, axis=0)[None, :] & fits_res
        util = jnp.where(feas, jnp.take(score, p, axis=0)[None, :], neg)
        cand_v, cand_n = jax.lax.top_k(util, C_eff)  # [K, C]
        assign_scores = acc[:, None] + jnp.where(
            cand_v > neg, cand_v, -(jnp.int64(1) << 58)
        )
        skip_scores = (acc - jnp.int64(_SKIP_PENALTY))[:, None]
        succ = jnp.concatenate([assign_scores, skip_scores], axis=1)
        flat = succ.reshape(K * (C_eff + 1))
        top_v, top_i = jax.lax.top_k(flat, K)
        parent = top_i // (C_eff + 1)
        slot = top_i % (C_eff + 1)
        is_assign = slot < C_eff
        slot_c = jnp.minimum(slot, C_eff - 1)
        picked_v = cand_v[parent, slot_c]
        feas_pick = is_assign & (picked_v > neg)
        node = jnp.where(feas_pick, cand_n[parent, slot_c], -1)
        add = jnp.where(
            feas_pick[:, None, None]
            & (jnp.arange(N)[None, :, None] == node[:, None, None]),
            jnp.take(commit, p, axis=0)[None, None, :],
            jnp.int64(0),
        )
        used = used[parent] + add
        # scatter-free column write (P is beam-sized, the where is cheap)
        choice = jnp.where(
            jnp.arange(P)[None, :] == p,
            node.astype(jnp.int32)[:, None],
            choice[parent],
        )
        return (used, top_v, choice), None

    used0 = jnp.zeros((K, N, RES_ROWS), jnp.int64)
    # beam 0 starts live; the clones start at -inf so step 1's top-K
    # picks distinct successors instead of K copies of one path
    acc0 = jnp.where(jnp.arange(K) == 0, jnp.int64(0),
                     -(jnp.int64(1) << 59))
    choice0 = jnp.full((K, P), -1, jnp.int32)
    (_used, acc, choice), _ = jax.lax.scan(
        step, (used0, acc0, choice0), jnp.arange(P))
    return choice[jnp.argmax(acc)]


def auction_rounds(P: int, N: int) -> int:
    """Static scan length: each round seats at most one slot per node,
    so ~P/N rounds clear an uncontended wave; the 8x headroom plus the
    16-round floor covers contention. Slots still unassigned after the
    horizon fall back to the greedy scan (the profile's safety net)."""
    import math

    return int(min(max(P, 1),
                   max(16, 8 * math.ceil(P / max(N, 1)))))


class AssignSolver:
    """Compile-cached dispatcher for the assignment programs.

    Slot and node axes arrive pow2-bucketed (padded slots carry an
    all-False fit row and can never be assigned), so repeated waves
    reuse one compiled program per shape — the same discipline every
    other wave program follows."""

    #: waves at or under this many slots take the beam (sequential but
    #: near-exhaustive); larger waves take the auction
    BEAM_MAX_SLOTS = 32
    BEAM_K = 4
    BEAM_C = 4

    def __init__(self):
        self._jit: Dict[Tuple, object] = {}

    def solve(self, fit: np.ndarray, score: np.ndarray, req: np.ndarray,
              commit: np.ndarray, check: np.ndarray, cap: np.ndarray,
              prio: np.ndarray, order: np.ndarray,
              n_real_slots: int) -> Tuple[np.ndarray, str]:
        """-> (owner i32[P] in slot order, solver name). ONE device
        dispatch. ``n_real_slots`` picks beam vs auction by the real
        (unpadded) wave size."""
        import functools

        import jax
        import jax.numpy as jnp

        P, N = fit.shape
        use_beam = n_real_slots <= self.BEAM_MAX_SLOTS
        if use_beam:
            key = ("beam", P, N)
            fn = self._jit.get(key)
            if fn is None:
                fn = jax.jit(functools.partial(
                    _beam_assign_fn, self.BEAM_K, self.BEAM_C))
                self._jit[key] = fn
            owner = fn(jnp.asarray(fit), jnp.asarray(score),
                       jnp.asarray(req), jnp.asarray(commit),
                       jnp.asarray(check), jnp.asarray(cap))
            return fetch(owner), "beam"
        rounds = auction_rounds(P, N)
        key = ("auction", P, N, rounds)
        fn = self._jit.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(_auction_assign_fn, rounds))
            self._jit[key] = fn
        score_span = int(max(int(score.max(initial=0))
                             - int(score.min(initial=0)), 1))
        eps0 = np.int64(max(1, (score_span * N) // 8))
        owner = fn(jnp.asarray(fit), jnp.asarray(score),
                   jnp.asarray(req), jnp.asarray(commit),
                   jnp.asarray(check), jnp.asarray(cap),
                   jnp.asarray(prio), jnp.asarray(order),
                   jnp.asarray(eps0))
        return fetch(owner), "auction"
