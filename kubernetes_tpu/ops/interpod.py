"""Device kernels for inter-pod (anti-)affinity.

Counts live in small `(term-class, domain)` tables threaded through the
scheduling scan's carry; a query reads a table's VIEW, its per-node
expansion: each node's domain id gathered from the table and the
logical terms expanded by inclusion-exclusion (see snapshot/interpod.py
for the compilation). `gather_counts` + `expand_lt` and `gather_lt`
define a view, and whoever holds a frozen carry (the wave probe, the
mesh scan, debug_evaluate) derives it with them. The single-chip scan
gathers the five views once a dispatch (`interpod_views`), carries them
beside the tables and adds each pick to them on the picked node's
domain (`interpod_commit_views`, the views' increment; the tables take
the same pick through `interpod_commit`), so its step gathers nothing.
Everything is integer arithmetic, bit-identical to the oracle
(predicates.go:754-947, interpod_affinity.go:86-216).

All kernels are total-shape-robust: with no affinity anywhere in the
workload every table is zero-width and XLA compiles the whole subsystem
away (the scheduler_perf benchmark pays nothing for this feature).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def gather_counts(table, u_topo, topo_dom):
    """table (U, D) -> per-node counts (U, N): table[u, topo_dom[q(u), n]],
    0 where the node has no valid domain for the combo."""
    U = table.shape[0]
    N = topo_dom.shape[1] if topo_dom.ndim == 2 else 0
    if U == 0:
        return jnp.zeros((0, N), table.dtype)
    dom = topo_dom[u_topo]  # (U, N)
    safe = jnp.clip(dom, 0, table.shape[1] - 1)
    vals = table[jnp.arange(U)[:, None], safe]
    return jnp.where(dom >= 0, vals, 0)


def expand_lt(cnt_u, lt_u, lt_sign, num_nodes):
    """(U, N) counts -> (LT, N) signed logical-term counts."""
    LT = lt_u.shape[0]
    if LT == 0 or cnt_u.shape[0] == 0:
        return jnp.zeros((LT, num_nodes), cnt_u.dtype)
    safe = jnp.clip(lt_u, 0, cnt_u.shape[0] - 1)
    picked = cnt_u[safe]  # (LT, E, N)
    signed = picked * lt_sign[:, :, None].astype(picked.dtype)
    return jnp.where((lt_u >= 0)[:, :, None], signed, 0).sum(axis=1)


def lt_domains(u_topo, topo_dom, lt_u):
    """Each node's domain id under every slot's combo, (LT, E, N): the
    index gather_lt reads a table at, -1 where the node lacks the label.
    Zero-size without terms or combos."""
    LT, E = lt_u.shape
    if LT == 0 or u_topo.shape[0] == 0:
        return jnp.zeros((LT, E, 0), jnp.int32)
    q = u_topo[jnp.clip(lt_u, 0, u_topo.shape[0] - 1)]  # (LT, E)
    return topo_dom[q]


def gather_lt(table, u_topo, topo_dom, lt_u, lt_sign):
    """Owned-term table (LT, E, D) -> (LT, N) signed per-node sums.

    Slot e of logical term lt holds counts/weights of owners at their
    node's domain under combo q = u_topo[lt_u[lt, e]]; the query reads the
    candidate node's domain column and applies the inclusion-exclusion
    sign."""
    LT, E = lt_u.shape
    N = topo_dom.shape[1] if topo_dom.ndim == 2 else 0
    if LT == 0 or u_topo.shape[0] == 0:
        return jnp.zeros((LT, N), table.dtype)
    dom = lt_domains(u_topo, topo_dom, lt_u)  # (LT, E, N)
    safe = jnp.clip(dom, 0, table.shape[2] - 1)
    vals = jnp.take_along_axis(table[:, :, :], safe, axis=2)  # (LT, E, N)
    valid = (lt_u >= 0)[:, :, None] & (dom >= 0)
    signed = vals * lt_sign[:, :, None].astype(vals.dtype)
    return jnp.where(valid, signed, 0).sum(axis=1)


def gather_lt_many(tables, u_topo, topo_dom, lt_u, lt_sign):
    """gather_lt of several owned-term tables (LT, E, D) that share
    their domain axis, in ONE gather -> a (LT, N) view each, bit for
    bit gather_lt's. A gather costs by its index vectors on the chip,
    not by the words a vector moves: the tables' entries at one (term,
    slot, domain) lie side by side as 32-bit words (an int64 is two)
    and a node's domain id fetches them all."""
    LT, E = lt_u.shape
    N = topo_dom.shape[1] if topo_dom.ndim == 2 else 0
    if LT == 0 or u_topo.shape[0] == 0:
        return tuple(jnp.zeros((LT, N), t.dtype) for t in tables)
    dom = lt_domains(u_topo, topo_dom, lt_u)  # (LT, E, N)
    safe = jnp.clip(dom, 0, tables[0].shape[2] - 1)
    words = [jax.lax.bitcast_convert_type(t, jnp.uint32) for t in tables]
    packed = jnp.concatenate(
        [w if w.ndim == 4 else w[..., None] for w in words], axis=3)
    rows = jax.vmap(jax.vmap(lambda table, at: table[at]))(packed, safe)
    valid = (lt_u >= 0)[:, :, None] & (dom >= 0)
    out, at = [], 0
    for t, w in zip(tables, words):
        width = w.shape[3] if w.ndim == 4 else 1
        vals = jax.lax.bitcast_convert_type(
            rows[..., at] if width == 1 else rows[..., at:at + width],
            t.dtype)  # (LT, E, N)
        at += width
        signed = vals * lt_sign[:, :, None].astype(vals.dtype)
        out.append(jnp.where(valid, signed, 0).sum(axis=1))
    return tuple(out)


class Views(NamedTuple):
    """The per-node expansions of the five domain tables, (LT, N) each."""

    cnt_lt: jax.Array  # i32, of term_count (gather_counts + expand_lt)
    own_lt: jax.Array  # i32, of own_anti (gather_lt, as the next three)
    rev_hard_lt: jax.Array  # i32
    rev_pref_lt: jax.Array  # i64
    rev_anti_lt: jax.Array  # i64


def interpod_views(
    term_count, own_anti, rev_hard, rev_pref, rev_anti,
    topo_dom, u_topo, lt_u, lt_sign, num_nodes,
):
    """The five tables' views, by the definitions above."""
    cnt_u = gather_counts(term_count, u_topo, topo_dom)
    return Views(
        expand_lt(cnt_u, lt_u, lt_sign, num_nodes),
        *(gather_lt(table, u_topo, topo_dom, lt_u, lt_sign)
          for table in (own_anti, rev_hard, rev_pref, rev_anti)),
    )


def match_interpod(
    cnt_lt,  # (LT, N) from term_count
    own_lt,  # (LT, N) from own_anti
    spec_total,  # (S,) carry
    lt_spec,  # (LT,)
    pod_match_spec,  # (S,) this pod's spec-match bits
    pod_ha_lt,  # (TA,)
    pod_ha_self,  # (TA,)
    pod_hq_lt,  # (TQ,)
    pod_has_affinity,  # scalar bool
    pod_has_anti,
    pod_sym_reject,
    num_nodes,
):
    """MatchInterPodAffinity (predicates.go:769) -> bool (N,)."""
    LT = lt_spec.shape[0]
    ones = jnp.ones((num_nodes,), bool)
    # hard affinity: every term needs a co-located match, OR the
    # first-pod-of-collection escape (predicates.go:819-843)
    if LT and pod_ha_lt.shape[0]:
        valid = pod_ha_lt >= 0  # (TA,)
        idx = jnp.clip(pod_ha_lt, 0, LT - 1)
        cnt = cnt_lt[idx]  # (TA, N)
        none_anywhere = spec_total[lt_spec[idx]] == 0  # (TA,)
        ok = (cnt > 0) | (pod_ha_self & none_anywhere)[:, None]
        aff_ok = jnp.where(valid[:, None], ok, True).all(axis=0)
    else:
        aff_ok = ones
    # own hard anti-affinity: no co-located match allowed
    if LT and pod_hq_lt.shape[0]:
        valid = pod_hq_lt >= 0
        cnt = cnt_lt[jnp.clip(pod_hq_lt, 0, LT - 1)]
        anti_ok = ~jnp.where(valid[:, None], cnt > 0, False).any(axis=0)
    else:
        anti_ok = ones
    # symmetric: an assigned pod owns a hard anti term matching this pod
    # and is co-located (predicates.go:858-921)
    if LT:
        pend = pod_match_spec[lt_spec] > 0  # (LT,)
        sym_ok = ~((own_lt > 0) & pend[:, None]).any(axis=0)
    else:
        sym_ok = ones
    fit = jnp.where(pod_has_affinity, aff_ok, True)
    fit = fit & jnp.where(pod_has_anti, anti_ok & sym_ok & ~pod_sym_reject, True)
    return fit


def interpod_priority(
    cnt_lt,  # (LT, N) from term_count
    rev_hard_lt,  # (LT, N)
    rev_pref_lt,  # (LT, N) i64
    rev_anti_lt,  # (LT, N) i64
    lt_spec,
    pod_match_spec,
    pod_fwd_lt,  # (TF,)
    pod_fwd_w,  # (TF,) signed i64
    hard_weight,  # python int (config)
    fit,
    num_nodes,
):
    """InterPodAffinityPriority (interpod_affinity.go:86-216) -> i64 (N,).

    total[n] = sum fwd_w * co-located matches of the pod's preferred terms
             + hardPodAffinityWeight * assigned hard-affinity terms
               matching the pod, co-located with n
             + weights of assigned preferred-affinity terms matching
             - weights of assigned preferred-anti terms matching,
    then 10*(t-min)/(max-min) over the FIT nodes with min<=0<=max pinned
    (Go's ints start at 0), truncated toward zero.
    """
    total = interpod_totals(
        cnt_lt,
        rev_hard_lt,
        rev_pref_lt,
        rev_anti_lt,
        lt_spec,
        pod_match_spec,
        pod_fwd_lt,
        pod_fwd_w,
        hard_weight,
        num_nodes,
    )
    mx, mn = interpod_minmax(total, fit)
    return interpod_normalize(total, fit, mx, mn)


def interpod_totals(
    cnt_lt,
    rev_hard_lt,
    rev_pref_lt,
    rev_anti_lt,
    lt_spec,
    pod_match_spec,
    pod_fwd_lt,
    pod_fwd_w,
    hard_weight,
    num_nodes,
):
    LT = lt_spec.shape[0]
    total = jnp.zeros((num_nodes,), jnp.int64)
    if LT and pod_fwd_lt.shape[0]:
        valid = pod_fwd_lt >= 0
        cnt = cnt_lt[jnp.clip(pod_fwd_lt, 0, LT - 1)].astype(jnp.int64)
        total = total + ((pod_fwd_w * valid)[:, None] * cnt).sum(axis=0)
    if LT:
        pend = (pod_match_spec[lt_spec] > 0)[:, None]  # (LT, 1)
        total = total + jnp.int64(hard_weight) * jnp.where(
            pend, rev_hard_lt.astype(jnp.int64), 0
        ).sum(axis=0)
        total = total + jnp.where(pend, rev_pref_lt, jnp.int64(0)).sum(axis=0)
        total = total - jnp.where(pend, rev_anti_lt, jnp.int64(0)).sum(axis=0)
    return total


def interpod_minmax(total, fit):
    """Go's max/min ints start at 0 (interpod_affinity.go:96-97)."""
    big = jnp.int64(2**62)
    mx = jnp.maximum(total.max(where=fit, initial=-big), 0)
    mn = jnp.minimum(total.min(where=fit, initial=big), 0)
    return mx, mn


def interpod_normalize(total, fit, mx, mn):
    rng = mx - mn
    f = jnp.where(
        rng > 0,
        10.0 * ((total - mn).astype(jnp.float64) / rng.astype(jnp.float64)),
        0.0,
    )
    return jnp.where(fit, f.astype(jnp.int64), 0)


def interpod_commit(
    term_count,
    own_anti,
    rev_hard,
    rev_pref,
    rev_anti,
    spec_total,
    topo_dom,
    u_topo,
    u_spec,
    lt_u,
    pod_match_spec,
    pod_own_hard,
    pod_own_pref,
    pod_own_anti_hard,
    pod_own_anti_pref,
    chosen,
    scheduled,
):
    """Fold a committed pod into the counting tables (the AssumePod
    analogue for affinity state)."""
    U = u_topo.shape[0]
    safe_n = jnp.maximum(chosen, 0)
    if U:
        dom = topo_dom[u_topo, safe_n]  # (U,)
        valid = (dom >= 0) & scheduled
        sd = jnp.clip(dom, 0, term_count.shape[1] - 1)
        idx = jnp.arange(U)
        mu = pod_match_spec[u_spec].astype(jnp.int32)
        term_count = term_count.at[idx, sd].add(mu * valid.astype(jnp.int32))
    LT, E = lt_u.shape
    if LT and U:
        q = u_topo[jnp.clip(lt_u, 0, U - 1)]  # (LT, E)
        domq = topo_dom[q, safe_n]  # (LT, E)
        validq = (lt_u >= 0) & (domq >= 0) & scheduled
        sdq = jnp.clip(domq, 0, own_anti.shape[2] - 1)
        lt_idx = jnp.arange(LT)[:, None]
        e_idx = jnp.arange(E)[None, :]
        v32 = validq.astype(jnp.int32)
        v64 = validq.astype(jnp.int64)
        own_anti = own_anti.at[lt_idx, e_idx, sdq].add(
            pod_own_anti_hard[:, None] * v32
        )
        rev_hard = rev_hard.at[lt_idx, e_idx, sdq].add(pod_own_hard[:, None] * v32)
        rev_pref = rev_pref.at[lt_idx, e_idx, sdq].add(pod_own_pref[:, None] * v64)
        rev_anti = rev_anti.at[lt_idx, e_idx, sdq].add(
            pod_own_anti_pref[:, None] * v64
        )
    if spec_total.shape[0]:
        spec_total = spec_total + pod_match_spec.astype(jnp.int32) * scheduled.astype(
            jnp.int32
        )
    return term_count, own_anti, rev_hard, rev_pref, rev_anti, spec_total


def interpod_commit_picks(
    term_count,
    own_anti,
    rev_hard,
    rev_pref,
    rev_anti,
    topo_dom,
    u_topo,
    u_spec,
    lt_u,
    pod_match_spec,
    pod_own_hard,
    pod_own_pref,
    pod_own_anti_hard,
    pod_own_anti_pref,
    nodes,  # (K,) the picked nodes, any id where not placed
    placed,  # (K,) bool
):
    """interpod_commit's five tables after K picks of ONE pod: a
    scatter-add a table with an update a pick and slot (a scatter costs
    by its updates on the chip, and a run's picks are far fewer than
    the nodes). `spec_total` is linear in the count and the caller's."""
    U = u_topo.shape[0]
    safe_n = jnp.maximum(nodes, 0)
    if U and term_count.shape[1]:
        dom = topo_dom[u_topo][:, safe_n]  # (U, K)
        valid = (dom >= 0) & placed
        mu = pod_match_spec[u_spec].astype(jnp.int32)
        term_count = term_count.at[
            jnp.arange(U)[:, None],
            jnp.clip(dom, 0, term_count.shape[1] - 1),
        ].add(mu[:, None] * valid.astype(jnp.int32))
    LT, E = lt_u.shape
    if LT and E and U and own_anti.shape[2]:
        q = u_topo[jnp.clip(lt_u, 0, U - 1)]  # (LT, E)
        domq = topo_dom[q][:, :, safe_n]  # (LT, E, K)
        validq = (lt_u >= 0)[:, :, None] & (domq >= 0) & placed
        at = (jnp.arange(LT)[:, None, None], jnp.arange(E)[None, :, None],
              jnp.clip(domq, 0, own_anti.shape[2] - 1))
        v32 = validq.astype(jnp.int32)
        v64 = validq.astype(jnp.int64)
        own_anti = own_anti.at[at].add(
            pod_own_anti_hard[:, None, None] * v32)
        rev_hard = rev_hard.at[at].add(pod_own_hard[:, None, None] * v32)
        rev_pref = rev_pref.at[at].add(pod_own_pref[:, None, None] * v64)
        rev_anti = rev_anti.at[at].add(
            pod_own_anti_pref[:, None, None] * v64)
    return term_count, own_anti, rev_hard, rev_pref, rev_anti


def interpod_commit_views(
    views,
    dom_lt,  # (LT, E, N) lt_domains
    width,  # the five tables' domain axis (snapshot/interpod cuts all to one)
    u_spec,
    lt_u,
    lt_sign,
    pod_match_spec,
    pod_own_hard,
    pod_own_pref,
    pod_own_anti_hard,
    pod_own_anti_pref,
    chosen,
    scheduled,
):
    """The increment of the views under interpod_commit: what gathering
    the committed tables anew would add to each, without a gather.

    interpod_commit adds the pod's share to one entry per (term, slot),
    the picked node's domain; a node's view reads that entry iff its own
    domain under the slot's combo is the same one. `hit` is that
    equality with interpod_commit's validity on the picked side,
    gather_lt's on the node's and the tables' clip on both, so a picked
    node without the label adds to no node; the signed sum over a
    term's slots is shared by the four owner tables, and term_count's
    weighs each slot with the pod's match on the slot's spec
    (expand_lt is linear). Integer arithmetic in each view's dtype."""
    LT, E = lt_u.shape
    if LT == 0 or dom_lt.shape[2] == 0:
        return views
    at = jnp.maximum(chosen, 0)
    domq = jax.lax.dynamic_slice_in_dim(dom_lt, at, 1, axis=2)  # (LT, E, 1)
    hit = (
        (lt_u >= 0)[:, :, None] & (domq >= 0) & (dom_lt >= 0) & scheduled
        & (jnp.clip(dom_lt, 0, width - 1) == jnp.clip(domq, 0, width - 1)))
    sign = jnp.where(hit, lt_sign.astype(jnp.int32)[:, :, None], 0)
    owners = sign.sum(axis=1)  # (LT, N)
    mu = pod_match_spec[u_spec].astype(jnp.int32)[
        jnp.clip(lt_u, 0, u_spec.shape[0] - 1)]  # (LT, E)

    def plus(view, share):
        return view + owners.astype(view.dtype) * share.astype(
            view.dtype)[:, None]

    return Views(
        views.cnt_lt + (sign * mu[:, :, None]).sum(axis=1).astype(
            views.cnt_lt.dtype),
        plus(views.own_lt, pod_own_anti_hard),
        plus(views.rev_hard_lt, pod_own_hard),
        plus(views.rev_pref_lt, pod_own_pref),
        plus(views.rev_anti_lt, pod_own_anti_pref),
    )
