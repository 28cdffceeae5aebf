"""Inter-pod (anti-)affinity compilation: terms -> counting tables.

The reference's MatchInterPodAffinity predicate (predicates.go:754-947) and
InterPodAffinityPriority (interpod_affinity.go:86-216) are O(nodes x pods x
terms) scans over object graphs. The tensor formulation observes that every
check is a *pair count*: "how many assigned pods match term T's
(namespace-set, selector) and are co-located with node n under T's
topology key". We therefore compile:

- **specs** `s`: distinct (namespace-set, label-selector) pairs. Whether a
  pod matches a spec is computed host-side (same code path as the oracle,
  so semantics are bit-identical) into per-pod bitmaps.
- **topology combos** `q`: conjunctions of topology keys. Each node gets a
  dense domain id per combo (`topo_dom[q, n]`, -1 when any key is missing:
  NodesHaveSameTopologyKey requires non-empty equal values,
  util/non_zero.go:97-113). Two nodes are co-located under the combo iff
  their domain ids are equal and valid.
- **term classes** `u = (s, q)`: the unit of counting. The scheduler carry
  holds `count[u, domain]` tables; committing a pod to node n scatter-adds
  its spec-match bits at `topo_dom[q(u), n]`.
- **logical terms** `lt = (s, topology_key)`: what pods reference. A term
  with a non-empty key expands to one (u, +1). The empty key means "any
  default failure domain" (an OR), which we count exactly by
  inclusion-exclusion over the 2^3-1 key subsets with alternating signs —
  `count(A or B or C) = sum_singles - sum_pairs + triple`.

Five carry tables cover every direction the reference checks:
  term_count  — `(U, D)`: assigned pods *matching* spec(u), at their
                node's domain (forward hard affinity / own anti-affinity /
                fwd priority). Keyed by term class u=(s,q): a pod's match
                depends only on the spec, so sharing u between logical
                terms is sound here.
  own_anti    — `(LT, E, D)`: assigned pods *owning* a hard anti-affinity
                term (the symmetric check, predicates.go:858-921)
  rev_hard    — `(LT, E, D)`: assigned pods owning a hard affinity term
                (priority reverse pass, hardPodAffinityWeight)
  rev_pref    — `(LT, E, D)`: summed weights of owned preferred terms
  rev_anti    — `(LT, E, D)`: same for preferred anti-affinity
plus `spec_total[s]` — assigned pods matching spec s anywhere (topology
ignored), for the first-pod-of-collection escape (predicates.go:819-843).

Owned-term tables are keyed per LOGICAL term with one domain column per
expansion slot, NOT per (spec, combo) class: two terms sharing a class
(say a zone-key term and an empty-key term over the same selector) would
otherwise pollute each other's inclusion-exclusion sums.
"""

from __future__ import annotations

from dataclasses import dataclass
from itertools import combinations
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.api.types import (
    LabelSelector,
    LabelSelectorRequirement,
    Pod,
    get_affinity,
)
from kubernetes_tpu.oracle.predicates import (
    DEFAULT_FAILURE_DOMAINS,
    get_namespaces_from_term,
    label_selector_as_selector,
)
from kubernetes_tpu.oracle.state import (
    ClusterState,
    PodTerms,
    pod_terms,
    selector_canon,
)
from kubernetes_tpu.snapshot.encode import grown


def _selector_of(canon):
    """The labels.Selector of a `selector_canon`."""
    if canon is None:
        return label_selector_as_selector(None)
    labels, exprs = canon
    return label_selector_as_selector(LabelSelector(
        match_labels=dict(labels),
        match_expressions=tuple(
            LabelSelectorRequirement(key=k, operator=op, values=values)
            for k, op, values in exprs
        ),
    ))


@dataclass
class InterPodProgram:
    """Compiled tables. Shapes: Q combos x N nodes; U term classes; LT
    logical terms x E expansion slots; S specs x D domains; P pending pods
    x per-pod term widths. All zero-width when the workload has no
    inter-pod affinity anywhere — the device kernels then compile away."""

    # static (ClusterSnapshot side)
    topo_dom: np.ndarray  # i32 (Q, N)
    u_topo: np.ndarray  # i32 (U,)
    u_spec: np.ndarray  # i32 (U,)
    lt_spec: np.ndarray  # i32 (LT,)
    lt_u: np.ndarray  # i32 (LT, E), -1 pad
    lt_sign: np.ndarray  # i8 (LT, E)
    # initial carry (ClusterSnapshot side)
    term_count: np.ndarray  # i32 (U, D)
    own_anti: np.ndarray  # i32 (LT, E, D)
    rev_hard: np.ndarray  # i32 (LT, E, D)
    rev_pref: np.ndarray  # i64 (LT, E, D)
    rev_anti: np.ndarray  # i64 (LT, E, D)
    spec_total: np.ndarray  # i32 (S,)
    # pending-pod arrays (PodBatch side)
    match_spec: np.ndarray  # i8 (P, S)
    ha_lt: np.ndarray  # i32 (P, TA), -1 pad — hard affinity terms
    ha_self: np.ndarray  # bool (P, TA) — pod matches its own term
    hq_lt: np.ndarray  # i32 (P, TQ), -1 pad — hard anti terms
    fwd_lt: np.ndarray  # i32 (P, TF), -1 pad — preferred terms
    fwd_w: np.ndarray  # i64 (P, TF) — signed weights (anti negative)
    own_hard: np.ndarray  # i32 (P, LT)
    own_pref: np.ndarray  # i64 (P, LT)
    own_anti_hard: np.ndarray  # i32 (P, LT)
    own_anti_pref: np.ndarray  # i64 (P, LT)
    has_affinity: np.ndarray  # bool (P,)
    has_anti: np.ndarray  # bool (P,)
    sym_reject: np.ndarray  # bool (P,) — fails everywhere (unknown-node
    #   anti owner matches this pod, or a poisoned symmetric scan)
    poison: bool  # an assigned pod's affinity fails to parse =>
    #   InterPodAffinityPriority errors for EVERY pod (interpod_affinity.go
    #   parses all pods; the error aborts the scheduling cycle)


class _Vocab:
    def __init__(self):
        self.ids: Dict[object, int] = {}
        self.items: List[object] = []

    def get(self, key) -> int:
        i = self.ids.get(key)
        if i is None:
            i = len(self.items)
            self.ids[key] = i
            self.items.append(key)
        return i

    def __len__(self):
        return len(self.items)


#: the tables of term OWNERS (LT, E, D) with their dtype, and the
#: pending-pod column that says what a pod will add to each once it is
#: committed: by the index `InterPodTables.entries` gives
OWNER_TABLES = (("own_anti", np.int32, "own_anti_hard"),
                ("rev_hard", np.int32, "own_hard"),
                ("rev_pref", np.int64, "own_pref"),
                ("rev_anti", np.int64, "own_anti_pref"))
_OWN_ANTI, _REV_HARD, _REV_PREF, _REV_ANTI = range(4)


class TermVocab:
    """Specs, topology combos, term classes and logical terms, interned
    in order of first appearance, and the tables that say what each
    is."""

    def __init__(self, default_keys: Sequence[str] = DEFAULT_FAILURE_DOMAINS):
        self.default_keys = tuple(default_keys)
        self.specs = _Vocab()  # (ns_frozenset, sel_canon) -> s
        self.spec_impl: List[Tuple[frozenset, object]] = []  # (names, selector)
        self.topos = _Vocab()  # tuple(keys) -> q
        self.units = _Vocab()  # (s, q) -> u
        self.lts = _Vocab()  # (s, topology_key) -> lt
        self.lt_expansion: List[List[Tuple[int, int]]] = []  # lt -> [(u, sign)]

    # -- interning -----------------------------------------------------------

    def _combos(self, topology_key: str) -> List[Tuple[Tuple[str, ...], int]]:
        """Inclusion-exclusion expansion of a topology spec into key
        conjunctions with signs."""
        if topology_key:
            return [((topology_key,), 1)]
        out = []
        for r in range(1, len(self.default_keys) + 1):
            sign = 1 if r % 2 == 1 else -1
            for keys in combinations(self.default_keys, r):
                out.append((tuple(sorted(keys)), sign))
        return out

    def _lt_of(self, s: int, topology_key: str) -> int:
        lt = self.lts.get((s, topology_key))
        if lt == len(self.lt_expansion):
            exp = []
            for keys, sign in self._combos(topology_key):
                q = self.topos.get(keys)
                u = self.units.get((s, q))
                exp.append((u, sign))
            self.lt_expansion.append(exp)
        return lt

    def matches_spec(self, namespace: str, labels: Dict[str, str],
                     s: int) -> bool:
        names, sel = self.spec_impl[s]
        if names and namespace not in names:
            return False
        return sel.matches(labels)

    def _pod_matches_spec(self, pod: Pod, s: int) -> bool:
        return self.matches_spec(pod.namespace, pod.metadata.labels, s)

    def _pod_self_match(self, pod: Pod, s: int) -> bool:
        """First-pod-of-collection self check (predicates.go:826-832):
        `names.Has(pod.Namespace)` is a LITERAL set membership — the empty
        all-namespaces set contains nothing, so the escape is denied."""
        names, sel = self.spec_impl[s]
        return pod.namespace in names and sel.matches(pod.metadata.labels)

    # -- tables of the vocabulary ----------------------------------------------

    @property
    def expansion_width(self) -> int:
        return max([1] + [len(e) for e in self.lt_expansion])

    def term_tables(self):
        """-> (u_topo, u_spec, lt_spec, lt_u, lt_sign)."""
        U, LT, E = len(self.units), len(self.lts), self.expansion_width
        u_topo = np.zeros(U, np.int32)
        u_spec = np.zeros(U, np.int32)
        for (s, q), u in self.units.ids.items():
            u_spec[u], u_topo[u] = s, q
        lt_spec = np.zeros(LT, np.int32)
        lt_u = np.full((LT, E), -1, np.int32)
        lt_sign = np.zeros((LT, E), np.int8)
        for (s, _k), lt in self.lts.ids.items():
            lt_spec[lt] = s
            for e, (u, sign) in enumerate(self.lt_expansion[lt]):
                lt_u[lt, e], lt_sign[lt, e] = u, sign
        return u_topo, u_spec, lt_spec, lt_u, lt_sign


class _Domains:
    """One topology combo's domains: an id for every tuple of label
    values that some node has now, given back when the last such node
    goes and taken again by the next domain first seen. Under node
    churn (a hostname key: a domain a node) the ids so stay below the
    most domains ever live at once, and the tables' shapes (and the
    programs built for them) stay as they are; a table holds nothing at
    an id given back, since a node that goes or moves with pods on it
    has the tables counted again (`InterPodTables.stale`)."""

    def __init__(self):
        self.ids: Dict[tuple, int] = {}  # label values -> domain
        self.nodes: Dict[tuple, int] = {}  # label values -> nodes there
        self.free: List[int] = []
        self.width = 0  # ids ever out at once

    def take(self, values: tuple) -> int:
        d = self.ids.get(values)
        if d is None:
            d = self.ids[values] = self.free.pop() if self.free \
                else self.width
            self.width = max(self.width, d + 1)
            self.nodes[values] = 0
        self.nodes[values] += 1
        return d

    def give(self, values: tuple) -> None:
        self.nodes[values] -= 1
        if not self.nodes[values]:
            del self.nodes[values]
            self.free.append(self.ids.pop(values))


class Owned(NamedTuple):
    """What the pods of one `PodTerms` add to the owner tables, in one
    InterPodTables' ids."""

    parsed: bool
    entries: tuple  # (index into OWNER_TABLES, lt, weight)
    anti_specs: tuple  # the spec of each required anti-affinity term


class InterPodTables(TermVocab):
    """The snapshot side of the inter-pod program, kept from wave to
    wave for one IncrementalEncoder (snapshot/incremental.py): the
    vocabularies persist, `topo_dom` follows the node events, and the
    five counting tables and `spec_total` take each bound pod's share
    when it comes and give it back when it goes. What comes out equals
    `InterPodCompiler.compile` over the same cluster up to the numbering
    of specs, terms and domains (the compiler interns the assigned pods
    first; here a spec's and a term's id is kept for good, a domain's
    for as long as a node has it: `_Domains`).

    A pod's share has two parts. Which specs it matches is a matter of
    its namespace and labels, that is of its spread class: `match` holds
    classes x specs, each pair matched once, and `term_count` /
    `spec_total` follow the encoder's `class_count`. A spec or a term
    class first seen is counted from `class_count` at the next `sync`
    (`_filled`: until then no event touches it). The terms a pod OWNS
    (`Owned`, derived once per distinct `PodTerms`) go to the owner
    tables at its node's domains; an owner whose node is unknown or gone
    is counted per spec instead (`unknown_anti`: the symmetric check
    then rejects every node for the pods that match), and a pod whose
    annotation does not parse is counted in `unparsed` (the poison).

    What the deltas do not cover marks the tables `stale`, and `sync`
    rebuilds them whole, counted by reason in `rebuilds`: "relabel", a
    node_set that moves a domain under the node's pods (or gives pods
    on an unknown node their node); "node_removed", a node deleted
    under its pods."""

    def __init__(self, classes,
                 default_keys: Sequence[str] = DEFAULT_FAILURE_DOMAINS):
        super().__init__(default_keys)
        self._classes = classes  # VocabBundle.classes: ids by class key
        # per slot: the node's labels; None where the slot has no node
        # (free, or held by pods whose node is unknown or gone)
        self._labels: List[Optional[Dict[str, str]]] = []
        self._doms: List[_Domains] = []  # per combo
        self._domains = 1  # D: the widest combo's, at least 1
        self._e = 1  # E: the longest expansion
        self._units_of: List[List[int]] = []  # per spec
        self.topo_dom = np.full((0, 0), -1, np.int32)  # (Q.., slots)
        self.match = np.zeros((0, 0), np.int8)  # (classes.., specs..)
        self._matched = (0, 0)
        self.term_count = np.zeros((0, 1), np.int32)  # (U.., D..)
        self.spec_total = np.zeros(0, np.int32)  # (S..)
        self._filled = (0, 0)  # units, specs counted so far
        self.owners = [np.zeros((0, 1, 1), dt)
                       for _name, dt, _column in OWNER_TABLES]
        self.unknown_anti = np.zeros(0, np.int64)  # (S..)
        self.unparsed = 0
        self._owned: Dict[PodTerms, Owned] = {}
        self.stale: Optional[str] = None
        self.rebuilds: Dict[str, int] = {}
        # what `snapshot_fields` last gave, by the version it was made at
        self.static_version = self.carry_version = 0
        self._static = self._carry = (-1, None)

    # -- a pod's terms (oracle.state.PodTerms) in these ids -------------------------

    def _spec_of(self, names: frozenset, canon) -> int:
        s = self.specs.get((names, canon))
        if s == len(self.spec_impl):
            self.spec_impl.append((names, _selector_of(canon)))
        return s

    def term_lt(self, term: tuple) -> int:
        """The logical term of a `PodTerms` term."""
        names, canon, topology_key = term
        return self._lt_of(self._spec_of(names, canon), topology_key)

    def entries(self, terms: Optional[PodTerms]) -> List[Tuple[int, int, int]]:
        """-> [(index into OWNER_TABLES, logical term, weight)] of every
        term a pod owns, interned here in the order a pass over its
        affinity meets them."""
        if terms is None:
            return []
        return (
            [(_REV_HARD, self.term_lt(t), 1) for t in terms.hard]
            + [(_REV_PREF, self.term_lt(t), w) for t, w in terms.pref]
            + [(_OWN_ANTI, self.term_lt(t), 1) for t in terms.anti_hard]
            + [(_REV_ANTI, self.term_lt(t), w) for t, w in terms.anti_pref])

    # -- pending-pod arrays ------------------------------------------------------

    def pod_rows(self, pods: Sequence[Pod]) -> Dict[str, np.ndarray]:
        """The pending-pod arrays that are a pod's own (a row each,
        whatever else the wave holds), the pods' terms interned here pod
        by pod: as wide as the vocabulary and the pods' longest lists."""
        parsed = [pod_terms(pod) for pod in pods]
        owned = [self.entries(terms) for terms in parsed]
        S, LT, P = len(self.specs), len(self.lts), len(pods)
        ha_lists: List[List[Tuple[int, bool]]] = []
        hq_lists: List[List[int]] = []
        fwd_lists: List[List[Tuple[int, int]]] = []
        for pod, entries in zip(pods, owned):
            ha_lists.append([
                (lt, self._pod_self_match(pod, self.lts.items[lt][0]))
                for table, lt, _w in entries if table == _REV_HARD])
            hq_lists.append([lt for table, lt, _w in entries
                             if table == _OWN_ANTI])
            # interpod_affinity.go:107 skips a weight of zero; the
            # anti-affinity terms' weights count against a node
            fwd_lists.append([
                (lt, w if table == _REV_PREF else -w)
                for table, lt, w in entries
                if table in (_REV_PREF, _REV_ANTI) and w])
        TA = max([1] + [len(x) for x in ha_lists])
        TQ = max([1] + [len(x) for x in hq_lists])
        TF = max([1] + [len(x) for x in fwd_lists])
        rows = dict(
            match_spec=np.zeros((P, S), np.int8),
            ha_lt=np.full((P, TA), -1, np.int32),
            ha_self=np.zeros((P, TA), bool),
            hq_lt=np.full((P, TQ), -1, np.int32),
            fwd_lt=np.full((P, TF), -1, np.int32),
            fwd_w=np.zeros((P, TF), np.int64),
            own_hard=np.zeros((P, LT), np.int32),
            own_pref=np.zeros((P, LT), np.int64),
            own_anti_hard=np.zeros((P, LT), np.int32),
            own_anti_pref=np.zeros((P, LT), np.int64),
            has_affinity=np.zeros(P, bool),
            has_anti=np.zeros(P, bool),
        )
        for i, (pod, terms) in enumerate(zip(pods, parsed)):
            for s in range(S):
                rows["match_spec"][i, s] = self._pod_matches_spec(pod, s)
            for j, (lt, selfm) in enumerate(ha_lists[i]):
                rows["ha_lt"][i, j] = lt
                rows["ha_self"][i, j] = selfm
            for j, lt in enumerate(hq_lists[i]):
                rows["hq_lt"][i, j] = lt
            for j, (lt, w) in enumerate(fwd_lists[i]):
                rows["fwd_lt"][i, j] = lt
                rows["fwd_w"][i, j] = w
            if terms is not None:
                rows["has_affinity"][i] = terms.affinity
                rows["has_anti"][i] = terms.anti
            # what this pod will contribute once committed mid-scan
            # (per logical term; the device scatters into all E slots)
            for table, lt, w in owned[i]:
                rows[OWNER_TABLES[table][2]][i, lt] += w
        return rows

    # -- shapes ----------------------------------------------------------------

    def _lt_of(self, s: int, topology_key: str) -> int:
        known = len(self.lt_expansion)
        lt = super()._lt_of(s, topology_key)
        if lt == known:
            self._fit()
        return lt

    def _fit(self) -> None:
        """Room for a logical term first seen, and for what came with
        it: its spec, its term classes, their topology combos (each
        node's domain under a new combo, in slot order)."""
        S, Q, U = len(self.specs), len(self.topos), len(self.units)
        self._e = max(self._e, len(self.lt_expansion[-1]))
        self.topo_dom = grown(self.topo_dom, (Q, len(self._labels)), -1)
        for q in range(len(self._doms), Q):
            self._doms.append(_Domains())
            for slot, labels in enumerate(self._labels):
                if labels is not None:
                    self._place(q, slot, None, labels)
        while len(self._units_of) < S:
            self._units_of.append([])
        for u in range(sum(len(x) for x in self._units_of), U):
            self._units_of[self.units.items[u][0]].append(u)
        self.spec_total = grown(self.spec_total, (S,))
        self.unknown_anti = grown(self.unknown_anti, (S,))
        self._fit_domains()
        self.static_version += 1
        self.carry_version += 1

    def _fit_domains(self) -> None:
        U, LT, D = len(self.units), len(self.lts), self._domains
        self.term_count = grown(self.term_count, (U, D))
        self.owners = [grown(t, (LT, self._e, D)) for t in self.owners]

    def _values(self, q: int, labels: Optional[Dict[str, str]]
                ) -> Optional[tuple]:
        """The label values that name a node's domain under combo q;
        None where it has none (no node, or a key missing or empty:
        never co-located)."""
        if labels is None:
            return None
        vv = tuple(labels.get(k, "") for k in self.topos.items[q])
        return None if "" in vv else vv

    def _place(self, q: int, slot: int, old: Optional[Dict[str, str]],
               new: Optional[Dict[str, str]]) -> bool:
        """`topo_dom[q, slot]` for a slot whose node's labels were
        `old` and are `new` (None: no node); whether it moved."""
        was, now = self._values(q, old), self._values(q, new)
        if was == now:
            return False
        doms = self._doms[q]
        if was is not None:
            doms.give(was)
        d = -1 if now is None else doms.take(now)
        if doms.width > self._domains:
            self._domains = doms.width
            self._fit_domains()
            self.carry_version += 1
        moved = self.topo_dom[q, slot] != d
        self.topo_dom[q, slot] = d
        return bool(moved)

    # -- node events -------------------------------------------------------------

    def grow(self, slots: int) -> None:
        self._labels += [None] * (slots - len(self._labels))
        self.topo_dom = grown(self.topo_dom, (len(self.topos), slots), -1)
        self.static_version += 1

    def _mark_stale(self, reason: str) -> None:
        if len(self.specs) and self.stale is None:
            self.stale = reason  # nothing is counted by node before a spec

    def node_set(self, slot: int, labels: Dict[str, str], pods: int,
                 was_gone: bool) -> None:
        """The slot's node is (now) this one; `pods` of it are held,
        `was_gone` if they were held without a node."""
        old, self._labels[slot] = self._labels[slot], labels
        moved = False
        for q in range(len(self._doms)):
            moved |= self._place(q, slot, old, labels)
        if moved:
            self.static_version += 1
        if pods and (moved or was_gone):
            self._mark_stale("relabel")

    def node_gone(self, slot: int, pods: int) -> None:
        """The slot has no node any more: it is free, or `pods` of the
        node linger on it."""
        old, self._labels[slot] = self._labels[slot], None
        moved = False
        for q in range(len(self._doms)):
            moved |= self._place(q, slot, old, None)
        if moved:
            self.static_version += 1
        if pods:
            self._mark_stale("node_removed")

    # -- pod events ----------------------------------------------------------------

    def owned(self, terms: PodTerms) -> Owned:
        o = self._owned.get(terms)
        if o is None:
            entries = tuple(self.entries(terms))
            o = self._owned[terms] = Owned(terms.parsed, entries, tuple(
                self.lts.items[lt][0] for table, lt, _w in entries
                if table == _OWN_ANTI))
        return o

    def _sync_match(self) -> None:
        """Every class against every spec, each pair once."""
        C, S = len(self._classes), len(self.specs)
        c0, s0 = self._matched
        if (C, S) == (c0, s0):
            return
        self._matched = (C, S)
        if not S:
            return
        self.match = grown(self.match, (C, S))
        for c, (ns, labels_fs, _deleted) in enumerate(self._classes.ids):
            first = s0 if c < c0 else 0
            if first < S:
                labels = dict(labels_fs)
                for s in range(first, S):
                    self.match[c, s] = self.matches_spec(ns, labels, s)

    def apply(self, slots: np.ndarray, row_of: np.ndarray, sign: np.ndarray,
              row_class: Sequence[int], row_owned: Sequence[Optional[Owned]],
              gone: bool = False) -> None:
        """Pod events into the tables: event i is a pod of template
        `row_of[i]` (its class `row_class[r]`, what it owns
        `row_owned[r]`) coming to (`sign[i]` +1) or leaving (-1)
        `slots[i]`; `gone` if those slots have no node."""
        if self.stale is not None:
            return  # `sync` counts the held pods again, these among them
        self._sync_match()
        U0, S0 = self._filled
        changed = False
        if S0:
            m = self.match[np.asarray(row_class, np.intp), :S0]
            hit = np.flatnonzero(m.any(axis=0))
            if hit.size:
                changed = True
                ev = m[row_of]  # events x specs
                self.spec_total[:S0] += (
                    ev.astype(np.int32) * sign[:, None].astype(np.int32)
                ).sum(axis=0, dtype=np.int32)
                for s in hit.tolist():
                    at = np.flatnonzero(ev[:, s])
                    for u in self._units_of[s]:
                        if u < U0:
                            self._add_at(self.term_count[u], u, slots[at],
                                         sign[at])
        for r, o in enumerate(row_owned):
            if o is None:
                continue
            at = np.flatnonzero(row_of == r)
            if at.size:
                changed = True
                self._apply_owned(o, slots[at], sign[at], gone)
        if changed:
            self.carry_version += 1

    def _add_at(self, row: np.ndarray, u: int, slots: np.ndarray,
                amount: np.ndarray) -> None:
        """row[domain of each slot under u's combo] += amount."""
        d = self.topo_dom[self.units.items[u][1], slots]
        ok = d >= 0
        np.add.at(row, d[ok], amount[ok].astype(row.dtype))

    def _apply_owned(self, o: Owned, slots: np.ndarray, sign: np.ndarray,
                     gone: bool) -> None:
        if not o.parsed:
            self.unparsed += int(sign.sum())
        elif gone:
            for s in o.anti_specs:
                self.unknown_anti[s] += int(sign.sum())
        else:
            for table, lt, w in o.entries:
                for e, (u, _sign) in enumerate(self.lt_expansion[lt]):
                    self._add_at(self.owners[table][lt, e], u, slots,
                                 sign * w)

    # -- a wave's tables ---------------------------------------------------------------

    def sync(self, class_count: np.ndarray, contribs, node_gone: np.ndarray
             ) -> None:
        """Bring the tables up to the events applied and the vocabulary
        interned: whole again if `stale` (the owners from `contribs`,
        the encoder's (slot, contribution) of every held pod), and the
        specs and term classes not counted yet from `class_count`."""
        if self.stale is not None:
            self.rebuilds[self.stale] = self.rebuilds.get(self.stale, 0) + 1
            self.stale = None
            self.term_count[:] = 0
            self.spec_total[:] = 0
            self.unknown_anti[:] = 0
            for table in self.owners:
                table[:] = 0
            self.unparsed = 0
            self._filled = (0, 0)
            by_owned: Dict[int, Tuple[Owned, List[int]]] = {}
            for slot, c in contribs.values():
                if c.terms is not None:
                    o = self.owned(c.terms)
                    by_owned.setdefault(id(o), (o, []))[1].append(slot)
            for o, held in by_owned.values():
                at = np.array(held, np.intp)
                lost = node_gone[at]
                for part, gone in ((at[~lost], False), (at[lost], True)):
                    if part.size:
                        self._apply_owned(
                            o, part, np.ones(part.size, np.int64), gone)
            self.carry_version += 1
        self._sync_match()
        U0, S0 = self._filled
        U, S = len(self.units), len(self.specs)
        if (U, S) == (U0, S0):
            return
        self._filled = (U, S)
        self.carry_version += 1
        C = self._matched[0]
        new_units: Dict[int, List[int]] = {s: [] for s in range(S0, S)}
        for u in range(U0, U):
            new_units.setdefault(self.units.items[u][0], []).append(u)
        slots = np.arange(class_count.shape[0])
        for s, units in new_units.items():
            classes = np.flatnonzero(self.match[:C, s])
            if not classes.size:
                continue
            held = class_count[:, classes].sum(axis=1)
            if s >= S0:
                self.spec_total[s] = held.sum()
            at = np.flatnonzero(held)
            for u in units:
                self._add_at(self.term_count[u], u, slots[at], held[at])

    def snapshot_fields(self) -> Dict[str, np.ndarray]:
        """The twelve `ip_*` fields of a ClusterSnapshot, cut to the
        vocabularies' widths. The same arrays come again for as long as
        nothing changed them (`static_version`, `carry_version`)."""
        S, Q, U, LT = (len(self.specs), len(self.topos), len(self.units),
                       len(self.lts))
        E, D = self._e, self._domains
        if self._static[0] != self.static_version:
            u_topo, u_spec, lt_spec, lt_u, lt_sign = self.term_tables()
            self._static = (self.static_version, dict(
                # cut on both axes: `grown` leaves room beyond the
                # combos and beyond the node axis alike. (No combo, no
                # node axis: what a compiler that saw no term gives, and
                # the programs are built per shape)
                ip_topo_dom=self.topo_dom[:Q, :len(self._labels)].copy()
                if Q else np.zeros((0, 0), np.int32), ip_u_topo=u_topo,
                ip_u_spec=u_spec, ip_lt_spec=lt_spec, ip_lt_u=lt_u,
                ip_lt_sign=lt_sign))
        if self._carry[0] != self.carry_version:
            fields = {
                "ip_" + name: table[:LT, :E, :D].copy()
                for (name, _dt, _column), table
                in zip(OWNER_TABLES, self.owners)}
            fields["ip_term_count"] = self.term_count[:U, :D].copy() \
                if U else np.zeros((0, 1), np.int32)
            fields["ip_spec_total"] = self.spec_total[:S].copy()
            self._carry = (self.carry_version, fields)
        return {**self._static[1], **self._carry[1]}

    def wave_flags(self, match_spec: np.ndarray, has_anti: np.ndarray
                   ) -> Tuple[np.ndarray, bool]:
        """-> (sym_reject, poison) of a wave's pods, which are not the
        pods' own: an owner of a required anti-affinity term that has no
        node rejects every node for the pods its spec matches, and one
        bound pod that does not parse poisons every pod."""
        poison = self.unparsed > 0
        hot = self.unknown_anti[: match_spec.shape[1]] > 0
        sym = has_anti & (poison | (match_spec[:, hot] != 0).any(axis=1))
        return sym, poison


class InterPodCompiler(TermVocab):
    def __init__(
        self,
        state: ClusterState,
        pods: Sequence[Pod],
        node_names: Sequence[str],
        default_keys: Sequence[str] = DEFAULT_FAILURE_DOMAINS,
    ):
        super().__init__(default_keys)
        self.state = state
        self.pods = list(pods)
        self.node_names = list(node_names)
        self.node_id = {n: i for i, n in enumerate(self.node_names)}

    def _lt_id(self, owner: Pod, term) -> int:
        """The logical term of an owner's PodAffinityTerm, straight from
        the objects (not through `oracle.state.pod_terms`, which the
        kept tables and the stored rows are made from: this class is
        what the tests hold those to)."""
        names = frozenset(get_namespaces_from_term(owner, term))
        s = self.specs.get((names, selector_canon(term.label_selector)))
        if s == len(self.spec_impl):
            self.spec_impl.append(
                (names, label_selector_as_selector(term.label_selector)))
        return self._lt_of(s, term.topology_key)

    @staticmethod
    def _affinity(pod: Pod):
        """(affinity, parse_ok)."""
        try:
            return get_affinity(pod), True
        except Exception:
            return None, False

    # -- compilation ---------------------------------------------------------

    def compile(self) -> InterPodProgram:
        state, pods = self.state, self.pods
        assigned = state.all_assigned_pods()

        # pass 1: intern every term reachable from any pod.
        a_parsed = []  # (aff, ok) per assigned pod
        for ep in assigned:
            aff, ok = self._affinity(ep)
            a_parsed.append((aff, ok))
            if aff is None:
                continue
            for side in (aff.pod_affinity, aff.pod_anti_affinity):
                if side is None:
                    continue
                for t in side.required_during_scheduling_ignored_during_execution:
                    self._lt_id(ep, t)
                for wt in side.preferred_during_scheduling_ignored_during_execution:
                    self._lt_id(ep, wt.pod_affinity_term)
        p_parsed = []
        for pod in pods:
            aff, ok = self._affinity(pod)
            p_parsed.append((aff, ok))
            if aff is None:
                continue
            for side in (aff.pod_affinity, aff.pod_anti_affinity):
                if side is None:
                    continue
                for t in side.required_during_scheduling_ignored_during_execution:
                    self._lt_id(pod, t)
                for wt in side.preferred_during_scheduling_ignored_during_execution:
                    self._lt_id(pod, wt.pod_affinity_term)

        S, Q, U, LT = len(self.specs), len(self.topos), len(self.units), len(self.lts)
        N, P = len(self.node_names), len(pods)
        E = self.expansion_width

        # topology domains per combo
        topo_dom = np.full((Q, N), -1, np.int32)
        n_dom = 1
        for q, keys in enumerate(self.topos.items):
            vals: Dict[Tuple[str, ...], int] = {}
            for n, name in enumerate(self.node_names):
                node = state.node_infos[name].node
                vv = tuple(node.metadata.labels.get(k, "") for k in keys)
                if any(v == "" for v in vv):
                    continue  # missing/empty label => never co-located
                d = vals.setdefault(vv, len(vals))
                topo_dom[q, n] = d
            n_dom = max(n_dom, len(vals))
        D = n_dom

        u_topo, u_spec, lt_spec, lt_u, lt_sign = self.term_tables()

        # initial carry from assigned pods
        term_count = np.zeros((U, max(1, D)), np.int32)
        own_anti = np.zeros((LT, E, max(1, D)), np.int32)
        rev_hard = np.zeros((LT, E, max(1, D)), np.int32)
        rev_pref = np.zeros((LT, E, max(1, D)), np.int64)
        rev_anti = np.zeros((LT, E, max(1, D)), np.int64)
        spec_total = np.zeros(max(0, S), np.int32)
        poison = False
        # (spec, ) anti-affinity specs owned by assigned pods on UNKNOWN
        # nodes: the symmetric check rejects every node for pods matching
        # them (oracle predicates.py `ep_node is None` branch).
        unknown_anti_specs: List[int] = []

        def _dom_of(u: int, n: int) -> int:
            return int(topo_dom[u_topo[u], n])

        for ep, (aff, ok) in zip(assigned, a_parsed):
            if not ok:
                poison = True
            m = np.array(
                [self._pod_matches_spec(ep, s) for s in range(S)], np.int32
            ) if S else np.zeros(0, np.int32)
            spec_total += m
            n = self.node_id.get(ep.spec.node_name, -1)
            if n >= 0:
                for u in range(U):
                    d = _dom_of(u, n)
                    if d >= 0 and m[u_spec[u]]:
                        term_count[u, d] += 1
            if aff is None:
                continue

            def _own(side_terms, table, weight_of=None):
                """Record ep's owned terms at its node's domains, one slot
                per expansion entry (the query re-applies the signs)."""
                for item in side_terms:
                    term = item if weight_of is None else item.pod_affinity_term
                    w = 1 if weight_of is None else weight_of(item)
                    lt = self._lt_id(ep, term)
                    if n < 0:
                        continue
                    for e, (u, _sign) in enumerate(self.lt_expansion[lt]):
                        d = _dom_of(u, n)
                        if d >= 0:
                            table[lt, e, d] += w
                return None

            if aff.pod_affinity is not None:
                _own(
                    aff.pod_affinity.required_during_scheduling_ignored_during_execution,
                    rev_hard,
                )
                _own(
                    aff.pod_affinity.preferred_during_scheduling_ignored_during_execution,
                    rev_pref,
                    lambda wt: wt.weight,
                )
            if aff.pod_anti_affinity is not None:
                for term in aff.pod_anti_affinity.required_during_scheduling_ignored_during_execution:
                    lt = self._lt_id(ep, term)
                    if n < 0:
                        unknown_anti_specs.append(int(lt_spec[lt]))
                    else:
                        for e, (u, _sign) in enumerate(self.lt_expansion[lt]):
                            d = _dom_of(u, n)
                            if d >= 0:
                                own_anti[lt, e, d] += 1
                _own(
                    aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution,
                    rev_anti,
                    lambda wt: wt.weight,
                )

        # pending-pod arrays
        ha_lists: List[List[Tuple[int, bool]]] = []
        hq_lists: List[List[int]] = []
        fwd_lists: List[List[Tuple[int, int]]] = []
        for pod, (aff, ok) in zip(pods, p_parsed):
            ha, hq, fwd = [], [], []
            if aff is not None:
                if aff.pod_affinity is not None:
                    for t in aff.pod_affinity.required_during_scheduling_ignored_during_execution:
                        lt = self._lt_id(pod, t)
                        ha.append((lt, self._pod_self_match(pod, int(lt_spec[lt]))))
                    for wt in aff.pod_affinity.preferred_during_scheduling_ignored_during_execution:
                        if wt.weight == 0:
                            continue  # interpod_affinity.go:107 skips
                        fwd.append((self._lt_id(pod, wt.pod_affinity_term), wt.weight))
                if aff.pod_anti_affinity is not None:
                    for t in aff.pod_anti_affinity.required_during_scheduling_ignored_during_execution:
                        hq.append(self._lt_id(pod, t))
                    for wt in aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution:
                        if wt.weight == 0:
                            continue
                        fwd.append(
                            (self._lt_id(pod, wt.pod_affinity_term), -wt.weight)
                        )
            ha_lists.append(ha)
            hq_lists.append(hq)
            fwd_lists.append(fwd)

        TA = max([1] + [len(x) for x in ha_lists])
        TQ = max([1] + [len(x) for x in hq_lists])
        TF = max([1] + [len(x) for x in fwd_lists])
        prog = InterPodProgram(
            topo_dom=topo_dom,
            u_topo=u_topo,
            u_spec=u_spec,
            lt_spec=lt_spec,
            lt_u=lt_u,
            lt_sign=lt_sign,
            term_count=term_count if U else np.zeros((0, 1), np.int32),
            own_anti=own_anti,
            rev_hard=rev_hard,
            rev_pref=rev_pref,
            rev_anti=rev_anti,
            spec_total=spec_total,
            match_spec=np.zeros((P, S), np.int8),
            ha_lt=np.full((P, TA), -1, np.int32),
            ha_self=np.zeros((P, TA), bool),
            hq_lt=np.full((P, TQ), -1, np.int32),
            fwd_lt=np.full((P, TF), -1, np.int32),
            fwd_w=np.zeros((P, TF), np.int64),
            own_hard=np.zeros((P, LT), np.int32),
            own_pref=np.zeros((P, LT), np.int64),
            own_anti_hard=np.zeros((P, LT), np.int32),
            own_anti_pref=np.zeros((P, LT), np.int64),
            has_affinity=np.zeros(P, bool),
            has_anti=np.zeros(P, bool),
            sym_reject=np.zeros(P, bool),
            poison=poison,
        )
        for i, (pod, (aff, ok)) in enumerate(zip(pods, p_parsed)):
            for s in range(S):
                prog.match_spec[i, s] = self._pod_matches_spec(pod, s)
            for j, (lt, selfm) in enumerate(ha_lists[i]):
                prog.ha_lt[i, j] = lt
                prog.ha_self[i, j] = selfm
            for j, lt in enumerate(hq_lists[i]):
                prog.hq_lt[i, j] = lt
            for j, (lt, w) in enumerate(fwd_lists[i]):
                prog.fwd_lt[i, j] = lt
                prog.fwd_w[i, j] = w
            if aff is not None:
                prog.has_affinity[i] = aff.pod_affinity is not None
                prog.has_anti[i] = aff.pod_anti_affinity is not None
                # what this pod will contribute once committed mid-scan
                # (per logical term; the device scatters into all E slots)
                if aff.pod_affinity is not None:
                    for t in aff.pod_affinity.required_during_scheduling_ignored_during_execution:
                        prog.own_hard[i, self._lt_id(pod, t)] += 1
                    for wt in aff.pod_affinity.preferred_during_scheduling_ignored_during_execution:
                        prog.own_pref[i, self._lt_id(pod, wt.pod_affinity_term)] += (
                            wt.weight
                        )
                if aff.pod_anti_affinity is not None:
                    for t in aff.pod_anti_affinity.required_during_scheduling_ignored_during_execution:
                        prog.own_anti_hard[i, self._lt_id(pod, t)] += 1
                    for wt in aff.pod_anti_affinity.preferred_during_scheduling_ignored_during_execution:
                        prog.own_anti_pref[
                            i, self._lt_id(pod, wt.pod_affinity_term)
                        ] += wt.weight
            # symmetric-check hard failures independent of the node
            if prog.has_anti[i]:
                if poison:
                    prog.sym_reject[i] = True
                for s in unknown_anti_specs:
                    if self._pod_matches_spec(pod, s):
                        prog.sym_reject[i] = True
        return prog
