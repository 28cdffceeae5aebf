"""A template's pending-side PodBatch row, derived once and kept.

Template-created pods (an RC's, an RS's, a Job's) differ in their name
alone, and `pod_feature_key` (snapshot/encode.py) says so: equal keys
give equal rows. A wave's backlog is deduplicated to one representative
per key; with hundreds of controllers that is hundreds of rows, and they
are the same hundreds as the wave before. `PendingRows` keeps each row
under its key and assembles a wave's batch by one gather per field; only
a key it does not hold goes through `SnapshotEncoder.encode_pods`.

What a row was derived from, and what happens when that moves (all of
it observed at every `batch()`; nothing is a setting):

  * the template's key: a different key is a different row;
  * the spread listers (services, ReplicationControllers, replica sets):
    `SpreadSelectors.sync` keys each listed object by its namespace and
    its selector's content, rebuilt from the objects every wave, and
    names the entries that came and went. Each row keeps the entries
    that select it: a new entry is matched against every row once, a
    gone one is dropped from the rows that held it, and only rows whose
    entries changed have their `spread_match` recomputed;
  * the spread-class vocabulary, which only grows: a row in the wave is
    extended by the classes that are new to it, from its own selectors;
  * the taint vocabulary: every row reads all of it (tolerated or not,
    per taint), so a taint first seen rebuilds the store, as does another
    scheduler config (`resets`);
  * the port vocabulary shapes `port_mask` and nothing else: stored rows
    are padded with zeros, as a fresh encode pads them;
  * `slot_of`, for a pod that names its node: `host_req` of such rows is
    looked up again every wave;
  * the wave's own image vocabulary (per wave by design,
    `SnapshotEncoder._build_vocabs`): `img_count` is filled per wave
    from each row's image names;
  * the inter-pod vocabulary (`VocabBundle.terms`), which only grows:
    the logical terms a row names and owns are its own entries and never
    move, its `ip_own_*` columns are zero for a term first seen later
    (zero-padding), and its `ip_match_spec` is extended by the specs new
    to it, from its own namespace and labels. `ip_sym_reject` and
    `ip_poison` are not a row's: every wave reads them off the bound
    pods' counts (`InterPodTables.wave_flags`);
  * the ids a row holds (label keys, value sets, numeric keys, ports,
    its class, its logical terms) are its own entries of append-only
    vocabularies, interned before it was encoded: they never move.

The program axes (R1, T, TP, R, and the inter-pod lists' TA, TQ, TF) of
a batch are the largest any of its pods asks; each row remembers what it
asks alone, is stored zero-padded (a list of term ids one up, so that
the padding reads -1), and is cut to the wave's. tests/test_pending_rows.py holds every field
of an assembled batch to a fresh `encode_pods` over the same
vocabularies and listers, one event kind at a time.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.metrics import scheduler_pending_row_lookups_total
from kubernetes_tpu.oracle.state import ClusterState
from kubernetes_tpu.snapshot.encode import (
    PodBatch,
    SnapshotEncoder,
    SpreadSelectors,
    VocabBundle,
    ClassPairs,
    grown,
    spread_match_row,
)
from kubernetes_tpu.trace import profile as trace_profile

#: row fields of PodBatch and the batch axes that shape each beyond the
#: pod axis: vocabulary widths (PW, TW, TV, C: `SnapshotEncoder.widths`;
#: S, LT: the inter-pod specs and logical terms) and program axes (R1,
#: T, TP, R: `SnapshotEncoder.term_widths`; TA, TQ, TF: the longest list
#: of required affinity, required anti-affinity and preferred terms)
_ROW_FIELDS: Dict[str, tuple] = {
    "req_mcpu": (), "req_mem": (), "req_gpu": (), "zero_req": (),
    "commit_mcpu": (), "commit_mem": (), "commit_gpu": (),
    "nz_mcpu": (), "nz_mem": (), "host_req": (),
    "port_mask": ("PW",),
    "ns_ops": ("R1",), "ns_key": ("R1",), "ns_set": ("R1",),
    "ns_numkey": ("R1",), "ns_num": ("R1",),
    "aff_has_req": (), "aff_term_valid": ("T",),
    "aff_ops": ("T", "R"), "aff_key": ("T", "R"), "aff_set": ("T", "R"),
    "aff_numkey": ("T", "R"), "aff_num": ("T", "R"),
    "pref_valid": ("TP",), "pref_weight": ("TP",),
    "pref_ops": ("TP", "R"), "pref_key": ("TP", "R"),
    "pref_set": ("TP", "R"), "pref_numkey": ("TP", "R"),
    "pref_num": ("TP", "R"),
    "tol_mask": ("TW",), "intolerable_prefer": ("TV",),
    "has_tolerations": (), "best_effort": (),
    "has_selectors": (), "spread_match": ("C",),
    "class_id": (), "unschedulable": (),
    "ip_match_spec": ("S",), "ip_ha_lt": ("TA",), "ip_ha_self": ("TA",),
    "ip_hq_lt": ("TQ",), "ip_fwd_lt": ("TF",), "ip_fwd_w": ("TF",),
    "ip_own_hard": ("LT",), "ip_own_pref": ("LT",),
    "ip_own_anti_hard": ("LT",), "ip_own_anti_pref": ("LT",),
    "ip_has_affinity": (), "ip_has_anti": (),
}
_TERM_AXES = ("R1", "T", "TP", "R", "TA", "TQ", "TF")
#: lists of logical-term ids, -1 beyond a pod's own: held one up
_ONE_UP = ("ip_ha_lt", "ip_hq_lt", "ip_fwd_lt")

_count_hit = scheduler_pending_row_lookups_total.child(result="hit")
_count_miss = scheduler_pending_row_lookups_total.child(result="miss")


class PendingRows:
    """Encoded pending-pod rows by `pod_feature_key`, for one
    IncrementalEncoder's persistent vocabularies."""

    #: rows held at most; when a wave's new keys would pass it, the rows
    #: the wave does not use go (a cluster of all-distinct pods pays a
    #: miss per pod, as before, and holds no more than a wave beyond this)
    MAX_ROWS = 8192

    def __init__(self, vocabs: VocabBundle):
        self.vocabs = vocabs
        self.selectors = SpreadSelectors()
        self._pairs = ClassPairs()  # of vocabs.classes, which only grows
        self.hits = self.misses = self.resets = 0
        self._config = None
        self._taints = 0
        self._clear()

    def _clear(self) -> None:
        self._index: Dict[tuple, int] = {}
        self._n = 0  # rows in use: 0.._n
        self._arrays: Dict[str, np.ndarray] = {}
        # per row: what its spread columns are made from (namespace,
        # labels, the SpreadSelectors keys that select it; None for a
        # row the encoder gave up on before it reached its selectors),
        # its containers' image names, the node it names
        self._ns: List[str] = []
        self._labels: List[Dict[str, str]] = []
        self._sel: List[Optional[List[tuple]]] = []
        self._images: List[tuple] = []
        self._named: Dict[int, str] = {}
        # per row: the program axes it asks alone; the classes its
        # spread_match and the specs its ip_match_spec are computed for
        self._asks = np.zeros((0, len(_TERM_AXES)), np.int64)
        self._classes_done = np.zeros(0, np.int64)
        self._specs_done = np.zeros(0, np.int64)

    def __len__(self) -> int:
        return self._n

    # -- storage --------------------------------------------------------------

    def _array(self, name: str, tail: tuple, dtype=None) -> np.ndarray:
        """The store's array for a field, holding at least `_n` rows of
        at least `tail` (grown by doubling, zero-filled). `dtype` makes
        it where the field has none yet."""
        a = self._arrays.get(name)
        want = (self._n,) + tuple(tail)
        if a is None:
            a = np.zeros((max(self._n, 64),) + want[1:], dtype)
        else:
            a = grown(a, want)
        self._arrays[name] = a
        return a

    def _compact(self, keep_rows: Sequence[int]) -> np.ndarray:
        """Drop every row but `keep_rows`; -> old row -> new row (-1)."""
        keep = np.array(sorted(set(keep_rows)), np.intp)
        renumber = np.full(self._n, -1, np.intp)
        renumber[keep] = np.arange(len(keep))
        for name, a in self._arrays.items():
            out = np.zeros_like(a)
            out[: len(keep)] = a[keep]
            self._arrays[name] = out
        self._index = {k: int(renumber[r]) for k, r in self._index.items()
                       if renumber[r] >= 0}
        for attr in ("_ns", "_labels", "_sel", "_images"):
            old = getattr(self, attr)
            setattr(self, attr, [old[r] for r in keep])
        self._named = {int(renumber[r]): nm for r, nm in self._named.items()
                       if renumber[r] >= 0}
        self._asks = self._asks[keep]
        self._classes_done = self._classes_done[keep]
        self._specs_done = self._specs_done[keep]
        self._n = len(keep)
        return renumber

    # -- what moved since the last wave ----------------------------------------

    def _respread(self, row: int, class_list: Sequence[tuple]) -> None:
        """Row `row`'s spread columns from its selectors, all classes."""
        sel = self._sel[row]
        sm = self._arrays["spread_match"]
        sm[row, :] = 0
        self._arrays["has_selectors"][row] = bool(sel)
        if sel:
            entries = self.selectors.entries
            spread_match_row([entries[k] for k in sel], self._ns[row],
                             class_list, sm[row], pairs=self._pairs)
        self._classes_done[row] = len(class_list)

    def _reselect(self, added: Sequence[tuple], removed: Sequence[tuple],
                  class_list: Sequence[tuple]) -> None:
        """The listers changed: every row meets each new entry once and
        loses the gone ones. A row that only gained entries takes their
        columns on top of its own; one that lost any is re-spread."""
        gone = set(removed)
        entries = self.selectors.entries
        sm = self._arrays["spread_match"]
        for row in range(self._n):
            sel = self._sel[row]
            if sel is None:
                continue
            lost = False
            if gone and sel:
                kept = [k for k in sel if k not in gone]
                if len(kept) != len(sel):
                    sel = self._sel[row] = kept
                    lost = True
            new = self.selectors.selecting(
                self._ns[row], self._labels[row], among=added
            ) if added else []
            sel.extend(new)
            if lost:
                self._respread(row, class_list)
            elif new:
                self._arrays["has_selectors"][row] = True
                done = int(self._classes_done[row])
                spread_match_row(
                    [entries[k] for k in new], self._ns[row],
                    class_list[:done], sm[row][:done], pairs=self._pairs)

    # -- a wave's batch --------------------------------------------------------

    def batch(self, enc: SnapshotEncoder, keys: Sequence[tuple],
              services=(), controllers=(), replica_sets=()) -> PodBatch:
        """The PodBatch of `enc.pods` (the wave's representatives, whose
        vocabularies `enc` has interned; `keys` their feature keys), equal
        field by field to `enc.encode_pods()` with the given listers."""
        pods = enc.pods
        if not pods:
            return enc.encode_pods()
        v = self.vocabs
        reset = 0
        if enc.config is not self._config or len(v.taints) != self._taints:
            reset = int(self._n > 0)
            self._clear()
            self._config, self._taints = enc.config, len(v.taints)
        class_list = list(v.classes.ids)
        n_classes = len(class_list)
        self._pairs.extend(class_list)
        added, removed = self.selectors.sync(
            services, controllers, replica_sets)
        if self._n and (added or removed):
            self._array("spread_match", (n_classes,))
            self._reselect(added, removed, class_list)

        rows = [self._index.get(k) for k in keys]
        new_at = [i for i, r in enumerate(rows) if r is None]
        if new_at:
            first_at: Dict[tuple, int] = {}  # a key twice in one wave
            for i in new_at:
                first_at.setdefault(keys[i], i)
            if self._n + len(first_at) > self.MAX_ROWS:
                renumber = self._compact([r for r in rows if r is not None])
                rows = [None if r is None else int(renumber[r]) for r in rows]
            self._encode_new(enc, [pods[i] for i in first_at.values()],
                             list(first_at), class_list)
            rows = [self._index[keys[i]] if r is None else r
                    for i, r in enumerate(rows)]
        hits, misses = len(keys) - len(new_at), len(new_at)
        self.hits += hits
        self.misses += misses
        self.resets += reset
        _count_hit(hits)
        _count_miss(misses)
        trace_profile.count_pending_rows(hits, misses, reset)

        at = np.array(rows, np.intp)
        if n_classes:
            # classes first seen since a row was last spread: its columns
            # for those alone, from the selectors it keeps
            sm = self._array("spread_match", (n_classes,))
            entries = self.selectors.entries
            behind = np.flatnonzero(self._classes_done[at] < n_classes)
            for row in dict.fromkeys(at[behind].tolist()):
                sel = self._sel[row]
                if sel:
                    spread_match_row(
                        [entries[k] for k in sel], self._ns[row], class_list,
                        sm[row], start=int(self._classes_done[row]),
                        pairs=self._pairs)
                self._classes_done[row] = n_classes
        terms = v.terms
        n_specs = len(terms.specs)
        if n_specs:
            # specs first seen since a row was last matched
            ms = self._array("ip_match_spec", (n_specs,))
            behind = np.flatnonzero(self._specs_done[at] < n_specs)
            for row in dict.fromkeys(at[behind].tolist()):
                for s in range(int(self._specs_done[row]), n_specs):
                    ms[row, s] = terms.matches_spec(
                        self._ns[row], self._labels[row], s)
                self._specs_done[row] = n_specs
        dims = dict(enc.widths, S=n_specs, LT=len(terms.lts))
        asks = self._asks[at].max(axis=0, initial=1)
        dims.update(zip(_TERM_AXES, (int(x) for x in asks)))
        fields = {}
        for name, axes in _ROW_FIELDS.items():
            tail = tuple(dims[a] for a in axes)
            a = self._array(name, tail)
            fields[name] = a[(at,) + tuple(slice(0, w) for w in tail)]
        for name in _ONE_UP:
            fields[name] -= 1
        fields["ip_sym_reject"], poison = terms.wave_flags(
            fields["ip_match_spec"], fields["ip_has_anti"])
        fields["ip_poison"] = np.full(len(pods), poison, bool)
        if self._named:
            host_req = fields["host_req"]
            for i, row in enumerate(rows):
                name = self._named.get(row)
                if name is not None:
                    host_req[i] = enc.node_id.get(name, -2)
        fields.update(enc.batch_fields(interpod=False))
        img_count, image_id = fields["img_count"], enc.images.ids
        for i, row in enumerate(rows):
            for image in self._images[row]:
                img_count[i, image_id[image]] += 1
        return PodBatch(
            pod_keys=[(p.namespace, p.name) for p in pods], **fields)

    def _encode_new(self, enc: SnapshotEncoder, pods: List[Pod],
                    keys: List[tuple], class_list: Sequence[tuple]) -> None:
        """Rows for keys the store does not hold, through encode_pods
        (the listers left out: the rows' spread columns are made here,
        from selectors built once, and kept with what made them)."""
        sub = SnapshotEncoder(
            ClusterState(), pods, config=enc.config, vocabs=self.vocabs,
            visit_state=False, node_id=enc.node_id,
        )
        b = sub.encode_pods()
        first = self._n
        new = np.arange(first, first + len(pods))
        self._n = first + len(pods)
        for name in _ROW_FIELDS:
            src = getattr(b, name)
            if name in _ONE_UP:
                src = src + 1
            a = self._array(name, src.shape[1:], src.dtype)
            a[(new,) + tuple(slice(0, w) for w in src.shape[1:])] = src
        self._asks = np.concatenate([self._asks, np.concatenate(
            [sub.term_widths()]
            + [(getattr(b, name) >= 0).sum(axis=1)[:, None]
               for name in _ONE_UP], axis=1)])
        self._classes_done = np.concatenate(
            [self._classes_done, np.zeros(len(pods), np.int64)])
        self._specs_done = np.concatenate(
            [self._specs_done,
             np.full(len(pods), b.ip_match_spec.shape[1], np.int64)])
        for row, pod, key in zip(new.tolist(), pods, keys):
            self._index[key] = row
            gave_up = bool(b.unschedulable[row - first])
            labels = dict(pod.metadata.labels)
            self._ns.append(pod.namespace)
            self._labels.append(labels)
            self._sel.append(
                None if gave_up
                else self.selectors.selecting(pod.namespace, labels))
            self._images.append(
                () if gave_up
                else tuple(c.image for c in pod.spec.containers))
            if pod.spec.node_name:
                self._named[row] = pod.spec.node_name
            if not gave_up:
                self._respread(row, class_list)
