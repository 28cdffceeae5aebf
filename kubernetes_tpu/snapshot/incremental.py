"""Incremental snapshot maintenance: O(event) columnar updates.

The reference never re-derives cluster state per scheduling cycle — the
scheduler cache applies O(1) NodeInfo deltas per watch event
(schedulercache/node_info.go:118-156) and the per-cycle snapshot is a
clone, not a rebuild (cache.go:77). Round 1 of this framework re-encoded
the whole cluster into columnar arrays every wave (O(cluster)); this
module restores the reference's cost model at the array level:

  * `IncrementalEncoder` subscribes to SchedulerCache mutations
    (cache.add_listener) and patches the node-axis arrays in place —
    O(changed rows) per event, never O(cluster) per wave. A wave's pod
    events go in a batch at a time: each carries its template's shared
    contribution (oracle/state.pod_contribution), and the aggregates
    take one scatter-add per batch, not eight scalar writes per pod.
  * Vocabularies live in a persistent `VocabBundle`, append-only, so ids
    agree across waves; a wave's pending pods are interned by a plain
    SnapshotEncoder sharing the bundle with `visit_state=False`
    (O(backlog), not O(cluster)).
  * A template's pending-side PodBatch row is encoded once and kept
    (`self.rows`, snapshot/pending_rows.py): a wave's batch is gathered
    from the rows stored under its pods' `pod_feature_key`s, and only a
    key the store does not hold goes through `encode_pods`. A row is
    reused for as long as what it was derived from stands, which is
    looked at on every wave and never configured: the spread listers
    (an entry added, deleted or re-selected repairs the rows it selects
    or selected, and no other), the spread-class vocabulary (a row is
    extended by the classes new to it), the taint vocabulary and the
    scheduler config (either rebuilds the store), the port vocabulary's
    width (zero-padding), `slot_of` for a pod that names its node, and
    the wave's own image vocabulary (both read afresh every wave).
  * Bitset widths / class columns grow by column-padding when a vocab
    crosses a word boundary (O(N) once, amortized nil).
  * Node slots are stable: removed nodes free their slot (zeroed
    allocatable => never fit, exactly like pad.py's dummy nodes) and new
    nodes reuse free slots. Decisions depend on the name-desc order, not
    slot order, so slot assignment is invisible to scheduling.
  * The inter-pod program's snapshot side is kept too
    (`VocabBundle.terms`, snapshot/interpod.InterPodTables): its
    vocabularies (specs, topology combos, term classes, logical terms,
    a domain numbering per combo) are persistent like the others;
    `topo_dom` follows the node events; and the five counting tables
    and `spec_total` take a bound pod's share when it comes and give it
    back when it goes, with the batch's one scatter-add. A pod's share
    is derived once per template: the terms it owns ride on its
    contribution (`PodContribution.terms`), the specs it matches are
    its spread class's. A wave whose pods own terms, over a cluster
    whose pods do, costs what any other wave costs.

Scope gates (wave_view returns ok=False and the caller falls back to the
from-scratch SnapshotEncoder — correctness is never at stake, only
cost): volumes on wave pods, a Policy using
ServiceAffinity/AntiAffinity, or a config without GeneralPredicates
(free slots are masked via zeroed allocatable, which needs the resource
predicate active). Inter-pod terms gate nothing. What the kept tables'
deltas do not cover (a node relabelled or removed under its pods)
rebuilds those tables whole from the held pods, O(bound pods), inside
this encoder and counted by reason (`take_rebuilds`); owners without a
node and pods whose annotation does not parse are kept as counts.

tests/test_incremental.py drives randomized event streams and proves
snapshot-after-deltas == snapshot-from-scratch, both semantically
(decoded per-node views; the inter-pod tables against
InterPodCompiler.compile by canonical key) and end-to-end (identical
decisions).
"""

from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.api.types import Node, Pod, get_taints
from kubernetes_tpu.oracle.priorities import get_zone_key
from kubernetes_tpu.oracle.state import (
    ClusterState,
    PodContribution,
    _pod_key,
    pod_contribution,
)
from kubernetes_tpu.snapshot.encode import (
    ClusterSnapshot,
    PodBatch,
    SnapshotEncoder,
    VocabBundle,
    _pack_bits,
    _words,
    build_set_table,
    grown,
    pod_feature_key,
    service_config_labels,
)
from kubernetes_tpu.snapshot.interpod import InterPodTables
from kubernetes_tpu.snapshot.pending_rows import PendingRows
from kubernetes_tpu.api.resource import (
    parse_quantity,
    resource_list_cpu_milli,
    resource_list_memory,
)
from kubernetes_tpu.trace import profile as trace_profile


def _grow_cols(a: np.ndarray, cols: int) -> np.ndarray:
    """`a` with at least `cols` columns (`grown`: by doubling)."""
    return grown(a, (a.shape[0], cols))


_SOURCE_COUNTER = itertools.count()


class IncrementalEncoder:
    """Maintains node-axis snapshot arrays from cache events."""

    def __init__(self, config=None, initial_slots: int = 64,
                 slot_step: Optional[int] = None):
        """`slot_step`: grow the node axis by that many slots at a time
        and not by doubling. The mesh driver gives a multiple of its
        devices: slots fill from the front, so a doubled axis leaves
        the last shards holding padding alone (5,000 nodes in 8,192
        slots over four chips are 2,048 / 2,048 / 904 / 0 a shard)."""
        self.config = config
        self._slot_step = slot_step
        # unique device-cache provenance token: vocab bit/slot
        # assignments are encoder-local, so a consumer's cached device
        # arrays must never outlive the encoder that produced them
        # (a monotonic counter — id() reuses freed addresses)
        self.source_token = f"inc:{next(_SOURCE_COUNTER)}"
        self.vocabs = VocabBundle()
        self.vocabs.terms = InterPodTables(self.vocabs.classes)
        # encoded pending-pod rows by template (snapshot/pending_rows.py)
        self.rows = PendingRows(self.vocabs)
        self._lock = threading.Lock()
        self._events: List[Tuple[str, object]] = []
        # slot map
        self._cap = 0
        self.slot_of: Dict[str, int] = {}
        self._free: List[int] = []
        self.node_names: List[str] = []  # per slot; "" == free
        self._node_labels: List[Optional[Dict[str, str]]] = []
        self._node_images: List[Optional[Dict[str, int]]] = []
        self._schedulable = np.zeros(0, bool)
        self._node_gone = np.zeros(0, bool)  # node deleted, pods linger
        self._pod_count_slot = np.zeros(0, np.int64)
        # (namespace, name) -> (slot, contribution): exactly what the
        # pod added to its node's row, recorded at add time so removal
        # is a perfect inverse (no re-parse drift). The contribution is
        # the template's shared, immutable one.
        self._contribs: Dict[
            Tuple[str, str], Tuple[int, PodContribution]
        ] = {}
        # the scope gate the last `wave_view` stopped at, if any
        self.fallback: Optional[str] = None
        # the fields of its snapshot that moved as one (the inter-pod
        # counting tables, when a bound pod touched any): a driver that
        # compares what is not in `keep` with its last copy, to ship
        # the rows that differ, ships these whole
        # (models/wave.WaveScheduler._to_dev_many)
        self.reship: frozenset = frozenset()
        # per-(slot) port id multiset
        self._port_counts: List[Optional[Dict[int, int]]] = []
        self._order_dirty = True
        self._name_desc: Optional[np.ndarray] = None
        self._alloc_raw = None  # (4, cap): mcpu, mem, gpu, pods
        # coarse dirty groups for device-residency (models/wave.py reuses
        # device arrays for clean groups between waves)
        self._dirty_node_side = True
        self._dirty_pod_side = True
        self._last_sets_len = -1
        self._last_img_vocab: Optional[tuple] = None
        self._last_terms = (-1, -1)  # InterPodTables versions emitted
        self._grow(slot_step or initial_slots)
        # column-capacity trackers
        self._lw = 1
        self._kw = 1
        self._pw = 1
        self._tw = 1
        self._tv = 1
        self._kg = 1
        self._c = 1

    # -- capacity ------------------------------------------------------------

    def _more_slots(self) -> int:
        if self._slot_step:
            return self._cap + self._slot_step
        return max(2 * self._cap, 64)

    def _grow(self, cap: int) -> None:
        cap = max(cap, 1)
        if cap <= self._cap:
            return
        old = self._cap

        def g1(a, dtype, fill=0):
            out = np.full(cap, fill, dtype)
            if old:
                out[:old] = a
            return out

        def g2(a, w, dtype):
            out = np.zeros((cap, w), dtype)
            if old and a is not None:
                out[:old, : a.shape[1]] = a
            return out

        if old == 0:
            self.alloc_mcpu = np.zeros(cap, np.int64)
            self.alloc_mem = np.zeros(cap, np.int64)
            self.alloc_gpu = np.zeros(cap, np.int64)
            self.alloc_pods = np.zeros(cap, np.int64)
            self.req_mcpu = np.zeros(cap, np.int64)
            self.req_mem = np.zeros(cap, np.int64)
            self.req_gpu = np.zeros(cap, np.int64)
            self.nz_mcpu = np.zeros(cap, np.int64)
            self.nz_mem = np.zeros(cap, np.int64)
            self.pod_count = np.zeros(cap, np.int64)
            self.port_mask = np.zeros((cap, 1), np.uint32)
            self.label_kv = np.zeros((cap, 1), np.uint32)
            self.label_key = np.zeros((cap, 1), np.uint32)
            self.numval = np.full((cap, 1), np.nan, np.float64)
            self.taint_mask = np.zeros((cap, 1), np.uint32)
            self.taint_count = np.zeros((cap, 1), np.int32)
            self.has_taints = np.zeros(cap, bool)
            self.taint_bad = np.zeros(cap, bool)
            self.mem_pressure = np.zeros(cap, bool)
            self.zone_id = np.zeros(cap, np.int32)
            self.class_count = np.zeros((cap, 1), np.int64)
        else:
            for f in ("alloc_mcpu", "alloc_mem", "alloc_gpu", "alloc_pods",
                      "req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem",
                      "pod_count"):
                setattr(self, f, g1(getattr(self, f), np.int64))
            for f, dt in (("port_mask", np.uint32), ("label_kv", np.uint32),
                          ("label_key", np.uint32), ("taint_mask", np.uint32),
                          ("taint_count", np.int32),
                          ("class_count", np.int64)):
                a = getattr(self, f)
                setattr(self, f, g2(a, a.shape[1], dt))
            nv = np.full((cap, self.numval.shape[1]), np.nan, np.float64)
            nv[:old] = self.numval
            self.numval = nv
            for f in ("has_taints", "taint_bad", "mem_pressure"):
                setattr(self, f, g1(getattr(self, f), bool))
            self.zone_id = g1(self.zone_id, np.int32)
        self._schedulable = g1(self._schedulable, bool, False)
        self._node_gone = g1(self._node_gone, bool, False)
        self._pod_count_slot = g1(self._pod_count_slot, np.int64)
        self.node_names += [""] * (cap - old)
        self._node_labels += [None] * (cap - old)
        self._node_images += [None] * (cap - old)
        self._port_counts += [None] * (cap - old)
        self._free += list(range(cap - 1, old - 1, -1))
        self._cap = cap
        self.vocabs.terms.grow(cap)
        self._order_dirty = True
        self._dirty_node_side = True
        self._dirty_pod_side = True

    def _widths_sync(self) -> None:
        """Grow column capacity to match vocab sizes (amortized O(1))."""
        before = (
            self.label_kv.shape, self.label_key.shape, self.port_mask.shape,
            self.taint_mask.shape, self.taint_count.shape,
            self.class_count.shape, self.numval.shape,
        )
        self._widths_sync_inner()
        after = (
            self.label_kv.shape, self.label_key.shape, self.port_mask.shape,
            self.taint_mask.shape, self.taint_count.shape,
            self.class_count.shape, self.numval.shape,
        )
        if before != after:
            self._dirty_node_side = True
            self._dirty_pod_side = True

    def _widths_sync_inner(self) -> None:
        v = self.vocabs
        lw, kw, pw = _words(len(v.kv)), _words(len(v.keys)), _words(len(v.ports))
        tw, tv = _words(len(v.taints)), max(1, len(v.taints))
        kg, c = max(1, len(v.numkeys)), max(1, len(v.classes))
        if lw > self.label_kv.shape[1]:
            self.label_kv = _grow_cols(self.label_kv, lw)
        if kw > self.label_key.shape[1]:
            self.label_key = _grow_cols(self.label_key, kw)
        if pw > self.port_mask.shape[1]:
            self.port_mask = _grow_cols(self.port_mask, pw)
        if tw > self.taint_mask.shape[1]:
            self.taint_mask = _grow_cols(self.taint_mask, tw)
        if tv > self.taint_count.shape[1]:
            self.taint_count = _grow_cols(self.taint_count, tv)
        if c > self.class_count.shape[1]:
            self.class_count = _grow_cols(self.class_count, c)
        if kg > self.numval.shape[1]:
            # new Gt/Lt key: backfill the column from retained node labels
            old_cols = self.numval.shape[1]
            nv = np.full((self._cap, kg), np.nan, np.float64)
            nv[:, :old_cols] = self.numval
            self.numval = nv
            for k, col in self.vocabs.numkeys.ids.items():
                if col < old_cols:
                    continue
                for slot, labels in enumerate(self._node_labels):
                    if labels and k in labels:
                        try:
                            self.numval[slot, col] = float(labels[k])
                        except ValueError:
                            pass

    # -- cache listener ------------------------------------------------------

    def on_cache_event(self, kind: str, obj) -> None:
        """Called under the cache lock; just queue (apply at wave time).
        A pod event is queued as what applying it takes, read while the
        cache has the pod in hand (NodeInfo has just read the same
        fields): its key and, for an add, its node and its contribution.
        Applying a wave's events then touches no pod object again."""
        if kind == "pod_add":
            obj = (_pod_key(obj), obj.spec.node_name, pod_contribution(obj))
        elif kind == "pod_remove":
            obj = (_pod_key(obj), None, None)
        with self._lock:
            self._events.append((kind, obj))

    def _drain(self) -> List[Tuple[str, object]]:
        with self._lock:
            ev, self._events = self._events, []
            return ev

    # -- event application ---------------------------------------------------

    def _apply_node_set(self, node: Node) -> None:
        name = node.metadata.name
        slot = self.slot_of.get(name)
        if slot is None:
            if not self._free:
                self._grow(self._more_slots())
            slot = self._free.pop()
            self.slot_of[name] = slot
            self.node_names[slot] = name
            self._order_dirty = True
        v = self.vocabs
        labels = dict(node.metadata.labels)
        self._node_labels[slot] = labels
        v.terms.node_set(slot, labels, int(self._pod_count_slot[slot]),
                         bool(self._node_gone[slot]))
        for k, val in labels.items():
            v.keys.get(k)
            v.kv.get((k, val))
        try:
            taints = get_taints(node)
            self.taint_bad[slot] = False
        except Exception:
            taints = []
            self.taint_bad[slot] = True
        for t in taints:
            v.taints.get((t.key, t.value, t.effect))
        zone = get_zone_key(node)
        zid = v.zones.get(zone) if zone else 0
        self._widths_sync()
        # row refresh (node-owned fields only; pod aggregates untouched)
        alloc = node.status.allocatable
        self.alloc_mcpu[slot] = resource_list_cpu_milli(alloc)
        self.alloc_mem[slot] = resource_list_memory(alloc)
        self.alloc_gpu[slot] = parse_quantity(
            alloc.get("alpha.kubernetes.io/nvidia-gpu", 0)
        ).value()
        self.alloc_pods[slot] = parse_quantity(alloc.get("pods", 0)).value()
        lw, kw = self.label_kv.shape[1], self.label_key.shape[1]
        self.label_kv[slot] = _pack_bits(
            [v.kv.ids[(k, val)] for k, val in labels.items()], lw
        )
        self.label_key[slot] = _pack_bits(
            [v.keys.ids[k] for k in labels], kw
        )
        self.numval[slot, :] = np.nan
        for k, col in v.numkeys.ids.items():
            val = labels.get(k)
            if val is not None:
                try:
                    self.numval[slot, col] = float(val)
                except ValueError:
                    pass
        tw = self.taint_mask.shape[1]
        tids = [v.taints.ids[(t.key, t.value, t.effect)] for t in taints]
        self.taint_mask[slot] = _pack_bits(tids, tw)
        self.taint_count[slot, :] = 0
        for tid in tids:
            self.taint_count[slot, tid] += 1
        self.has_taints[slot] = bool(taints)
        self.mem_pressure[slot] = any(
            c.type == "MemoryPressure" and c.status == "True"
            for c in node.status.conditions
        )
        self.zone_id[slot] = zid
        imgs: Dict[str, int] = {}
        for img in node.status.images:
            for nm in img.names:
                if nm not in imgs:
                    imgs[nm] = img.size_bytes
        self._node_images[slot] = imgs
        from kubernetes_tpu.scheduler.factory import node_schedulable

        self._schedulable[slot] = node_schedulable(node)
        self._node_gone[slot] = False

    def _free_slot(self, slot: int) -> None:
        name = self.node_names[slot]
        if name:
            del self.slot_of[name]
        self.node_names[slot] = ""
        self._node_labels[slot] = None
        self._node_images[slot] = None
        self._port_counts[slot] = None
        self._schedulable[slot] = False
        self._node_gone[slot] = False
        # zero the whole row: a freed slot behaves exactly like a pad.py
        # dummy node (zero allocatable => the resource predicate fails)
        for f in ("alloc_mcpu", "alloc_mem", "alloc_gpu", "alloc_pods",
                  "req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem",
                  "pod_count"):
            getattr(self, f)[slot] = 0
        self.port_mask[slot, :] = 0
        self.label_kv[slot, :] = 0
        self.label_key[slot, :] = 0
        self.numval[slot, :] = np.nan
        self.taint_mask[slot, :] = 0
        self.taint_count[slot, :] = 0
        self.has_taints[slot] = False
        self.taint_bad[slot] = False
        self.mem_pressure[slot] = False
        self.zone_id[slot] = 0
        self.class_count[slot, :] = 0
        self.vocabs.terms.node_gone(slot, 0)
        self._free.append(slot)
        self._order_dirty = True
        self._dirty_node_side = True
        self._dirty_pod_side = True

    def _apply_node_remove(self, node: Node) -> None:
        slot = self.slot_of.get(node.metadata.name)
        if slot is None:
            return
        if self._pod_count_slot[slot] > 0:
            # pods still reference the node (cache.go:272): keep the row
            # but never schedule onto it (the reference's snapshot drops
            # node-less NodeInfos)
            self._node_gone[slot] = True
            self._schedulable[slot] = False
            self.vocabs.terms.node_gone(slot, int(self._pod_count_slot[slot]))
        else:
            self._free_slot(slot)

    def _slot_for_pod(self, name: str) -> int:
        slot = self.slot_of.get(name)
        if slot is None:
            # pod on an unknown node (cache tolerates it); materialize a
            # gone-node slot to hold the aggregates
            if not self._free:
                self._grow(self._more_slots())
            slot = self._free.pop()
            self.slot_of[name] = slot
            self.node_names[slot] = name
            self._node_labels[slot] = {}
            self._node_images[slot] = {}
            self._node_gone[slot] = True
            self._schedulable[slot] = False
            self._order_dirty = True
            # the slot's name changed, so name_desc_order (device-resident
            # between waves) must be re-shipped even though no node event
            # fired -- wave_view's keep is driven by this flag
            self._dirty_node_side = True
        return slot

    def _bump(self, slot: int, c: PodContribution, class_id: int,
              sign: int) -> None:
        self.req_mcpu[slot] += sign * c.cpu
        self.req_mem[slot] += sign * c.mem
        self.req_gpu[slot] += sign * c.gpu
        self.nz_mcpu[slot] += sign * c.nonzero_cpu
        self.nz_mem[slot] += sign * c.nonzero_mem
        self.pod_count[slot] += sign
        self._pod_count_slot[slot] += sign
        self.class_count[slot, class_id] += sign
        terms = self.vocabs.terms
        if c.terms is not None or len(terms.specs):
            terms.apply(
                np.array([slot]), np.zeros(1, np.intp),
                np.array([sign], np.int64), [class_id],
                [None if c.terms is None else terms.owned(c.terms)],
                gone=bool(self._node_gone[slot]),
            )

    def _add_one(self, key: Tuple[str, str], node_name: str,
                 c: PodContribution) -> None:
        """One pod_add applied on the spot (the per-event path)."""
        v = self.vocabs
        slot = self._slot_for_pod(node_name)
        ports = [v.ports.get(p) for p in c.host_ports]
        class_id = v.classes.get(c.class_key)
        self._widths_sync()
        self._contribs[key] = (slot, c)
        self._bump(slot, c, class_id, 1)
        if ports:
            pc = self._port_counts[slot]
            if pc is None:
                pc = self._port_counts[slot] = {}
            for pid in ports:
                pc[pid] = pc.get(pid, 0) + 1
            self.port_mask[slot] = _pack_bits(
                list(pc), self.port_mask.shape[1]
            )

    def _remove_one(self, slot: int, c: PodContribution) -> None:
        """One held pod taken out on the spot (the per-event path); its
        `_contribs` entry is already popped."""
        v = self.vocabs
        self._bump(slot, c, v.classes.ids[c.class_key], -1)
        if c.host_ports:
            pc = self._port_counts[slot] or {}
            for port in c.host_ports:
                pid = v.ports.ids[port]
                n = pc.get(pid, 0) - 1
                if n <= 0:
                    pc.pop(pid, None)
                else:
                    pc[pid] = n
            self.port_mask[slot] = _pack_bits(
                list(pc), self.port_mask.shape[1]
            )
        if self._node_gone[slot] and self._pod_count_slot[slot] == 0:
            self._free_slot(slot)

    def _apply_pod_events(self, run: List[Tuple[str, tuple]]) -> None:
        """One run of consecutive pod events (as on_cache_event queued
        them) as a batch: per event a row lookup, a slot lookup and a
        dict insert or pop; per batch one integer scatter-add per
        aggregate. The arrays come out exactly as event-by-event
        application leaves them.

        The sums commute, so only three things depend on order, and
        each keeps it: `_contribs` is updated event by event; a spread
        class takes its vocabulary id when its contribution first
        appears, as do the terms it owns (`InterPodTables.owned`); and
        whatever touches a gone-node slot (a pod on an unknown node
        materialises one, its last pod leaving frees and zeroes it) or
        carries host ports is applied on the spot (`_add_one` /
        `_remove_one`). Within a batch a slot is either live throughout
        and takes deferred deltas only, or gone or free and takes
        immediate ones only: no node event falls inside a batch, so a
        zeroed row never meets a deferred delta. The inter-pod tables
        take the batch's deferred deltas in one `InterPodTables.apply`,
        which is a length test where no pod of the batch owns a term
        and none matches a spec."""
        contribs = self._contribs
        slot_of = self.slot_of
        classes = self.vocabs.classes
        terms = self.vocabs.terms
        # id(contribution) -> its row in `sums` / `class_ids`, or -1 for
        # one that goes event by event; `alive` pins each contribution so
        # that an id names one of them for the whole batch
        rows: Dict[int, int] = {}
        alive: List[PodContribution] = []
        sums: List[Tuple[int, int, int, int, int]] = []
        class_ids: List[int] = []
        owned: list = []  # per row: InterPodTables.owned, or None

        def first_seen(c: PodContribution) -> int:
            alive.append(c)
            if c.host_ports:
                rows[id(c)] = -1
                return -1
            known = len(classes)
            class_ids.append(classes.get(c.class_key))
            if len(classes) != known:
                self._widths_sync()
            owned.append(None if c.terms is None else terms.owned(c.terms))
            sums.append((c.cpu, c.mem, c.gpu, c.nonzero_cpu, c.nonzero_mem))
            rows[id(c)] = row = len(sums) - 1
            return row

        # deferred deltas, as (slot, row) pairs: pods in, pods out
        in_at: List[int] = []
        in_row: List[int] = []
        out_at: List[int] = []
        out_row: List[int] = []
        any_gone = bool(self._node_gone.any())
        fallbacks = 0
        for kind, (key, node_name, added) in run:
            # an add of a pod already held is an update: out, then in
            held = contribs.pop(key, None)
            if held is not None:
                slot, c = held
                row = rows.get(id(c))
                if row is None:
                    row = first_seen(c)
                if row < 0 or (any_gone and self._node_gone[slot]):
                    self._remove_one(slot, c)
                    fallbacks += 1
                else:
                    out_at.append(slot)
                    out_row.append(row)
            if kind == "pod_remove":
                continue
            row = rows.get(id(added))
            if row is None:
                row = first_seen(added)
            slot = slot_of.get(node_name)
            if (row < 0 or slot is None
                    or (any_gone and self._node_gone[slot])):
                self._add_one(key, node_name, added)
                any_gone = True  # it may have materialised a gone slot
                fallbacks += 1
                continue
            contribs[key] = (slot, added)
            in_at.append(slot)
            in_row.append(row)
        if in_at or out_at:
            slots = np.array(in_at + out_at, np.intp)
            row_of = np.array(in_row + out_row, np.intp)
            sign = np.ones(len(slots), np.int64)
            sign[len(in_at):] = -1
            deltas = np.array(sums, np.int64)[row_of] * sign[:, None]
            for col, f in enumerate(
                ("req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem")
            ):
                np.add.at(getattr(self, f), slots, deltas[:, col])
            np.add.at(self.pod_count, slots, sign)
            np.add.at(self._pod_count_slot, slots, sign)
            np.add.at(
                self.class_count,
                (slots, np.array(class_ids, np.intp)[row_of]), sign,
            )
            if len(terms.specs) or any(o is not None for o in owned):
                terms.apply(slots, row_of, sign, class_ids, owned)
        self._dirty_pod_side = True
        trace_profile.count_encoder_batch(len(run), fallbacks)

    def apply_pending(self) -> None:
        run: List[Tuple[str, tuple]] = []  # consecutive pod events
        for event in self._drain():
            kind, obj = event
            if kind == "pod_add" or kind == "pod_remove":
                run.append(event)
                continue
            if run:  # a node event ends the batch: order against it holds
                self._apply_pod_events(run)
                run = []
            if kind == "node_set":
                self._apply_node_set(obj)
                self._dirty_node_side = True
            elif kind == "node_remove":
                self._apply_node_remove(obj)
                self._dirty_node_side = True
                self._dirty_pod_side = True
        if run:
            self._apply_pod_events(run)
        self._sync_terms()

    def _sync_terms(self) -> None:
        """The inter-pod tables brought up to the events applied and
        the terms interned (rebuilt whole where a node event left them
        stale); cheap where neither moved."""
        self._widths_sync()
        self.vocabs.terms.sync(self.class_count, self._contribs,
                               self._node_gone)

    def take_rebuilds(self) -> Dict[str, int]:
        """Whole rebuilds of the inter-pod tables since the last call,
        by reason: for the caller's counters (models/wave.count_encoder)."""
        terms = self.vocabs.terms
        taken, terms.rebuilds = terms.rebuilds, {}
        return taken

    # -- wave view -----------------------------------------------------------

    def _config_ok(self) -> bool:
        from kubernetes_tpu.models.batch import wants_resources

        cfg = self.config
        if cfg is None:
            return True
        if not wants_resources(cfg):
            return False  # free slots are masked via zeroed allocatable
        if service_config_labels(cfg):
            return False  # SA/SAA programs need the full compiler
        return True

    def _scope_gate(self, pending: Sequence[Pod]) -> Optional[str]:
        """Why this wave's snapshot cannot come from the kept state, if
        it cannot: "config" (a policy the kept tables do not cover),
        "volumes" (a pending pod mounts one). The caller counts it
        (models/wave.count_encoder)."""
        if not self._config_ok():
            return "config"
        for p in pending:
            if p.spec.volumes:
                return "volumes"
        return None

    # snapshot fields per dirty group, for device-array reuse between
    # waves (models/wave.py `keep` protocol)
    NODE_SIDE_FIELDS = frozenset({
        "alloc_mcpu", "alloc_mem", "alloc_gpu", "alloc_pods",
        "label_kv", "label_key", "numval", "taint_mask", "taint_count",
        "has_taints", "taint_bad", "mem_pressure", "zone_id",
        "name_desc_order", "noschedule_taints", "prefer_taints",
    })
    POD_SIDE_FIELDS = frozenset({
        "req_mcpu", "req_mem", "req_gpu", "nz_mcpu", "nz_mem",
        "pod_count", "port_mask", "class_count",
    })
    # the inter-pod program (InterPodTables.snapshot_fields): what the
    # node events and the term vocabulary shape, and the counting tables
    # that bound pods move. Zero-width, and kept, where no pod ever
    # carried a term
    TERM_STATIC_FIELDS = frozenset({
        "ip_topo_dom", "ip_u_topo", "ip_u_spec", "ip_lt_spec", "ip_lt_u",
        "ip_lt_sign",
    })
    TERM_CARRY_FIELDS = frozenset({
        "ip_term_count", "ip_own_anti", "ip_rev_hard", "ip_rev_pref",
        "ip_rev_anti", "ip_spec_total",
    })
    # deterministically empty under the wave gates: reusable by shape
    WAVE_CONST_FIELDS = frozenset({
        "vol_any", "vol_rw", "ebs_mask", "gce_mask", "ebs_bad", "gce_bad",
        "vz_zone", "vz_region", "vz_has",
        "svc_lbl_val", "svc_node_ord", "svc_ord_node", "svc_first_peer",
        "svc_peer_node_count", "svc_peer_total",
    })

    def wave_view(
        self,
        pending: Sequence[Pod],
        services=(),
        controllers=(),
        replica_sets=(),
        keys: Optional[Sequence[tuple]] = None,
    ) -> Tuple[Optional[ClusterSnapshot], Optional[PodBatch], frozenset]:
        """Apply queued deltas and emit (snapshot, batch, keep) for this
        wave — `keep` names snapshot fields whose device copies from the
        previous wave are still valid — or (None, None, ø) when a scope
        gate forces the full encoder.

        `keys` are the pending pods' `pod_feature_key`s where the caller
        has them (its dedup computed them). The batch is field for field
        what `encode_pods` over `pending` and the three listers gives,
        but a row whose key the store holds is gathered, not encoded:
        it is reused while the listers' entries that select it, the
        taints seen and the config are the ones it was made under, and
        is repaired (its spread columns), extended (classes first seen
        since) or rebuilt (a taint first seen) in this call otherwise —
        see snapshot/pending_rows.py for each dependency."""
        self.apply_pending()
        self.fallback = self._scope_gate(pending)
        if self.fallback is not None:
            return None, None, frozenset()
        # the wave's encoder interns the pending pods' vocabulary into
        # the shared bundle and holds what is the wave's own (its image
        # vocabulary, the empty per-batch programs); the light state
        # carries only the spread listers (no node scan). The rows come
        # from the store: only a key it does not hold is encoded.
        light = ClusterState(
            services=list(services),
            controllers=list(controllers),
            replica_sets=list(replica_sets),
        )
        enc = SnapshotEncoder(
            light, list(pending), config=self.config, vocabs=self.vocabs,
            visit_state=False, node_id=dict(self.slot_of),
        )
        if keys is None:
            keys = [pod_feature_key(p) for p in pending]
        batch = self.rows.batch(
            enc, keys, light.services, light.controllers,
            light.replica_sets,
        )
        self._sync_terms()  # the wave's own terms may be first seen
        keep = set(self.WAVE_CONST_FIELDS)
        if not self._dirty_node_side:
            keep |= self.NODE_SIDE_FIELDS
        if not self._dirty_pod_side:
            keep |= self.POD_SIDE_FIELDS
        terms = self.vocabs.terms
        if terms.static_version == self._last_terms[0]:
            keep |= self.TERM_STATIC_FIELDS
        if terms.carry_version == self._last_terms[1]:
            keep |= self.TERM_CARRY_FIELDS
            self.reship = frozenset()
        else:
            self.reship = self.TERM_CARRY_FIELDS
        self._last_terms = (terms.static_version, terms.carry_version)
        if len(self.vocabs.set_members) == self._last_sets_len:
            keep.add("set_table")
        img_vocab = tuple(enc.images.ids)
        if img_vocab == self._last_img_vocab and not self._dirty_node_side:
            keep.add("img_size")
        self._dirty_node_side = False
        self._dirty_pod_side = False
        self._last_sets_len = len(self.vocabs.set_members)
        self._last_img_vocab = img_vocab
        snap = self._snapshot_arrays(enc)
        return snap, batch, frozenset(keep)

    def _snapshot_arrays(self, enc: SnapshotEncoder) -> ClusterSnapshot:
        v = self.vocabs
        w = enc.widths
        N = self._cap
        if self._order_dirty:
            self._name_desc = np.argsort(
                np.array(self.node_names, dtype=object), kind="stable"
            )[::-1].astype(np.int32)
            self._order_dirty = False
        # unschedulable/gone slots: zero allocatable == never fit, and
        # (being unfit) excluded from every normalizer — identical to the
        # reference's restricted snapshot dropping them
        live = self._schedulable
        alloc_mcpu = np.where(live, self.alloc_mcpu, 0)
        alloc_mem = np.where(live, self.alloc_mem, 0)
        alloc_gpu = np.where(live, self.alloc_gpu, 0)
        alloc_pods = np.where(live, self.alloc_pods, 0)

        def cut(a, cols):
            return a[:, :cols] if a.shape[1] != cols else a

        img_names = list(enc.images.ids)
        img_size = np.zeros((N, len(img_names)), np.int64)
        for j, nm in enumerate(img_names):
            for slot, imgs in enumerate(self._node_images):
                if imgs:
                    sz = imgs.get(nm)
                    if sz:
                        img_size[slot, j] = sz
        empty_i32 = np.zeros(0, np.int32)
        return ClusterSnapshot(
            node_names=list(self.node_names),
            alloc_mcpu=alloc_mcpu,
            alloc_mem=alloc_mem,
            alloc_gpu=alloc_gpu,
            alloc_pods=alloc_pods,
            req_mcpu=self.req_mcpu.copy(),
            req_mem=self.req_mem.copy(),
            req_gpu=self.req_gpu.copy(),
            nz_mcpu=self.nz_mcpu.copy(),
            nz_mem=self.nz_mem.copy(),
            pod_count=self.pod_count.copy(),
            port_mask=cut(self.port_mask, w["PW"]).copy(),
            label_kv=cut(self.label_kv, w["LW"]),
            label_key=cut(self.label_key, w["KW"]),
            numval=cut(self.numval, w["KG"]),
            taint_mask=cut(self.taint_mask, w["TW"]),
            taint_count=cut(self.taint_count, w["TV"]),
            has_taints=self.has_taints,
            taint_bad=self.taint_bad,
            mem_pressure=self.mem_pressure,
            zone_id=self.zone_id,
            class_count=cut(self.class_count, w["C"]).copy(),
            name_desc_order=self._name_desc,
            set_table=build_set_table(
                v.set_members, v.kv.ids, w["LW"]
            ),
            noschedule_taints=self._taint_effect_mask("NoSchedule", w["TW"]),
            prefer_taints=self._taint_effect_mask("PreferNoSchedule", w["TW"]),
            **v.terms.snapshot_fields(),
            # wave pods carry no volumes (gate), so the node-side volume
            # state is vacuous — but the arrays must still be node-axis
            # shaped for the predicate ops (the light compiler saw zero
            # nodes). Widths follow the pod-side masks.
            vol_any=np.zeros((N, enc.volumes.p_vol_rw.shape[1]), np.uint32),
            vol_rw=np.zeros((N, enc.volumes.p_vol_rw.shape[1]), np.uint32),
            ebs_mask=np.zeros((N, enc.volumes.p_ebs.shape[1]), np.uint32),
            gce_mask=np.zeros((N, enc.volumes.p_gce.shape[1]), np.uint32),
            ebs_bad=np.zeros(N, bool),
            gce_bad=np.zeros(N, bool),
            vz_zone=np.zeros(N, np.int32),
            vz_region=np.zeros(N, np.int32),
            vz_has=np.zeros(N, bool),
            img_size=img_size,
            key_ids=dict(v.keys.ids),
            svc_lbl_val=enc.services_program.lbl_val,
            svc_node_ord=enc.services_program.node_ord,
            svc_ord_node=enc.services_program.ord_node,
            svc_first_peer=enc.services_program.first_peer,
            svc_peer_node_count=enc.services_program.peer_node_count,
            svc_peer_total=enc.services_program.peer_total,
            svc_labels=enc.services_program.labels,
            svc_num_values=0,
        )

    def _taint_effect_mask(self, effect: str, tw: int) -> np.ndarray:
        return _pack_bits(
            [
                tid
                for (k, val, eff), tid in self.vocabs.taints.ids.items()
                if eff == effect
            ],
            tw,
        )
