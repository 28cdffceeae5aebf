"""A template's pending-side row is encoded once and kept
(snapshot/pending_rows.py): what IncrementalEncoder.wave_view assembles
from stored rows equals, field by field, what a fresh
SnapshotEncoder.encode_pods gives, whatever happened between two waves;
and the work it takes is bounded without a clock."""

import copy
import dataclasses
import random

import numpy as np
import pytest

from kubernetes_tpu.api import labels as labelpkg
from kubernetes_tpu.api.types import (
    Container,
    ContainerImage,
    LabelSelector,
    LabelSelectorRequirement,
    Node,
    NodeCondition,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ReplicaSet,
    ReplicaSetSpec,
    ReplicationController,
    ReplicationControllerSpec,
    Service,
    ServiceSpec,
    Taint,
    Toleration,
)
from kubernetes_tpu.oracle import ClusterState
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.snapshot.encode import SnapshotEncoder, pod_feature_key
from kubernetes_tpu.snapshot.incremental import IncrementalEncoder
from kubernetes_tpu.trace import profile
from kubernetes_tpu.utils.clock import FakeClock

from tests.test_conformance import random_scenario

IMAGES = ("kubernetes/pause:go", "nginx:1.9", "redis:3")


def _same(got, want, ctx):
    """Every field of two snapshots or two batches: value, dtype, shape."""
    assert type(got) is type(want)
    for f in dataclasses.fields(want):
        x, y = getattr(got, f.name), getattr(want, f.name)
        if isinstance(y, np.ndarray):
            assert isinstance(x, np.ndarray), f"{ctx}: {f.name}"
            assert x.dtype == y.dtype, f"{ctx}: {f.name} {x.dtype} {y.dtype}"
            assert x.shape == y.shape, f"{ctx}: {f.name} {x.shape} {y.shape}"
            assert np.array_equal(x, y, equal_nan=y.dtype.kind == "f"), \
                f"{ctx}: {f.name}"
        else:
            assert x == y, f"{ctx}: {f.name}"


def _fresh_view(inc, pending, services, controllers, replica_sets):
    """What wave_view did before rows were kept: every pending pod
    through encode_pods, against the encoder's own vocabularies."""
    inc.apply_pending()
    light = ClusterState(services=list(services),
                         controllers=list(controllers),
                         replica_sets=list(replica_sets))
    enc = SnapshotEncoder(light, list(pending), config=inc.config,
                          vocabs=inc.vocabs, visit_state=False,
                          node_id=dict(inc.slot_of))
    batch = enc.encode_pods()
    inc._sync_terms()  # the wave's own terms, where they are first seen
    inc._dirty_node_side = inc._dirty_pod_side = False
    return inc._snapshot_arrays(enc), batch


class _Cluster:
    """A scheduler cache feeding two incremental encoders the same
    events: `kept` assembles its waves from stored rows, `plain` encodes
    every pending pod of every wave."""

    def __init__(self, rng, interpod_p=0.0):
        self.rng = rng
        state, templates = random_scenario(
            rng, n_nodes=10, n_existing=14, n_pending=24,
            interpod_p=interpod_p)
        for i, t in enumerate(templates):
            for c in t.spec.containers:
                c.image = rng.choice(IMAGES)
            t.metadata.labels = dict(t.metadata.labels)
            if i % 5 == 0:
                t.metadata.labels["rev"] = str(i)
        self.templates = templates
        # nothing selects the `db` pods until an event does
        self.services = [s for s in state.services
                         if s.metadata.name != "db"]
        self.controllers = list(state.controllers)
        self.replica_sets = []
        self.cache = SchedulerCache(clock=FakeClock())
        self.kept, self.plain = IncrementalEncoder(), IncrementalEncoder()
        self.cache.add_listener(self.kept.on_cache_event)
        self.cache.add_listener(self.plain.on_cache_event)
        for info in state.node_infos.values():
            self.cache.add_node(info.node)
            for p in info.pods:
                self.cache.add_pod(p)
        self.serial = 0

    def wave(self, ctx):
        """One wave of template clones, some templates twice, in a
        seeded order; both encoders' views compared."""
        rng = self.rng
        pending = []
        for t in rng.sample(self.templates,
                            rng.randint(len(self.templates) // 2,
                                        len(self.templates))):
            for _ in range(rng.choice([1, 1, 2])):
                self.serial += 1
                clone = copy.deepcopy(t)
                clone.metadata.name = f"w-{self.serial:05d}"
                pending.append(clone)
        rng.shuffle(pending)
        listers = (self.services, self.controllers, self.replica_sets)
        snap, batch, _keep = self.kept.wave_view(
            pending, *listers,
            keys=[pod_feature_key(p) for p in pending])
        want_snap, want_batch = _fresh_view(self.plain, pending, *listers)
        assert snap is not None, ctx
        _same(batch, want_batch, ctx)
        _same(snap, want_snap, ctx)
        for vocab in ("classes", "ports", "kv", "keys", "numkeys", "taints"):
            assert (list(getattr(self.kept.vocabs, vocab).ids.items())
                    == list(getattr(self.plain.vocabs, vocab).ids.items())), \
                f"{ctx}: vocabulary {vocab}"
        return batch

    def node(self, name, labels=None, taints=None, images=()):
        return Node(
            metadata=ObjectMeta(name=name, labels={
                "kubernetes.io/hostname": name, **(labels or {})}),
            spec=NodeSpec(taints=taints),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "8Gi", "pods": "110"},
                conditions=[NodeCondition("Ready", "True")],
                images=list(images)))

    def bound(self, name, labels, node="node-001", deleting=False):
        return Pod(
            metadata=ObjectMeta(
                name=name, labels=labels,
                deletion_timestamp="2026-01-01T00:00:00Z" if deleting
                else None),
            spec=PodSpec(node_name=node, containers=[
                Container(requests={"cpu": "100m"})]))

    def template(self, name, **spec):
        return Pod(
            metadata=ObjectMeta(name=name, labels={"app": "web"}),
            spec=PodSpec(containers=[Container(
                image=IMAGES[0], requests={"cpu": "100m"})], **spec))


def _controller_added(c):
    c.controllers.append(ReplicationController(
        metadata=ObjectMeta(name="db-rc"),
        spec=ReplicationControllerSpec(selector={"app": "db"})))


def _controller_deleted(c):
    c.controllers[:] = [rc for rc in c.controllers
                        if rc.metadata.name != "cache-rc"]


def _selector_edited(c):
    # the listed object itself, edited where it stands
    rc = next(rc for rc in c.controllers if rc.metadata.name == "cache-rc")
    rc.spec.selector = {"app": "db"}


def _service_added(c):
    c.services.append(Service(
        metadata=ObjectMeta(name="db"),
        spec=ServiceSpec(selector={"app": "db"})))


def _replica_set_added(c):
    c.replica_sets.append(ReplicaSet(
        metadata=ObjectMeta(name="db-rs"),
        spec=ReplicaSetSpec(selector=LabelSelector(
            match_labels={"app": "db"},
            match_expressions=(LabelSelectorRequirement(
                "rev", "NotIn", ("0",)),)))))


def _new_class_bound(c):
    # a label set no pod carried yet, which the `web` service selects
    c.cache.add_pod(c.bound("canary", {"app": "web", "track": "canary"}))


def _deleting_pod(c):
    # a bound pod on its way out is a class of its own that no selector
    # counts; a pending one is a template of its own
    c.cache.add_pod(c.bound("leaving", {"app": "web"}, deleting=True))
    going = c.template("going")
    going.metadata.deletion_timestamp = "2026-01-01T00:00:00Z"
    c.templates.append(going)


def _node_label(c):
    c.cache.add_node(c.node("node-lbl", labels={
        f"rack-{i}": f"r{i}" for i in range(40)}))


def _node_taint(c):
    c.cache.add_node(c.node("node-tnt", taints=[
        Taint(key="fresh", value="v", effect="PreferNoSchedule"),
        Taint(key="fresh", value="v", effect="NoSchedule")]))
    c.templates.append(c.template("tolerant", tolerations=[
        Toleration(key="fresh", operator="Exists", effect="")]))


def _node_image(c):
    c.cache.add_node(c.node("node-img", images=[
        ContainerImage(names=[IMAGES[1]], size_bytes=300 * 1024 * 1024)]))


def _names_its_node(c):
    # one node the cache knows, one it comes to know a wave later
    c.templates.append(c.template("pinned", node_name="node-002"))
    c.templates.append(c.template("early", node_name="node-late"))


def _selector_value_seen_later(c):
    c.templates.append(c.template("blue", node_selector={"pool": "blue"}))


def _late_arrivals(c):
    """What the two events above wait for, one wave after them."""
    c.cache.add_node(c.node("node-late", labels={"pool": "blue"}))


EVENTS = {
    "controller-added": _controller_added,
    "controller-deleted": _controller_deleted,
    "selector-edited": _selector_edited,
    "service-added": _service_added,
    "replica-set-added": _replica_set_added,
    "new-class-bound": _new_class_bound,
    "deleting-pod": _deleting_pod,
    "node-label": _node_label,
    "node-taint": _node_taint,
    "node-image": _node_image,
    "names-its-node": _names_its_node,
    "selector-value-seen-later": _selector_value_seen_later,
}


@pytest.mark.parametrize("event", sorted(EVENTS))
def test_kept_rows_equal_a_fresh_encode(event):
    for seed in range(3):
        c = _Cluster(random.Random(7000 + seed))
        for w in range(2):
            c.wave(f"{event} seed {seed} wave {w}")
        EVENTS[event](c)
        c.wave(f"{event} seed {seed}, the wave after")
        _late_arrivals(c)
        for w in range(2):
            c.wave(f"{event} seed {seed} late wave {w}")
        rows = c.kept.rows
        assert rows.hits > 0 and rows.misses > 0
        # only a taint first seen rebuilds the store: once for the
        # event's, once for nothing else
        assert rows.resets == (1 if event == "node-taint" else 0), event
        assert c.plain.rows.hits == c.plain.rows.misses == 0


# -- the inter-pod columns of a kept row ------------------------------------------

def _terms(side, required=(), preferred=()):
    """An affinity annotation: `required` terms as (labels, topology
    key[, namespaces]), `preferred` ones with their weight first."""
    from tests.test_incremental import _term, _terms_json

    return _terms_json(side, [_term(*t) for t in required],
                       [(w, _term(*t)) for w, *t in preferred])


def _with_terms(pod, annotation):
    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    pod.metadata.annotations[AFFINITY_ANNOTATION] = annotation
    return pod


HOSTNAME = "kubernetes.io/hostname"


def _spec_seen_later(c):
    # a selector no term had: every stored row meets the spec once
    c.templates.append(_with_terms(c.template("picky"), _terms(
        "podAffinity", [({"app": "web", "track": "canary"}, HOSTNAME)])))


def _term_seen_later(c):
    # specs the scenario's terms may hold already, under keys and in
    # tables none of them used: logical terms first seen, rows widened
    c.templates.append(_with_terms(c.template("wide"), _terms(
        "podAntiAffinity", [({"app": "web"}, "rack"), ({"app": "db"}, "")],
        [(7, {"app": "web"}, "rack"), (0, {"app": "db"}, HOSTNAME)])))


def _longer_lists(c):
    # more terms of each kind than any row of the store has
    c.templates.append(_with_terms(c.template("many"), _terms(
        "podAffinity",
        [({"app": v}, HOSTNAME) for v in ("web", "db", "cache", "x")],
        [(w, {"app": "web"}, k) for w, k in
         ((1, HOSTNAME), (2, "rack"), (3, ""), (4, "zone-b"), (5, "c"))])))


def _bound_owner(c):
    # bound pods that own terms: the counting tables move, and a spec
    # comes from the cluster's side that the rows have to be matched to
    c.cache.add_pod(_with_terms(
        c.bound("owner-0", {"app": "guard"}), _terms(
            "podAntiAffinity", [({"app": "web"}, HOSTNAME, [])],
            [(9, {"app": "db"}, "")])))
    c.cache.add_pod(_with_terms(
        c.bound("owner-1", {"app": "guard"}, node="node-004"), _terms(
            "podAffinity", [({"app": "guard"}, "")])))


def _owner_without_a_node(c):
    # the symmetric check rejects every node for the pods its spec
    # matches: a wave's `ip_sym_reject`, no row's
    c.cache.add_pod(_with_terms(
        c.bound("lost", {"app": "guard"}, node="node-never"), _terms(
            "podAntiAffinity", [({"app": "web"}, HOSTNAME)])))
    c.templates.append(_with_terms(c.template("wary"), _terms(
        "podAntiAffinity", [({"app": "none"}, HOSTNAME)])))


def _bound_pod_does_not_parse(c):
    c.cache.add_pod(_with_terms(c.bound("odd", {"app": "odd"}), "{"))
    c.templates.append(_with_terms(c.template("strange"), "[not json"))


TERM_EVENTS = {
    "spec-seen-later": _spec_seen_later,
    "term-seen-later": _term_seen_later,
    "longer-lists": _longer_lists,
    "bound-owner": _bound_owner,
    "owner-without-a-node": _owner_without_a_node,
    "bound-pod-does-not-parse": _bound_pod_does_not_parse,
}


@pytest.mark.parametrize("event", sorted(TERM_EVENTS))
def test_kept_term_rows_equal_a_fresh_encode(event):
    """Templates and bound pods that own inter-pod terms (the fuzz's,
    as `spec.affinity`; the events', as annotations): every `ip_*`
    field of the batch assembled from kept rows, and of the snapshot
    made from kept tables, is what a fresh encode over the same
    vocabularies gives, before the event and after."""
    for seed in range(3):
        c = _Cluster(random.Random(7300 + seed), interpod_p=0.4)
        for w in range(2):
            c.wave(f"{event} seed {seed} wave {w}")
        terms = c.kept.vocabs.terms
        before = len(terms.specs), len(terms.lts)
        assert min(before) > 0, "the fuzz made no term"
        TERM_EVENTS[event](c)
        for w in range(2):
            batch = c.wave(f"{event} seed {seed}, wave {w} after")
        if event.endswith("seen-later") or event == "bound-owner":
            assert (len(terms.specs), len(terms.lts)) > before
        if event == "owner-without-a-node":
            assert batch.ip_sym_reject.any() \
                and not batch.ip_sym_reject.all()
        assert batch.ip_poison.all() == (
            event == "bound-pod-does-not-parse")
        rows = c.kept.rows
        assert rows.hits > 0 and rows.misses > 0 and rows.resets == 0
        assert c.kept.fallback is None


def test_a_full_store_drops_the_rows_the_wave_does_not_use(monkeypatch):
    from kubernetes_tpu.snapshot.pending_rows import PendingRows

    monkeypatch.setattr(PendingRows, "MAX_ROWS", 16)
    c = _Cluster(random.Random(7100))
    for w in range(6):
        c.wave(f"wave {w}")
        assert len(c.kept.rows) <= len(c.templates)
    assert c.kept.rows.resets == 0


# -- a work bound without a clock ----------------------------------------------

T = 500


def _deployment(t):
    return Pod(
        metadata=ObjectMeta(
            name=f"p-{t}", labels={"rc": f"rc-{t}", "grp": f"g{t % 50}"}),
        spec=PodSpec(containers=[Container(
            image=IMAGES[0], requests={"cpu": "100m", "memory": "500Mi"})]))


def test_selectors_are_matched_once_per_template_not_once_per_wave(monkeypatch):
    calls = [0]
    matches = labelpkg.Selector.matches

    def counted(self, labels):
        calls[0] += 1
        return matches(self, labels)

    monkeypatch.setattr(labelpkg.Selector, "matches", counted)
    controllers = [ReplicationController(
        metadata=ObjectMeta(name=f"rc-{t}"),
        spec=ReplicationControllerSpec(selector={"rc": f"rc-{t}"}))
        for t in range(T)]
    inc = IncrementalEncoder()
    inc.on_cache_event("node_set", Node(
        metadata=ObjectMeta(name="n0"),
        status=NodeStatus(allocatable={"cpu": "4", "memory": "8Gi",
                                       "pods": "110"})))
    pods = [_deployment(t) for t in range(T)]
    keys = [pod_feature_key(p) for p in pods]
    shown = profile.pending_row_totals()

    def wave(matched=T):
        before = calls[0], inc.rows.hits, inc.rows.misses
        _snap, batch, _keep = inc.wave_view(
            pods, controllers=controllers, keys=keys)
        assert batch.has_selectors.sum() == T
        assert batch.spread_match.sum() == matched
        return (calls[0] - before[0], inc.rows.hits - before[1],
                inc.rows.misses - before[2])

    work, hits, misses = wave()
    assert (hits, misses) == (0, T)
    # per template: the controllers whose selector carries one of its
    # label pairs (its own), then that selector against the classes that
    # carry the pair (its own): nothing per (template, controller) pair,
    # nor per (template, class) pair, since the selectors and the
    # classes are found by label pair
    assert T <= work <= 3 * T
    for _ in range(2):
        assert wave() == (0, T, 0)  # no selector evaluated at all
    # one more controller, selecting ten of the templates: its selector
    # meets each row once and its column is added to those ten rows
    controllers.append(ReplicationController(
        metadata=ObjectMeta(name="grp"),
        spec=ReplicationControllerSpec(selector={"grp": "g7"})))
    # (each of the ten now also counts the other nine's pods)
    work, hits, misses = wave(T + 10 * 9)
    assert (hits, misses) == (T, 0)
    assert T <= work <= T + 10 * T
    assert wave(T + 10 * 9) == (0, T, 0)
    assert inc.rows.resets == 0
    moved = {k: v - shown[k]
             for k, v in profile.pending_row_totals().items()}
    assert moved == {"row_hits": 4 * T, "row_misses": T, "row_resets": 0}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 5])
def test_lookups_by_label_pair_find_what_meeting_every_entry_finds(seed):
    """SpreadSelectors.selecting and spread_match_row look selectors
    and classes up by label pair; the plain definitions, written out
    here, meet every entry and every class. Same answers, in the same order, on
    selectors of every kind: sets, match-all, In with several values,
    NotIn, Exists, and the same labels in another namespace."""
    from kubernetes_tpu.snapshot.encode import (
        ClassPairs,
        SpreadSelectors,
        spread_match_row,
    )

    rng = random.Random(seed)
    vals = ["a", "b", "c", "d"]

    def labels():
        return {k: rng.choice(vals) for k in ("app", "tier", "rel")
                if rng.random() < 0.7}

    def expression():
        op = rng.choice(["In", "In", "NotIn", "Exists", "DoesNotExist"])
        return LabelSelectorRequirement(
            key=rng.choice(["app", "tier", "rel"]), operator=op,
            values=tuple(rng.sample(vals, rng.randint(1, 3)))
            if op in ("In", "NotIn") else ())

    controllers = [ReplicationController(
        metadata=ObjectMeta(name=f"rc{i}",
                            namespace=rng.choice(["default", "other"])),
        spec=ReplicationControllerSpec(selector=labels()))
        for i in range(40)]
    replica_sets = [ReplicaSet(
        metadata=ObjectMeta(name=f"rs{i}",
                            namespace=rng.choice(["default", "other"])),
        spec=ReplicaSetSpec(selector=LabelSelector(
            match_labels=labels() if rng.random() < 0.5 else {},
            match_expressions=tuple(expression()
                                    for _ in range(rng.randint(0, 2))))))
        for i in range(40)]
    spread = SpreadSelectors()
    spread.sync((), controllers, replica_sets)
    class_list = [(rng.choice(["default", "other"]),
                   frozenset(labels().items()), rng.random() < 0.1)
                  for _ in range(120)]
    pairs = ClassPairs().extend(class_list[:70]).extend(class_list)
    for step in range(3):
        for _ in range(60):
            ns, lbls = rng.choice(["default", "other", "none"]), labels()
            plain = [k for k, s in spread._by_ns.get(ns, {}).items()
                     if s.matches(lbls)]
            assert spread.selecting(ns, lbls) == plain
            selectors = [spread.entries[k] for k in plain]
            start = rng.choice([0, 0, 50])
            want = np.zeros(len(class_list), np.int64)
            got = want.copy()
            for c in range(start, len(class_list)):
                c_ns, c_labels, deleted = class_list[c]
                want[c] = not deleted and c_ns == ns and any(
                    s.matches(dict(c_labels)) for s in selectors)
            spread_match_row(selectors, ns, class_list, got, pairs,
                             start=start)
            assert np.array_equal(got, want)
            # a shorter class list bounds the columns written
            short = np.zeros(60, np.int64)
            spread_match_row(selectors, ns, class_list[:60], short, pairs,
                             start=start)
            assert np.array_equal(short, want[:60])
        # entries go and come: the lookups follow
        gone = rng.sample(range(40), 10)
        controllers = [c for i, c in enumerate(controllers)
                       if i not in gone]
        replica_sets = replica_sets[step * 5:]
        added, removed = spread.sync((), controllers, replica_sets)
        assert removed and not added
