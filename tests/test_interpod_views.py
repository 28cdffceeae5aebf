"""The scan's carried inter-pod views (ops/interpod.Views).

`gather_counts` + `expand_lt` and `gather_lt` define a view: the
per-node expansion of a domain table. `jit_batch_scan` gathers the five
once a dispatch and commits every pick into them on the picked node's
domain (`interpod_commit_views`) while `interpod_commit` scatters the
same pick into the tables. Here: after every one of k random commits
the carried views are the committed tables' views bit for bit, over the
shapes a deployment can give them; the scan on the 48-node cut of the
two term deployments picks as the serial oracle does and hands back the
carry that the per-step gather (the step before the views, which the
step still is for whoever passes no views) hands back; and the step's
jaxpr holds no gather over the node axis' domain ids.
"""

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.models.batch import (
    BatchScheduler,
    SchedulerConfig,
    _run_steps,
    _scan_fn,
    scan_backlog,
)
from kubernetes_tpu.ops import interpod as IP

N = 24  # node slots of the synthetic shapes; the last three are padding
REAL = N - 3


def _zone(rng):
    return (np.arange(N) % 3)[None, :], 3


def _hostname(rng):
    return np.arange(N)[None, :], N


def _both(rng):
    return np.stack([np.arange(N) % 3, np.arange(N)]), N


def _unlabelled(rng):
    """Two combos; a third of the nodes lack a combo's label and the
    padded slots lack both."""
    dom, width = _both(rng)
    dom = np.where(rng.random(dom.shape) < 0.33, -1, dom)
    dom[:, REAL:] = -1
    return dom, width


#: name -> (topo_dom builder, E, a commit's pick: "placed" every step /
#: "mixed" with unscheduled steps, chosen -1 and padded pods, weights)
SHAPES = {
    "zone": (_zone, 1, "placed", 100),
    "hostname": (_hostname, 1, "placed", 100),
    "zone_and_hostname": (_both, 1, "placed", 100),
    "two_slots_signed": (_both, 2, "placed", 100),
    "unlabelled_and_padded_nodes": (_unlabelled, 2, "placed", 100),
    "unscheduled_and_padded_pods": (_unlabelled, 2, "mixed", 100),
    "int64_weights_past_2_31": (_both, 2, "placed", 2 ** 31 - 150),
}


def _tables(name, k, seed):
    """A random inter-pod program in the shapes' own dtypes, its tables
    after some history, and k pods with the node each was committed to."""
    build, E, picks, weight = SHAPES[name]
    rng = np.random.default_rng(seed)
    topo_dom, D = build(rng)
    topo_dom = topo_dom.astype(np.int32)
    Q = topo_dom.shape[0]
    U, S, LT = 3 * Q, 4, 10
    u_topo = (np.arange(U) % Q).astype(np.int32)
    u_spec = rng.integers(0, S, U).astype(np.int32)
    lt_u = rng.integers(0, U, (LT, E)).astype(np.int32)
    lt_sign = np.ones((LT, E), np.int8)
    if E > 1:
        # inclusion-exclusion: a second slot subtracts, or is unused
        lt_sign[:, 1:] = -1
        lt_u[rng.random(LT) < 0.3, 1:] = -1
        lt_sign[lt_u < 0] = 0
    static = dict(topo_dom=topo_dom, u_topo=u_topo, u_spec=u_spec,
                  lt_u=lt_u, lt_sign=lt_sign)
    tables = (
        rng.integers(0, 9, (U, D)).astype(np.int32),
        rng.integers(0, 9, (LT, E, D)).astype(np.int32),
        rng.integers(0, 9, (LT, E, D)).astype(np.int32),
        rng.integers(0, weight, (LT, E, D)).astype(np.int64) + weight,
        rng.integers(0, weight, (LT, E, D)).astype(np.int64),
        rng.integers(0, 50, S).astype(np.int32),
    )
    own = lambda hi, dt: (rng.integers(0, hi, (k, LT))  # noqa: E731
                          * (rng.random((k, LT)) < 0.4)).astype(dt)
    pods = dict(
        match_spec=rng.integers(0, 2, (k, S)).astype(np.int8),
        own_hard=own(2, np.int32), own_pref=own(weight, np.int64) * 100,
        own_anti_hard=own(2, np.int32),
        own_anti_pref=own(weight, np.int64) * 100,
        chosen=rng.integers(0, REAL, k).astype(np.int32),
        scheduled=np.ones(k, bool),
    )
    if picks == "mixed":
        nowhere = rng.random(k) < 0.25
        pods["chosen"][nowhere] = -1
        pods["scheduled"][nowhere] = False
        # a pick the selection did not keep, and a padded pod (all zero)
        pods["scheduled"][rng.random(k) < 0.15] = False
        padded = rng.random(k) < 0.2
        for f in ("match_spec", "own_hard", "own_pref", "own_anti_hard",
                  "own_anti_pref"):
            pods[f][padded] = 0
    return static, tables, pods


def _views(static, tables):
    return IP.interpod_views(
        *tables[:5], static["topo_dom"], static["u_topo"], static["lt_u"],
        static["lt_sign"], N)


@jax.jit
def _commit_all(static, tables, pods):
    """k commits: the tables by interpod_commit's scatters, the views by
    their increment -> after every step, (carried, gathered anew)."""
    dom_lt = IP.lt_domains(static["u_topo"], static["topo_dom"],
                           static["lt_u"])

    def step(loop, pod):
        tables, views = loop
        tables = IP.interpod_commit(
            *tables, static["topo_dom"], static["u_topo"], static["u_spec"],
            static["lt_u"], pod["match_spec"], pod["own_hard"],
            pod["own_pref"], pod["own_anti_hard"], pod["own_anti_pref"],
            pod["chosen"], pod["scheduled"])
        views = IP.interpod_commit_views(
            views, dom_lt, tables[1].shape[2], static["u_spec"],
            static["lt_u"], static["lt_sign"], pod["match_spec"],
            pod["own_hard"], pod["own_pref"], pod["own_anti_hard"],
            pod["own_anti_pref"], pod["chosen"], pod["scheduled"])
        return (tables, views), (views, _views(static, tables))

    (tables, _), (carried, anew) = jax.lax.scan(
        step, (tables, _views(static, tables)), pods)
    return tables, carried, anew


@pytest.mark.parametrize("k", [1, 7, 64])
@pytest.mark.parametrize("name", list(SHAPES))
def test_carried_views_are_the_committed_tables_views(name, k):
    static, tables, pods = _tables(name, k, seed=k * 1000 + len(name))
    final, carried, anew = _commit_all(static, tables, pods)
    for field, got, want in zip(IP.Views._fields, carried, anew):
        assert got.dtype == want.dtype and got.shape == (k, 10, N), field
        assert np.array_equal(np.asarray(got), np.asarray(want)), field
    # the commits were not all no-ops, and the case is what it says
    moved = [not np.array_equal(np.asarray(a), b)
             for a, b in zip(final[:5], tables[:5])]
    assert all(moved) or k == 1
    last = IP.Views(*(np.asarray(v[-1]) for v in carried))
    assert np.all(last.cnt_lt[:, REAL:] == 0) or "padded" not in name
    if name == "two_slots_signed":
        assert (np.asarray(carried.own_lt) < 0).any() or k == 1
    if name == "int64_weights_past_2_31":
        assert last.rev_pref_lt.max() > 2 ** 31
        assert last.rev_pref_lt.dtype == np.int64
    if name == "unscheduled_and_padded_pods" and k > 1:
        assert not pods["scheduled"].all() and (pods["chosen"] < 0).any()


def test_a_picked_node_without_the_label_adds_to_no_node():
    static, tables, pods = _tables("unlabelled_and_padded_nodes", 1, seed=5)
    bare = int(np.flatnonzero((static["topo_dom"][:, :REAL] < 0).all(0))[0])
    pods["chosen"][:] = bare
    _, carried, anew = _commit_all(static, tables, pods)
    before = _views(static, tables)
    for got, want, was in zip(carried, anew, before):
        assert np.array_equal(np.asarray(got[0]), np.asarray(was))
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("name", list(SHAPES))
def test_one_gather_of_the_four_owner_tables_reads_what_four_read(name):
    """`gather_lt_many` (the device replay's views, once a dispatch):
    the four owned-term tables side by side as 32-bit words under one
    gather, each view bit for bit `gather_lt`'s, int64 weights past
    2^31 and unlabelled nodes among them."""
    static, tables, _ = _tables(name, 1, seed=len(name))
    where = (static["u_topo"], static["topo_dom"], static["lt_u"],
             static["lt_sign"])
    many = jax.jit(IP.gather_lt_many)(tables[1:5], *where)
    for table, got in zip(tables[1:5], many):
        want = IP.gather_lt(table, *where)
        assert got.dtype == want.dtype
        assert got.shape == (10, N) and np.asarray(want).any()
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_zero_width_tables_carry_zero_size_views_and_commit_nothing():
    z = functools.partial(np.zeros, dtype=np.int32)
    views = IP.interpod_views(
        z((0, 1)), z((0, 0, 1)), z((0, 0, 1)), np.zeros((0, 0, 1), np.int64),
        np.zeros((0, 0, 1), np.int64), z((0, 0)), z(0), z((0, 0)),
        np.zeros((0, 0), np.int8), N)
    assert all(v.size == 0 for v in views)
    dom_lt = IP.lt_domains(z(0), z((0, 0)), z((0, 0)))
    assert dom_lt.size == 0
    same = IP.interpod_commit_views(
        views, dom_lt, 1, z(0), z((0, 0)), np.zeros((0, 0), np.int8),
        np.zeros(0, np.int8), z(0), z(0), z(0), z(0), jnp.int32(3),
        jnp.bool_(True))
    assert same is views


# -- the scan's level: the two term deployments cut to 48 nodes ---------------


def _deployment(which, seed, bound=60, backlog=90):
    """-> (make_state, pods, counter): `bound` pods of the deployment
    already placed by the serial oracle (so every table holds a
    history), and a backlog of all ten controllers in rows. The oracle
    binds into the state it is given: each side makes its own."""
    from benchmark import deploy
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import GenericScheduler
    from tests.benchmark import (
        test_benchmark_antiaffinity as anti,
        test_benchmark_podaffinity as aff,
    )

    cfg = (aff if which == "podaffinity" else anti)._cfg(48)
    rng = random.Random(seed)
    scheme = rest.default_scheme

    def pods(prefix, n):
        rows = [t for t in (rng.randrange(10) for _ in range(n // 5))
                for _ in range(5)]
        return rows, [scheme.decode(deploy.pod(cfg, t, name=f"{prefix}{i:04d}"))
                      for i, t in enumerate(rows)]

    counter = rng.randrange(1000)
    rows, first = pods("held-", bound)
    picks = GenericScheduler(last_node_index=counter).schedule_backlog(
        first, anti._oracle_state(cfg, {}))
    index = {deploy.node_name(cfg, i): i for i in range(48)}
    live = {p.metadata.name: (t, index[node])
            for p, t, node in zip(first, rows, picks) if node is not None}
    _, waiting = pods("new-", backlog)
    return (lambda: anti._oracle_state(cfg, live)), waiting, \
        counter + len(live)


@functools.lru_cache(maxsize=None)
def _scanned(which, seed):
    from kubernetes_tpu.oracle import GenericScheduler
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder

    make_state, waiting, counter = _deployment(which, seed)
    want = GenericScheduler(last_node_index=counter).schedule_backlog(
        waiting, make_state())
    snap, batch = SnapshotEncoder(make_state(), waiting).encode()
    sched = BatchScheduler(SchedulerConfig())
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    pods = {f: jnp.asarray(getattr(batch, f))
            for f in BatchScheduler.POD_FIELDS}
    return want, snap, sched, static, sched.initial_carry(snap, counter), pods


def _gathered_scan(config, num_zones, num_values, static, carry, pods):
    """The scan before the views, which the step still is for whoever
    passes none: every node's view of every table gathered from the
    carry's tables in every step -> (final carry, chosen)."""
    step = functools.partial(
        _scan_fn, config, num_zones, num_values, static, None)
    (final, views), chosen, _steps = _run_steps(
        step, (carry, None), pods, np.int32(len(pods["class_id"])))
    assert views is None
    return final, chosen


SCANS = [("podaffinity", 1), ("podaffinity", 2 ** 31 + 46),
         ("antiaffinity", 3)]


@pytest.mark.parametrize("which,seed", SCANS)
def test_the_scan_picks_as_the_oracle_and_returns_the_gathered_steps_carry(
        which, seed):
    want, snap, sched, static, carry, pods = _scanned(which, seed)
    assert snap.ip_lt_u.shape[0] >= 5 and snap.ip_term_count.any()
    assert any(np.asarray(t).any() for t in carry[5:9])
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    final, chosen, steps = sched._compiled(
        num_zones, int(snap.svc_num_values))(
            static, carry, pods, np.int32(len(want)))
    assert int(steps) == len(want)
    assert [snap.node_names[i] if i >= 0 else None
            for i in np.asarray(chosen)] == want
    assert len(final) == 17 and want.count(None) < len(want) // 2

    plain, plain_chosen = jax.jit(functools.partial(
        _gathered_scan, sched.config, num_zones, int(snap.svc_num_values)))(
            static, carry, pods)
    assert np.array_equal(np.asarray(chosen), np.asarray(plain_chosen))
    for i, (got, was) in enumerate(zip(jax.tree.leaves(final),
                                       jax.tree.leaves(plain))):
        assert got.dtype == was.dtype, i
        assert np.array_equal(np.asarray(got), np.asarray(was)), i
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(final[4:9], carry[4:9]))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _scan_body(fn, *args):
    """The body of the one loop of fn's jaxpr (`_run_steps`'s `while`),
    flattened."""
    body, = [eqn.params["body_jaxpr"].jaxpr
             for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
             if eqn.primitive.name == "while"]
    return list(_equations(body))


def _node_gathers(body, snap):
    """Gathers that give a value for every (term, slot, node) or every
    (combo unit, node): a node's domain id looked up, or a table read at
    it (an element gather or a gather of rows alike)."""
    LT, E = snap.ip_lt_u.shape
    U, nodes = snap.ip_u_topo.shape[0], snap.num_nodes
    wide = {(LT, E, nodes), (LT, nodes), (U, nodes)}
    return [eqn for eqn in body if eqn.primitive.name == "gather"
            and tuple(eqn.outvars[0].aval.shape) in wide]


@pytest.mark.parametrize("which,seed", SCANS[1:])
def test_the_steps_jaxpr_gathers_no_nodes_domain(which, seed):
    _, snap, sched, static, carry, pods = _scanned(which, seed)
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    args = (sched.config, num_zones, int(snap.svc_num_values))
    body = _scan_body(functools.partial(scan_backlog, *args),
                      static, carry, pods, np.int32(1))
    assert _node_gathers(body, snap) == []

    # what the step held: the five tables' reads (the chip's compiler
    # makes seven of them, an int64 table's two halves apart), the
    # domain ids' rows for each, and expand_lt's rows
    before = _node_gathers(_scan_body(
        functools.partial(_gathered_scan, *args), static, carry, pods), snap)
    assert len(before) >= 11, len(before)


def test_a_step_without_terms_is_the_step_it_was():
    """Zero-width tables: the views are zero-size, made before the loop,
    and the step's equations are the gathered step's less the zero-size
    constants it made each time."""
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder
    from tests.test_wave_paths import _labelled, _nodes

    from kubernetes_tpu.oracle import ClusterState

    backlog = [_labelled(f"p{i}", {"rc": "rc-1"}) for i in range(8)]
    state = ClusterState.build(_nodes(9, ["a", "b", "c"]))
    snap, batch = SnapshotEncoder(state, backlog).encode()
    assert snap.ip_lt_u.shape[0] == 0 and snap.ip_u_topo.shape[0] == 0
    sched = BatchScheduler(SchedulerConfig())
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    pods = {f: jnp.asarray(getattr(batch, f))
            for f in BatchScheduler.POD_FIELDS}
    carry = sched.initial_carry(snap)
    args = (sched.config, max(int(snap.zone_id.max()) + 1, 1), 0)

    now = _scan_body(functools.partial(scan_backlog, *args),
                     static, carry, pods, np.int32(1))
    was = _scan_body(functools.partial(_gathered_scan, *args),
                     static, carry, pods)

    def empty(body):
        return [eqn for eqn in body
                if all(0 in v.aval.shape for v in eqn.outvars)]

    # the loop reads a zero-width pod field's row like any other row,
    # with or without views; the step itself makes nothing zero-size
    reads = {"dynamic_slice", "squeeze"}
    assert {e.primitive.name for e in empty(now)} <= reads
    made = [e for e in empty(was) if e.primitive.name not in reads]
    assert made and len(now) == len(was) - len(made)
    assert {e.primitive.name for e in made} <= {"broadcast_in_dim", "iota"}


# -- the device replay's level: the views ride the run-slot loop --------------
#
# `jit_zreplay_group` gathers the five views once a dispatch and carries
# them from run slot to run slot (models/zreplay._run_slots): a slot's
# probe reads them, its fold scatters the run's PICKS into the tables
# (ops/interpod.interpod_commit_picks, an update a pick) and adds the
# same picks to the views (`_advance_views`). Here: after every run slot
# the carried views are the folded tables' views bit for bit, the folded
# tables are the ones the fold by counts makes, and the group picks as
# the serial per-run sequence, the host spec replay and the serial
# oracle do, on zoned clusters with hostname and zone terms of all four
# kinds among the bound pods and the runs' own.

HOSTNAME = "kubernetes.io/hostname"
ZONE = "failure-domain.beta.kubernetes.io/zone"


def _term(app, topo, weight=None):
    term = {"labelSelector": {"matchLabels": {"app": app}},
            "topologyKey": topo, "namespaces": []}
    return term if weight is None else {
        "weight": weight, "podAffinityTerm": term}


def _app_pods(app, k, affinity=None, cpu="100m", node=None):
    """k pods of service `app`; `affinity` is {"podAffinity" /
    "podAntiAffinity": {"required": [...], "preferred": [...]}}."""
    import json

    from kubernetes_tpu.api.types import Container, ObjectMeta, Pod, PodSpec

    long = {"required": "requiredDuringSchedulingIgnoredDuringExecution",
            "preferred": "preferredDuringSchedulingIgnoredDuringExecution"}
    pods = []
    for i in range(k):
        pod = Pod(metadata=ObjectMeta(name=f"{app}-{cpu}-{i:03d}",
                                      labels={"app": app}),
                  spec=PodSpec(containers=[Container(
                      requests={"cpu": cpu, "memory": "200Mi"})]))
        if affinity:
            pod.metadata.annotations = {
                "scheduler.alpha.kubernetes.io/affinity": json.dumps({
                    kind: {long[when]: terms for when, terms in body.items()}
                    for kind, body in affinity.items()})}
        if node is not None:
            pod.spec.node_name = node
        pods.append(pod)
    return pods


#: the runs a backlog is dealt from: name -> (service, the pod's own
#: terms). None owns a required podAffinity term or a preferred term on
#: its own service (`run_verdict` leaves those to the scan).
TERM_RUNS = {
    # scored by the bound pods' and the earlier runs' terms on it
    "plain-d": ("d", None),
    "plain-b": ("b", None),
    # preferred hostname anti against d, required hostname anti against g
    "owner-p": ("p", {"podAntiAffinity": {
        "preferred": [_term("d", HOSTNAME, 4)],
        "required": [_term("g", HOSTNAME)]}}),
    # the self-anti veto: one of g a node, none beside p's and f's
    "veto-g": ("g", {"podAntiAffinity": {
        "required": [_term("g", HOSTNAME)]}}),
    # preferred zone affinity to d, required zone anti against z
    "owner-q": ("q", {"podAffinity": {"preferred": [_term("d", ZONE, 2)]},
                      "podAntiAffinity": {"required": [_term("z", ZONE)]}}),
    # kept out of q's zones once q is placed (the symmetric check is
    # made for a pod that owns an anti-affinity term, of whatever kind)
    "owner-z": ("z", {"podAntiAffinity": {
        "required": [_term("nobody", HOSTNAME)]}}),
}


def _term_cluster(order, lengths, nodes=18, seed=0):
    """-> (make_state, backlog): zoned nodes with hostname labels, a
    service an app, bound pods that own a term of every kind (required
    zone affinity to b, preferred hostname affinity to d, preferred zone
    anti against d, required hostname anti against g), and the backlog's
    runs in a row each."""
    from kubernetes_tpu.api.types import (
        ObjectMeta, Service, ServiceSpec,
    )
    from kubernetes_tpu.oracle import ClusterState
    from tests.test_wave import zoned_density_nodes

    def make_state():
        made = zoned_density_nodes(nodes, cpu="16")
        for node in made:
            node.metadata.labels[HOSTNAME] = node.metadata.name
        at = [n.metadata.name for n in made]
        spot = random.Random(seed)
        bound = []
        for app, k, affinity in [
                ("b", 3, None), ("d", 2, None),
                ("a", 3, {"podAffinity": {"required": [_term("b", ZONE)]}}),
                ("c", 2, {"podAffinity": {
                    "preferred": [_term("d", HOSTNAME, 5)]}}),
                ("e", 2, {"podAntiAffinity": {
                    "preferred": [_term("d", ZONE, 3)]}}),
                ("f", 1, {"podAntiAffinity": {
                    "required": [_term("g", HOSTNAME)]}})]:
            for i, pod in enumerate(_app_pods(app, k, affinity)):
                pod.metadata.name = f"held-{app}-{i}"
                pod.spec.node_name = spot.choice(at)
                bound.append(pod)
        return ClusterState.build(made, bound, services=[
            Service(metadata=ObjectMeta(name=f"svc-{app}"),
                    spec=ServiceSpec(selector={"app": app}))
            for app in "abcdefgpqz"])

    backlog = []
    for r, (name, k) in enumerate(zip(order, lengths)):
        app, affinity = TERM_RUNS[name]
        # a request shape a run, so that two runs of one service are two
        # templates
        run = _app_pods(app, k, affinity, cpu=f"{100 + 10 * r}m")
        backlog += run
    return make_state, backlog


TERM_BACKLOGS = {
    # every kind once: an owner, a plain run its terms score, the veto,
    # a zone owner, the plain runs its terms score and exclude
    "every-kind": (["owner-p", "plain-d", "veto-g", "owner-q", "plain-b",
                    "plain-d", "owner-z"], [8, 10, 9, 8, 9, 8, 8]),
    # the veto first and last, longer than the nodes left to it
    "veto-runs-out-of-nodes": (["veto-g", "owner-p", "plain-d", "veto-g"],
                               [12, 9, 9, 10]),
}


def _fuzzed(seed):
    rng = random.Random(seed)
    names = sorted(TERM_RUNS)
    order = [rng.choice(names) for _ in range(rng.randint(3, 8))]
    return order, [rng.randint(8, 14) for _ in order]


TERM_BACKLOGS.update({f"fuzz-{seed}": _fuzzed(seed) for seed in range(6)})


def _driven(make_state, backlog, **kw):
    from tests.test_wave import _wave_scheduler_run

    return _wave_scheduler_run(make_state(), backlog, **kw)


@pytest.mark.parametrize("case", sorted(TERM_BACKLOGS))
def test_the_grouped_replay_under_terms_picks_as_the_serial_sequences_do(
        case, monkeypatch):
    from kubernetes_tpu.models import waveloop
    from kubernetes_tpu.models.replay import replay_spec
    from kubernetes_tpu.oracle import GenericScheduler
    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    make_state, backlog = _term_cluster(*TERM_BACKLOGS[case])
    want = GenericScheduler(
        predicates=ORACLE_PREDICATES,
        priorities=ORACLE_PRIORITIES).schedule_backlog(backlog, make_state())
    got, ws = _driven(make_state, backlog)
    assert got == want
    assert ws.dispatches.get("zreplay_group", 0) >= 1, ws.dispatches
    assert ws.stats["zreplay_steps"] >= ws.stats["zreplay_picks"] > 0
    got_host, _ = _driven(make_state, backlog, replay=replay_spec)
    assert got_host == want
    # the serial per-run sequence: every run a `jit_zreplay_run` of its own
    monkeypatch.setattr(waveloop, "DEVICE_GROUP_RUNS", 1)
    got_serial, serial = _driven(make_state, backlog)
    assert got_serial == want
    assert "zreplay_group" not in serial.dispatches \
        and serial.dispatches["zreplay"] >= 2
    if case == "every-kind":
        # every pod found a node but z's, which q's zones exclude
        assert [h for h, p in zip(want, backlog)
                if p.metadata.labels["app"] != "z"].count(None) == 0
        assert any(h is None for h in want)


@pytest.mark.parametrize("case", ["every-kind", "veto-runs-out-of-nodes",
                                  "fuzz-1", "fuzz-4"])
def test_after_every_run_slot_the_carried_views_are_the_folded_tables(
        case, monkeypatch):
    from kubernetes_tpu.models.pack import unpack
    from kubernetes_tpu.models.zreplay import ZReplay, _run_slots
    from kubernetes_tpu.models.batch import interpod_views

    make_state, backlog = _term_cluster(*TERM_BACKLOGS[case])
    calls = []
    sound = ZReplay.run_group

    def recorded(self, *a):
        calls.append((self, a))
        return sound(self, *a)

    monkeypatch.setattr(ZReplay, "run_group", recorded)
    _, ws = _driven(make_state, backlog)
    zr, (static, carry, prev, buf, layout, num_zones, num_values, J, K, G,
         zone_id, vetos, has_sels, rows_arr, k_reals, runs, L0) = calls[0]
    assert prev is None and runs >= 3
    assert static["ip_lt_u"].shape[0] >= 4

    @functools.partial(jax.jit, static_argnums=0)
    def slots(by_picks, static, carry, buf, runs):
        def by_counts(static, carry, pod, counts, picks=None):
            return zr.apply_fn(static, carry, pod, counts)

        carry, views, chosen, n_done, L, ran, _fits = _run_slots(
            zr.config, num_zones, num_values, J, K, G,
            zr.apply_fn if by_picks else by_counts, static, carry,
            unpack(layout, buf), jnp.asarray(zone_id), jnp.asarray(vetos),
            jnp.asarray(has_sels), jnp.asarray(rows_arr),
            jnp.asarray(k_reals), runs, np.int64(L0))
        return carry, views, interpod_views(zr.config, static, carry), \
            chosen, ran

    moved = 0
    for r in range(1, runs + 1):
        carry_r, carried, anew, chosen, ran = slots(
            True, static, carry, buf, np.int32(r))
        assert int(ran[1]) == r
        for field, got, want in zip(IP.Views._fields, carried, anew):
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                (r, field)
        # the fold by picks leaves the carry the fold by counts leaves
        dense, _, _, chosen_dense, _ = slots(
            False, static, carry, buf, np.int32(r))
        assert np.array_equal(np.asarray(chosen), np.asarray(chosen_dense))
        for i, (got, want) in enumerate(zip(jax.tree.leaves(carry_r),
                                            jax.tree.leaves(dense))):
            assert got.dtype == want.dtype, i
            assert np.array_equal(np.asarray(got), np.asarray(want)), (r, i)
        moved += any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(carry_r[4:9], carry[4:9]))
    assert moved  # some run's terms or matches reached the tables
