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
