"""Which path of the wave driver decided a pod, counted: the tallies
WaveScheduler.stats["pods_by_path"] / ["dispatches_by_kind"] /
["pods_unplaced"] add up to the pods handed in, wave after wave, and
/debug/traces shows the same numbers. And the scan path picks as the
serial oracle does where selector rows are all distinct, multi-hot, or
followed by a pod that fits nowhere and by padding."""

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container,
    Node,
    NodeCondition,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    ReplicationController,
    ReplicationControllerSpec,
    Service,
    ServiceSpec,
)
from kubernetes_tpu.models.wave import PATHS
from kubernetes_tpu.trace import profile

ZONE = "failure-domain.beta.kubernetes.io/zone"


def _nodes(n, zones="abc", pods="110"):
    out = []
    for i in range(n):
        labels = {"kubernetes.io/hostname": f"znode-{i:05d}"}
        if zones:
            labels[ZONE] = zones[i % len(zones)]
        out.append(Node(
            metadata=ObjectMeta(name=f"znode-{i:05d}", labels=labels),
            status=NodeStatus(
                allocatable={"cpu": "4", "memory": "32Gi", "pods": pods},
                conditions=[NodeCondition("Ready", "True")])))
    return out


def _controllers(n):
    return [ReplicationController(
        metadata=ObjectMeta(name=f"rc-{t}"),
        spec=ReplicationControllerSpec(selector={"rc": f"rc-{t}"}))
        for t in range(n)]


def _pod(t, i):
    return Pod(
        metadata=ObjectMeta(name=f"rc{t}-{i:04d}", labels={"rc": f"rc-{t}"}),
        spec=PodSpec(containers=[Container(requests={
            "cpu": "100m", "memory": "500Mi"})]))


def _dealt_in_turn(controllers, replicas):
    """As benchmark/loadgen.py deals them: every pending pod next to
    another controller's."""
    return [_pod(t, i) for i in range(replicas) for t in range(controllers)]


def _in_rows(controllers, replicas):
    """A controller's replicas arriving in a row."""
    return [_pod(t, i) for t in range(controllers) for i in range(replicas)]


CASES = {
    # name: (nodes, zones, controllers, backlog, the one path expected
    #        to decide every pod, or None where they are mixed)
    "dealt-in-turn-zoned": (30, "abc", 12, _dealt_in_turn(12, 10), "scan"),
    "dealt-in-turn-unzoned": (30, "", 12, _dealt_in_turn(12, 10), "scan"),
    "rows-zoned": (30, "abc", 4, _in_rows(4, 40), "group_device"),
    "rows-unzoned": (30, "", 4, _in_rows(4, 40), "group_host"),
    "one-row-zoned": (30, "abc", 1, _in_rows(1, 64), "single"),
    "one-row-unzoned": (30, "", 1, _in_rows(1, 64), "single"),
    "short-rows-zoned": (30, "abc", 8, _in_rows(8, 5), "scan"),
    "rows-then-turns": (30, "abc", 6,
                        _in_rows(3, 40) + _dealt_in_turn(6, 4), None),
}


def _delta(after, before):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_paths_add_up_to_the_pods_decided(case):
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    nodes, zones, controllers, backlog, only = CASES[case]
    state = ClusterState.build(_nodes(nodes, zones),
                               controllers=_controllers(controllers))
    algo = TPUScheduleAlgorithm()
    shown_before = profile.wave_totals()
    hosts = algo.schedule_backlog(backlog, state)
    stats = algo._wave.stats
    assert set(stats["pods_by_path"]) == set(PATHS)
    assert sum(stats["pods_by_path"].values()) == len(backlog)
    assert stats["pods_unplaced"] == sum(h is None for h in hosts) == 0
    assert sum(stats["dispatches_by_kind"].values()) == stats["dispatches"]
    assert stats["dispatches_by_kind"] == algo._wave.dispatches  # one wave
    if only is not None:
        assert stats["pods_by_path"][only] == len(backlog), stats
    else:
        assert stats["pods_by_path"]["group_device"] == 120
        assert stats["pods_by_path"]["scan"] == 24
    # the process-wide totals /debug/traces serves moved by the same
    shown = profile.wave_totals()
    assert shown["waves"] - shown_before["waves"] == 1
    assert _delta(shown["pods_by_path"], shown_before["pods_by_path"]) \
        == stats["pods_by_path"]
    moved = _delta(shown["dispatches_by_kind"],
                   shown_before["dispatches_by_kind"])
    assert {k: v for k, v in moved.items() if v} \
        == stats["dispatches_by_kind"]
    # a second wave adds to the tallies; the per-wave dict starts again
    algo.schedule_backlog(backlog[:7], state)
    assert sum(stats["pods_by_path"].values()) == len(backlog) + 7
    assert sum(algo._wave.dispatches.values()) \
        == stats["dispatches"] - sum(moved.values())


def test_pods_that_fit_nowhere_are_counted():
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    state = ClusterState.build(_nodes(3, pods="10"),
                               controllers=_controllers(5))
    algo = TPUScheduleAlgorithm()
    before = profile.wave_totals()["pods_unplaced"]
    hosts = algo.schedule_backlog(_dealt_in_turn(5, 9), state)
    stats = algo._wave.stats
    assert sum(h is None for h in hosts) == 15
    assert stats["pods_unplaced"] == 15
    assert sum(stats["pods_by_path"].values()) == 45
    assert profile.wave_totals()["pods_unplaced"] - before == 15


def test_debug_traces_shows_the_wave_totals():
    from kubernetes_tpu.trace.httpd import render_traces

    shown = render_traces({"limit": "1"})["wave"]
    assert shown == profile.wave_totals()
    assert {"waves", "pods_by_path", "dispatches_by_kind",
            "pods_unplaced"} <= set(shown)


# -- the scan path against the serial oracle ---------------------------------


def _labelled(name, labels, cpu="100m"):
    return Pod(metadata=ObjectMeta(name=name, labels=dict(labels)),
               spec=PodSpec(containers=[Container(requests={
                   "cpu": cpu, "memory": "500Mi"})]))


def _own_controllers():
    """(a) every pod has its own controller: runs of length 1, every
    spread_match row another."""
    return (_controllers(23), [],
            [_pod(t, 0) for t in range(23)])


def _multi_hot():
    """(b) `both-*` pods are selected by two controllers and a service:
    their row is hot in four classes, so the commit of a plain rc-0,
    tier or svc pod has to raise THEIR counts too, not only its own
    row's."""
    controllers = _controllers(2) + [ReplicationController(
        metadata=ObjectMeta(name="tier"),
        spec=ReplicationControllerSpec(selector={"tier": "x"}))]
    services = [Service(metadata=ObjectMeta(name="svc"),
                        spec=ServiceSpec(selector={"app": "svc"}))]
    kinds = [
        ("both", {"rc": "rc-0", "tier": "x", "app": "svc"}),
        ("rc0", {"rc": "rc-0"}),
        ("tier", {"tier": "x"}),
        ("svc", {"app": "svc"}),
        ("rc1", {"rc": "rc-1"}),
        ("free", {"nobody": "selects"}),
    ]
    backlog = [_labelled(f"{kind}-{i:03d}", labels)
               for i in range(9) for kind, labels in kinds]
    return controllers, services, backlog


def _unfit_in_the_middle():
    """(c) a pod in the middle fits nowhere (64 CPUs on 4-CPU nodes)
    and 37 pods pad to 64 scan steps: neither moves a count."""
    backlog = _dealt_in_turn(6, 6)
    backlog.insert(17, _labelled("huge", {"rc": "rc-2"}, cpu="64"))
    return _controllers(6), [], backlog


SCAN_CASES = {
    "own-controllers": _own_controllers,
    "multi-hot-row": _multi_hot,
    "unfit-in-the-middle": _unfit_in_the_middle,
}


def _oracle(state, backlog):
    from kubernetes_tpu.oracle import GenericScheduler

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    return GenericScheduler(
        predicates=ORACLE_PREDICATES, priorities=ORACLE_PRIORITIES,
    ).schedule_backlog(backlog, state.clone())


@pytest.mark.parametrize("zones", ["abc", ""], ids=["zoned", "unzoned"])
@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_picks_equal_the_serial_oracles(case, zones):
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    controllers, services, backlog = SCAN_CASES[case]()
    # some pods bound already, so the first pick's counts are not all zero
    bound = []
    nodes = _nodes(9, zones)
    for i, p in enumerate(backlog[::4]):
        q = _labelled(f"bound-{i:03d}", p.metadata.labels)
        q.spec.node_name = nodes[(i * i) % 9].metadata.name
        bound.append(q)
    state = ClusterState.build(nodes, bound, services=services,
                               controllers=controllers)
    want = _oracle(state, backlog)
    algo = TPUScheduleAlgorithm(min_run=10 ** 6)  # every run to the scan
    got = algo.schedule_backlog(backlog, state)
    assert got == want
    assert algo._wave.stats["pods_by_path"]["scan"] == len(backlog)
    assert (None in got) == (case == "unfit-in-the-middle")


@pytest.mark.parametrize("zones", ["abc", ""], ids=["zoned", "unzoned"])
def test_two_scans_in_a_row_hand_on_the_class_counts(zones):
    """(d) the class_count a scan returns in its carry is the table it
    was given plus one per (chosen node, class) pair, entry for entry;
    and a second scan started from it picks as the oracle does over
    both waves."""
    import jax.numpy as jnp

    from kubernetes_tpu.models.batch import BatchScheduler
    from kubernetes_tpu.models.wave import gather_batch
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder

    controllers, services, backlog = _multi_hot()
    backlog.insert(20, _labelled("huge", {"rc": "rc-1"}, cpu="64"))
    state = ClusterState.build(_nodes(9, zones), services=services,
                               controllers=controllers)
    want = _oracle(state, backlog)
    snap, batch = SnapshotEncoder(state, backlog).encode()
    sched = BatchScheduler()
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    run = sched._compiled(num_zones, int(snap.svc_num_values))
    carry = sched.initial_carry(snap)
    table = np.asarray(snap.class_count).copy()
    CLASS_COUNT = 2  # its place in the carry (models/batch._scan_fn)
    got = []
    cut = 31
    for rows in (np.arange(cut), np.arange(cut, len(backlog))):
        part = gather_batch(batch, rows)
        carry, chosen = run(static, carry, {
            f: jnp.asarray(getattr(part, f))
            for f in BatchScheduler.POD_FIELDS})
        chosen = np.asarray(chosen)
        placed = chosen >= 0
        np.add.at(table, (chosen[placed], part.class_id[placed]), 1)
        assert np.array_equal(np.asarray(carry[CLASS_COUNT]), table)
        got += [snap.node_names[i] if i >= 0 else None for i in chosen]
    assert got == want
    assert got.count(None) == 1
    assert int(carry[BatchScheduler.LAST_IDX]) == len(backlog) - 1
