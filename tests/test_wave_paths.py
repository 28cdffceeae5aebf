"""Which path of the wave driver decided a pod, counted: the tallies
WaveScheduler.stats["pods_by_path"] / ["dispatches_by_kind"] /
["pods_unplaced"] add up to the pods handed in, wave after wave, and
/debug/traces shows the same numbers."""

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
