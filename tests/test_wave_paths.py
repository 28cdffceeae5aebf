"""Which path of the wave driver decided a pod, counted: the tallies
WaveScheduler.stats["pods_by_path"] / ["dispatches_by_kind"] /
["pods_unplaced"] add up to the pods handed in, wave after wave, and
/debug/traces shows the same numbers. A group of runs whose templates
commit different requests goes through one grouped header probe and
picks as the serial oracle does, and the `group_*` counters say so on
both drivers. The grouped device replay's `zreplay_*` counters say how
many run slots and pick steps its loops ran and how many steps evaluated
the carried score again, and a wave of another run count inside one
bucket builds no program. The `anti_*` counters say what the runs with
a self-anti veto decided and how many nodes the terms of bound pods had
taken from them, `waves_by_encoder` / `encoder_fallbacks` which encoder
made a wave's snapshot and which scope gate sent it there (an inter-pod
term sends none: the tables are kept), `interpod_rebuilds` how often the
kept inter-pod tables were rebuilt whole and why, and a later
wave of an unchanged set of terms builds no program. And the scan path
picks as
the serial oracle does where
selector rows are all distinct, multi-hot, or followed by a pod that
fits nowhere and by padding."""

from types import SimpleNamespace

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
    Toleration,
)
from kubernetes_tpu.models.wave import (
    ANTI_COUNTERS,
    ENCODERS,
    PATHS,
    ZREPLAY_COUNTERS,
)
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
            "pods_unplaced", *ZREPLAY_COUNTERS} <= set(shown)


# -- the grouped device replay's own counters ----------------------------------


@pytest.mark.parametrize("case, runs, alone", [
    ("rows-zoned", 4, 0), ("rows-then-turns", 3, 0),
    ("rows-unzoned", 0, 0), ("dealt-in-turn-zoned", 0, 0),
    # a lone run is `jit_zreplay_run`'s: its pick loop counts too
    ("one-row-zoned", 0, 64),
])
def test_zreplay_counters_say_what_the_two_loops_ran(case, runs, alone):
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.trace.httpd import render_traces

    nodes, zones, controllers, backlog, _only = CASES[case]
    state = ClusterState.build(_nodes(nodes, zones),
                               controllers=_controllers(controllers))
    algo = TPUScheduleAlgorithm()
    shown_before = render_traces({"limit": "1"})["wave"]
    assert None not in algo.schedule_backlog(backlog, state)
    stats = algo._wave.stats
    # every pod of a whole run is placed: a step a pick, a slot a run
    assert stats["zreplay_slots"] == runs
    assert stats["zreplay_steps"] == stats["zreplay_picks"] \
        == 40 * runs + alone
    assert stats["pods_by_path"]["group_device"] == 40 * runs
    assert stats["zreplay_rescores"] <= stats["zreplay_steps"]
    # where the parent paid 8 run slots x 64 pick steps
    assert stats["zreplay_steps"] <= 160 < 8 * 64
    shown = render_traces({"limit": "1"})["wave"]
    for key in ZREPLAY_COUNTERS:
        assert shown[key] - shown_before[key] == stats[key]


def test_the_rows_shape_is_one_evaluation_a_run():
    """`spread-3k.rows` cut down (72 runs of 40 on 3,000 nodes there):
    a controller's 40 replicas in a row on a zoned cluster with more
    nodes a zone than a run has pods, one request shape. No run comes
    back to a node and no node fills, so the score a run carries is
    evaluated once a run: no step rescores, and a step is a pick."""
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    state = ClusterState.build(_nodes(150), controllers=_controllers(9))
    backlog = _in_rows(9, 40)
    algo = TPUScheduleAlgorithm()
    oracle = GenericScheduler(predicates=ORACLE_PREDICATES,
                              priorities=ORACLE_PRIORITIES)
    assert algo.schedule_backlog(backlog, state) \
        == oracle.schedule_backlog(backlog, state.clone())
    stats = algo._wave.stats
    assert stats["pods_by_path"]["group_device"] == 360
    assert stats["zreplay_slots"] == 9
    assert stats["zreplay_steps"] == stats["zreplay_picks"] == 360
    assert stats["zreplay_rescores"] == 0


def test_a_run_longer_than_its_nodes_rescores_and_says_so():
    """`rows-zoned`: 40 replicas on 30 nodes come back to a node, so the
    carried score is evaluated again, and /debug/traces counts it."""
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    nodes, zones, controllers, backlog, _only = CASES["rows-zoned"]
    state = ClusterState.build(_nodes(nodes, zones),
                               controllers=_controllers(controllers))
    algo = TPUScheduleAlgorithm()
    before = profile.wave_totals()["zreplay_rescores"]
    assert None not in algo.schedule_backlog(backlog, state)
    rescores = algo._wave.stats["zreplay_rescores"]
    assert 0 < rescores <= algo._wave.stats["zreplay_steps"] == 160
    assert profile.wave_totals()["zreplay_rescores"] - before == rescores


# -- an epoch of the device replay ends only when its evaluation is spent --------
#
# A run with the self-anti veto takes every node it picks out of the fit
# set. The carried score reads the fit set through three normalisers'
# extremes alone (models/zreplay._replay_run's `holds_extreme`), so such
# a run is one evaluation unless a node that leaves HELD one of them.


def _annotated(pod, **affinity):
    import json

    pod.metadata.annotations = {
        "scheduler.alpha.kubernetes.io/affinity": json.dumps(affinity)}
    return pod


def _self_anti(t, i, **more):
    """rc-t's replica i with a required hostname anti-affinity term on
    its own controller's label: one a node."""
    return _annotated(_pod(t, i), podAntiAffinity={
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"rc": f"rc-{t}"}},
            "topologyKey": "kubernetes.io/hostname", "namespaces": []}]},
        **more)


@pytest.mark.parametrize("shape", ["alone", "in-a-group"])
def test_a_vetoed_run_of_40_is_one_evaluation(shape):
    """mixed-5k's vetoed controllers cut down: 40 replicas in a row on
    one zone, their service scoring 0 on NodeAffinity, TaintToleration
    and InterPodAffinity. Every pick leaves the fit set and none holds
    an extreme: no step rescores, alone (`jit_zreplay_run`) or between
    a plain run and another vetoed one (`jit_zreplay_group`)."""
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    rows = {"alone": [0], "in-a-group": [0, 1, 2]}[shape]
    state = ClusterState.build(_nodes(64, "a"), controllers=_controllers(3))
    backlog = [_pod(t, i) if t == 1 else _self_anti(t, i)
               for t in rows for i in range(40)]
    algo = TPUScheduleAlgorithm()
    hosts = algo.schedule_backlog(backlog, state)
    oracle = GenericScheduler(predicates=ORACLE_PREDICATES,
                              priorities=ORACLE_PRIORITIES)
    assert hosts == oracle.schedule_backlog(backlog, state.clone())
    assert len(set(hosts[:40])) == 40  # one a node
    stats = algo._wave.stats
    kind = "zreplay" if shape == "alone" else "zreplay_group"
    assert stats["dispatches_by_kind"] == {kind: 1}
    assert stats["zreplay_steps"] == stats["zreplay_picks"] == len(backlog)
    assert stats["zreplay_slots"] == (0 if shape == "alone" else 3)
    assert stats["zreplay_rescores"] == 0


def _loaded(node, cpu, i):
    """A bound pod of no service that takes `cpu` of `node`."""
    return Pod(metadata=ObjectMeta(name=f"load-{i}", labels={"load": "y"}),
               spec=PodSpec(node_name=node, containers=[Container(
                   requests={"cpu": cpu, "memory": "500Mi"})]))


def _holder_of_the_node_affinity_maximum():
    """node 0 alone holds gold (90) and silver (10): NodeAffinity's
    maximum is 100 while it fits and 10 once it is picked, so the silver
    nodes' share goes 1 -> 10 and they overtake the emptier plain ones."""
    nodes = _nodes(12, "a")
    nodes[0].metadata.labels["gold"] = "y"
    for node in nodes[:6]:
        node.metadata.labels["silver"] = "y"
    bound = [_loaded(n.metadata.name, "2", i)
             for i, n in enumerate(nodes[1:6])]

    prefer = {"nodeAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 90, "preference": {"matchExpressions": [
                {"key": "gold", "operator": "In", "values": ["y"]}]}},
            {"weight": 10, "preference": {"matchExpressions": [
                {"key": "silver", "operator": "In", "values": ["y"]}]}}]}}
    return nodes, bound, prefer


def _taints(node, count):
    import json

    from kubernetes_tpu.api.types import TAINTS_ANNOTATION

    node.metadata.annotations = {TAINTS_ANNOTATION: json.dumps([
        {"key": f"t{k}", "value": "v", "effect": "PreferNoSchedule"}
        for k in range(count)])}


def _holder_of_the_taint_maximum():
    """node 0 alone has 20 PreferNoSchedule taints, node 1 has 18
    (zreplay.py's own mx = 20, c = 18: (1 - 18/20) * 10 truncates to 0,
    where (10 * (20 - 18)) // 20 gives 1), five have 10 and five none.
    Node 0 is the only empty one, so it is picked while the others fit,
    and TaintToleration's maximum falls 20 -> 18."""
    nodes = _nodes(12, "a")
    for node, count in zip(nodes, [20, 18, 10, 10, 10, 10, 10]):
        _taints(node, count)
    bound = [_loaded(n.metadata.name, cpu, i) for i, (n, cpu) in enumerate(
        zip(nodes[1:], ["1", "1", "1", "1", "1", "1", "2", "2", "3", "3",
                        "3"]))]
    return nodes, bound, {}


def _holder_of_an_interpod_extreme(sign):
    """node 0 holds a bound pod whose preferred hostname (anti-)affinity
    term on the run's controller weighs 50, nodes 1-5 one of weight 5:
    the inter-pod totals' maximum (affinity) falls 50 -> 5, or their
    minimum (anti-affinity) rises -50 -> -5, when node 0 is picked."""
    nodes = _nodes(12, "a")
    kind = "podAffinity" if sign > 0 else "podAntiAffinity"
    bound = []
    for i, node in enumerate(nodes[:6]):
        owner = Pod(
            metadata=ObjectMeta(name=f"owner-{i}", labels={"own": "y"}),
            spec=PodSpec(node_name=node.metadata.name, containers=[
                Container(requests={"cpu": "100m", "memory": "500Mi"})]))
        bound.append(_annotated(owner, **{kind: {
            "preferredDuringSchedulingIgnoredDuringExecution": [{
                "weight": 50 if i == 0 else 5, "podAffinityTerm": {
                    "labelSelector": {"matchLabels": {"rc": "rc-0"}},
                    "topologyKey": "kubernetes.io/hostname",
                    "namespaces": []}}]}}))
    # the plain nodes are fuller: with the anti-affinity term node 0 is
    # the least wanted of its kind and still goes before them
    bound += [_loaded(n.metadata.name, "3", i)
              for i, n in enumerate(nodes[6:])]
    return nodes, bound, {}


ADVERSARIAL = {
    "node-affinity-maximum": _holder_of_the_node_affinity_maximum,
    "taint-maximum-20-over-18": _holder_of_the_taint_maximum,
    "interpod-maximum": lambda: _holder_of_an_interpod_extreme(+1),
    "interpod-minimum": lambda: _holder_of_an_interpod_extreme(-1),
}


@pytest.mark.parametrize("case", sorted(ADVERSARIAL))
def test_a_node_that_leaves_holding_an_extreme_still_ends_the_epoch(case):
    """A vetoed run of 10 on 12 nodes: the one node that holds the
    extreme is picked while others still fit, the normaliser moves, and
    the picks behind it are the oracle's only if the score is evaluated
    again."""
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    nodes, bound, more = ADVERSARIAL[case]()
    state = ClusterState.build(nodes, bound, controllers=_controllers(1))
    backlog = [_self_anti(0, i, **more) for i in range(10)]
    for pod in backlog:
        # a pod without tolerations fits no tainted node at all
        pod.spec.tolerations = [Toleration(
            key="t0", operator="Equal", value="v",
            effect="PreferNoSchedule")]
    oracle = GenericScheduler(predicates=ORACLE_PREDICATES,
                              priorities=ORACLE_PRIORITIES)
    want = oracle.schedule_backlog(backlog, state.clone())
    holder = nodes[0].metadata.name
    assert holder in want[:-1] and None not in want, want
    algo = TPUScheduleAlgorithm(min_run=1)
    assert algo.schedule_backlog(backlog, state) == want
    stats = algo._wave.stats
    assert stats["dispatches_by_kind"] == {"zreplay": 1}
    assert stats["zreplay_steps"] == stats["zreplay_picks"] == 10
    assert 0 < stats["zreplay_rescores"] < 10


def test_a_wave_of_another_run_count_builds_no_program():
    """7 runs, then 8 of the same seven controllers (the program is
    built per width of the class axis): both in the bucket of 32 run
    slots and 64 picks; the run count is an argument of the one
    program, not a shape."""
    import time

    from kubernetes_tpu.oracle import ClusterState, GenericScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    profile.install_compile_listener()
    state = ClusterState.build(_nodes(30), controllers=_controllers(7))
    algo = TPUScheduleAlgorithm()
    # one oracle for both waves: the round-robin index goes on counting
    oracle = GenericScheduler(predicates=ORACLE_PREDICATES,
                              priorities=ORACLE_PRIORITIES)
    backlog = _in_rows(7, 33)
    assert algo.schedule_backlog(backlog, state) \
        == oracle.schedule_backlog(backlog, state.clone())
    stats = algo._wave.stats
    assert (stats["zreplay_slots"], stats["zreplay_steps"]) == (7, 231)
    t_between = time.time()
    backlog = _in_rows(7, 20) + [_pod(0, 20 + i) for i in range(25)]
    assert algo.schedule_backlog(backlog, state) \
        == oracle.schedule_backlog(backlog, state.clone())
    assert (stats["zreplay_slots"], stats["zreplay_steps"]) == (15, 396)
    assert stats["dispatches_by_kind"]["zreplay_group"] == 2
    assert len(algo._wave._zreplay._jitted) == 1
    built = [c["program"] for c in profile.recent_compiles()
             if c["at"] >= t_between]
    assert not [p for p in built if "zreplay" in p], built


def test_a_wave_of_another_count_of_vetoed_runs_builds_no_program():
    """The same on an unzoned cluster whose runs carry a self-anti veto:
    7 runs, then 8 of the same seven controllers, both in the bucket of
    32 run slots and 64 picks, one `jit_zreplay_group` for both, no
    probe."""
    import time

    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    profile.install_compile_listener()
    state = ClusterState.build(_nodes(80, ""),
                               controllers=_anti_controllers())
    algo = TPUScheduleAlgorithm()
    oracle = _a_serial_oracle()
    backlog = _rows_of(_anti_pod, range(7), 16)
    assert algo.schedule_backlog(backlog, state) \
        == oracle.schedule_backlog(backlog, state.clone())
    stats = algo._wave.stats
    assert (stats["zreplay_slots"], stats["zreplay_steps"]) == (7, 112)
    t_between = time.time()
    backlog = _rows_of(_anti_pod, tuple(range(7)) + (0,), 16, serial=1000)
    assert algo.schedule_backlog(backlog, state) \
        == oracle.schedule_backlog(backlog, state.clone())
    assert (stats["zreplay_slots"], stats["zreplay_steps"]) == (15, 240)
    assert stats["dispatches_by_kind"] == {"zreplay_group": 2}
    assert len(algo._wave._zreplay._jitted) == 1
    built = [c["program"] for c in profile.recent_compiles()
             if c["at"] >= t_between]
    assert not [p for p in built if "zreplay" in p or "probe" in p], built
    assert stats["anti_runs"] == 15 and stats["anti_picks"] == 240


# -- runs with a self-anti veto, and the encoder behind a wave ----------------

HOSTNAME = "kubernetes.io/hostname"


def _anti_pod(t, i, groups=5):
    """A replica of controller `t` whose required hostname anti-affinity
    term selects its group: controllers t and t + groups
    (benchmark/configs/antiaffinity-2k.json's shape)."""
    import json

    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    k = t % groups
    term = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchExpressions": [{
                "key": "group", "operator": "In",
                "values": [f"g{k}", f"g{k + groups}"]}]},
            "topologyKey": HOSTNAME}]}}
    return Pod(
        metadata=ObjectMeta(name=f"anti{t}-{i:04d}",
                            labels={"group": f"g{t}"},
                            annotations={AFFINITY_ANNOTATION:
                                         json.dumps(term)}),
        spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]))


def _anti_controllers(n=10):
    return [ReplicationController(
        metadata=ObjectMeta(name=f"anti-{t}"),
        spec=ReplicationControllerSpec(selector={"group": f"g{t}"}))
        for t in range(n)]


def _anti_rows(controllers, replicas, serial=0):
    return [_anti_pod(t, serial + i) for t in controllers
            for i in range(replicas)]


ANTI_CASES = {
    # name: (nodes, backlog, runs with a veto, pods they place, nodes
    #        their first probes find unfit)
    # two controllers of ONE group: the second's run meets the first's
    # 16 picks as excluded nodes, inside the wave
    "one-group-two-controllers": (40, _anti_rows((0, 5), 16), 2, 32, 16),
    # the group fills every node: the second run places 8 of its 16
    "the-group-fills-the-nodes": (24, _anti_rows((0, 5), 16), 2, 24, 16),
    # two groups: neither's picks exclude a node from the other
    "two-groups": (40, _anti_rows((0, 1), 16), 2, 32, 0),
    # runs without terms carry no veto
    "no-terms": (30, _in_rows(2, 16), 0, 0, 0),
}


def _on_route(route):
    """The served driver on one of the two routes a run the grouped
    header probe cannot take has: the device replay (one chip's own) or
    a probe and a host replay a run (`replay=`, which the mesh's runs
    take too)."""
    from kubernetes_tpu.models.replay import replay_fast
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    return TPUScheduleAlgorithm(
        replay=replay_fast if route == "host" else None)


ROUTES = ("device", "host")


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("case", sorted(ANTI_CASES))
def test_anti_counters_say_what_the_vetoed_runs_decided(case, route):
    """The same runs, picks and excluded nodes on both routes: on the
    device the group is one dispatch and counts the nodes that fit in
    the program, on the host a run is a probe and counts them on the
    tables it shipped."""
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.trace.httpd import render_traces

    nodes, backlog, runs, picks, excluded = ANTI_CASES[case]
    state = ClusterState.build(_nodes(nodes, ""),
                               controllers=_anti_controllers()
                               + _controllers(2))
    algo = _on_route(route)
    shown_before = render_traces({"limit": "1"})["wave"]
    got = algo.schedule_backlog(backlog, state)
    assert got == _oracle(state, backlog)
    stats = algo._wave.stats
    assert ANTI_COUNTERS == ("anti_runs", "anti_picks",
                             "anti_nodes_excluded")
    assert (stats["anti_runs"], stats["anti_picks"],
            stats["anti_nodes_excluded"]) == (runs, picks, excluded)
    assert stats["pods_unplaced"] == len(backlog) - sum(
        h is not None for h in got) == (8 if "fills" in case else 0)
    if runs:
        # one node holds one pod of the group
        by_group = [h for h in got if h is not None]
        assert len(set(by_group)) == len(by_group) or case == "two-groups"
    if runs and route == "host":
        # a run is one probe (the one that finds its last node gone
        # asks once more)
        assert stats["pods_by_path"]["single"] >= picks
        assert stats["dispatches_by_kind"]["probe"] >= runs
    elif runs:
        # the runs are one group, one dispatch, no probe
        assert stats["pods_by_path"]["group_device"] == len(backlog)
        assert algo._wave.dispatches == {"zreplay_group": 1}
        assert stats["zreplay_slots"] == runs
        assert stats["zreplay_picks"] == picks
    shown = render_traces({"limit": "1"})["wave"]
    for key in ANTI_COUNTERS:
        assert shown[key] - shown_before[key] == stats[key]


@pytest.mark.parametrize("route", ROUTES)
def test_anti_picks_never_run_ahead_of_the_pods_decided(route, monkeypatch):
    """Both tallies move at a wave's end: a reader that falls into the
    middle of a wave (the benchmark's second read did, on the chip:
    `anti_run_share.fill` 101.4) finds the picks of whole waves only,
    at a run's probe on the host route and at a wave's one dispatch on
    the device's."""
    from kubernetes_tpu.models.probe import WaveProbe
    from kubernetes_tpu.models.zreplay import ZReplay
    from kubernetes_tpu.oracle import ClusterState

    state = ClusterState.build(_nodes(40, ""),
                               controllers=_anti_controllers())
    algo = _on_route(route)
    stats = algo._wave.stats
    seen = []
    owner, name = (WaveProbe, "probe_fused") if route == "host" \
        else (ZReplay, "run_group")
    sound = getattr(owner, name)

    def watched(self, *args, **kwargs):
        seen.append((stats["anti_picks"], stats["anti_runs"],
                     sum(stats["pods_by_path"].values())))
        return sound(self, *args, **kwargs)

    monkeypatch.setattr(owner, name, watched)
    algo.schedule_backlog(_anti_rows((0, 1, 2), 16), state)
    algo.schedule_backlog(_anti_rows((3, 4), 16, serial=50), state)
    per_wave = (3, 2) if route == "host" else (1, 1)
    assert seen == [(0, 0, 0)] * per_wave[0] + [(48, 3, 48)] * per_wave[1]
    assert (stats["anti_picks"], stats["anti_runs"]) == (80, 5)


@pytest.mark.parametrize("route", ROUTES)
def test_bound_pods_terms_take_nodes_from_a_run_before_it_starts(route):
    """12 of 30 nodes hold a pod of the group: a run of its other
    controller finds them unfit at its first probe, and nothing else,
    by the program's own count of the nodes that fit as by the host's
    count on the shipped tables."""
    from kubernetes_tpu.oracle import ClusterState

    nodes = _nodes(30, "")
    bound = _anti_rows((0,), 12, serial=100)
    for i, p in enumerate(bound):
        p.spec.node_name = nodes[2 * i].metadata.name
    state = ClusterState.build(nodes, bound,
                               controllers=_anti_controllers())
    backlog = _anti_rows((5,), 16) + _anti_rows((1,), 16)
    algo = _on_route(route)
    got = algo.schedule_backlog(backlog, state)
    assert got == _oracle(state, backlog)
    taken = {p.spec.node_name for p in bound}
    assert not taken & set(got[:16]) and taken & set(got[16:])
    stats = algo._wave.stats
    assert (stats["anti_runs"], stats["anti_picks"]) == (2, 32)
    assert stats["anti_nodes_excluded"] == 12


# -- a run the grouped header probe cannot take goes to the device replay -----
#
# On one chip a run with a self-anti veto, an owner of a term or a
# matcher of a spec takes the device replay whatever the cluster's
# zoning (`classify_runs`): neighbours are one `jit_zreplay_group`
# dispatch where the host's route makes a probe round trip a run. The
# picks are the host route's and the serial oracle's, pod for pod.


def _rows_of(make, controllers, replicas, serial=0):
    """`replicas` in a row of each of `controllers` in turn, a
    controller that comes twice under other names."""
    return [make(t, serial + 100 * j + i)
            for j, t in enumerate(controllers) for i in range(replicas)]


def _soft_on_the_next_group(t, i, groups=5):
    """A replica of controller `t` with a preferred hostname
    anti-affinity term, weight 10, on the NEXT service's pods: it owns
    a term, so no grouped header probe takes its run, and the term does
    not select the pod's own labels, so the run has no veto and no
    refusal."""
    k = (t + 1) % groups
    pod = _annotated(_anti_pod(t, i), podAntiAffinity={
        "preferredDuringSchedulingIgnoredDuringExecution": [{
            "weight": 10, "podAffinityTerm": {
                "labelSelector": {"matchExpressions": [{
                    "key": "group", "operator": "In",
                    "values": [f"g{k}", f"g{k + groups}"]}]},
                "topologyKey": HOSTNAME}}]})
    pod.metadata.name = f"soft{t}-{i:04d}"
    return pod


ROUTE_CASES = {
    # name: (unzoned nodes, [(bound pods, the nodes they lie on)], the
    #        backlog, the steps of the device's route by kind, of the
    #        host's, runs with a veto, pods that fit nowhere)
    "runs-of-several-controllers-and-groups": (
        40, [(lambda: _anti_rows((0,), 6, serial=900), range(0, 18, 3)),
             (lambda: _anti_rows((1,), 4, serial=950), range(1, 17, 4))],
        lambda: _rows_of(_anti_pod, (0, 6, 2, 5), 16),
        {"group_device": 1}, {"single": 4}, 4, 0),
    "a-run-finds-fewer-nodes-than-pods": (
        24, [(lambda: _anti_rows((0,), 12, serial=900), range(0, 24, 2))],
        lambda: _rows_of(_anti_pod, (5, 1), 16),
        {"group_device": 1}, {"single": 2}, 2, 4),
    "a-run-cut-under-min-run": (
        40, [(lambda: _anti_rows((2,), 5, serial=900), range(0, 40, 8))],
        lambda: _rows_of(_anti_pod, (0, 1), 16) + _anti_rows((2,), 9),
        {"group_device": 1, "scan": 1}, {"single": 2, "scan": 1}, 2, 0),
    "a-group-alternates-between-two-groups-terms": (
        64, [(lambda: _anti_rows((0,), 4, serial=900), range(0, 64, 16)),
             (lambda: _anti_rows((6,), 4, serial=950), range(1, 64, 16))],
        lambda: _rows_of(_anti_pod, (0, 1, 5, 6, 0, 1), 16),
        {"group_device": 1}, {"single": 6}, 6, 0),
    "a-lone-vetoed-run": (
        30, [(lambda: _anti_rows((8,), 3, serial=900), (4, 5, 6))],
        lambda: _anti_rows((3,), 20),
        {"single": 1}, {"single": 1}, 1, 0),
    # owners of a term that does not select them: no veto, and still no
    # grouped header probe's
    "owners-of-a-term-on-another-group": (
        30, [(lambda: _anti_rows((1,), 5, serial=900), range(0, 30, 6)),
             (lambda: _anti_rows((2,), 3, serial=950), (1, 2, 3))],
        lambda: _rows_of(_soft_on_the_next_group, (0, 1, 5), 16),
        {"group_device": 1}, {"single": 3}, 0, 0),
    # a plain run whose labels no term selects keeps the host's route,
    # beside term owners too: the fold of its probe rides the device
    # run's dispatch
    "a-vetoed-run-between-two-plain-ones": (
        30, [(lambda: _anti_rows((0,), 4, serial=900), range(0, 28, 7))],
        lambda: _in_rows(1, 16) + _anti_rows((5,), 16)
        + [_pod(1, i) for i in range(16)],
        {"single": 3}, {"single": 3}, 1, 0),
    "plain-runs-on-a-cluster-with-terms-group-on-the-host": (
        30, [(lambda: _anti_rows((0,), 4, serial=900), range(0, 28, 7))],
        lambda: _in_rows(2, 16),
        {"group_host": 1}, {"group_host": 1}, 0, 0),
}


@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
def test_the_device_route_picks_as_the_host_route_and_the_oracle(case):
    from kubernetes_tpu.oracle import ClusterState

    n, bound_on, backlog, steps, host_steps, vetoed, unplaced = \
        ROUTE_CASES[case]
    nodes, bound = _nodes(n, ""), []
    for pods, places in bound_on:
        for p, at in zip(pods(), places):
            p.spec.node_name = nodes[at].metadata.name
            bound.append(p)
    state = ClusterState.build(
        nodes, bound, controllers=_anti_controllers() + _controllers(2))
    backlog = backlog()
    want = _oracle(state, backlog)
    assert want.count(None) == unplaced
    on_device, on_host = _on_route("device"), _on_route("host")
    assert on_device.schedule_backlog(backlog, state) == want
    assert on_host.schedule_backlog(backlog, state) == want
    stats, host_stats = on_device._wave.stats, on_host._wave.stats
    assert {k: v for k, v in stats["steps_by_kind"].items() if v} == steps
    assert {k: v for k, v in host_stats["steps_by_kind"].items() if v} \
        == host_steps
    for key in ANTI_COUNTERS:
        assert stats[key] == host_stats[key], key
    assert stats["anti_runs"] == vetoed
    # the host's route pays a probe for every run that is not a plain
    # one's neighbour, the device's one dispatch for the neighbours
    launched = on_device._wave.dispatches
    if "group_device" in steps:
        assert launched.get("zreplay_group") == 1 and "probe" not in launched
        assert stats["zreplay_rescores"] >= 0
        assert stats["zreplay_steps"] == stats["zreplay_picks"] + unplaced
    if case == "a-vetoed-run-between-two-plain-ones":
        assert launched == {"probe": 2, "zreplay": 1, "apply": 1}
    assert "zreplay" not in on_host._wave.dispatches
    assert "zreplay_group" not in on_host._wave.dispatches


def _volume_pod(i):
    from kubernetes_tpu.api.types import GCEPersistentDisk, Volume

    p = _pod(0, i)
    p.spec.volumes = [Volume(name="data", gce_persistent_disk=GCEPersistentDisk(
        pd_name=f"disk-{i}"))]
    return p


ENCODER_CASES = {
    # name: (pods bound before the wave, the wave, has a scheduler
    #        cache, encoder, the scope gate counted, whether the bound
    #        pods' node is relabelled under them before the wave)
    # terms gate nothing: the inter-pod tables are kept
    "a-pending-term": ([], lambda: _anti_rows((0, 5), 16), True,
                       "incremental", None, False),
    "a-bound-pods-term": (lambda: _anti_rows((3,), 1, serial=900),
                          lambda: _in_rows(2, 16), True, "incremental",
                          None, False),
    # what the kept tables' deltas do not cover rebuilds them whole,
    # counted by reason, and the wave stays with the kept snapshot
    "a-relabel-under-a-term-owner": (
        lambda: _anti_rows((3,), 1, serial=900), lambda: _in_rows(2, 16),
        True, "incremental", None, True),
    "a-volume": ([], lambda: [_volume_pod(i) for i in range(3)], True,
                 "full", "volumes", False),
    "neither": ([], lambda: _in_rows(2, 16), True, "incremental", None,
                False),
    # no cache to keep a snapshot from: from scratch, and no gate
    "no-cache": ([], lambda: _in_rows(2, 16), False, "full", None, False),
}


@pytest.mark.parametrize("case", sorted(ENCODER_CASES))
def test_waves_are_counted_by_encoder_and_fallbacks_by_reason(case):
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.utils.clock import FakeClock

    bound, wave, cached, encoder, reason, relabel = ENCODER_CASES[case]
    nodes = _nodes(30, "")
    bound = bound() if callable(bound) else bound
    for p in bound:
        p.spec.node_name = nodes[7].metadata.name
    cache = SchedulerCache(clock=FakeClock()) if cached else None
    if cached:
        for n in nodes:
            cache.add_node(n)
        for p in bound:
            cache.add_pod(p)
    rebuilds = {"relabel": 1} if relabel else {}
    controllers = _anti_controllers() + _controllers(2)
    algo = TPUScheduleAlgorithm(
        cache=cache, controller_lister=SimpleNamespace(
            list=lambda: controllers))
    if relabel:
        # the owner's term counts by hostname: its node moves into its
        # neighbour's domain (a hostname nobody has would take the
        # domain the old one gave back, and move nothing)
        moved = _nodes(30, "")[7]
        moved.metadata.labels["kubernetes.io/hostname"] = "znode-00008"
        cache.update_node(nodes[7], moved)
        nodes[7] = moved
    state = ClusterState.build(nodes, bound, controllers=controllers)
    shown_before = profile.wave_totals()
    backlog = wave()
    assert algo.schedule_backlog(backlog, state) == _oracle(state, backlog)
    stats = algo._wave.stats
    assert set(stats["waves_by_encoder"]) == set(ENCODERS) \
        == {"incremental", "full"}
    assert stats["waves_by_encoder"][encoder] == 1 == stats["waves"]
    assert stats["encoder_fallbacks"] == ({reason: 1} if reason else {})
    assert stats["interpod_rebuilds"] == rebuilds
    # a second wave counts again, under the same reason; nothing moved
    # under the kept tables since, so they are not rebuilt again
    algo.schedule_backlog(backlog[:3], state)
    assert stats["waves_by_encoder"][encoder] == 2
    assert sum(stats["waves_by_encoder"].values()) == 2
    assert stats["encoder_fallbacks"] == ({reason: 2} if reason else {})
    assert stats["interpod_rebuilds"] == rebuilds
    shown = profile.wave_totals()
    assert _delta(shown["waves_by_encoder"],
                  shown_before["waves_by_encoder"]).get(encoder) == 2
    moved = _delta(shown["encoder_fallbacks"],
                   shown_before["encoder_fallbacks"])
    assert {k: v for k, v in moved.items() if v} \
        == ({reason: 2} if reason else {})
    rebuilt = _delta(shown["interpod_rebuilds"],
                     shown_before["interpod_rebuilds"])
    assert {k: v for k, v in rebuilt.items() if v} == rebuilds


@pytest.mark.parametrize("route", ROUTES)
def test_a_later_wave_of_the_same_terms_builds_no_program(route):
    """The from-scratch encoder's tables are as wide as the terms and
    spread classes the cluster and the wave hold (specs, logical terms,
    classes), and the probe, the fold, the device replay and the scan
    are built per width. Once a pod of every controller is bound the
    widths stand: a wave of other runs of the same controllers (another
    run count in the same bucket of run slots, on the device's route)
    builds none of them again, and a third wave shaped like the second
    builds nothing at all (the second may still build the transfer
    programs of a layout it is first to ship: `jit_pack_unpack`,
    `jit_row_set`)."""
    import time

    from kubernetes_tpu.oracle import ClusterState, GenericScheduler

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    profile.install_compile_listener()
    nodes = _nodes(64, "")
    controllers = _anti_controllers()
    bound = []
    algo = _on_route(route)
    # one oracle for all waves: the round-robin index goes on counting
    oracle = GenericScheduler(predicates=ORACLE_PREDICATES,
                              priorities=ORACLE_PRIORITIES)

    def wave(backlog):
        state = ClusterState.build(nodes, bound, controllers=controllers)
        t = time.time()
        got = algo.schedule_backlog(backlog, state)
        assert got == oracle.schedule_backlog(backlog, state.clone())
        assert None not in got
        for p, host in zip(backlog, got):
            p.spec.node_name = host
            bound.append(p)
        return [c["program"] for c in profile.recent_compiles()
                if c["at"] >= t]

    # one pod of every controller (lone pods: the scan), then runs
    first = wave(_anti_rows(range(10), 1, serial=0))
    assert any("scan" in p for p in first)
    runs = wave(_anti_rows((0, 6, 2, 5), 16, serial=10))
    assert any(("probe_fused" if route == "host" else "zreplay_group") in p
               for p in runs)
    again = wave(_anti_rows((7, 1, 8, 3, 9), 16, serial=30))
    assert not [p for p in again if "probe" in p or "apply" in p
                or "scan" in p or "zreplay" in p], again
    third = wave(_anti_rows((4, 9, 3, 6, 2), 16, serial=50))
    assert third == [], third
    stats = algo._wave.stats
    assert stats["anti_runs"] == 14 and stats["anti_picks"] == 14 * 16
    assert stats["waves_by_encoder"] == {"incremental": 0, "full": 4}
    if route == "device":
        # 4, 5 and 5 runs in the bucket of 32 run slots: one program
        assert stats["dispatches_by_kind"]["zreplay_group"] == 3
        assert len(algo._wave._zreplay._jitted) == 1


# -- a group of runs with distinct commit vectors -----------------------------


def _shaped_rows(controllers, replicas):
    """A controller's replicas in a row, each controller's pods asking
    for resources of their own (benchmark/configs/hetero-1k.json's
    formula)."""
    return [Pod(
        metadata=ObjectMeta(name=f"rc{t}-{i:04d}", labels={"rc": f"rc-{t}"}),
        spec=PodSpec(containers=[Container(requests={
            "cpu": f"{50 + (t % 8) * 25}m",
            "memory": f"{100 + (t % 5) * 100}Mi"})]))
        for t in range(controllers) for i in range(replicas)]


GROUP_CASES = {
    # name: (nodes, pods a node holds, controllers, replicas a run)
    "roomy": (30, "110", 12, 40),
    # 9 nodes of 20 pods hold 180 of the 200: the last runs meet nodes
    # that PodFitsResources filters, and 20 pods fit nowhere
    "full": (9, "20", 5, 40),
    # more runs than eight: sixteen run slots in the one probe
    "many-runs": (20, "110", 11, 16),
}


@pytest.mark.parametrize("case", sorted(GROUP_CASES))
def test_a_group_of_distinct_commit_vectors_picks_as_the_serial_oracle(case):
    from kubernetes_tpu.models.probe import N_STK_ROWS
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    nodes, pods_a_node, controllers, replicas = GROUP_CASES[case]
    backlog = _shaped_rows(controllers, replicas)
    state = ClusterState.build(_nodes(nodes, "", pods=pods_a_node),
                               controllers=_controllers(controllers))
    want = _oracle(state, backlog)
    algo = TPUScheduleAlgorithm()
    shown_before = profile.wave_totals()
    got = algo.schedule_backlog(backlog, state)
    assert got == want
    assert (None in got) == (case == "full")
    stats = algo._wave.stats
    assert stats["pods_by_path"]["group_host"] \
        + stats["pods_by_path"]["single"] == len(backlog)
    # one probe for the whole group: every run's header rows and the
    # resource block's six, 8 bytes a node slot, run slots by power of
    # two from eight
    by_kind = stats["dispatches_by_kind"]
    slots = 8 if controllers <= 8 else 16
    assert by_kind["group_probe"] == 1
    assert stats["group_runs"] == controllers
    assert stats["group_d2h_bytes"] == (slots * N_STK_ROWS + 6) * 64 * 8
    # a group that stops early hands its run to a probe of its own
    assert stats["group_reprobes"] == by_kind.get("probe", 0)
    assert stats["pods_by_path"]["single"] == 0 or stats["group_reprobes"]
    shown = profile.wave_totals()
    for key in ("group_runs", "group_d2h_bytes", "group_reprobes"):
        assert shown[key] - shown_before[key] == stats[key]


def test_the_mesh_driver_keeps_the_same_group_counters():
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.models.wave import GROUP_COUNTERS, WaveScheduler
    from kubernetes_tpu.parallel.mesh import MeshWaveScheduler

    mesh = Mesh(np.array(jax.devices()[:2]), ("nodes",))
    on_mesh, on_chip = MeshWaveScheduler(mesh).stats, WaveScheduler().stats
    assert GROUP_COUNTERS == ("group_runs", "group_d2h_bytes",
                              "group_reprobes")
    for key in GROUP_COUNTERS:
        assert on_mesh[key] == on_chip[key] == 0
    assert set(on_chip["pods_by_path"]) == set(on_mesh["pods_by_path"])
    # and counts in them what its own grouped header probe does (the
    # usage comes from the resident state's mirror: headers only)
    from kubernetes_tpu.models.probe import N_STK_ROWS
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    backlog = _shaped_rows(4, 40)
    state = ClusterState.build(_nodes(30, ""), controllers=_controllers(4))
    algo = TPUScheduleAlgorithm(mesh=mesh)
    assert algo.schedule_backlog(backlog, state) == _oracle(state, backlog)
    stats = algo._wave.stats
    assert stats["pods_by_path"]["group_host"] == 160
    assert stats["group_runs"] == 4 and stats["group_reprobes"] == 0
    assert stats["group_d2h_bytes"] == 4 * N_STK_ROWS * 64 * 8


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
        carry, chosen, _steps = run(static, carry, {
            f: jnp.asarray(getattr(part, f))
            for f in BatchScheduler.POD_FIELDS}, np.int32(len(rows)))
        chosen = np.asarray(chosen)
        placed = chosen >= 0
        np.add.at(table, (chosen[placed], part.class_id[placed]), 1)
        assert np.array_equal(np.asarray(carry[CLASS_COUNT]), table)
        got += [snap.node_names[i] if i >= 0 else None for i in chosen]
    assert got == want
    assert got.count(None) == 1
    assert int(carry[BatchScheduler.LAST_IDX]) == len(backlog) - 1


# -- terms over a topology that couples nodes: why the scan, and its re-warm --


def _term_pod(t, i, kinds, groups=5):
    """A replica of controller `t` with terms that select its service
    (controllers t and t + groups), `kinds` of: "affinity" (required
    podAffinity over the zone; benchmark/configs/podaffinity-2k.json's
    first term), "soft" (preferred podAntiAffinity, weight 100, over
    the hostname: its second), "zone_anti" (required podAntiAffinity
    over the zone)."""
    import json

    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    k = t % groups
    selector = {"matchExpressions": [{
        "key": "group", "operator": "In",
        "values": [f"g{k}", f"g{k + groups}"]}]}
    stated: dict = {}
    if "affinity" in kinds:
        stated["podAffinity"] = {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": selector, "topologyKey": ZONE}]}
    anti = {}
    if "soft" in kinds:
        anti["preferredDuringSchedulingIgnoredDuringExecution"] = [{
            "weight": 100, "podAffinityTerm": {
                "labelSelector": selector, "topologyKey": HOSTNAME}}]
    if "zone_anti" in kinds:
        anti["requiredDuringSchedulingIgnoredDuringExecution"] = [{
            "labelSelector": selector, "topologyKey": ZONE}]
    if anti:
        stated["podAntiAffinity"] = anti
    return Pod(
        metadata=ObjectMeta(name=f"term{t}-{i:05d}",
                            labels={"group": f"g{t}"},
                            annotations={AFFINITY_ANNOTATION:
                                         json.dumps(stated)}),
        spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]))


def _term_rows(controllers, replicas, kinds=("affinity", "soft"), serial=0):
    return [_term_pod(t, serial + i, kinds) for t in controllers
            for i in range(replicas)]


def _one_disk_pod(i):
    """Replicas that mount ONE disk: a run, where `_volume_pod`'s
    disks make every pod a template of its own."""
    p = _volume_pod(0)
    p.metadata.name = f"disk0-{i:04d}"
    return p


REASON_CASES = {
    # name: (the wave, {reason: pods} counted)
    # the deployment's two terms: the required one is met first
    "zone-affinity-and-soft-spread": (
        lambda: _term_rows((0, 6), 16), {"hard_affinity": 32}),
    "soft-spread-alone": (
        lambda: _term_rows((0, 6), 16, ("soft",)), {"self_preferred": 32}),
    "zone-anti-affinity": (
        lambda: _term_rows((0, 1), 16, ("zone_anti",)), {"zone_anti": 32}),
    "a-volume": (lambda: [_one_disk_pod(i) for i in range(16)],
                 {"volumes": 16}),
    # a run shorter than `min_run` is the scan's for its length alone
    "short-runs": (lambda: _term_rows(range(10), 3), {}),
    # the hostname anti-affinity term is the tables': no reason
    "hostname-anti": (lambda: _anti_rows((0, 1), 16), {}),
    "no-terms": (lambda: _in_rows(2, 16), {}),
}


@pytest.mark.parametrize("case", sorted(REASON_CASES))
def test_scan_reasons_say_why_a_run_went_to_the_scan(case):
    from kubernetes_tpu.models.wave import SCAN_REASONS
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.trace.httpd import render_traces

    wave, reasons = REASON_CASES[case]
    backlog = wave()
    state = ClusterState.build(_nodes(30), controllers=_anti_controllers()
                               + _controllers(2))
    algo = TPUScheduleAlgorithm()
    shown_before = render_traces({"limit": "1"})["wave"]["scan_reasons"]
    assert algo.schedule_backlog(backlog, state) == _oracle(state, backlog)
    stats = algo._wave.stats
    assert stats["scan_reasons"] == reasons
    assert set(reasons) <= set(SCAN_REASONS)
    if reasons:
        assert stats["pods_by_path"]["scan"] == len(backlog)
    shown = render_traces({"limit": "1"})["wave"]["scan_reasons"]
    assert {k: v for k, v in _delta(shown, shown_before).items() if v} \
        == reasons


def test_affinity_counters_say_how_much_of_the_cluster_the_term_takes():
    """Service 0 holds pods in zone b: a run of either of its
    controllers is kept off the 20 nodes of zones a and c; service 1
    holds none anywhere, so its first pod may go to all 30 (the
    escape); runs without a required podAffinity term are not counted."""
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    nodes = _nodes(30)
    bound = _term_rows((0, 5), 2, serial=900)
    for p, n in zip(bound, (1, 4, 7, 1)):
        p.spec.node_name = nodes[n].metadata.name
    state = ClusterState.build(nodes, bound, controllers=_anti_controllers())
    backlog = (_term_rows((5,), 16) + _term_rows((1,), 16)
               + _term_rows((0,), 3) + _anti_rows((2,), 16))
    algo = TPUScheduleAlgorithm()
    got = algo.schedule_backlog(backlog, state)
    assert got == _oracle(state, backlog)
    zone_b = {n.metadata.name for n in nodes[1::3]}
    assert set(got[:16]) <= zone_b and set(got[32:35]) <= zone_b
    stats = algo._wave.stats
    assert (stats["affinity_runs"], stats["affinity_nodes_excluded"]) \
        == (3, 20 + 0 + 20)
    assert stats["scan_reasons"] == {"hard_affinity": 32}


def _daemon(nodes, controllers, bound=()):
    """The served wave driver as the daemon holds it: a scheduler cache
    feeding the kept snapshot."""
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.utils.clock import FakeClock

    cache = SchedulerCache(clock=FakeClock())
    for n in nodes:
        cache.add_node(n)
    for p in bound:
        cache.add_pod(p)
    algo = TPUScheduleAlgorithm(
        cache=cache, controller_lister=SimpleNamespace(
            list=lambda: controllers))
    return cache, algo


#: (zoned nodes, rounds, runs a round, pods a run, share deleted between
#: rounds, seed): every stream begins with the first pod of each service
#: (the escape) and meets both of its controllers; a share of 1.0
#: empties the services, which choose a zone again
SERVED_CASES = [(24, 3, 6, 5, 0.3, 11), (48, 3, 8, 16, 1.0, 2 ** 31 + 12),
                (96, 2, 10, 8, 0.5, 13)]


@pytest.mark.parametrize("n,rounds,runs,row,deleted,seed", SERVED_CASES)
def test_the_served_driver_decides_zone_affinity_as_the_serial_oracle(
        n, rounds, runs, row, deleted, seed):
    import random

    rng = random.Random(seed)
    nodes, controllers = _nodes(n), _anti_controllers()
    cache, algo = _daemon(nodes, controllers)
    oracle = _a_serial_oracle()
    live = []
    zones_taken = set()
    for r in range(rounds):
        order = rng.sample(range(10), 10)
        backlog = []
        for j in range(runs):
            backlog += _term_rows((order[j % 10],), row,
                                  serial=1000 * r + 100 * j)
        state = cache.snapshot(controllers=controllers)
        got = algo.schedule_backlog(backlog, state)
        assert got == oracle.schedule_backlog(backlog, state.clone())
        assert None not in got
        for p, host in zip(backlog, got):
            p.spec.node_name = host
            cache.add_pod(p)
            live.append(p)
        zone_of = {nd.metadata.name: nd.metadata.labels[ZONE]
                   for nd in nodes}
        for k in range(5):
            zones = {zone_of[p.spec.node_name] for p in live
                     if int(p.metadata.labels["group"][1:]) % 5 == k}
            assert len(zones) <= 1
            zones_taken |= {(k, z) for z in zones}
        gone = rng.sample(range(len(live)), int(deleted * len(live)))
        for i in sorted(gone, reverse=True):
            cache.remove_pod(live.pop(i))
    stats = algo._wave.stats
    assert stats["waves_by_encoder"] == {"incremental": rounds, "full": 0}
    assert stats["pods_by_path"]["scan"] == rounds * runs * row
    assert stats["affinity_runs"] == rounds * runs
    assert stats["rewarms"] == 0  # no KUBERNETES_TPU_WARM_SCAN here


def _a_serial_oracle():
    from kubernetes_tpu.oracle import GenericScheduler

    from tests.test_conformance import ORACLE_PREDICATES, ORACLE_PRIORITIES

    return GenericScheduler(predicates=ORACLE_PREDICATES,
                            priorities=ORACLE_PRIORITIES)


def _dealt(controllers, pods, serial, make=None):
    """`pods` pods of `controllers` dealt in turn (runs of length 1):
    the deployment's two terms on each, or what `make(t, i)` makes."""
    make = make or (lambda t, i: _term_pod(t, i, ("affinity", "soft")))
    return [make(controllers[i % len(controllers)], serial + i)
            for i in range(pods)]


def _recorded_waves(algo):
    """Wrap the driver: what every wave of the daemon's own encoder was
    handed (a warm-up's waves come from an encoder of their own)."""
    seen = []
    inner = algo._wave.schedule_backlog

    def schedule_backlog(snap, batch, rep_idx, **kw):
        if kw.get("source") == algo._live_inc.source_token:
            seen.append((snap, batch, kw["keep"], kw["reship"],
                         kw["last_node_index"]))
        return inner(snap, batch, rep_idx, **kw)

    algo._wave.schedule_backlog = schedule_backlog
    return seen


def _same_arrays(a, b):
    import dataclasses

    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and x.shape == y.shape, f.name
            assert np.array_equal(x, y, equal_nan=x.dtype.kind == "f"), \
                f.name
        else:
            assert x == y, f.name


def test_the_rewarm_builds_every_bucket_once_and_leaves_the_view_alone(
        monkeypatch):
    """With KUBERNETES_TPU_WARM_SCAN on, the first wave that carries a
    term warms the scan at every pod bucket; afterwards a wave of any
    bucket builds no scan, a second wave of the same widths never
    re-warms, and what the live encoder hands the driver next is bit
    for bit what it hands a daemon that never re-warmed."""
    import time

    from kubernetes_tpu.trace import spans

    profile.install_compile_listener()
    controllers = _anti_controllers()
    first = lambda: _dealt(range(10), 10, 0)  # noqa: E731
    second = lambda: _dealt(range(10), 70, 100)  # noqa: E731

    def two_waves(warm):
        if warm:
            monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
        else:
            monkeypatch.delenv("KUBERNETES_TPU_WARM_SCAN", raising=False)
        nodes = _nodes(48)
        cache, algo = _daemon(nodes, controllers)
        seen = _recorded_waves(algo)
        picks = []
        for backlog in (first(), second()):
            state = cache.snapshot(controllers=controllers)
            got = algo.schedule_backlog(backlog, state)
            picks.append(got)
            for p, host in zip(backlog, got):
                p.spec.node_name = host
                cache.add_pod(p)
        return cache, algo, seen, picks

    t_began = time.time()
    cache, algo, seen, picks = two_waves(warm=True)
    _cache, plain, seen_plain, picks_plain = two_waves(warm=False)
    stats = algo._wave.stats
    assert stats["rewarms"] == 1 and stats["rewarm_mismatches"] == 0
    assert stats["rewarm_programs"] >= 7 and stats["rewarm_seconds"] > 0
    assert plain._wave.stats["rewarms"] == 0
    assert picks == picks_plain
    # the wave after the re-warm: the same snapshot, batch, `keep`,
    # `reship` and round-robin counter as without it
    assert len(seen) == len(seen_plain) == 2
    for (snap, batch, keep, reship, last), \
            (snap_p, batch_p, keep_p, reship_p, last_p) in zip(seen,
                                                               seen_plain):
        _same_arrays(snap, snap_p)
        _same_arrays(batch, batch_p)
        assert (keep, reship, last) == (keep_p, reship_p, last_p)
    assert algo._last_node_index == plain._last_node_index
    # one span, with the widths and the buckets
    mine = [s for s in spans.BUFFER.snapshot(limit=16384)
            if s["name"] == "scheduler.rewarm" and s["start"] >= t_began]
    assert len(mine) == 1
    assert mine[0]["attrs"]["buckets"] == [64, 128, 256, 512, 1024, 2048,
                                           4096]
    assert mine[0]["attrs"]["domains"] == 48
    # now a wave in every bucket: no scan is built, whatever else is.
    # A warm wave fills its bucket and a live one does not: the scan's
    # trip count is an operand, the same program's (its callers pass it
    # as one dtype), so the jitted scans hold the entries they held
    def scans_held():
        return sum(fn._cache_size()
                   for fn in algo._wave.scan._jitted.values())

    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    warmed = scans_held()
    assert warmed >= 7
    serial = 1000
    for pods in (10, 100, 200, 400, 900, 1800):
        backlog = _dealt(range(10), pods, serial)
        serial += pods
        state = cache.snapshot(controllers=controllers)
        t = time.time()
        got = algo.schedule_backlog(backlog, state)
        built = [c["program"] for c in profile.recent_compiles()
                 if c["at"] >= t]
        assert not [p for p in built if "scan" in p], (pods, built)
        for p, host in zip(backlog, got):
            if host is not None:
                p.spec.node_name = host
                cache.add_pod(p)
    assert stats["rewarms"] == 1
    assert scans_held() == warmed
    assert stats["scan_steps"] == stats["pods_by_path"]["scan"]
    # the warm waves filled their buckets, the eight live ones did not
    assert stats["scan_bucket_steps"] - stats["scan_steps"] \
        == (64 - 10) + (128 - 70) + (64 - 10) + (128 - 100) + (256 - 200) \
        + (512 - 400) + (1024 - 900) + (2048 - 1800)


def test_a_cluster_without_terms_never_rewarms(monkeypatch):
    """The spread-class axis grows wave by wave as controllers' pods
    first appear, and must not trigger it: only inter-pod widths do."""
    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    controllers = _controllers(12)
    cache, algo = _daemon(_nodes(30), controllers)
    oracle = _a_serial_oracle()
    serial = 0
    for wave in ((0, 1, 2), (3, 4, 5, 6), tuple(range(12))):
        backlog = _dealt(wave, 24, serial, make=_pod)
        serial += 24
        state = cache.snapshot(controllers=controllers)
        got = algo.schedule_backlog(backlog, state)
        assert got == oracle.schedule_backlog(backlog, state.clone())
        for p, host in zip(backlog, got):
            p.spec.node_name = host
            cache.add_pod(p)
    stats = algo._wave.stats
    assert stats["rewarms"] == 0 and stats["rewarm_programs"] == 0
    assert algo._warmed_widths == set() and algo._last_widths is None


def test_the_rewarm_hands_the_loop_back_and_goes_on_behind_the_next_wave(
        monkeypatch):
    """A slice of no seconds: one bucket a wave, smallest first, until
    none is left; widths seen meanwhile are not warmed twice."""
    from kubernetes_tpu.scheduler import tpu_algorithm

    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    monkeypatch.setattr(tpu_algorithm, "REWARM_SLICE_S", 0.0)
    controllers = _anti_controllers()
    cache, algo = _daemon(_nodes(24), controllers)
    stats = algo._wave.stats
    left = []
    for wave in range(8):
        backlog = _dealt(range(10), 10, 100 * wave)
        state = cache.snapshot(controllers=controllers)
        got = algo.schedule_backlog(backlog, state)
        for p, host in zip(backlog, got):
            p.spec.node_name = host
            cache.add_pod(p)
        left.append(len(algo._rewarm_left))
    assert left == [6, 5, 4, 3, 2, 1, 0, 0]
    assert stats["rewarms"] == 7 and len(algo._warmed_widths) == 1


def test_terms_the_run_tables_hold_warm_the_replay_and_the_scan_once_used(
        monkeypatch):
    """A hostname anti-affinity term is the run tables': its runs take
    the device replay, a wave is ONE group of as many run slots as it
    has runs, and the scan meets the term widths through lone pods and
    cut runs alone, in its smallest bucket, which the wave that meets it
    builds. Behind the first wave that shows the widths the re-warm
    builds the run programs (a run alone, the group at every run-slot
    bucket a wave can fill) and none of the scan's buckets; a live wave
    of any run count then builds no replay program. Once a wave's scan
    decides more pods than its smallest bucket holds, every bucket of
    the scan is warmed too, once."""
    import time

    from kubernetes_tpu.scheduler import core
    from kubernetes_tpu.trace import spans

    profile.install_compile_listener()
    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    monkeypatch.setattr(core, "WAVE_CAP", 1024)  # 64 runs of 16 at most
    controllers = _anti_controllers()
    cache, algo = _daemon(_nodes(200, ""), controllers)
    stats = algo._wave.stats
    t_began = time.time()

    def wave(backlog):
        state = cache.snapshot(controllers=controllers)
        t = time.time()
        got = algo.schedule_backlog(backlog, state)
        for p, host in zip(backlog, got):
            if host is not None:
                p.spec.node_name = host
                cache.add_pod(p)
        return [c["program"] for c in profile.recent_compiles()
                if c["at"] >= t]

    built = wave(_anti_rows(range(10), 1, serial=0))  # one of each: lone
    assert stats["rewarms"] == 1 and stats["rewarm_mismatches"] == 0
    assert algo._warmed_run_widths == {algo._last_widths}
    assert not algo._scan_bound and algo._warmed_widths == set()
    assert set(algo._template_kinds.values()) == {"device"}
    assert built.count("jit(zreplay_run)") == 1
    assert built.count("jit(zreplay_group)") == 2  # 32, 128 run slots
    # the scan at its smallest bucket alone: the wave's own lone pods
    assert built.count("jit(batch_scan)") == 1
    mine = [s for s in spans.BUFFER.snapshot(limit=16384)
            if s["name"] == "scheduler.rewarm" and s["start"] >= t_began]
    assert len(mine) == 1
    attrs = mine[0]["attrs"]
    assert (attrs["buckets"], attrs["slots"], attrs["left"]) \
        == ([], [32, 128], 0)
    assert attrs["steps"]["single"] == 10  # each template's run alone
    assert attrs["steps"]["group_device"] == 2  # ten side by side; nine
    # live waves of 3 runs, 2 and a cut one, 12 and 40: no program of
    # the replay's or the scan's is built, and nothing warms again
    anti_runs = stats["anti_runs"]

    def rows(ts, serial):
        return [_anti_pod(t, serial + 20 * j + i)
                for j, t in enumerate(ts) for i in range(16)]

    for backlog in (rows((0, 6, 2), 100),
                    rows((7, 3), 200) + _anti_rows((1,), 9, serial=300),
                    rows(tuple(range(10)) + (4, 8), 400),
                    rows(tuple(range(10)) * 4, 1000)):
        built = wave(backlog)
        assert not [p for p in built if "zreplay" in p or "scan" in p
                    or "probe" in p or "apply" in p], built
    assert stats["rewarms"] == 1 and not algo._scan_bound
    assert stats["anti_runs"] - anti_runs == 3 + 2 + 12 + 40
    assert "probe" not in stats["dispatches_by_kind"]
    # dealt in turn: 70 runs of one pod, the scan's second bucket
    wave([_anti_pod(t % 10, 2000 + t) for t in range(70)])
    assert stats["rewarms"] == 2 and algo._scan_bound
    assert algo._warmed_widths == algo._warmed_run_widths \
        == {algo._last_widths}
    wave([_anti_pod(t % 10, 3000 + t) for t in range(70)])
    assert stats["rewarms"] == 2


def test_the_rewarm_of_the_replay_goes_a_run_slot_bucket_a_wave(monkeypatch):
    """A slice of no seconds on a cluster whose runs are the device
    replay's: the run backlog behind the first wave, then one run-slot
    bucket behind each wave, smallest first, until none is left."""
    from kubernetes_tpu.scheduler import core, tpu_algorithm

    monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
    monkeypatch.setattr(tpu_algorithm, "REWARM_SLICE_S", 0.0)
    monkeypatch.setattr(core, "WAVE_CAP", 1024)  # 64 runs of 16 at most
    controllers = _anti_controllers()
    cache, algo = _daemon(_nodes(24, ""), controllers)
    stats = algo._wave.stats
    left = []
    for wave in range(4):
        backlog = _dealt(range(10), 10, 100 * wave, make=_anti_pod)
        state = cache.snapshot(controllers=controllers)
        got = algo.schedule_backlog(backlog, state)
        for p, host in zip(backlog, got):
            if host is not None:
                p.spec.node_name = host
                cache.add_pod(p)
        left.append((algo._rewarm_runs, list(algo._rewarm_slots)))
    assert left == [(False, [32, 128]), (False, [128]), (False, []),
                    (False, [])]
    assert stats["rewarms"] == 3 and algo._rewarm_left == []
    groups = stats["dispatches_by_kind"]["zreplay_group"]
    assert groups == 2 + 2  # the run backlog's two, a bucket's one each


# -- a cluster of five kinds of pod: one wave that changes path run by run ----


def _mixed_pod(t, i, groups=5):
    """A replica of controller `t` of benchmark/configs/mixed-5k.json's
    ten: kind t % 5 of (0) no annotation, (1) a required podAffinity
    term over the zone, (2) a required podAntiAffinity term over the
    hostname, (3) a preferred podAffinity term, weight 1, over the
    hostname, (4) a preferred podAntiAffinity term, weight 1, over the
    hostname, each on its own service (controllers t and t + groups);
    cpu 100m and memory 500Mi stated."""
    import json

    from kubernetes_tpu.api.types import AFFINITY_ANNOTATION

    k = t % groups
    selector = {"matchExpressions": [{
        "key": "group", "operator": "In",
        "values": [f"g{k}", f"g{k + groups}"]}]}
    required = "requiredDuringSchedulingIgnoredDuringExecution"
    preferred = "preferredDuringSchedulingIgnoredDuringExecution"
    soft = [{"weight": 1, "podAffinityTerm": {
        "labelSelector": selector, "topologyKey": HOSTNAME}}]
    stated = [None,
              {"podAffinity": {required: [{
                  "labelSelector": selector, "topologyKey": ZONE}]}},
              {"podAntiAffinity": {required: [{
                  "labelSelector": selector, "topologyKey": HOSTNAME}]}},
              {"podAffinity": {preferred: soft}},
              {"podAntiAffinity": {preferred: soft}}][k]
    return Pod(
        metadata=ObjectMeta(
            name=f"mix{t}-{i:05d}", labels={"group": f"g{t}"},
            annotations={} if stated is None else {
                AFFINITY_ANNOTATION: json.dumps(stated)}),
        spec=PodSpec(containers=[Container(requests={
            "cpu": "100m", "memory": "500Mi"})]))


def _mixed_rows(controllers, replicas, serial=0):
    return [_mixed_pod(t, serial + 100 * j + i)
            for j, t in enumerate(controllers) for i in range(replicas)]


def _steps_and_flushes(algo):
    """Wrap the loop's planner and the seam's scan: the steps
    `next_step` handed `run_wave` and the scans the loop dispatched, of
    the daemon's own waves and of its warm waves alike."""
    from kubernetes_tpu.models import waveloop

    seen = {"steps": [], "scans": 0}
    plan, scan = waveloop.next_step, algo._wave.scan_pending

    def next_step(runs, idx, policy):
        step = plan(runs, idx, policy)
        seen["steps"].append(step.kind)
        return step

    def scan_pending(wave, rows):
        seen["scans"] += 1
        return scan(wave, rows)

    return seen, next_step, scan_pending


#: (one-zone nodes, the controllers of a round's runs in order, pods a
#: run, share deleted between rounds, seed): every stream begins with
#: one pod of each controller (the escape of service 1, a
#: preferred-affinity maximum above 0 from then on); a round's wave
#: then goes scan -> device single with the veto -> scan -> device
#: group of two plain runs -> scan -> device single, and the round
#: after it deals the same runs in a seeded order. Small, because the
#: serial oracle takes a second a pod once a few hundred are bound
MIXED_CASES = [(48, (1, 2, 3, 0, 5, 4, 7), 16, 0.5, 49),
               (24, (8, 7, 6, 5, 0, 9, 2, 3), 16, 1.0, 2 ** 31 + 49)]


@pytest.mark.parametrize("n,runs,row,deleted,seed", MIXED_CASES)
def test_a_mixed_wave_changes_path_run_by_run_and_picks_as_the_serial_oracle(
        n, runs, row, deleted, seed, monkeypatch):
    import itertools
    import random

    from kubernetes_tpu.models import waveloop

    import copy

    rng = random.Random(seed)
    nodes, controllers = _nodes(n, "a"), _anti_controllers()
    cache, algo = _daemon(nodes, controllers)
    rounds = []
    seen, next_step, scan_pending = _steps_and_flushes(algo)
    monkeypatch.setattr(waveloop, "next_step", next_step)
    monkeypatch.setattr(algo._wave, "scan_pending", scan_pending)
    oracle = _a_serial_oracle()
    live = []
    shown = profile.wave_totals()
    for r in range(3):
        backlog = _mixed_rows(rng.sample(range(10), 10), 1) if r == 0 \
            else _mixed_rows(runs if r == 1 else rng.sample(runs, len(runs)),
                             row, serial=10_000 * r)
        state = cache.snapshot(controllers=controllers)
        got = algo.schedule_backlog(backlog, state)
        assert got == oracle.schedule_backlog(backlog, state.clone())
        rounds.append((copy.deepcopy(backlog), state.clone(), got))
        if r == 1:
            # kinds 0 and 2 take the device replay, neighbours as a group
            want = []
            for device, span in itertools.groupby(
                    runs, key=lambda t: t % 5 in (0, 2)):
                want.append("scan" if not device else "single"
                            if len(list(span)) == 1 else "group_device")
            assert seen["steps"][-len(want):] == want
            assert {"scan", "single", "group_device"} == set(want)
        for p, host in zip(backlog, got):
            if host is not None:
                p.spec.node_name = host
                cache.add_pod(p)
                live.append(p)
        # the read-back guarantee: one pod of the anti-affine service a node
        held = [p.spec.node_name for p in live
                if int(p.metadata.labels["group"][1:]) % 5 == 2]
        assert len(held) == len(set(held))
        gone = rng.sample(range(len(live)), int(deleted * len(live)))
        for i in sorted(gone, reverse=True):
            cache.remove_pod(live.pop(i))
    stats = algo._wave.stats
    # the three services of refused terms go to the scan, by reason
    assert set(stats["scan_reasons"]) == {"hard_affinity", "self_preferred"}
    # on one zone a plain run and a vetoed run both take the device
    # replay, alone or as neighbours; no probe reaches the host, and
    # the program's count of the nodes that fit at a vetoed run's probe
    # says what the host's route reads off its tables
    kinds = stats["steps_by_kind"]
    assert kinds["scan"] and kinds["single"] and kinds["group_device"]
    assert kinds["group_host"] == 0 and "probe" not in \
        stats["dispatches_by_kind"]
    assert stats["anti_runs"] > 0 and stats["anti_nodes_excluded"] > 0
    # the counters are the plan's steps and the scan's dispatches
    assert sum(kinds.values()) == len(seen["steps"])
    assert {k: n for k, n in kinds.items() if n} \
        == {k: seen["steps"].count(k) for k in set(seen["steps"])}
    assert stats["scan_flushes"] == seen["scans"] \
        == stats["dispatches_by_kind"]["scan"]
    # and /debug/traces moved by the same
    after = profile.wave_totals()
    assert _delta(after["steps_by_kind"], shown["steps_by_kind"]) == kinds
    assert after["scan_flushes"] - shown["scan_flushes"] \
        == stats["scan_flushes"]
    # the same waves on the host's route (a probe a run, counted on the
    # tables it shipped): the same picks and the same three counts
    on_host = _on_route("host")
    for backlog, state, got in rounds:
        assert on_host.schedule_backlog(backlog, state) == got
    assert on_host._wave.stats["dispatches_by_kind"]["probe"] > 0
    for key in ANTI_COUNTERS:
        assert stats[key] == on_host._wave.stats[key], key


def test_a_group_that_breaks_off_counts_its_single_step_too(monkeypatch):
    """Three pure runs planned as one group; the group stops in its
    second run, which goes on as a `single`, and the third is planned
    again, alone: one `group_host` step and two `single` steps."""
    from collections import Counter

    from kubernetes_tpu.models import waveloop

    runs = [waveloop.Run(rep, 16 * rep, 16, eligible=True, pure=True)
            for rep in range(3)]
    ran = []
    monkeypatch.setattr(
        waveloop, "run_group_host",
        lambda dev, wave, group: ran.append(len(group)) or (1, 5))
    monkeypatch.setattr(
        waveloop, "run_single",
        lambda dev, wave, run, done=0: ran.append((run.rep, done)))
    wave = SimpleNamespace(steps=Counter(), tallies=Counter(), pending=[])
    waveloop.run_wave(SimpleNamespace(finish=lambda wave: None), wave, runs,
                      waveloop.Policy(host_cap=8))
    assert ran == [3, (1, 5), (2, 0)]
    assert wave.steps == {"group_host": 1, "single": 2}
    assert wave.tallies["scan_flushes"] == 0  # nothing was pending


def test_both_drivers_keep_the_loops_counters():
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.models.wave import LOOP_COUNTERS, WaveScheduler
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.parallel.mesh import MeshWaveScheduler
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.trace.httpd import render_traces

    mesh = Mesh(np.array(jax.devices()[:2]), ("nodes",))
    on_mesh, on_chip = MeshWaveScheduler(mesh).stats, WaveScheduler().stats
    assert LOOP_COUNTERS == ("scan_flushes",)
    for stats in (on_mesh, on_chip):
        assert stats["scan_flushes"] == 0
        assert stats["steps_by_kind"] == dict.fromkeys(PATHS, 0)
    # the mesh's loop is the same loop: rows, then lone pods
    backlog = _shaped_rows(4, 40) + _dealt_in_turn(4, 3)
    state = ClusterState.build(_nodes(30, ""), controllers=_controllers(4))
    algo = TPUScheduleAlgorithm(mesh=mesh)
    assert algo.schedule_backlog(backlog, state) == _oracle(state, backlog)
    stats = algo._wave.stats
    assert stats["steps_by_kind"] == {"scan": 1, "single": 0,
                                      "group_host": 1, "group_device": 0}
    assert stats["scan_flushes"] == 1
    shown = render_traces({"limit": "1"})["wave"]
    assert {"steps_by_kind", "scan_flushes"} <= set(shown)
    assert set(shown["steps_by_kind"]) <= set(PATHS)


def test_the_rewarm_of_a_mixed_cluster_warms_the_run_programs_once(
        monkeypatch):
    """On a cluster where inter-pod widths are live and some templates'
    runs leave the scan, the re-warm also runs one backlog of runs of
    every template seen pending: each eligible run alone, side by side
    and as a group over the smallest run-slot bucket, scan stretches
    between them. Afterwards a live wave of any such plan builds no
    scheduling program (the transfers' unpack programs are the
    prefill's), nothing is built twice, and the live encoder, the
    round-robin counter and the driver's mirrors are what a daemon
    that never re-warmed holds."""
    import time

    from kubernetes_tpu.trace import spans

    profile.install_compile_listener()
    controllers = _anti_controllers()
    first = lambda: _mixed_rows(range(10), 1)  # noqa: E731
    second = lambda: _mixed_rows(  # noqa: E731
        (0, 5, 2, 1, 7, 3, 0, 4, 2, 7, 5, 0, 9), 16, serial=1000)

    def two_waves(warm):
        if warm:
            monkeypatch.setenv("KUBERNETES_TPU_WARM_SCAN", "1")
        else:
            monkeypatch.delenv("KUBERNETES_TPU_WARM_SCAN", raising=False)
        cache, algo = _daemon(_nodes(48, "a"), controllers)
        seen = _recorded_waves(algo)
        picks, built = [], []
        for backlog in (first(), second()):
            state = cache.snapshot(controllers=controllers)
            t = time.time()
            got = algo.schedule_backlog(backlog, state)
            built.append([c["program"] for c in profile.recent_compiles()
                          if c["at"] >= t])
            picks.append(got)
            for p, host in zip(backlog, got):
                p.spec.node_name = host
                cache.add_pod(p)
        return cache, algo, seen, picks, built

    t_began = time.time()
    cache, algo, seen, picks, built = two_waves(warm=True)
    _cache, plain, seen_plain, picks_plain, _built = two_waves(warm=False)
    stats = algo._wave.stats
    assert stats["rewarms"] == 1 and stats["rewarm_mismatches"] == 0
    assert picks == picks_plain
    assert list(algo._template_kinds.values()) == [
        "device", "scan", "device", "scan", "scan"] * 2
    # the wave behind the re-warm meets every kind of step and builds
    # no scheduling program: the re-warm had them all
    assert not [p for p in built[1]
                if "scan" in p or "zreplay" in p or "probe" in p
                or "apply" in p], built[1]
    # nothing twice: every program of the re-warm's wave differs
    warm_built = [p for p in built[0] if "zreplay" in p or "scan" in p]
    assert sorted(warm_built).count("jit(zreplay_run)") == 1
    # four side by side and nine of two: both in the bucket of 32 slots
    assert sorted(warm_built).count("jit(zreplay_group)") == 1
    assert sorted(warm_built).count("jit(batch_scan)") == 7
    # the wave after the re-warm: the same snapshot, batch, `keep`,
    # `reship` and round-robin counter as without it
    assert len(seen) == len(seen_plain) == 2
    for (snap, batch, keep, reship, last), \
            (snap_p, batch_p, keep_p, reship_p, last_p) in zip(seen,
                                                               seen_plain):
        _same_arrays(snap, snap_p)
        _same_arrays(batch, batch_p)
        assert (keep, reship, last) == (keep_p, reship_p, last_p)
    assert algo._last_node_index == plain._last_node_index
    # one span: the steps its run backlog made, by kind, and the buckets
    mine = [s for s in spans.BUFFER.snapshot(limit=16384)
            if s["name"] == "scheduler.rewarm" and s["start"] >= t_began]
    assert len(mine) == 1
    attrs = mine[0]["attrs"]
    assert attrs["buckets"] == [64, 128, 256, 512, 1024, 2048, 4096]
    # the scan's stretches cut a wave's groups short: no ladder of run
    # slots on a cluster that uses the scan
    assert attrs["slots"] == []
    assert attrs["steps"]["single"] == 4  # each eligible template alone
    assert attrs["steps"]["group_device"] == 2  # 4 side by side; 9 of two
    assert attrs["steps"]["scan"] >= 5
    # a cluster whose every template is the scan's warms no run backlog
    assert plain._wave.stats["rewarms"] == 0
