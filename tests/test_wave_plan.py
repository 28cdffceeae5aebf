"""A wave's plan as a value (models/waveloop): `next_step` over hand-built
`Run` lists, the loop over a fake device, `classify_runs`' finished
`Run`s, and a real wave's printed plan beside the dispatches it then
makes, on one chip and on a 2-device mesh."""

import numpy as np
import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.models.waveloop import (
    DEVICE_GROUP_RUNS,
    DEVICE_RUN_PODS,
    Policy,
    Run,
    Step,
    Wave,
    format_plan,
    next_step,
    plan_steps,
    run_wave,
)

ZONE = "failure-domain.beta.kubernetes.io/zone"
ONE_CHIP = Policy(host_cap=8)
MESH = Policy(host_cap=8, lone_pure_grouped=True)


def _runs(*specs):
    """Runs laid end to end from (length, kind) pairs: `scan` is
    ineligible, `pure` and `impure` take the host tables, `device` the
    device replay."""
    runs, start = [], 0
    for rep, (length, kind) in enumerate(specs):
        runs.append(Run(rep, start, length, eligible=kind != "scan",
                        pure=kind == "pure", device=kind == "device"))
        start += length
    return runs


PLAN_CASES = {
    # name: (runs, policy, idx, the step's kind, its runs)
    "an ineligible run goes to the scan, with those behind it":
        (_runs((1, "scan"), (3, "scan"), (20, "pure")), ONE_CHIP, 0,
         "scan", 2),
    "pure runs make one group_host, cut at the host cap":
        (_runs(*[(20, "pure")] * 12), ONE_CHIP, 0, "group_host", 8),
    "and the runs behind the cap make the next":
        (_runs(*[(20, "pure")] * 12), ONE_CHIP, 8, "group_host", 4),
    "a pure run before an impure one is a single":
        (_runs((20, "pure"), (20, "impure")), ONE_CHIP, 0, "single", 1),
    "and so is the impure one":
        (_runs((20, "pure"), (20, "impure")), ONE_CHIP, 1, "single", 1),
    "an impure run takes no pure run with it":
        (_runs((20, "impure"), (20, "pure"), (20, "pure")), ONE_CHIP, 0,
         "single", 1),
    "device runs group until the cap of runs":
        (_runs(*[(64, "device")] * (DEVICE_GROUP_RUNS + 88)), ONE_CHIP, 0,
         "group_device", DEVICE_GROUP_RUNS),
    "device runs group until a run over the cap of pods":
        (_runs((100, "device"), (100, "device"),
               (DEVICE_RUN_PODS + 1, "device"), (100, "device")),
         ONE_CHIP, 0, "group_device", 2),
    "and that run goes alone":
        (_runs((100, "device"), (100, "device"),
               (DEVICE_RUN_PODS + 1, "device"), (100, "device")),
         ONE_CHIP, 2, "single", 1),
    # 9 slots of 4,096 are 36,864 > 8 x (4,096 + 8 pods)
    "device runs group until the slots pass 8 x the picks":
        (_runs((4096, "device"), *[(1, "device")] * 20), ONE_CHIP, 0,
         "group_device", 8),
    "a device run takes no host run with it":
        (_runs((40, "device"), (40, "pure")), ONE_CHIP, 0, "single", 1),
    "a lone pure run on one chip is a single":
        (_runs((48, "pure"), (1, "scan")), ONE_CHIP, 0, "single", 1),
    "on the mesh it takes the header probe":
        (_runs((48, "pure"), (1, "scan")), MESH, 0, "group_host", 1),
    'under reuse="reship" it is a single again':
        (_runs((48, "pure"), (1, "scan")),
         Policy(host_cap=8, lone_pure_grouped=False), 0, "single", 1),
    "a lone impure run on the mesh stays a single":
        (_runs((48, "impure"),), MESH, 0, "single", 1),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_next_step(case):
    runs, policy, idx, kind, n = PLAN_CASES[case]
    step = next_step(runs, idx, policy)
    assert step == Step(kind, tuple(runs[idx:idx + n]))


def test_a_whole_plan_prints():
    runs = _runs((1, "scan"), (1, "scan"), (20, "pure"), (30, "pure"),
                 (16, "impure"), (5, "scan"))
    steps = plan_steps(runs, ONE_CHIP)
    assert [s.kind for s in steps] == ["scan", "group_host", "single",
                                       "scan"]
    assert format_plan(steps) == (
        "scan[2 runs, 2 pods] group_host[2 runs, 50 pods] "
        "single[1 runs, 16 pods] scan[1 runs, 5 pods]")


class _FakeDevice:
    """The seam with no device behind it: every device group stops at
    its run 1 after 5 picks, and a single run is placed whole."""

    def __init__(self):
        self.calls = []

    def replay_group_device(self, wave, runs):
        self.calls.append(("group_device", [r.rep for r in runs]))
        wave.out[runs[0].start:runs[0].stop] = 0
        wave.out[runs[1].start:runs[1].start + 5] = 0
        return 1, 5

    def replay_run_device(self, wave, run, done0):
        self.calls.append(("single", run.rep, done0))
        wave.out[run.start + done0:run.stop] = 0

    def scan_pending(self, wave, rows):
        self.calls.append(("scan", rows.tolist()))
        return np.zeros(len(rows), np.int32), 0

    def finish(self, wave):
        self.calls.append(("finish",))


def test_a_group_that_stops_at_run_g_replans_from_g_plus_1():
    from types import SimpleNamespace

    runs = _runs(*[(10, "device")] * 5, (2, "scan"))
    snap = SimpleNamespace(zone_id=np.ones(4, np.int32), num_nodes=4,
                           name_desc_order=np.arange(4), svc_num_values=0)
    wave = Wave(None, snap, None, np.zeros(52, np.int64), 0, 128, None)
    dev = _FakeDevice()
    run_wave(dev, wave, runs, ONE_CHIP)
    assert dev.calls == [
        ("group_device", [0, 1, 2, 3, 4]), ("single", 1, 5),
        ("group_device", [2, 3, 4]), ("single", 3, 5),
        ("single", 4, 0),
        ("scan", [50, 51]), ("finish",)]
    assert (wave.out == 0).all()
    # the span a group took and handed on is the single's in the end
    assert wave.via.tolist() == [3] * 10 + [3] * 5 + [1] * 5 \
        + [3] * 10 + [3] * 5 + [1] * 5 + [1] * 10 + [0] * 2


# -- classification and real waves --------------------------------------------


def _nodes(n, zones="abc"):
    return [t.Node(
        metadata=t.ObjectMeta(name=f"pn{i:03d}", labels=(
            {ZONE: zones[i % len(zones)]} if zones else {})),
        status=t.NodeStatus(
            allocatable={"cpu": "4", "memory": "32Gi", "pods": "110"},
            conditions=[t.NodeCondition("Ready", "True")]))
        for i in range(n)]


def _pod(name, cpu="100m", labels=None):
    return t.Pod(
        metadata=t.ObjectMeta(name=name, namespace="default",
                              labels=labels or {}),
        spec=t.PodSpec(containers=[
            t.Container(name="c", requests={"cpu": cpu})]))


def _encoded(state, pods, pad):
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.encode import (
        SnapshotEncoder,
        pod_feature_key,
    )

    uniq, rep_of, rep_idx = [], {}, []
    for p in pods:
        rep_idx.append(rep_of.setdefault(pod_feature_key(p), len(uniq)))
        if rep_idx[-1] == len(uniq):
            uniq.append(p)
    enc = SnapshotEncoder(state, uniq)
    return (_pad_snapshot(enc.encode_nodes(), pad), enc.encode_pods(),
            np.asarray(rep_idx, np.int64))


def test_an_atomic_gang_span_is_its_own_run_off_the_device():
    """40 zoned pods of one controller: a device run, but for the gang
    span in its middle, which is cut out and keeps to the host tables;
    a span of mixed templates is no atomic gang, and runs plainly."""
    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.oracle import ClusterState

    rc = t.ReplicationController(
        metadata=t.ObjectMeta(name="rc-a", namespace="default"),
        spec=t.ReplicationControllerSpec(selector={"rc": "a"}))
    state = ClusterState.build(_nodes(6), controllers=[rc])
    pods = [_pod(f"g{i}", labels={"rc": "a"}) for i in range(40)] \
        + [_pod("odd", cpu="250m", labels={"rc": "a"})]
    snap, batch, rep_idx = _encoded(state, pods, 8)
    ws = WaveScheduler(min_run=16)
    wave = Wave(ws.config, snap, batch, rep_idx, 0, ws.max_j, ws._replay)
    gang = {"start": 16, "length": 4, "score_add": None}
    mixed = {"start": 38, "length": 3, "score_add": None}
    runs, policy = ws.plan(wave, [gang, mixed])
    assert [(r.start, r.length, r.eligible, r.device, r.gang)
            for r in runs] == [
        (0, 16, True, True, None), (16, 4, True, False, gang),
        (20, 18, True, True, None), (38, 2, True, True, None),
        (40, 1, False, False, None)]
    assert [s.kind for s in plan_steps(runs, policy)] == [
        "single", "single", "group_device", "scan"]
    with pytest.raises(AttributeError):
        runs[0].device = False  # a classification is not changed


HOSTNAME = "kubernetes.io/hostname"


def _term_pod(name, group, anti=None, soft=None):
    """A pod of service `group`, with a required hostname anti-affinity
    term on service `anti` and a preferred one on service `soft`."""
    import json

    def term(on):
        return {"labelSelector": {"matchLabels": {"group": on}},
                "topologyKey": HOSTNAME}

    stated = {}
    if anti:
        stated["requiredDuringSchedulingIgnoredDuringExecution"] = [
            term(anti)]
    if soft:
        stated["preferredDuringSchedulingIgnoredDuringExecution"] = [
            {"weight": 10, "podAffinityTerm": term(soft)}]
    pod = _pod(name, labels={"group": group})
    if stated:
        pod.metadata.annotations = {
            t.AFFINITY_ANNOTATION: json.dumps({"podAntiAffinity": stated})}
    return pod


def _hostnamed(nodes):
    for node in nodes:
        node.metadata.labels[HOSTNAME] = node.metadata.name
    return nodes


#: the pending pods of `KIND_CASES`: a vetoed one (its term selects its
#: own service), an owner of a term on another service, one whose labels
#: a bound pod's term selects, a plain one, and one with a required
#: podAffinity term
def _kind_pods():
    import json

    affine = _pod("affine", labels={"group": "e"})
    affine.metadata.annotations = {t.AFFINITY_ANNOTATION: json.dumps({
        "podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"group": "e"}},
            "topologyKey": HOSTNAME}]}})}
    return [_term_pod("vetoed", "a", anti="a"),
            _term_pod("owner", "b", soft="c"),
            _pod("matched", labels={"group": "c"}),
            _pod("plain", labels={"group": "d"}), affine]


KIND_CASES = {
    # name: (zones, whether the host replays (`replay=`), the kinds of a
    #        run of: vetoed, owner, matched, plain, affine)
    "unzoned: what no grouped header probe takes is the device's":
        ("", False, ["device", "device", "device", "pure", "scan"]),
    "zoned: the plain run is the device's too, as before":
        ("abc", False, ["device", "device", "device", "device", "scan"]),
    "the host's route (replay=): a probe of its own a run":
        ("", True, ["single", "single", "single", "pure", "scan"]),
}


@pytest.mark.parametrize("case", sorted(KIND_CASES))
def test_run_kinds_say_which_runs_take_the_device_replay(case):
    from kubernetes_tpu.models.replay import replay_fast
    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.oracle import ClusterState

    zones, host, kinds = KIND_CASES[case]
    nodes = _hostnamed(_nodes(6, zones))
    bound = _term_pod("held", "b", soft="c")
    bound.spec.node_name = nodes[0].metadata.name
    # the plain pod's controller: a zoned run is the device's for the
    # zone blend of its selector spread
    rc = t.ReplicationController(
        metadata=t.ObjectMeta(name="rc-d", namespace="default"),
        spec=t.ReplicationControllerSpec(selector={"group": "d"}))
    state = ClusterState.build(nodes, [bound], controllers=[rc])
    snap, batch, _rep_idx = _encoded(state, _kind_pods(), 8)
    ws = WaveScheduler(replay=replay_fast if host else None)
    assert ws.run_kinds(snap, batch, range(5)) == kinds
    # without a term anywhere every row is a grouped header probe's
    bare = ClusterState.build(_hostnamed(_nodes(6, "")))
    snap, batch, _rep_idx = _encoded(
        bare, [_pod("p", labels={"group": "a"}),
               _pod("q", cpu="200m", labels={"group": "b"})], 8)
    assert ws.run_kinds(snap, batch, range(2)) == ["pure", "pure"]


def test_the_mesh_keeps_its_runs_on_the_host_tables():
    """The mesh's classification sets no `device`: a vetoed run there is
    a `single` whatever the zoning."""
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.parallel.mesh import MeshWaveScheduler

    state = ClusterState.build(_hostnamed(_nodes(8, "")))
    pods = [_term_pod(f"v{i}", "a", anti="a") for i in range(16)] \
        + [_term_pod(f"w{i}", "b", anti="b") for i in range(16)]
    snap, batch, rep_idx = _encoded(state, pods, 8)
    ws = MeshWaveScheduler(
        mesh=Mesh(np.array(jax.devices()[:2]), ("nodes",)))
    wave = Wave(ws.config, snap, batch, rep_idx, 0, ws.max_j, ws._replay)
    runs, policy = ws.plan(wave)
    assert [(r.eligible, r.device, r.pure, r.veto is not None)
            for r in runs] == [(True, False, False, True)] * 2
    assert format_plan(plan_steps(runs, policy)) \
        == "single[1 runs, 16 pods] single[1 runs, 16 pods]"


def test_a_wave_of_vetoed_runs_plans_one_group_and_a_cut_run_for_the_scan():
    """Unzoned nodes, three services' vetoed runs and the head of a
    fourth that the wave's end cut under `min_run`: one device group,
    one scan, and the dispatches are the plan's."""
    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.oracle import ClusterState

    state = ClusterState.build(_hostnamed(_nodes(40, "")))
    pods = [_term_pod(f"{g}{i}", g, anti=g)
            for g in "abc" for i in range(16)] \
        + [_term_pod(f"d{i}", "d", anti="d") for i in range(7)]
    snap, batch, rep_idx = _encoded(state, pods, 64)
    ws = WaveScheduler()
    wave = Wave(ws.config, snap, batch, rep_idx, 0, ws.max_j, ws._replay)
    steps = plan_steps(*ws.plan(wave))
    assert format_plan(steps) \
        == "group_device[3 runs, 48 pods] scan[1 runs, 7 pods]"
    chosen, _carry, _last = ws.schedule_backlog(snap, batch, rep_idx)
    assert (chosen >= 0).all()
    for start in (0, 16, 32):  # one of a service a node
        assert len(set(chosen[start:start + 16].tolist())) == 16
    assert dict(ws.dispatches) == {"zreplay_group": 1, "scan": 1}
    assert ws.stats["pods_by_path"] == {
        "scan": 7, "single": 0, "group_host": 0, "group_device": 48}
    assert (ws.stats["anti_runs"], ws.stats["anti_picks"],
            ws.stats["anti_nodes_excluded"]) == (3, 48, 0)


#: which program a step of each kind launches (a `single` on the host
#: tables; `apply`, a fold in a dispatch of its own, is the drivers')
LAUNCHES = {"scan": "scan", "single": "probe", "group_host": "group_probe",
            "group_device": "zreplay_group"}


@pytest.mark.parametrize("devices", [1, 2])
def test_a_waves_printed_plan_is_the_dispatches_it_makes(devices):
    """The backlog of tests/test_timeline.py's mesh pin: a run of 48,
    then two lone pods."""
    import jax
    from jax.sharding import Mesh

    from kubernetes_tpu.models.wave import WaveScheduler
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.parallel.mesh import MeshWaveScheduler

    state = ClusterState.build(_nodes(200, zones=""))
    pods = [_pod(f"tmp{i}") for i in range(48)] \
        + [_pod("tm-a", cpu="150m"), _pod("tm-b", cpu="250m")]
    snap, batch, rep_idx = _encoded(state, pods, 256)
    if devices == 1:
        ws = WaveScheduler()
    else:
        ws = MeshWaveScheduler(
            mesh=Mesh(np.array(jax.devices()[:devices]), ("nodes",)))
    wave = Wave(ws.config, snap, batch, rep_idx, 0, ws.max_j, ws._replay)
    steps = plan_steps(*ws.plan(wave))
    assert format_plan(steps) == (
        "single[1 runs, 48 pods] scan[2 runs, 2 pods]" if devices == 1
        else "group_host[1 runs, 48 pods] scan[2 runs, 2 pods]")
    chosen, _carry, _last = ws.schedule_backlog(snap, batch, rep_idx)
    assert (chosen >= 0).all()
    launched = dict(ws.dispatches)
    assert launched.pop("apply") == 1
    planned = {}
    for step in steps:
        planned[LAUNCHES[step.kind]] = \
            planned.get(LAUNCHES[step.kind], 0) + 1
    assert launched == planned
    assert ws.stats["pods_by_path"] == {
        "scan": 2, "single": 48 if devices == 1 else 0,
        "group_host": 0 if devices == 1 else 48, "group_device": 0}
