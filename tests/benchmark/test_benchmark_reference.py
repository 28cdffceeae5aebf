"""The plain reference against the program's serial oracle, and the
control against the reference: the comparison that decides `correct`
has to be one that a degraded scheduler fails."""

import copy
import os
import random

import pytest

from benchmark import controls, deploy, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(nodes, controllers=1):
    """density-1k cut to a test's size; with more controllers than
    one, each selects the pods of its own template."""
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    cfg["nodes"]["count"] = nodes
    if controllers != 1:
        cfg["controllers"].update(count=controllers,
                                  name_format="rc-{t}")
        cfg["pods"]["labels"] = {"name": "sched-perf-{t}"}
    return cfg


def _filled(cfg, pods, rng, skew=0.0):
    """A cluster with `pods` bound at random: uneven, as a window
    leaves it. -> (cluster, [(template, node)])"""
    cluster = reference.Cluster(cfg)
    placed = []
    templates = deploy.num_templates(cfg)
    n = cfg["nodes"]["count"]
    for _ in range(pods):
        t = 0 if rng.random() < skew else rng.randrange(templates)
        node = rng.randrange(n)
        if cluster.fits()[node]:
            cluster.bind(t, node)
            placed.append((t, node))
    return cluster, placed


@pytest.mark.parametrize("nodes,controllers,seed", [
    (40, 1, 1), (12, 1, 2), (45, 9, 3), (30, 0, 4), (33, 6, 2 ** 31 + 5),
])
def test_reference_decides_as_the_programs_oracle(nodes, controllers, seed):
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.oracle import ClusterState, GenericScheduler

    scheme = rest.default_scheme
    rng = random.Random(seed)
    cfg = _cfg(nodes, controllers)
    cluster, placed = _filled(cfg, 20 * nodes, rng, skew=0.3)
    bound = []
    for i, (t, node) in enumerate(placed):
        pod = scheme.decode(deploy.pod(cfg, t, name=f"old-{i}"))
        pod.spec.node_name = cluster.names[node]
        bound.append(pod)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    templates = deploy.num_templates(cfg)
    backlog = [rng.randrange(templates) for _ in range(150)]
    pods = [scheme.decode(deploy.pod(cfg, t, name=f"new-{i:04d}"))
            for i, t in enumerate(backlog)]
    counter = rng.randrange(10_000)
    want = GenericScheduler(last_node_index=counter).schedule_backlog(
        pods, state)
    start = copy.deepcopy(cluster)
    got = reference.decide(cluster, backlog, counter)
    assert [cluster.names[g] if g is not None else None
            for g in got] == want
    # and the oracle's picks pass the comparison, which also recovers
    # the round-robin counter it was started with
    held = reference.verify(start, backlog, got)
    assert held["mismatches"] == 0 and held["checked"] == 150
    residue, modulus = held["counter"]
    assert counter % modulus == residue


@pytest.mark.parametrize("nodes,controllers,seed", [
    (100, 1, 21), (100, 0, 22), (300, 50, 23),
])
def test_batch_that_does_not_thread_commitments_fails_the_comparison(
        nodes, controllers, seed):
    """The control: a wave decided against the state it started from
    breaks 'decisions are the serial ones'."""
    rng = random.Random(seed)
    cfg = _cfg(nodes, controllers)
    cluster, _ = _filled(cfg, 28 * nodes if controllers < 2 else 6 * nodes,
                         rng)
    templates = deploy.num_templates(cfg)
    backlog = [rng.randrange(templates) for _ in range(600)]
    stale = reference.decide(copy.deepcopy(cluster), backlog, 5, stale=128)
    assert reference.verify(copy.deepcopy(cluster), backlog,
                            stale)["mismatches"] >= 1


@pytest.mark.parametrize("seed", [41, 42, 2 ** 31 + 43])
def test_control_reads_a_runs_record_and_fails_where_the_run_is_sound(seed):
    """benchmark/controls.py on a record as the load generator writes
    it: the run's own picks read 0, the stale wave's do not."""
    rng = random.Random(seed)
    cfg = _cfg(128)
    cluster, placed = _filled(cfg, 128 * 12, rng)
    before = {f"p-t0-{i:08d}": cluster.names[node]
              for i, (_, node) in enumerate(placed)}
    backlog = [0] * (2 * controls.STALE_WAVE)
    names = [f"check-{i:05d}" for i in range(len(backlog))]
    picks = reference.decide(copy.deepcopy(cluster), backlog, seed % 1000)
    after = dict(before, **{nm: cluster.names[p]
                            for nm, p in zip(names, picks)})
    record = {"check": {"backlog": backlog, "names": names,
                        "before": before, "after": after}}
    read = controls.stale_wave(record, cfg)
    assert read["sound"] == 0 and read["stale_wave"] >= 1


def test_one_altered_pick_is_one_mismatch():
    rng = random.Random(31)
    cfg = _cfg(60)
    cluster, _ = _filled(cfg, 1500, rng)
    backlog = [0] * 200
    picks = reference.decide(copy.deepcopy(cluster), backlog, 9)
    ties = copy.deepcopy(cluster).ranking(0)
    worst = next(i for i in range(60) if i not in set(ties.tolist()))
    picks[0] = worst
    held = reference.verify(copy.deepcopy(cluster), backlog, picks)
    assert held["mismatches"] >= 1


def test_the_controller_makes_the_spread_scorer_count():
    """Upstream's density test creates rc1 before its pods, so
    SelectorSpreadPriority counts every pod; without the controller it
    gives every node the same score."""
    rng = random.Random(51)
    with_rc, _ = _filled(_cfg(50), 600, rng)
    fit = with_rc.fits()
    assert len(set(with_rc._spread(0, fit).tolist())) > 1
    without, _ = _filled(_cfg(50, controllers=0), 600, rng)
    assert set(without._spread(0, without.fits()).tolist()) == {10}


def test_committed_deployment_has_upstreams_controller():
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    (rc,) = deploy.controllers(cfg)
    assert rc["spec"]["selector"] == deploy.pod(cfg, 0)["metadata"]["labels"]
    assert rc["spec"]["replicas"] == cfg["pods"]["population"]


def test_this_reference_refuses_zoned_nodes():
    cfg = _cfg(9)
    cfg["nodes"]["zones"] = ["a", "b", "c"]
    with pytest.raises(ValueError):
        reference.Cluster(cfg)
