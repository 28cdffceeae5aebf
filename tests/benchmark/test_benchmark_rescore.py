"""`zreplay_rescore_share.fill` (PR 50): its entry in the manifest by
membership (never by position), its reader on made-up pairs of
snapshots and on a driver's own tallies, and its two cells: the one the
device replay's vetoed runs are in (`mixed-5k.rows-12k`) and the
control whose runs were one evaluation already (`spread-3k.rows`)."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import deploy, run
from benchmark.layers import zreplay_rescore_share

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "zreplay_rescore_share.fill"
CELLS = ["spread-3k.rows", "mixed-5k.rows-12k"]


def test_the_metric_is_in_the_manifest_by_membership():
    manifest = deploy.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter",
                     "layer": "single-chip driver",
                     "moves": "pods_bound_per_s", "workloads": CELLS}
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] != NAME}
    assert entry["layer"] in layers  # a layer the manifest already names


@pytest.mark.parametrize("cell", CELLS)
def test_its_cells_report_what_it_moves_and_load_its_reader(cell):
    manifest = deploy.load_manifest()
    assert cell in {w["name"] for w in manifest["workloads"]}
    bound, = [m for m in manifest["end_to_end"]
              if m["name"] == "pods_bound_per_s"]
    assert cell in bound["workloads"]
    per_layer = run.metrics_of(manifest, "per_layer", cell)
    assert NAME in {m["name"] for m in per_layer}
    readers = run.load_readers(
        per_layer, os.path.join(REPO, "benchmark", "layers"))
    assert hasattr(readers[NAME], "snapshot") \
        and hasattr(readers[NAME], "read")
    # and no other cell lists it
    others = {w["name"] for w in manifest["workloads"]} - set(CELLS)
    for other in others:
        assert NAME not in {
            m["name"] for m in run.metrics_of(manifest, "per_layer", other)}


@pytest.mark.parametrize("before,after,want", [
    # no step ran in the window: nothing to read
    ({"steps": 80, "rescores": 40}, {"steps": 80, "rescores": 40}, None),
    # 40 rescoring steps of 80
    ({"steps": 120, "rescores": 7}, {"steps": 200, "rescores": 47}, 50.0),
    # steps that never rescored read 0.0, which is not "nothing"
    ({"steps": 0, "rescores": 0}, {"steps": 36_000, "rescores": 0}, 0.0),
    # a program without the counters
    ({}, {}, None),
])
def test_the_reader_divides_a_diff(before, after, want):
    got = zreplay_rescore_share.read({
        "snapshots": {"zreplay_rescore_share": (before, after)},
        "metric": NAME})
    assert got == want and (want is None or isinstance(got, float))


def _ctx(stats):
    return {"sched": NS(scheduler=NS(config=NS(algorithm=NS(
        _wave=NS(stats=stats)))))}


def test_the_reader_reads_the_drivers_own_counters():
    assert zreplay_rescore_share.snapshot(_ctx(
        {"zreplay_steps": 400, "zreplay_slots": 10, "zreplay_rescores": 156,
         "zreplay_picks": 400})) == {"steps": 400, "rescores": 156}
    # a driver older than the counters (before PR 42), or the mesh's
    assert zreplay_rescore_share.snapshot(_ctx(
        {"zreplay_steps": 400, "zreplay_slots": 10})) == {}


def test_the_reader_reads_a_live_drivers_vetoed_run_as_zero():
    """The driver's own `stats` under the reader, round one wave of a
    vetoed run and a plain one on a zoned cluster: steps ran, none
    rescored."""
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from tests.test_wave_paths import (
        _controllers, _nodes, _pod, _self_anti,
    )

    state = ClusterState.build(_nodes(48, "a"), controllers=_controllers(2))
    algo = TPUScheduleAlgorithm()
    ctx = _ctx(algo._wave.stats)
    before = zreplay_rescore_share.snapshot(ctx)
    hosts = algo.schedule_backlog(
        [_self_anti(0, i) for i in range(40)]
        + [_pod(1, i) for i in range(40)], state)
    assert None not in hosts
    after = zreplay_rescore_share.snapshot(ctx)
    assert after["steps"] - before["steps"] == 80
    assert zreplay_rescore_share.read({"snapshots": {
        "zreplay_rescore_share": (before, after)}}) == 0.0
