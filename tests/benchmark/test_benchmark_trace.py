"""The reduction from a profiler trace to device numbers, on traces
whose answers are known."""

import json
import os

import pytest

from benchmark import trace_reduce
from benchmark.layers import (
    device_busy_ms_per_kpod,
    device_idle_share,
    device_launches_per_kpod,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _synthetic():
    """One chip: three launches; a while-loop op with two fusions
    nested in it, an overlap, and two idle gaps of 40 and 10 us."""
    us = 1000
    ops = [
        ["while.1", 0, 50 * us],
        ["fusion.2", 5 * us, 10 * us],     # nested: counts once
        ["fusion.3", 20 * us, 25 * us],    # nested
        ["copy.4", 90 * us, 10 * us],      # after a 40 us gap
        ["fusion.2", 95 * us, 15 * us],    # overlaps copy.4 by 5 us
        ["fusion.5", 120 * us, 30 * us],   # after a 10 us gap
    ]
    modules = [["jit_probe", 0, 50 * us], ["jit_apply", 90 * us, 20 * us],
               ["jit_probe", 120 * us, 30 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
            {"name": "Steps", "events": [["0", 0, 150 * us]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["sleep", 0, 500 * us]]}]},
    ]}


def test_busy_is_the_union_of_the_op_intervals():
    out = trace_reduce.reduce(_synthetic())
    assert out["chips"] == 1 and out["launches"] == 3
    assert out["busy_s"] == pytest.approx(100e-6)  # 50 + 20 + 30 us
    assert out["idle_gaps"][0] == ["unattributed;before:jit_apply",
                                   pytest.approx(40e-6)]
    assert out["idle_gaps"][1] == ["unattributed;before:jit_probe",
                                   pytest.approx(10e-6)]
    ops = dict(out["device_ops"])
    assert ops["while.1"] == pytest.approx(50e-6)
    assert ops["fusion.2"] == pytest.approx(25e-6)
    assert out["device_ops"][0][0] == "while.1"


def test_two_chips_average_their_busy_time_and_sum_their_launches():
    trace = _synthetic()
    second = json.loads(json.dumps(trace["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["fusion.9", 0, 20_000]]
    second["lines"][0]["events"] = [["jit_probe", 0, 20_000]]
    trace["planes"].append(second)
    out = trace_reduce.reduce(trace)
    assert out["chips"] == 2 and out["launches"] == 4
    assert out["busy_s"] == pytest.approx((100e-6 + 20e-6) / 2)


def test_trace_without_device_work_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": []},
                        {"name": "/device:TPU:0", "lines": [
                            {"name": "XLA Ops", "events": []}]}]}
    out = trace_reduce.reduce(trace)
    assert out["chips"] == 0 and out["busy_s"] == 0.0
    run = {"trace": {**out, "window_s": 1.0, "bound_in_slice": 10}}
    assert device_idle_share.read(run) is None
    assert device_busy_ms_per_kpod.read(run) is None
    assert device_launches_per_kpod.read(run) is None


def test_device_readers_on_a_known_slice():
    out = trace_reduce.reduce(_synthetic())
    run = {"trace": {**out, "window_s": 200e-6, "bound_in_slice": 500}}
    assert device_idle_share.read(run) == pytest.approx(50.0)
    assert device_busy_ms_per_kpod.read(run) == pytest.approx(0.2)
    assert device_launches_per_kpod.read(run) == pytest.approx(6.0)


def test_recorded_chip_trace_reduces_to_its_known_numbers():
    """A slice of a trace recorded on the TPU v5e by this benchmark
    (benchmark/run.py --trace 1), cut to a few hundred events, with the
    numbers worked out by hand from it beside it."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded["trace"])
    want = recorded["expect"]
    assert out["chips"] == want["chips"]
    assert out["launches"] == want["launches"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert out["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"],
                                                   rel=1e-9)
