"""The readers of PR 25, each on a run made by hand, and the host-state
partition of the device's idle time on traces whose answers are known:
a synthetic one and a slice recorded on the chip."""

import json
import os

import pytest

from benchmark.layers import (
    _waves,
    apiserver_gc_long_pauses,
    apiserver_gc_pause_share,
    bind_commit_p50_ms,
    device_idle_by_host,
    dispatches_per_wave,
    queue_wait_p50_ms,
    sched_idle_share,
    wave_algorithm_p50_ms,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000


def _trace(host_lines, ops, modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": name, "events": events}
            for name, events in host_lines]},
    ]}


def _synthetic():
    """1,000 us. The chip runs [100, 200) and [600, 650): 850 us idle.
    scheduler thread: queue_wait [0, 90), a wave [90, 700) holding
    encode [100, 300) with transfer [150, 180) nested, and assume
    [650, 700); gather [700, 800). A bind thread: bind [250, 900). An
    informer thread: wire [400, 450), ingest [450, 500)."""
    sched = [["sched/queue_wait", 0, 90 * US],
             ["sched/wave", 90 * US, 610 * US],
             ["sched/encode", 100 * US, 200 * US],
             ["sched/transfer", 150 * US, 30 * US],
             ["sched/assume", 650 * US, 50 * US],
             ["sched/gather", 700 * US, 100 * US],
             ["not ours", 0, 1000 * US]]
    binder = [["sched/bind", 250 * US, 650 * US]]
    informer = [["sched/wire", 400 * US, 50 * US],
                ["sched/ingest", 450 * US, 50 * US]]
    ops = [["fusion.1", 100 * US, 100 * US], ["fusion.2", 600 * US, 50 * US],
           ["end", 999 * US, 1 * US]]
    modules = [["jit_pack_unpack(1)", 160 * US, 40 * US],
               ["jit_probe_fused_same(2)", 600 * US, 50 * US]]
    return _trace([("scheduler", sched), ("bind_0", binder),
                   ("unassigned-pods", informer)], ops, modules)


def test_idle_time_goes_to_the_top_ranked_open_annotation():
    got = device_idle_by_host.shares(_synthetic())
    idle = 849.0  # 1,000 less the chip's 100 + 50 + 1
    want = {
        "encode": 100,      # [200, 300): the chip ran through [100, 200)
        "transfer": 0,      # nested in encode, which outranks it
        "wire": 50,         # [400, 450) outranks bind
        "bind": 500,        # [300, 900) less wire's 50 and the chip's 50
        "assume": 0,        # bind outranks what comes after it:
        "ingest": 0,
        "queue_wait": 90,
        "gather": 0,        # waiting too
    }
    for state, us in want.items():
        assert got[state] == pytest.approx(100.0 * us / idle), state
    # [90, 100) in a wave but in no phase, and [900, 999) in nothing
    assert got["uncovered"] == pytest.approx(100.0 * (10 + 99) / idle)
    assert sum(got.values()) == pytest.approx(100.0)


def test_shares_by_a_walk_over_every_microsecond():
    """The same partition the slow way: each microsecond of the slice
    asks every annotation whether it is open."""
    trace = _synthetic()
    got = device_idle_by_host.shares(trace)
    from kubernetes_tpu.trace import profile

    order = list(profile.PHASES + profile.IDLE_STATES)
    events = [e for line in trace["planes"][1]["lines"]
              for e in line["events"] if e[0].startswith("sched/")
              and e[0][6:] in order]
    ops = trace["planes"][0]["lines"][1]["events"]
    tally = dict.fromkeys(order + ["uncovered"], 0)
    for at in range(0, 1000 * US, US):
        if any(s <= at < s + d for _n, s, d in ops):
            continue
        here = [order.index(n[6:]) for n, s, d in events if s <= at < s + d]
        tally[order[min(here)] if here else "uncovered"] += 1
    idle = sum(tally.values())
    for state, count in tally.items():
        assert got.get(state, 0.0) == pytest.approx(100.0 * count / idle,
                                                    abs=0.2), state


def test_a_trace_without_annotations_or_without_a_chip_reads_nothing():
    bare = _trace([("python", [["sleep", 0, 500 * US]])],
                  [["fusion.1", 0, 10 * US]])
    assert device_idle_by_host.shares(bare) == {}
    no_chip = _synthetic()
    no_chip["planes"] = no_chip["planes"][1:]
    assert device_idle_by_host.shares(no_chip) == {}
    run = {"metric": "device_idle_by_host.encode.fill",
           "device_idle_by_host": {}}
    assert device_idle_by_host.read(run) is None


def _patched(monkeypatch, trace, start_ns=0):
    calls = []
    monkeypatch.setattr(device_idle_by_host, "newest_trace",
                        lambda cell: calls.append(cell) or "x.pb")
    monkeypatch.setattr(device_idle_by_host.trace_reduce, "load_xplane",
                        lambda path, keep: trace)
    monkeypatch.setattr(device_idle_by_host, "session_start_ns",
                        lambda path: start_ns)
    return calls


def _read_all(run, traffic="fill"):
    got = {}
    for state in ("encode", "bind", "wire", "waiting", "uncovered"):
        run["metric"] = f"device_idle_by_host.{state}.{traffic}"
        got[state] = device_idle_by_host.read(run)
    return got


def test_the_five_names_share_one_pass_and_waiting_sums_the_idle_states(
        monkeypatch):
    calls = _patched(monkeypatch, _synthetic())
    got = _read_all({"cell": {"name": "density-1k.fill"}, "snapshots": {}})
    assert calls == ["density-1k.fill"]
    assert got["waiting"] == pytest.approx(100.0 * 90 / 849)
    assert sum(got.values()) <= 100.0 + 1e-9


def test_a_state_the_slice_never_saw_reads_zero_not_nothing(monkeypatch):
    trace = _synthetic()
    trace["planes"][1]["lines"] = trace["planes"][1]["lines"][:1]
    _patched(monkeypatch, trace)  # the scheduler's thread alone
    got = _read_all({"cell": {"name": "density-1k.fill"}, "snapshots": {}})
    assert got["bind"] == 0.0 and got["wire"] == 0.0
    assert got["encode"] > 0 and None not in got.values()


def test_ring_bind_spans_stand_in_for_annotations_the_slice_cut_off(
        monkeypatch):
    """The slice's profiler started at wall clock 1,000 s. The bind
    thread's annotation is lost (it straddled the slice's end); the
    wave's `wave.bind` span of the ring, 1,000.00025 s to 1,000.0009 s,
    says the same."""
    trace = _synthetic()
    whole = device_idle_by_host.shares(trace)
    trace["planes"][1]["lines"][1]["events"] = []  # no sched/bind at all
    start_ns = 1000 * 10 ** 9
    waves = {"w": {"pods": 5, "bind": (1000.00025, 0.00065)},
             "late": {"pods": 5, "bind": (1000.5, 0.1)},  # after the slice
             "open": {"pods": 5}}
    binds = device_idle_by_host.ring_binds(waves, start_ns)
    assert sorted(b[1] for b in binds) == [250 * US, 500_000 * US]
    assert device_idle_by_host.shares(trace, binds) == pytest.approx(whole)
    lost = device_idle_by_host.shares(trace)
    assert "bind" not in lost and lost["gather"] > 0  # what it hid
    _patched(monkeypatch, trace, start_ns)
    run = {"cell": {"name": "density-1k.fill"},
           "snapshots": {"device_idle_by_host": (None, waves)}}
    assert _read_all(run)["bind"] == pytest.approx(whole["bind"])
    assert device_idle_by_host.ring_binds(None, start_ns) == []
    assert device_idle_by_host.ring_binds(waves, None) == []


def test_clock_check_places_each_module_against_its_dispatcher():
    trace = _synthetic()
    check = device_idle_by_host.clock_check(trace)
    # jit_pack_unpack began inside transfer; the probe began at 600 us,
    # 420 us after transfer, the only dispatcher, had ended
    assert check == {"modules": 2, "inside": 1, "early": 0,
                     "early_us_max": None, "after": 1, "after_us_max": 420.0}
    # a device clock 30 us ahead: the unpack now begins 20 us before
    # the transfer that launched it
    for event in trace["planes"][0]["lines"][0]["events"]:
        event[1] -= 30 * US
    check = device_idle_by_host.clock_check(trace)
    assert (check["inside"], check["early"], check["early_us_max"]) == (
        0, 1, 20.0)
    # the ring's clock against the profiler's: sched/wave began at 90 us
    waves = {"w": {"pods": 1, "gather": (1000.000093, 0.0001)}}
    check = device_idle_by_host.clock_check(_synthetic(), waves,
                                            1000 * 10 ** 9)
    assert check["ring_us_median"] == pytest.approx(3.0, abs=0.01)


def test_recorded_host_plane_reduces_to_its_known_shares():
    """One wave of a trace recorded on the TPU v5e by a traced run of
    this benchmark with the annotations on (PR 25), with its shares
    beside it: worked out from it once by the interval arithmetic and
    once by a walk over every microsecond, which agreed to 0.001."""
    with open(os.path.join(DATA, "trace_host_small.json")) as f:
        recorded = json.load(f)
    got = device_idle_by_host.shares(recorded["trace"])
    want = recorded["expect"]["shares"]
    assert set(got) == set(want)
    for state, share in want.items():
        assert got[state] == pytest.approx(share, abs=1e-6), state
    assert got["encode"] == pytest.approx(71.93, abs=0.01)
    assert got["uncovered"] == pytest.approx(8.91, abs=0.01)
    assert sum(got.values()) == pytest.approx(100.0)
    check = device_idle_by_host.clock_check(recorded["trace"])
    assert check["modules"] == recorded["expect"]["modules"] == 3
    # the device's clock ran 0.12 ms ahead of the host's at that wave
    assert check["early"] == 1 and 100 < check["early_us_max"] < 150


# -- readers on a run made by hand --------------------------------------------


def _run(reader, before, after, seconds=50.0):
    return {"window": {"seconds": seconds}, "snapshots": {
        reader: (before, after)}}


def test_sched_idle_share_is_the_idle_states_diff_over_the_window():
    run = _run("sched_idle_share", {"queue_wait": 1.0, "gather": 2.0},
               {"queue_wait": 4.0, "gather": 9.0})
    assert sched_idle_share.read(run) == pytest.approx(20.0)
    assert sched_idle_share.read(_run("sched_idle_share", None, None)) is None


def test_queue_wait_median_is_interpolated_inside_its_bucket():
    buckets = [0.01, 0.02, 0.03]
    before = {"buckets": buckets, "counts": [5, 5, 5, 5]}
    after = {"buckets": buckets, "counts": [5 + 10, 5 + 60, 5 + 30, 5]}
    # 100 pods: the 50th lies 40/60 into (10, 20] ms
    got = queue_wait_p50_ms.read(_run("queue_wait_p50_ms", before, after))
    assert got == pytest.approx(10.0 + 10.0 * 40 / 60)
    assert queue_wait_p50_ms.read(
        _run("queue_wait_p50_ms", before, before)) is None


def _waves_of():
    return {
        "a": {"pods": 100, "algorithm": (10.0, 0.010), "assume": (10.01, .002),
              "bind": (10.012, 0.020)},
        "b": {"pods": 300, "algorithm": (11.0, 0.030), "assume": (11.03, .004),
              "bind": (11.034, 0.050)},
        "c": {"pods": 50, "algorithm": (12.0, 0.500)},  # bind not in yet
    }


def test_wave_readers_weigh_each_wave_by_its_pods():
    run = {"snapshots": {"wave_algorithm_p50_ms": (None, _waves_of()),
                         "bind_commit_p50_ms": (None, _waves_of())}}
    # 450 pods: the 225th sits in wave b by either measure
    assert wave_algorithm_p50_ms.read(run) == pytest.approx(30.0)
    assert bind_commit_p50_ms.read(run) == pytest.approx(54.0)
    empty = {"snapshots": {"wave_algorithm_p50_ms": (None, None)}}
    assert wave_algorithm_p50_ms.read(empty) is None


def test_wave_snapshot_takes_the_windows_waves_from_the_ring(monkeypatch):
    from kubernetes_tpu.trace import spans

    ring = spans.TraceBuffer(capacity=64)
    monkeypatch.setattr(spans, "BUFFER", ring)
    monkeypatch.setattr(_waves, "_window_began", None)
    ring.record({"trace_id": "old", "name": "scheduler.wave", "start": 1.0,
                 "duration": 0.1, "attrs": {"pods": 9}})
    assert _waves.snapshot({}) is None  # the window's start: note the time
    began = _waves._window_began
    for tid, pods in (("w1", 7), ("w2", 0)):
        ring.record({"trace_id": tid, "name": "wave.algorithm",
                     "start": began + 1, "duration": 0.25})
        ring.record({"trace_id": tid, "name": "scheduler.wave",
                     "start": began + 1, "duration": 0.5,
                     "attrs": {"pods": pods}})
    ctx = {}
    got = _waves.snapshot(ctx)
    assert got == {"w1": {"pods": 7, "algorithm": (began + 1, 0.25)}}
    assert _waves.snapshot(ctx) is got  # one pass a snapshot


def test_wave_snapshot_refuses_a_ring_that_lost_the_window(monkeypatch,
                                                           capsys):
    from kubernetes_tpu.trace import spans

    ring = spans.TraceBuffer(capacity=2)
    monkeypatch.setattr(spans, "BUFFER", ring)
    monkeypatch.setattr(_waves, "_window_began", 5.0)
    for start in (9.0, 10.0, 11.0):
        ring.record({"trace_id": "w", "name": "scheduler.wave",
                     "start": start, "duration": 0.1, "attrs": {"pods": 1}})
    assert _waves.snapshot({}) is None
    assert "younger than the window" in capsys.readouterr().err


class _Sched:
    def __init__(self, stats):
        wave = type("W", (), {"stats": stats})()
        algorithm = type("A", (), {"_wave": wave})()
        config = type("C", (), {"algorithm": algorithm})()
        self.scheduler = type("S", (), {"config": config})()


def test_dispatches_per_wave_is_a_diff_of_two_cumulative_counts():
    before = dispatches_per_wave.snapshot(
        {"sched": _Sched({"dispatches": 10, "waves": 4})})
    after = dispatches_per_wave.snapshot(
        {"sched": _Sched({"dispatches": 40, "waves": 14})})
    run = _run("dispatches_per_wave", before, after)
    assert dispatches_per_wave.read(run) == pytest.approx(3.0)
    # a program without the cumulative count: nothing, and no error
    old = dispatches_per_wave.snapshot({"sched": _Sched({"waves": 4})})
    assert dispatches_per_wave.read(
        _run("dispatches_per_wave", old, old)) is None


def test_apiserver_collector_readers_diff_its_own_counters():
    before = {"api_metrics": {"process_gc_pause_seconds_total": 1.5,
                              "process_gc_long_pauses_total": 2.0}}
    after = {"api_metrics": {"process_gc_pause_seconds_total": 4.0,
                             "process_gc_long_pauses_total": 5.0}}
    run = _run("apiserver_gc_pause_share",
               apiserver_gc_pause_share.snapshot(before),
               apiserver_gc_pause_share.snapshot(after))
    assert apiserver_gc_pause_share.read(run) == pytest.approx(5.0)
    run = _run("apiserver_gc_long_pauses",
               apiserver_gc_long_pauses.snapshot(before),
               apiserver_gc_long_pauses.snapshot(after))
    assert apiserver_gc_long_pauses.read(run) == 3.0
    # an apiserver that serves no such counter (before PR 25)
    bare = apiserver_gc_pause_share.snapshot({"api_metrics": {}})
    assert apiserver_gc_pause_share.read(
        _run("apiserver_gc_pause_share", bare, bare)) is None
