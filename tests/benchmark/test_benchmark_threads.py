"""The readers of the per-thread ledger (PR 41), each on two snapshots
made by hand, and the return lag of a device wait on traces whose lags
are known: a synthetic one and a slice recorded on the chip."""

import json
import os

import pytest

from benchmark import deploy
from benchmark import run as bench_run
from benchmark.layers import device_sync_return_lag_ms as sync_lag
from benchmark.layers import (
    loop_device_wait_us_per_pod,
    loop_host_us_per_pod,
    loop_lock_wait_us_per_pod,
    loop_uncovered_share,
    side_thread_cpu_us_per_pod,
)
from kubernetes_tpu.trace import profile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
US = 1000

LEDGER_READERS = {
    "loop_device_wait_us_per_pod": loop_device_wait_us_per_pod,
    "loop_host_us_per_pod": loop_host_us_per_pod,
    "loop_lock_wait_us_per_pod": loop_lock_wait_us_per_pod,
    "loop_uncovered_share": loop_uncovered_share,
    "side_thread_cpu_us_per_pod": side_thread_cpu_us_per_pod,
}
SIX = ["density-1k.fill", "spread-3k.fill", "mesh-20k.fill",
       "spread-3k.rows", "hetero-1k.rows", "hetero-1k.fill"]
NEW_NAMES = {
    "loop_device_wait_us_per_pod.fill": SIX,
    "loop_host_us_per_pod.fill": SIX,
    "loop_lock_wait_us_per_pod.fill": SIX,
    "loop_lock_wait_us_per_pod.steady": ["density-1k.steady"],
    "loop_uncovered_share.fill": SIX,
    "loop_uncovered_share.steady": ["density-1k.steady"],
    "side_thread_cpu_us_per_pod.informer.fill": SIX,
    "side_thread_cpu_us_per_pod.binder.fill": SIX,
    "device_sync_return_lag_ms.fill": [c for c in SIX if c != "mesh-20k.fill"],
}


def _cells(**given):
    """A role's cells: every key of the ledger, zero but the given
    (wall s, cpu s, entries)."""
    keys = profile.PHASES + profile.IDLE_STATES + (profile.DEVICE_WAIT,)
    out = {k: {"wall": 0.0, "cpu": 0.0, "count": 0} for k in keys}
    for key, (wall, cpu, count) in given.items():
        out[key] = {"wall": wall, "cpu": cpu, "count": count}
    return out


def _snapshots():
    """Two reads 50.25 s apart round a 50 s window in which 200,000
    pods were bound. Between them the loop's thread: replay 20 s of
    wall on 2 s of CPU, 18 s of that wall and 0.5 s of that CPU inside
    its device waits; encode 10 s on 6 s; transfer 5 s on 5 s; assume
    4 s on 3.5 s; queue_wait 1 s and gather 0.25 s; so 10 s inside no
    timer. The informers' threads: wire 8 s of CPU, ingest 4 s; the
    binder's: bind 30 s of wall on 3 s of CPU, wire 1 s. Every counter
    starts from what an earlier window left."""
    before = {"threads": {
        "loop": _cells(replay=(7.0, 1.0, 10), device_wait=(5.0, 0.1, 10),
                       encode=(1.0, 1.0, 10), queue_wait=(3.0, 0.0, 4)),
        "informer": _cells(wire=(2.0, 2.0, 100)),
        "other": _cells(wire=(0.5, 0.5, 3))},
        "exclusive": dict.fromkeys(profile.PHASES, 1.0),
        "process_cpu": 100.0}
    after = {"threads": {
        "loop": _cells(replay=(27.0, 3.0, 83), device_wait=(23.0, 0.6, 83),
                       encode=(11.0, 7.0, 83), transfer=(5.0, 5.0, 146),
                       assume=(4.0, 3.5, 73), queue_wait=(4.0, 0.0, 77),
                       gather=(0.25, 0.0, 2)),
        "informer": _cells(wire=(10.5, 10.0, 5100),
                           ingest=(4.5, 4.0, 5000)),
        "binder": _cells(bind=(30.0, 3.0, 73), wire=(1.0, 1.0, 73)),
        "other": _cells(wire=(0.5, 0.5, 3))},
        "exclusive": {**dict.fromkeys(profile.PHASES, 1.0), "replay": 21.0},
        "process_cpu": 140.0}
    return before, after


def _run(reader, metric=None, snapshots=None):
    before, after = snapshots or _snapshots()
    return {"window": {"seconds": 50.0, "reads": [0.05, 0.30]},
            "loadgen": {"bound_in_window": 200_000},
            "metric": metric or reader + ".fill",
            "snapshots": {reader: (before, after)}}


@pytest.mark.parametrize("reader, metric, want", [
    ("loop_device_wait_us_per_pod", None, 90.0),      # 18 s
    ("loop_host_us_per_pod", None, 105.0),            # 39 s less 18 s
    # off the CPU 39 - 16.5 = 22.5 s, of them 17.5 s in the waits
    ("loop_lock_wait_us_per_pod", None, 25.0),
    ("loop_uncovered_share", None, 100.0 * 10.0 / 50.25),
    ("side_thread_cpu_us_per_pod",
     "side_thread_cpu_us_per_pod.informer.fill", 60.0),  # 12 s
    ("side_thread_cpu_us_per_pod",
     "side_thread_cpu_us_per_pod.binder.fill", 20.0),    # 4 s
], ids=["device_wait", "host", "lock_wait", "uncovered", "informer",
        "binder"])
def test_a_reader_is_the_difference_of_two_reads(reader, metric, want,
                                                 capsys):
    got = LEDGER_READERS[reader].read(_run(reader, metric))
    assert got == pytest.approx(want)
    # the whole table goes to stderr with the two checks of the
    # instrument, once a run
    said = capsys.readouterr().err
    assert '"informer": {"wire": [8.5, 8.0, 5000]' in said
    assert "loop's wall in probe + score + replay 20.0000 s against the " \
        "timeline's 20.0000 s" in said
    assert "32.5000 s of the process's 40.0000 s (81.2%)" in said


def test_the_loops_path_and_its_idle_time_are_the_period():
    """device wait + host + (idle + uncovered) a pod is the time
    between the reads a pod, by construction."""
    us = {name: LEDGER_READERS[name].read(_run(name))
          for name in ("loop_device_wait_us_per_pod", "loop_host_us_per_pod")}
    uncovered = loop_uncovered_share.read(_run("loop_uncovered_share"))
    between, bound = 50.25, 200_000
    idle_us = 1e6 * 1.25 / bound
    period_us = 1e6 * between / bound
    assert sum(us.values()) + idle_us + uncovered / 100.0 * period_us \
        == pytest.approx(period_us)


def test_the_table_is_said_once_a_run(capsys):
    run = _run("loop_host_us_per_pod")
    run["snapshots"]["loop_uncovered_share"] = \
        run["snapshots"]["loop_host_us_per_pod"]
    loop_host_us_per_pod.read(run)
    loop_uncovered_share.read(run)
    assert capsys.readouterr().err.count("thread ledger between") == 1


@pytest.mark.parametrize("reader", sorted(LEDGER_READERS))
def test_a_program_without_the_ledger_reads_nothing(reader, monkeypatch):
    """The parent under this PR's benchmark files: the snapshot is
    None, the reader returns None, the line leaves the name out."""
    monkeypatch.delattr(profile, "thread_totals")
    module = LEDGER_READERS[reader]
    assert module.snapshot({}) is None
    role = ".informer" if reader.startswith("side") else ""
    assert module.read(_run(reader, f"{reader}{role}.fill",
                            snapshots=(None, None))) is None


def test_one_read_of_the_ledger_serves_the_five_readers(monkeypatch):
    calls = []
    real = profile.thread_totals
    monkeypatch.setattr(profile, "thread_totals",
                        lambda: calls.append(1) or real())
    ctx = {}
    shots = [m.snapshot(ctx) for m in LEDGER_READERS.values()]
    assert len(calls) == 1 and all(s is shots[0] for s in shots)
    assert set(shots[0]) == {"threads", "exclusive", "process_cpu"}
    assert set(shots[0]["exclusive"]) == set(profile.PHASES)


def test_no_pod_bound_reads_nothing_per_pod():
    run = _run("loop_host_us_per_pod")
    run["loadgen"]["bound_in_window"] = 0
    assert loop_host_us_per_pod.read(run) is None


def test_the_manifest_lists_the_nine_names_and_their_readers_load():
    manifest = deploy.load_manifest(ROOT)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, cells in NEW_NAMES.items():
        entry = by_name[name]
        assert entry["workloads"] == cells, name
        assert entry["better"] == "lower"
        assert entry["moves"] == ("bind_latency_p50_ms"
                                  if name.endswith(".steady")
                                  else "pods_bound_per_s")
        assert entry["source"] == ("device_trace" if name.startswith("device_")
                                   else "program_span")
    readers = bench_run.load_readers(
        [by_name[n] for n in NEW_NAMES],
        os.path.join(ROOT, "benchmark", "layers"))
    assert all(hasattr(m, "read") for m in readers.values())


# -- the return lag -----------------------------------------------------------


def _trace(waits, modules, other_host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "scheduler", "events": [
                ["sched/device_wait", s, d] for s, d in waits]
                + list(other_host)}]},
    ]}


def test_a_waits_lag_is_from_the_last_program_done_to_its_own_end():
    """Three waits. The first begins at 100 us while a program runs to
    400 us, and ends at 430: lag 30 us, the device still ran. The
    second begins at 600 after the program of 500-550 was done, ends at
    620: lag 70 us, it was ready. The third, at 10-20 us, ends before
    any program has: nothing to measure. A module that ends after a
    wait's end is not that wait's."""
    modules = [["jit_zreplay_group(1)", 90 * US, 310 * US],
               ["jit_pack_unpack(2)", 500 * US, 50 * US],
               ["jit_later(3)", 610 * US, 100 * US]]
    trace = _trace([(100 * US, 330 * US), (600 * US, 20 * US),
                    (10 * US, 10 * US)], modules)
    assert sync_lag.lags(trace) == [(30 * US, True),
                                              (70 * US, False)]


def test_the_metric_is_the_median_lag_in_ms(monkeypatch, capsys):
    modules = [["jit_a(1)", 0, 100 * US], ["jit_b(2)", 1000 * US, 100 * US],
               ["jit_c(3)", 2000 * US, 100 * US]]
    waits = [(50 * US, 60 * US), (1050 * US, 90 * US), (2050 * US, 250 * US)]
    dispatch = [["sched/replay", 0, 120 * US], ["sched/replay", 990 * US,
                                                200 * US],
                ["sched/replay", 1990 * US, 400 * US]]
    monkeypatch.setattr(sync_lag._by_host, "newest_trace",
                        lambda cell: "a.xplane.pb")
    monkeypatch.setattr(sync_lag, "load",
                        lambda path: _trace(waits, modules, dispatch))
    got = sync_lag.read({"cell": {"name": "spread-3k.rows"}})
    assert got == pytest.approx(0.040)  # lags 10, 40 and 200 us
    said = capsys.readouterr().err
    assert "3 waits; the device still ran when 3 began" in said
    assert "'modules': 3, 'inside': 3, 'early': 0" in said


def test_recorded_slice_reads_its_known_lags(monkeypatch, capsys):
    """Three waves of `spread-3k.rows` recorded on the TPU v5e (PR 41):
    each wave's one wait ends 22.6, 48.8 and 31.9 ms after its
    `jit_zreplay_group` did, worked out beside the file by a plain
    search; the loop's `sched/replay` holds each `sched/device_wait`."""
    with open(os.path.join(DATA, "trace_sync_small.json")) as f:
        recorded = json.load(f)
    trace, expect = recorded["trace"], recorded["expect"]
    got = sync_lag.lags(trace)
    assert [lag for lag, _w in got] == expect["lags_ns"]
    assert [w for _lag, w in got] == expect["waited"] == [True] * 3
    monkeypatch.setattr(sync_lag._by_host, "newest_trace",
                        lambda cell: "a.xplane.pb")
    monkeypatch.setattr(sync_lag, "load", lambda path: trace)
    assert sync_lag.read({"cell": {"name": "spread-3k.rows"}}) \
        == pytest.approx(expect["median_ms"]) == pytest.approx(31.893816)
    assert "3 waits; the device still ran when 3 began" \
        in capsys.readouterr().err
    waits = [e for plane in trace["planes"] for line in plane["lines"]
             for e in line["events"] if e[0] == "sched/device_wait"]
    replays = [e for plane in trace["planes"] for line in plane["lines"]
               for e in line["events"] if e[0] == "sched/replay"]
    for (_n, start, dur), (_r, r_start, r_dur) in zip(sorted(
            waits, key=lambda e: e[1]), sorted(replays, key=lambda e: e[1])):
        assert r_start <= start and start + dur <= r_start + r_dur


def test_a_trace_that_marks_no_wait_reads_nothing(monkeypatch, capsys):
    """The parent's trace: phases annotated, no wait marked."""
    with open(os.path.join(DATA, "trace_host_small.json")) as f:
        recorded = json.load(f)["trace"]
    assert sync_lag.lags(recorded) == []
    monkeypatch.setattr(sync_lag._by_host, "newest_trace",
                        lambda cell: "a.xplane.pb")
    monkeypatch.setattr(sync_lag, "load", lambda path: recorded)
    assert sync_lag.read({"cell": {"name": "density-1k.fill"}}) \
        is None
    assert "no sched/device_wait annotation" in capsys.readouterr().err


def test_no_traced_run_reads_nothing(monkeypatch, capsys):
    def missing(cell):
        raise FileNotFoundError(f"no traced run of {cell}")

    monkeypatch.setattr(sync_lag._by_host, "newest_trace", missing)
    assert sync_lag.read({"cell": {"name": "x"}}) is None
    assert "no traced run of x" in capsys.readouterr().err


def test_the_readers_own_pass_keeps_the_waits_and_drops_the_rest(tmp_path):
    """A real profile of this process (no chip here, so no device
    plane): `load` finds the marked wait among the host's events and
    keeps `sched/` events only."""
    import jax
    import jax.numpy as jnp

    was = profile.set_annotations(True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        with profile.phase_timer("score"):
            with jax.profiler.TraceAnnotation("not ours"):
                got = profile.fetch(jnp.arange(8) * 2)
        jax.profiler.stop_trace()
    finally:
        profile.set_annotations(was)
    assert got.tolist() == list(range(0, 16, 2))
    trace = sync_lag.load(
        sync_lag.trace_reduce.find_xplane(str(tmp_path)))
    names = [e[0] for plane in trace["planes"] for line in plane["lines"]
             for e in line["events"]]
    assert names.count("sched/device_wait") == 1
    assert names.count("sched/score") == 1
    assert set(names) == {"sched/device_wait", "sched/score"}
    assert sync_lag.lags(trace) == []  # no device line here


def test_the_waits_annotation_moves_no_share_of_the_idle_time():
    """device_idle_by_host ranks the phases and the idle states; a
    `sched/device_wait` inside `sched/replay` is neither and takes
    nothing from the phase it lies in."""
    from benchmark.layers import device_idle_by_host

    def trace(with_waits):
        host = [["sched/replay", 100 * US, 500 * US],
                ["sched/encode", 700 * US, 200 * US]]
        if with_waits:
            host.append(["sched/device_wait", 150 * US, 440 * US])
        return {"planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules",
                 "events": [["jit_zreplay_group(1)", 140 * US, 400 * US]]},
                {"name": "XLA Ops",
                 "events": [["while.1", 140 * US, 400 * US]]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "scheduler", "events": host}]}]}

    without = device_idle_by_host.shares(trace(False))
    assert device_idle_by_host.shares(trace(True)) == without
    assert "device_wait" not in without
    assert device_idle_by_host.clock_check(trace(True)) \
        == device_idle_by_host.clock_check(trace(False))
