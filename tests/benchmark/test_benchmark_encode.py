"""encode_us_per_pod and the counters that say its mechanism engages:
the manifest's two entries, the reader on a run made by hand and in the
tiny CPU run, and the daemon's /metrics and /debug/traces after it."""

import json
import os
import urllib.request

import pytest

from benchmark import deploy
from benchmark.layers import encode_us_per_pod, sched_host_busy_share

from tests.benchmark.test_benchmark_run import _serve, tiny  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_manifest_has_the_two_entries_and_their_reader():
    manifest = deploy.load_manifest()
    ends = {m["name"] for m in manifest["end_to_end"]}
    got = {m["name"]: m for m in manifest["per_layer"]
           if m["name"].startswith("encode_us_per_pod.")}
    assert set(got) == {"encode_us_per_pod.fill", "encode_us_per_pod.steady"}
    for traffic, moves in (("fill", "pods_bound_per_s"),
                           ("steady", "bind_latency_p50_ms")):
        m = got[f"encode_us_per_pod.{traffic}"]
        assert m["workloads"] == [f"density-1k.{traffic}"]
        assert m["moves"] == moves and moves in ends
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "us/pod", "lower", "program_span", "scheduler host side")
    # appended: nothing that was there moved
    assert [m["name"] for m in manifest["per_layer"][-2:]] == sorted(got)
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "layers", "encode_us_per_pod.py"))
    # the same snapshot as the share that already reads `encode`
    assert encode_us_per_pod.snapshot is sched_host_busy_share.snapshot


def test_reader_on_a_run_made_by_hand():
    before = {"encode": 10.0, "probe": 1.0, "bind": 5.0}
    after = {"encode": 10.25, "probe": 3.0, "bind": 9.0}
    run = {"snapshots": {"encode_us_per_pod": (before, after)},
           "loadgen": {"bound_in_window": 5000}}
    assert encode_us_per_pod.read(run) == pytest.approx(50.0)
    run["loadgen"]["bound_in_window"] = 0  # nothing bound: nothing to read
    assert encode_us_per_pod.read(run) is None


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.read().decode()


def _counters(port):
    """(contribution lookups by result, the encoder's totals), read over
    HTTP from the mux every daemon serves (scheduler/server.py)."""
    lookups = {}
    for line in _get(port, "/metrics").splitlines():
        if line.startswith("scheduler_pod_contribution_lookups_total{"):
            labels, value = line.rsplit(" ", 1)
            lookups[labels.split('"')[1]] = float(value)
    traces = json.loads(_get(port, "/debug/traces?limit=1"))
    assert "compiles" in traces
    return lookups, traces["encoder"]


@pytest.fixture(scope="module")
def traced_steady(tiny):  # noqa: F811
    """-> (the result, the counters before the run, after it). The
    counters are the process's, and other tests of this worker moved
    them: only their difference is the run's."""
    from kubernetes_tpu.trace.httpd import start_component_server

    saved = dict(os.environ)
    server, port = start_component_server(name="test")
    try:
        before = _counters(port)
        result = _serve(tiny, "density-1k.steady", True)
        return result, before, _counters(port)
    finally:
        server.shutdown()
        server.server_close()
        os.environ.clear()
        os.environ.update(saved)


def test_tiny_run_reads_encode_per_pod(traced_steady):
    result = traced_steady[0]
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]["encode_us_per_pod.steady"]
    assert got["unit"] == "us/pod" and got["value"] > 0


def test_daemon_shows_that_the_mechanism_engaged(traced_steady):
    _, (lookups0, encoder0), (lookups1, encoder1) = traced_steady
    assert set(lookups1) == {"hit", "miss"}
    hits = lookups1["hit"] - lookups0.get("hit", 0)
    misses = lookups1["miss"] - lookups0.get("miss", 0)
    # one template: its pods are parsed once, not once each, thrice over
    assert hits > 100 * misses and misses <= 4
    assert set(encoder1) == {"events", "batches", "per_event_fallbacks"}
    events = encoder1["events"] - encoder0["events"]
    batches = encoder1["batches"] - encoder0["batches"]
    assert events > batches > 0
    # no ports, no affinity, no pod on a node the cache has not seen
    assert encoder1["per_event_fallbacks"] == encoder0["per_event_fallbacks"]
