"""benchmark/run.py below its look for a chip, on the CPU at tiny
sizes: a sound run is `correct`, a run with the timed path broken
underneath is not, and a new cell needs new files and entries only."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import deploy, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """Deployments and mixes cut to a size a test run holds; only
    counts change, every shape stays."""
    d = tmp_path_factory.mktemp("tiny")
    cfg = deploy.load_json(os.path.join(REPO, "benchmark", "configs",
                                        "density-1k.json"))
    cfg["nodes"]["count"] = 16
    cfg["pods"]["population"] = 300
    (d / "density-1k.json").write_text(json.dumps(cfg))
    check = {"pods": 64}
    fill = deploy.load_json(deploy.traffic_path("fill"))
    fill.update(workers=2, chunk=50, backlog_cap=128, warm_s=0.5,
                drain_s=2.0, check=check)
    (d / "fill.json").write_text(json.dumps(fill))
    steady = deploy.load_json(deploy.traffic_path("steady"))
    steady.update(workers=2, rate_per_s=100, backlog_cap=128, warm_s=0.5,
                  drain_s=2.0, check=check)
    (d / "steady.json").write_text(json.dumps(steady))
    return d


@pytest.fixture()
def env():
    """serve() gives the program its deployment's environment; a test
    gives the old one back."""
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def _serve(tiny, workload, trace, manifest=None, **kw):
    manifest = manifest or deploy.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    return run.serve(cell, str(tiny / f"{cell['config']}.json"),
                     str(tiny / f"{cell['traffic']}.json"), BIG_SEED, 2.0,
                     trace, manifest, **kw)


@pytest.fixture(scope="module")
def traced_fill(tiny):
    saved = dict(os.environ)
    try:
        return _serve(tiny, "density-1k.fill", True)
    finally:
        os.environ.clear()
        os.environ.update(saved)


def test_sound_tiny_run_is_correct(traced_fill):
    assert traced_fill["correct"] is True
    assert traced_fill["attempted"] > 0 and traced_fill["failed"] == 0
    assert all(v == limit for v, limit in traced_fill["numbers"].values())
    assert tuple(traced_fill["numbers"]["picks_off_reference"]) == (0, 0)


def test_traced_run_reports_the_cells_per_layer_metrics(traced_fill):
    want = {m["name"]: m["unit"]
            for m in deploy.load_manifest()["per_layer"]
            if "density-1k.fill" in m["workloads"]}
    got = traced_fill["metrics"]
    # the CPU has no device plane: a reader that finds nothing to read
    # returns nothing, and the line leaves the metric out
    host_side = {n for n in want if not n.startswith("device_")}
    assert host_side <= set(got) <= set(want)
    for name, entry in got.items():
        assert entry["unit"] == want[name]
        assert isinstance(entry["value"], (int, float))
    assert got["pods_per_wave.fill"]["value"] > 1
    assert 0 <= got["sched_unattributed_share.fill"]["value"] <= 100
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(
        traced_fill["device"])
    assert set(traced_fill["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "setup_s" not in got and "pods_bound_per_s" not in got


def _rotate_check_picks(monkeypatch):
    """A scorer that answers wrongly: the check batch's picks, each
    handed to the pod after it."""
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    sound = TPUScheduleAlgorithm.schedule_backlog

    def degraded(self, pods, state, *a, **kw):
        hosts = list(sound(self, pods, state, *a, **kw))
        idx = [i for i, p in enumerate(pods)
               if p.metadata.name.startswith("check-")]
        if len(idx) > 1:
            moved = [hosts[i] for i in idx]
            for i, h in zip(idx, moved[1:] + moved[:1]):
                hosts[i] = h
        return hosts

    monkeypatch.setattr(TPUScheduleAlgorithm, "schedule_backlog", degraded)


def _drop_one_binding(monkeypatch):
    """A binder that loses one binding and says it did not."""
    from kubernetes_tpu.scheduler.factory import ConfigFactory

    sound = ConfigFactory._bind_many

    def lossy(self, pairs):
        pairs = list(pairs)
        keep = [pr for pr in pairs if pr[0].metadata.name != "check-00003"]
        results = list(sound(self, keep)) if keep else []
        for i, pr in enumerate(pairs):
            if pr[0].metadata.name == "check-00003":
                results.insert(i, {"status": "Success"})
        return results

    monkeypatch.setattr(ConfigFactory, "_bind_many", lossy)


@pytest.mark.parametrize("breakage,number", [
    (_rotate_check_picks, "picks_off_reference"),
    (_drop_one_binding, "pods_unbound_read_back"),
])
def test_broken_timed_path_is_not_correct(tiny, env, monkeypatch, breakage,
                                          number):
    breakage(monkeypatch)
    result = _serve(tiny, "density-1k.fill", False)
    assert result["correct"] is False
    assert result["numbers"][number][0] >= 1
    # an untraced run reports the cell's end-to-end metrics, no others
    assert set(result["metrics"]) == {"setup_s", "pods_bound_per_s"}
    assert result["metrics"]["pods_bound_per_s"]["unit"] == "pods/s"


def test_new_cell_needs_only_new_files_and_entries(tiny, env, tmp_path):
    """A later PR adds a deployment, a mix and a per-layer metric by
    adding files and entries: run.py names none of them."""
    layers = tmp_path / "layers"
    shutil.copytree(os.path.join(REPO, "benchmark", "layers"), layers)
    (layers / "watch_events_per_pod.py").write_text(
        "def read(run):\n"
        "    bound = run['loadgen']['bound']\n"
        "    return run['loadgen']['watch_events'] / bound\n")
    shutil.copy(tiny / "density-1k.json", tiny / "thrown.json")
    shutil.copy(tiny / "steady.json", tiny / "away.json")
    manifest = copy.deepcopy(deploy.load_manifest())
    manifest["workloads"].append({
        "name": "thrown.away", "config": "thrown", "traffic": "away",
        "chips": 1, "why": "a throwaway cell"})
    for m in manifest["end_to_end"]:
        if m["name"].startswith("bind_latency"):
            m["workloads"].append("thrown.away")
    manifest["per_layer"].append({
        "name": "watch_events_per_pod.away", "unit": "events/pod",
        "better": "lower", "source": "host_clock", "layer": "load generator",
        "moves": "bind_latency_p50_ms", "workloads": ["thrown.away"]})
    manifest["per_layer"][0]["workloads"].append("thrown.away")
    result = _serve(tiny, "thrown.away", True, manifest,
                    layers_dir=str(layers))
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert got["watch_events_per_pod.away"]["unit"] == "events/pod"
    assert got["watch_events_per_pod.away"]["value"] >= 1
    first = manifest["per_layer"][0]["name"]
    assert first.startswith("loadgen_late") and got[first]["value"] >= 0


def test_run_py_names_no_cell_configuration_or_metric():
    manifest = deploy.load_manifest()
    with open(os.path.join(REPO, "benchmark", "run.py")) as f:
        source = f.read()
    names = ([w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [w["traffic"] + ".json" for w in manifest["workloads"]]
             + [m["name"] for m in manifest["per_layer"]]
             + [m["name"] for m in manifest["end_to_end"]
                if m["name"] != "setup_s"])
    assert not [n for n in names if n in source]


def test_run_py_refuses_to_start_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "density-1k.fill",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under `paths` there is no system to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "density-1k.fill",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu",
                           "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
