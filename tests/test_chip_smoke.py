"""chip_smoke.py's phases, rehearsed on the CPU at tiny sizes.

The script itself has no flag that lets a CPU run pass — its platform
check is unconditional — so the rehearsal calls its phase functions
directly. Also here: the contracts the smoke leans on (children stay
off jax, the compile cache is placed from outside, a native library is
keyed on its source).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, **env):
    """Run `code` in a fresh interpreter from the repo root; env values
    of None are removed from the child's environment."""
    child_env = dict(os.environ)
    for k, v in env.items():
        if v is None:
            child_env.pop(k, None)
        else:
            child_env[k] = v
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=child_env, capture_output=True, text=True,
                          timeout=120)


def test_engines_phase_loads_all_three():
    assert all(chip_smoke.phase_engines())


def test_raw_phase_tiny():
    count = chip_smoke.phase_raw(64, 640)
    assert set(count.values()) == {10}


def test_oracle_identity_phase_tiny():
    ran = chip_smoke.phase_oracle(40, 32, 24, 200)
    # the mixed backlog exists to execute every single-chip program
    assert {"scan", "group_probe", "probe", "apply", "zreplay",
            "zreplay_group"} <= set(ran)


def test_served_phase_tiny():
    raw = chip_smoke.phase_raw(16, 200)
    stats = chip_smoke.phase_served(16, 200, raw)
    assert stats["ready_seconds"] >= 0 and "check" not in stats


def test_mesh_phase_on_four_virtual_devices():
    import jax

    ran = chip_smoke.phase_mesh(200, 2000,
                                devices=jax.devices()[:4])["dispatches"]
    assert ran.get("group_probe", 0) >= 1 and ran.get("scan", 0) >= 1


def test_platform_check_refuses_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu()


def test_script_exits_nonzero_on_cpu_before_scheduling():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and "[b]" not in proc.stdout


def test_result_line_exact_shape():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(device)
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(line) == {"ok": True, "device": device}


def test_control_plane_children_never_import_jax():
    """One process holds the chip. The apiserver and creator children
    of the served path must not touch jax, or they would fight the
    daemon for it."""
    proc = _python(
        "import sys\n"
        "import kubernetes_tpu.hyperkube\n"
        "import kubernetes_tpu.apiserver.server\n"
        "import kubernetes_tpu.harness.creator\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_is_placed_from_outside(outside, tmp_path):
    want = str(tmp_path) if outside else os.path.join(REPO, ".xla_cache")
    proc = _python(
        "import os, kubernetes_tpu\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n",
        JAX_COMPILATION_CACHE_DIR=want if outside else None,
        KUBERNETES_TPU_NO_XLA_CACHE=None)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == want


def test_second_run_compiles_less(tmp_path):
    """The compile counter the smoke prints, against a cache directory
    set from outside: the second process builds the same program from
    the cache, and nothing is written anywhere else."""
    code = (
        "import kubernetes_tpu, chip_smoke, jax, jax.numpy as jnp\n"
        "c = chip_smoke.CompileCounter()\n"
        "jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(17))"
        ".block_until_ready()\n"
        "print(c.built - c.hits, c.hits)\n")
    runs = []
    for _ in range(2):
        proc = _python(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                       KUBERNETES_TPU_NO_XLA_CACHE=None)
        assert proc.returncode == 0, proc.stderr
        runs.append(tuple(int(x) for x in proc.stdout.split()))
    (cold_compiled, _), (warm_compiled, warm_hits) = runs
    assert cold_compiled > 0 and os.listdir(tmp_path)
    assert warm_compiled < cold_compiled and warm_hits > 0


def test_native_library_is_keyed_on_its_source(tmp_path, monkeypatch):
    from kubernetes_tpu.native import build

    if build._compiler() is None:
        pytest.skip("no C compiler")
    src = os.path.join(build._NATIVE_DIR, "replay.c")
    shutil.copy(src, tmp_path / "replay.c")
    # libraries that git would never have committed: an mtime-fresh
    # plain name and a keyed name of some other source
    for stale in ("_replay.so", "_replay.000000000000.so"):
        (tmp_path / stale).write_bytes(b"not a library")
    monkeypatch.setattr(build, "_NATIVE_DIR", str(tmp_path))

    first = build.ensure_replay()
    assert os.path.basename(first) not in ("_replay.so",
                                           "_replay.000000000000.so")
    assert build.ensure_replay() == first  # same bytes, same library
    with open(tmp_path / "replay.c", "a") as f:
        f.write("\n/* one more byte of source */\n")
    second = build.ensure_replay()
    assert second != first and os.path.exists(second)
    assert not os.path.exists(first)  # dead weight is cleared


def test_daemon_with_a_failed_warmup_never_reports_ready(monkeypatch, caplog):
    """No fallback that hides the device: a warmup that fails on the
    device path is an error, the daemon never reports ready, and a
    waiter gets the reason at once (not after the 180 s deadline)."""
    import io
    import logging

    from kubernetes_tpu.apiserver.server import APIServer
    from kubernetes_tpu.client.rest import RESTClient
    from kubernetes_tpu.client.transport import LocalTransport
    from kubernetes_tpu.harness.creator import make_nodes
    from kubernetes_tpu.harness.perf import _wait_sched_ready
    from kubernetes_tpu.scheduler.server import (
        SchedulerServer,
        SchedulerServerOptions,
    )
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm

    def broken(self, num_nodes, phase="all", nodes=None):
        raise RuntimeError("the device refused the probe program")

    monkeypatch.setattr(TPUScheduleAlgorithm, "warmup", broken)
    client = RESTClient(LocalTransport(APIServer()))
    make_nodes(client, 4)
    caplog.set_level(logging.INFO, logger="kubernetes_tpu.scheduler.server")
    sched = SchedulerServer(client, SchedulerServerOptions(
        algorithm_provider="TPUProvider", serve_port=None)).start()
    try:
        with pytest.raises(RuntimeError, match="failed to start") as err:
            _wait_sched_ready(sched, io.StringIO(), timeout=60.0)
        assert "refused the probe program" in str(err.value.__cause__)
        assert not sched.ready.is_set()
        text = caplog.text
        # the daemon said which device it got, and the failure is loud
        assert "scheduler device backend: platform=cpu" in text
        assert "warmup failed" in text
        assert any(r.levelno == logging.ERROR for r in caplog.records)
    finally:
        sched.stop()
