"""Test harness configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding
(kubernetes_tpu.parallel) is exercised without TPU hardware, per the
kubemark idea in the reference (hollow nodes: real scheduler, fake
everything else — SURVEY.md §4).

NOTE: the jaxtyping pytest plugin imports jax before this conftest runs,
so env vars alone are too late — jax.config.update still works as long as
no backend has been initialized yet.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses
# The 8-device CPU mesh below would flip EVERY TPUProvider daemon test
# onto the mesh path via KUBERNETES_TPU_MESH=auto, silently dropping
# coverage of the single-chip daemon path (the production path on any
# 1-device host). Tests that want the mesh daemon opt in with
# monkeypatch.setenv("KUBERNETES_TPU_MESH", "force").
os.environ.setdefault("KUBERNETES_TPU_MESH", "off")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Build the native engines up front (cached by source hash) so the C-replay
# differential fuzz tests exercise replay.c instead of silently skipping
# (the round-2 failure: the driver's test run never executed the C path).
from kubernetes_tpu.native.build import ensure_all

ensure_all()


# -- optional-dependency auto-skip --------------------------------------------
#
# The image lacks `cryptography` (service-account JWT signing). Tests
# needing it are an environment gap, not a regression — report them as
# SKIPPED instead of collection errors / failures so tier-1 output only
# goes red for real breakage. The conversion is gated on the dependency
# actually being absent: with it installed, a matching error is a
# genuine failure and stays one.

import importlib.util

import pytest

_MISSING_DEPS = [
    dep for dep in ("cryptography",)
    if importlib.util.find_spec(dep) is None
]


def _missing_dep_in(exc) -> str:
    if not isinstance(exc, (ImportError, AttributeError)):
        return ""
    text = str(exc)
    for dep in _MISSING_DEPS:
        if dep in text:
            return dep
    return ""


def pytest_pycollect_makemodule(module_path, parent):
    """Collect test modules through a guard that turns an ImportError
    caused by a known-missing optional dependency into a module-level
    skip (the importorskip outcome, without editing every test file)."""

    class GuardedModule(pytest.Module):
        def _getobj(self):
            try:
                return super()._getobj()
            except self.CollectError as e:
                # pytest wraps the module's ImportError into CollectError
                # (with the traceback text) before it reaches us
                text = str(e)
                for dep in _MISSING_DEPS:
                    if dep in text:
                        raise pytest.skip.Exception(
                            f"optional dependency {dep!r} not in this image",
                            allow_module_level=True,
                        ) from e
                raise
            except ImportError as e:
                dep = _missing_dep_in(e)
                if dep:
                    raise pytest.skip.Exception(
                        f"optional dependency {dep!r} not in this image: {e}",
                        allow_module_level=True,
                    ) from e
                raise

    return GuardedModule.from_parent(parent, path=module_path)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """A lazily-imported optional dep fails inside the test call; remap
    those failures to skips the same way."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when in ("setup", "call") and rep.failed and call.excinfo is not None:
        dep = _missing_dep_in(call.excinfo.value)
        if dep:
            rep.outcome = "skipped"
            rep.longrepr = (
                str(item.path),
                item.location[1],
                f"Skipped: optional dependency {dep!r} not in this image",
            )


if os.environ.get("KUBERNETES_TPU_LOCK_SANITIZER"):
    # opt-in suite-wide arming of the lock-order sanitizer (the chaos
    # module arms it unconditionally): KUBERNETES_TPU_LOCK_SANITIZER=1
    # wraps EVERY test, so any suite doubles as an ordering witness
    from kubernetes_tpu.analysis import locks as _locks

    @pytest.fixture(autouse=True)
    def _global_lock_sanitizer():
        with _locks.instrumented():
            yield
        _locks.assert_no_cycles("(suite-wide)")


if os.environ.get("KUBERNETES_TPU_RACE_SANITIZER"):
    # opt-in suite-wide arming of the DATA-RACE sanitizer (lockset +
    # vector-clock happens-before, analysis/races), mirroring the lock
    # sanitizer: KUBERNETES_TPU_RACE_SANITIZER=1 wraps every test so
    # any suite doubles as a race witness. Findings accumulate into the
    # KUBERNETES_TPU_RACE_REPORT JSONL artifact (when set) that
    # `python -m kubernetes_tpu.analysis --race-report` merges back
    # into the CI gate; an unsuppressed race also fails the exposing
    # test directly. This is a SEPARATE CI invocation, not the default
    # tier-1 run — the detector's instrumentation overhead rides every
    # tracked attribute access (see README "Static analysis").
    from kubernetes_tpu.analysis import races as _races

    # truncate the artifact once per session: dump_jsonl appends per
    # test, and stale rows from a PREVIOUS run (races since fixed)
    # would keep failing the --race-report gate forever
    _report = os.environ.get("KUBERNETES_TPU_RACE_REPORT")
    if _report:
        open(_report, "w").close()

    @pytest.fixture(autouse=True)
    def _global_race_sanitizer():
        with _races.instrumented(reset=True):
            yield
        report = os.environ.get("KUBERNETES_TPU_RACE_REPORT")
        if report:
            _races.dump_jsonl(report)
        _races.assert_no_races("(suite-wide)")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; the slow set is the hours-long
    # production-realism forms (full chaos scenarios, A/B soaks)
    config.addinivalue_line(
        "markers",
        "slow: production-realism long forms excluded from tier-1",
    )


def wait_until(cond, timeout=60.0, interval=0.01):
    """Poll `cond` until truthy or `timeout` elapses. The single shared
    copy (each test file used to carry its own, and the defaults
    drifted): a passing wait returns immediately, so the generous
    deadline only slows genuinely failing tests."""
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return bool(cond())
