"""Static-analysis suite (kubernetes_tpu/analysis): the tree must be
clean under every pass, AND each pass must catch its seeded violation —
a gate that can't fail is not a gate.

Seeded violations per the issues: an s64 dot_general (the PR 3 TPU
lowering incident), a ``.item()`` host sync in a hot module, a
lock-order inversion, a two-thread data race (lockset path and
missing-happens-before path separately), a ``# guarded-by`` write
without the lock, a drifted PartitionSpec, and a non-commutative
scatter smuggled into a commit fold."""

import dataclasses
import json
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.analysis import Finding, render_report
from kubernetes_tpu.analysis import lint
from kubernetes_tpu.analysis import jaxpr_audit
from kubernetes_tpu.analysis import locks
from kubernetes_tpu.analysis import races
from kubernetes_tpu.analysis.compile_guard import CompileSentinel
from kubernetes_tpu.analysis.jaxpr_audit import (
    audit_jaxpr,
    registered_programs,
)
from kubernetes_tpu.analysis.programs import ProgramSpec


# -- pass 1: jaxpr auditor ----------------------------------------------------


def _active(findings):
    return [f for f in findings if not f.suppressed]


def test_tree_jaxpr_audit_clean():
    """Every registered device program honors the lowering/transfer
    contracts (this is the `python -m kubernetes_tpu.analysis` body)."""
    findings = jaxpr_audit.audit_all()
    assert not _active(findings), render_report(findings)


def test_registry_covers_the_wave_programs():
    names = {s.name for s in registered_programs()}
    for expect in ("scan", "probe", "probe_fused_same", "apply",
                   "apply_group", "zreplay", "zreplay_group"):
        assert expect in names, f"{expect} missing from the registry"
    assert any(n.startswith("group_probe_G") for n in names)
    # mesh variants ride when the host can form a mesh (conftest
    # forces 8 CPU devices, so here they must be present)
    if len(jax.devices()) >= 2:
        assert {"mesh_scan", "mesh_probe", "mesh_group_probe",
                "mesh_apply", "mesh_apply_group",
                "resident_scatter"} <= names


def test_the_replay_loops_brought_no_program_more():
    """The device replay's loops end at counts read from the program's
    input: one `zreplay` and one `zreplay_group` stand for every run
    count and run length inside their buckets, and the gate audits as
    many programs as it did before the loops had a traced bound."""
    names = [s.name for s in registered_programs()]
    assert [n for n in names if n.startswith("zreplay")] \
        == ["zreplay", "zreplay_group"]
    if len(jax.devices()) >= 2:
        assert len(names) == len(set(names)) == 22, names


def test_donation_contract_is_audited():
    """Every registered resident-state program declares donation and
    passes the aliasing audit; the donated folds cover the carry."""
    specs = {s.name: s for s in registered_programs()}
    if "mesh_apply" not in specs:
        import pytest

        pytest.skip("no mesh on this host")
    donated = [n for n, s in specs.items() if s.donate_argnums]
    assert {"mesh_apply", "mesh_apply_group",
            "resident_scatter"} <= set(donated)
    for n in donated:
        assert not jaxpr_audit._donation_findings(specs[n]), n


def test_seeded_broken_donation_is_flagged():
    """A donated input the program cannot alias (shape/dtype drift —
    XLA would silently copy it) must trip the donation audit."""
    def drops_donated(a, b):
        return b[:2] * 2  # output shape matches neither donated leaf

    fn = jax.jit(drops_donated, donate_argnums=(0,))
    spec = ProgramSpec(
        name="seeded_drop", fn=fn,
        args=(jnp.zeros(7, jnp.float32), jnp.zeros(5, jnp.float32)),
        carry_out_leaves=1, expected_host_leaves=None,
        donate_argnums=(0,),
    )
    found = jaxpr_audit._donation_findings(spec)
    assert any(f.rule in ("donation-contract", "donation-unusable")
               for f in found), found

    def keeps_donated(a, b):
        return a + b.sum()

    good = ProgramSpec(
        name="seeded_keep", fn=jax.jit(keeps_donated, donate_argnums=(0,)),
        args=(jnp.zeros(7, jnp.float32), jnp.zeros(5, jnp.float32)),
        carry_out_leaves=1, expected_host_leaves=None,
        donate_argnums=(0,),
    )
    assert not jaxpr_audit._donation_findings(good)


def test_grouped_wave_transfer_contract_is_static():
    """The O(1)-dispatch property as a STRUCTURAL invariant: the
    grouped probe ships exactly ONE host-bound array at every
    registered G (probe=1 transfer per wave regardless of template
    count) and the folds ship zero (apply=1 dispatch, 0 transfers)."""
    specs = {s.name: s for s in registered_programs()}
    gp = [s for n, s in specs.items() if n.startswith("group_probe_G")]
    assert len(gp) >= 2, "need two G values to pin G-independence"
    for s in gp:
        assert s.expected_host_leaves == 1
        assert not jaxpr_audit._transfer_findings(s), s.name
    for n in ("apply", "apply_group"):
        assert specs[n].expected_host_leaves == 0
        assert not jaxpr_audit._transfer_findings(specs[n]), n


def test_seeded_transfer_contract_violation_is_flagged():
    """An extra device->host output must trip the transfer audit."""
    carry = (jnp.zeros(3), jnp.zeros(3))

    def leaky(c, x):
        return c, x * 2, x + 1  # 2 host-bound outputs

    spec = ProgramSpec(
        name="seeded_leak", fn=jax.jit(leaky),
        args=(carry, jnp.zeros(3)),
        carry_out_leaves=2, expected_host_leaves=1,
    )
    found = jaxpr_audit._transfer_findings(spec)
    assert len(found) == 1 and found[0].rule == "transfer-contract"


def test_seeded_s64_dot_general_is_flagged():
    """Reintroduce the PR 3 incident: an s64 matmul must be denylisted."""
    bad = jax.jit(lambda a, b: a @ b)
    jaxpr = jax.make_jaxpr(bad)(
        jnp.ones((4, 4), jnp.int64), jnp.ones((4, 4), jnp.int64)
    )
    found = audit_jaxpr("seeded_s64", jaxpr)
    assert any(f.rule == "denylisted-primitive" for f in found), found
    # and the f32 spelling of the same program is fine
    ok = jax.make_jaxpr(bad)(
        jnp.ones((4, 4), jnp.float32), jnp.ones((4, 4), jnp.float32)
    )
    assert not audit_jaxpr("ok_f32", ok)


def test_seeded_callback_and_f64_upcast_are_flagged():
    def with_cb(x):
        import numpy as np

        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), x.dtype), x
        )

    jaxpr = jax.make_jaxpr(with_cb)(jnp.ones(4))
    assert any(f.rule == "host-callback"
               for f in audit_jaxpr("seeded_cb", jaxpr))

    # a weak-type float division promotes int64 -> float64: the classic
    # silent upcast the probe/apply contract forbids
    jaxpr2 = jax.make_jaxpr(jax.jit(lambda x: x / 3.0))(
        jnp.ones(4, jnp.int64))
    found = audit_jaxpr("seeded_f64", jaxpr2)
    assert any(f.rule == "f64-upcast" for f in found), found
    # ...and the same jaxpr passes when the program is registered f64
    assert not audit_jaxpr("allowed_f64", jaxpr2, allow_f64=True)


# -- pass 2: AST lint ---------------------------------------------------------


def test_tree_lint_clean():
    findings = lint.lint_tree()
    assert not _active(findings), render_report(findings)


_HOT_FIXTURE = '''\
import jax
import jax.numpy as jnp


def _traced_body(x):
    k = x.sum(){item}  # seeded host sync
    return x * k


def run(x):
    return jax.jit(_traced_body)(x)
'''


def test_seeded_item_in_hot_module_is_flagged():
    src = _HOT_FIXTURE.format(item=".item()")
    found = lint.lint_sources(
        {"kubernetes_tpu/models/_seeded_fixture.py": src})
    hs = [f for f in found if f.rule == "host-sync"]
    assert len(hs) == 1 and not hs[0].suppressed, found
    assert "_seeded_fixture.py:6" in hs[0].where


def test_lint_suppression_syntax():
    src = _HOT_FIXTURE.format(
        item=".item()  # lint: allow[host-sync]")
    found = lint.lint_sources(
        {"kubernetes_tpu/models/_seeded_fixture.py": src})
    hs = [f for f in found if f.rule == "host-sync"]
    assert len(hs) == 1 and hs[0].suppressed, found


_WALL_CLOCK_FIXTURE = '''\
import time
from time import time as walltime


class Lease:
    def renew(self, window):
        self.expiry = time.time() + window        # arithmetic
        return self.expiry

    def valid(self):
        return time.time() < self.expiry          # comparison

    def wait_for(self, cond):
        cond.wait(timeout=time.time())            # deadline keyword
        self.deadline = walltime()                # deadline-ish bind

    def stamp_event(self):
        return time.time()                        # bare read: legal

    def monotonic_path(self, window):
        return time.monotonic() + window          # the correct form
'''


def test_seeded_wall_clock_deadline_is_flagged():
    found = lint.lint_sources(
        {"kubernetes_tpu/storage/quorum/_seeded_lease.py":
         _WALL_CLOCK_FIXTURE})
    wc = [f for f in found if f.rule == "wall-clock-deadline"]
    assert len(wc) == 4 and not any(f.suppressed for f in wc), found
    lines = sorted(int(f.where.rsplit(":", 1)[1]) for f in wc)
    assert lines == [7, 11, 14, 15], wc


def test_wall_clock_rule_covers_all_named_modules_and_no_others():
    src = "import time\ndeadline = time.time() + 5.0\n"
    for rel in ("kubernetes_tpu/storage/quorum/_seeded.py",
                "kubernetes_tpu/client/transport.py",
                "kubernetes_tpu/apiserver/flowcontrol.py"):
        found = lint.lint_sources({rel: src})
        assert any(f.rule == "wall-clock-deadline" for f in found), rel
    # identical source outside the consensus-critical scope is exempt
    found = lint.lint_sources(
        {"kubernetes_tpu/scheduler/_seeded.py": src})
    assert not any(f.rule == "wall-clock-deadline" for f in found)


def test_wall_clock_suppression_syntax():
    src = ("import time\n"
           "t = time.time() + 5  # lint: allow[wall-clock-deadline]\n")
    found = lint.lint_sources(
        {"kubernetes_tpu/storage/quorum/_seeded.py": src})
    wc = [f for f in found if f.rule == "wall-clock-deadline"]
    assert len(wc) == 1 and wc[0].suppressed, found


def test_lint_traced_scope_is_transitive_and_cold_code_is_exempt():
    src = '''\
import jax
import jax.numpy as jnp


def helper(x):
    return x.sum().item()  # reached from a traced body


def _traced_body(x):
    return helper(x)


def run(x):
    return jax.jit(_traced_body)(x)


def host_driver(arr):
    return arr.sum().item()  # NOT traced: no finding here
'''
    found = lint.lint_sources(
        {"kubernetes_tpu/models/_seeded_fixture2.py": src})
    hs = [f for f in found if f.rule == "host-sync"]
    assert len(hs) == 1, found
    assert ":6" in hs[0].where  # helper's .item(), not host_driver's


def test_lint_package_wide_rules_fire():
    src = '''\
import threading
from kubernetes_tpu.metrics import Counter


def f(x=[]):
    try:
        pass
    except:
        pass
    threading.Thread(target=f).start()
    return Counter("loose_total", "constructed outside the registry")
'''
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded3.py": src})
    rules = {f.rule for f in found}
    assert {"mutable-default", "bare-except", "nondaemon-thread",
            "metric-outside-registry"} <= rules, found


def test_lint_syntax_error_is_a_finding_not_a_crash():
    found = lint.lint_sources({
        "kubernetes_tpu/models/_broken.py": "def f(:\n",
        "kubernetes_tpu/models/_fine.py": "x = 1\n",
    })
    se = [f for f in found if f.rule == "syntax-error"]
    assert len(se) == 1 and "_broken.py" in se[0].where, found


def test_lint_impure_traced_rules_fire():
    src = '''\
import time

import jax


def _traced_body(x):
    t = time.time()  # seeded impurity
    print("trace me")
    return x


def run(x):
    return jax.jit(_traced_body)(x)
'''
    found = lint.lint_sources(
        {"kubernetes_tpu/ops/_seeded4.py": src})
    impure = [f for f in found if f.rule == "traced-impure"]
    assert len(impure) == 2, found


# -- pass 3: runtime sanitizers ----------------------------------------------


def _fake_component():
    """Locks created from a module whose __name__ is inside the
    package, so the instrumented factories track them."""
    mod = types.ModuleType("kubernetes_tpu._seeded_locks")
    sys.modules["kubernetes_tpu._seeded_locks"] = mod
    src = ("import threading\n"
           "def make_a():\n    return threading.Lock()\n"
           "def make_b():\n    return threading.Lock()\n")
    exec(compile(src, "_seeded_locks.py", "exec"), mod.__dict__)
    return mod


def test_seeded_lock_order_inversion_is_flagged():
    mod = _fake_component()
    locks.GRAPH.reset()
    with locks.instrumented():
        a, b = mod.make_a(), mod.make_b()

        def t1():
            with a:
                with b:
                    pass

        def t2():
            with b:
                with a:
                    pass

        for fn in (t1, t2):
            th = threading.Thread(target=fn)
            th.start()
            th.join()
    try:
        cycles = locks.GRAPH.cycles()
        assert cycles, "inversion not detected"
        with pytest.raises(AssertionError, match="lock-order"):
            locks.assert_no_cycles("(seeded)")
    finally:
        locks.GRAPH.reset()  # never leak the seeded cycle into chaos


def test_consistent_lock_order_stays_clean():
    mod = _fake_component()
    locks.GRAPH.reset()
    with locks.instrumented():
        a, b = mod.make_a(), mod.make_b()
        for _ in range(3):
            with a:
                with b:
                    pass
        with a:
            pass
        with b:
            pass
    assert not locks.GRAPH.cycles()
    locks.assert_no_cycles("(ordered)")


def test_reentrant_rlock_is_not_a_cycle():
    mod = _fake_component()
    src = ("import threading\n"
           "def make_r():\n    return threading.RLock()\n")
    exec(compile(src, "_seeded_locks.py", "exec"), mod.__dict__)
    locks.GRAPH.reset()
    with locks.instrumented():
        r = mod.make_r()
        with r:
            with r:  # re-entrant: no self-edge
                pass
    assert not locks.GRAPH.cycles()


def test_untracked_modules_get_raw_locks():
    with locks.instrumented():
        lk = threading.Lock()  # caller: tests/, not kubernetes_tpu
    assert not isinstance(lk, locks.TrackedLock)


def test_compile_sentinel_catches_steady_state_compiles():
    sentinel = CompileSentinel()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(7))  # compile happens OUTSIDE the guarded window
    with sentinel.expect_no_compiles("warm replay"):
        f(jnp.ones(7))
    with pytest.raises(AssertionError, match="recompilation"):
        with sentinel.expect_no_compiles("cold"):
            jax.jit(lambda x: x * 3 - 1)(jnp.ones(7))


# -- pass 4: data-race detector (lockset + happens-before) --------------------


class _Shared:
    """A plain shared object the seeded-race tests track."""

    def __init__(self):
        self.val = 0


def _run_pair(t1, t2):
    a = threading.Thread(target=t1)
    b = threading.Thread(target=t2)
    a.start()
    b.start()
    a.join()
    b.join()


def test_seeded_race_disjoint_locksets_is_flagged():
    """The Eraser path: both threads DO hold locks — just never a
    common one — so only lockset intersection (not mere lock use)
    may clear an access pair."""
    mod = _fake_component()
    try:
        with races.instrumented(reset=True):
            la, lb = mod.make_a(), mod.make_b()
            obj = races.track(_Shared(), "seeded.Shared")

            def t1():
                with la:
                    obj.val = 1

            def t2():
                with lb:
                    obj.val = 2

            _run_pair(t1, t2)
            found = [f for f in races.findings() if not f.suppressed]
            assert any(f.rule == "data-race"
                       and "seeded.Shared.val" in f.where
                       for f in found), races.findings()
            # the finding carries BOTH sample stacks (this file twice)
            msg = found[0].message
            assert msg.count("test_analysis.py") >= 2, msg
            assert "write/write" in msg
            with pytest.raises(AssertionError, match="data race"):
                races.assert_no_races("(seeded)")
    finally:
        races.reset()  # never leak the seeded race into later tests


def test_seeded_race_missing_hb_is_flagged():
    """The happens-before path: no locks anywhere, two sibling threads
    with no ordering edge between them."""
    try:
        with races.instrumented(reset=True):
            obj = races.track(_Shared(), "seeded.NoHB")

            def t1():
                obj.val = 1

            def t2():
                obj.val = 2

            _run_pair(t1, t2)
            found = [f for f in races.findings() if not f.suppressed]
            assert any("seeded.NoHB.val" in f.where for f in found), \
                races.findings()
            assert "no common lock, no happens-before" in found[0].message
    finally:
        races.reset()


def test_common_lock_keeps_the_pair_clean():
    mod = _fake_component()
    with races.instrumented(reset=True):
        lk = mod.make_a()
        obj = races.track(_Shared(), "seeded.Locked")

        def t1():
            with lk:
                obj.val = 1

        def t2():
            with lk:
                obj.val = 2

        _run_pair(t1, t2)
        races.assert_no_races("(common lock)")


def test_thread_start_join_edges_order_accesses():
    """Parent-before-start and join-before-parent are HB edges: the
    classic create/join lifecycle never reports."""
    with races.instrumented(reset=True):
        obj = races.track(_Shared(), "seeded.Lifecycle")
        obj.val = 5  # parent write BEFORE start

        def child():
            obj.val = obj.val + 1

        th = threading.Thread(target=child)
        th.start()
        th.join()
        obj.val = 7  # parent write AFTER join
        races.assert_no_races("(start/join)")


def test_queue_put_get_handoff_is_ordered():
    """The workqueue put→get hook: producer-side mutations are ordered
    before the draining consumer's accesses — the highest-traffic
    cross-thread handoff must not false-positive."""
    from kubernetes_tpu.utils.workqueue import WorkQueue

    with races.instrumented(reset=True):
        q = WorkQueue(name="hb-witness")
        obj = races.track(_Shared(), "seeded.Handoff")

        def producer():
            obj.val = 41  # unlocked write, ordered only by the queue
            q.add("item")

        def consumer():
            item = q.get()
            obj.val = obj.val + 1
            q.done(item)

        _run_pair(consumer, producer)
        assert obj.val == 42
        races.assert_no_races("(queue handoff)")


def test_fifo_pop_handoff_is_ordered():
    from kubernetes_tpu.client.cache.fifo import FIFO

    with races.instrumented(reset=True):
        fifo = FIFO(key_func=lambda o: o["name"])
        obj = races.track(_Shared(), "seeded.FifoHandoff")

        def producer():
            obj.val = 10
            fifo.add({"name": "x"})

        def consumer():
            fifo.pop()
            obj.val = obj.val + 1

        _run_pair(consumer, producer)
        assert obj.val == 11
        races.assert_no_races("(fifo handoff)")


def test_race_suppression_syntax_is_honored():
    """`# race: allow[reason]` at EITHER access site suppresses the
    pair; the finding stays counted (reported, marked), like lint."""
    try:
        with races.instrumented(reset=True):
            obj = races.track(_Shared(), "seeded.Benign")

            def t1():
                obj.val = 1  # race: allow[seeded benign fixture]

            def t2():
                obj.val = 2

            _run_pair(t1, t2)
            found = races.findings()
            assert found and all(f.suppressed for f in found), found
            assert "allow[seeded benign fixture]" in found[0].message
            races.assert_no_races("(suppressed only)")  # does not raise
    finally:
        races.reset()


def test_shared_decorator_registers_instances():
    """@shared instances self-register at construction: the decorator
    path must catch the same race track() does (and stay a no-op while
    disarmed)."""
    from kubernetes_tpu.analysis.races import shared

    @shared("seeded.Decorated")
    class _Deco:
        def __init__(self):
            self.val = 0

    cold = _Deco()  # constructed disarmed: stays raw
    assert type(cold).__name__ == "_Deco"
    try:
        with races.instrumented(reset=True):
            obj = _Deco()

            def t1():
                obj.val = 1

            def t2():
                obj.val = 2

            _run_pair(t1, t2)
            found = [f for f in races.findings() if not f.suppressed]
            assert any("seeded.Decorated.val" in f.where
                       for f in found), races.findings()
    finally:
        races.reset()


def test_track_registration_is_weakref_safe():
    """Tracking must never extend an object's lifetime (the cacher feed
    holds its cacher only weakly; a pinning registry would leak every
    discarded apiserver's caches)."""
    import gc
    import weakref

    with races.instrumented(reset=True):
        obj = races.track(_Shared(), "seeded.Collectable")
        obj.val = 3
        ref = weakref.ref(obj)
        del obj
        gc.collect()
        assert ref() is None, "track() pinned the object alive"


def test_disarmed_track_is_a_no_op(monkeypatch):
    # force-disarm even under the suite-wide sanitizer
    monkeypatch.setattr(races, "_armed", False)
    obj = _Shared()
    assert races.track(obj) is obj
    assert type(obj) is _Shared  # no retyping while disarmed
    races.note_put(obj)  # all hooks are flag-check no-ops
    races.note_get(obj)


# -- true-positive sweep regressions ------------------------------------------
#
# Each race the armed sweep confirmed got a fix; these pin the fixes so
# a refactor can't silently reintroduce them.


def test_delaying_queue_waiter_shutdown_is_race_clean():
    """The waiter used to read the base queue's _shutting_down (guarded
    by self._cond) under self._heap_cond — two different guards on one
    field. The fix gives the waiter its own _heap_cond-guarded flag;
    the armed detector must stay silent across a threaded shutdown."""
    from kubernetes_tpu.utils.workqueue import DelayingQueue

    with races.instrumented(reset=True):
        q = DelayingQueue(name="race-regress")
        q.add_after("a", 0.01)

        t = threading.Thread(target=q.shut_down)
        t.start()
        t.join()
        q._waiter.join(timeout=5)
        assert not q._waiter.is_alive(), "waiter missed the stop flag"
        races.assert_no_races("(delaying-queue shutdown)")


def test_replicated_store_stop_flag_is_guarded(tmp_path):
    """close() used to flip _stopped lock-free while repl-accept polled
    it lock-free; both sides now hold _repl_lock."""
    import time

    from kubernetes_tpu.storage.replicated import ReplicatedStore

    with races.instrumented(reset=True):
        st = ReplicatedStore(str(tmp_path))
        time.sleep(0.2)  # let repl-accept reach its guarded poll
        t = threading.Thread(target=st.close)
        t.start()
        t.join()
        races.assert_no_races("(replicated close)")


def test_leaderelection_observation_cache_fix_is_pinned():
    """The armed lint sweep found try_acquire_or_renew writing
    observed_record/observed_time bare while stop()'s release path
    reads them under _write_lock. The file must lint clean now, AND
    un-fixing it must still be caught — the gate can't go blind."""
    import kubernetes_tpu.client.leaderelection as le

    with open(le.__file__, "r", encoding="utf-8") as f:
        src = f.read()
    rel = "kubernetes_tpu/client/leaderelection.py"
    conc = [f for f in lint.lint_sources({rel: src})
            if f.rule in ("guarded-by", "unguarded-shared-write")
            and not f.suppressed]
    assert not conc, conc
    reverted = src.replace(
        "                with self._write_lock:\n"
        "                    self.observed_record = existing\n"
        "                    self.observed_time = now\n",
        "                self.observed_record = existing\n"
        "                self.observed_time = now\n",
    )
    assert reverted != src, "fix site moved; update this regression"
    found = lint.lint_sources({rel: reverted})
    assert any(f.rule == "unguarded-shared-write" and not f.suppressed
               for f in found), found


def test_kubelet_pod_ips_fix_is_pinned():
    """_kill_pod popped _pod_ips outside self._lock while per-pod
    workers mutate it under the lock; same clean-now / caught-if-
    reverted pin as the leaderelection fix."""
    import kubernetes_tpu.kubelet.kubelet as kl

    with open(kl.__file__, "r", encoding="utf-8") as f:
        src = f.read()
    rel = "kubernetes_tpu/kubelet/kubelet.py"
    conc = [f for f in lint.lint_sources({rel: src})
            if f.rule in ("guarded-by", "unguarded-shared-write")
            and not f.suppressed]
    assert not conc, conc
    fixed_block = (
        "        with self._lock:\n"
        "            # _pod_ips is mutated under the lock by every per-pod\n"
        "            # worker's _pod_ip(); the delete must hold it too\n"
        "            self._pod_ips.pop(pod.metadata.uid, None)\n"
    )
    assert fixed_block in src, "fix site moved; update this regression"
    reverted = src.replace(
        fixed_block,
        "        self._pod_ips.pop(pod.metadata.uid, None)\n"
        "        with self._lock:\n",
    )
    found = lint.lint_sources({rel: reverted})
    assert any(f.rule == "unguarded-shared-write" and not f.suppressed
               for f in found), found


# -- guarded-by / thread-escape lint ------------------------------------------


_GUARDED_FIXTURE = '''\
import threading


class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._data = {{}}  # guarded-by: self._lock

    def good(self, k, v):
        with self._lock:
            self._data[k] = v

    def also_good(self, k, v):
        with self._cond:  # Condition aliases its lock
            self._data[k] = v

    def _helper(self, k):  # guarded-by: self._lock
        self._data.pop(k, None)

    def _drop_locked(self, k):
        self._data.pop(k, None)

    def bad(self, k, v):
        {bad_write}
'''


def test_seeded_guarded_by_violation_is_flagged():
    src = _GUARDED_FIXTURE.format(bad_write="self._data[k] = v")
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded_gb.py": src})
    gb = [f for f in found if f.rule == "guarded-by"]
    assert len(gb) == 1 and not gb[0].suppressed, found
    assert "Box._data" in gb[0].message
    assert "self._lock" in gb[0].message
    # only the bare write fires: with-lock, with-Condition-alias,
    # def-line held-on-entry annotation, and *_locked naming all pass


def test_guarded_by_clean_class_is_clean():
    src = _GUARDED_FIXTURE.format(
        bad_write="with self._lock:\n            self._data[k] = v")
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded_gb.py": src})
    assert not [f for f in found if f.rule == "guarded-by"], found


def test_guarded_by_suppression_is_honored():
    src = _GUARDED_FIXTURE.format(
        bad_write="self._data[k] = v  # lint: allow[guarded-by]")
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded_gb.py": src})
    gb = [f for f in found if f.rule == "guarded-by"]
    assert len(gb) == 1 and gb[0].suppressed, found


def test_unguarded_shared_write_in_escaping_class_is_flagged():
    src = '''\
import threading


class Esc:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def start(self):
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        with self._lock:
            self._items.append(1)

    def nudge(self):
        self._items.append(2)
'''
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded_esc.py": src})
    uw = [f for f in found if f.rule == "unguarded-shared-write"]
    assert len(uw) == 1, found
    assert "Esc._items" in uw[0].message
    # the same class WITHOUT the thread escape is not a finding (the
    # inconsistent guarding may be phase discipline; only escape makes
    # it a shared-state signal)
    solo = src.replace(
        "        threading.Thread(target=self._run, daemon=True)"
        ".start()\n", "        pass\n")
    found2 = lint.lint_sources(
        {"kubernetes_tpu/client/_seeded_esc.py": solo})
    assert not [f for f in found2
                if f.rule == "unguarded-shared-write"], found2


# -- sharding-drift + scatter-contract audits ---------------------------------


def _mesh_and_shardings():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = np.array(jax.devices())
    if devs.size < 2:
        pytest.skip("needs a multi-device host platform")
    mesh = Mesh(devs, ("nodes",))
    return (mesh,
            NamedSharding(mesh, PartitionSpec("nodes")),
            NamedSharding(mesh, PartitionSpec()))


def test_seeded_sharding_drift_is_flagged():
    from jax.sharding import PartitionSpec as P

    mesh, sharded, repl = _mesh_and_shardings()
    n = len(jax.devices()) * 4
    fn = jax.jit(lambda a, b: a * 2 + b.sum(),
                 in_shardings=(sharded, repl), out_shardings=sharded)
    args = (jnp.zeros(n), jnp.zeros(3))
    jaxpr = jax.make_jaxpr(fn)(*args)
    spec = ProgramSpec(
        name="seeded_drift", fn=fn, args=args, carry_out_leaves=0,
        arg_shardings=(P("nodes"), P()), out_shardings_decl=P("nodes"),
    )
    # the agreeing declaration is clean...
    assert not jaxpr_audit._sharding_findings(spec, jaxpr)
    # ...a drifted input PartitionSpec is a finding...
    bad_in = dataclasses.replace(spec, arg_shardings=(P(), P()))
    found = jaxpr_audit._sharding_findings(bad_in, jaxpr)
    assert found and all(f.rule == "sharding-drift" for f in found)
    assert "PartitionSpec" in found[0].message
    # ...and so is a drifted output
    bad_out = dataclasses.replace(spec, out_shardings_decl=P())
    assert jaxpr_audit._sharding_findings(bad_out, jaxpr)
    # trailing-None canonicalization: P('nodes') == P('nodes', None)
    two_d = jax.jit(lambda a: a * 2, in_shardings=(sharded,),
                    out_shardings=sharded)
    args2 = (jnp.zeros((n, 3)),)
    spec2 = ProgramSpec(
        name="seeded_trailing", fn=two_d, args=args2, carry_out_leaves=0,
        arg_shardings=(P("nodes", None),),
        out_shardings_decl=P("nodes", None),
    )
    assert not jaxpr_audit._sharding_findings(
        spec2, jax.make_jaxpr(two_d)(*args2))


def test_mesh_programs_declare_and_pass_the_sharding_audit():
    """The registry's mesh programs all carry declarations built from
    resident.carry_specs()/static_specs() and the audit passes — the
    acceptance-criteria clean run, scoped to the drift pass."""
    specs = {s.name: s for s in registered_programs()}
    if "mesh_apply" not in specs:
        pytest.skip("no mesh on this host")
    for name in ("mesh_scan", "mesh_probe", "mesh_group_probe",
                 "mesh_apply", "mesh_apply_group", "resident_scatter"):
        s = specs[name]
        assert s.arg_shardings is not None, f"{name} undeclared"
        jaxpr = jax.make_jaxpr(s.fn)(*s.args)
        assert not jaxpr_audit._sharding_findings(s, jaxpr), name
    # and a seeded drift against the REAL mesh_apply program fires
    ma = specs["mesh_apply"]
    from jax.sharding import PartitionSpec as P

    drifted_carry = (P(),) + ma.arg_shardings[1][1:]
    bad = dataclasses.replace(
        ma, arg_shardings=(ma.arg_shardings[0], drifted_carry)
        + ma.arg_shardings[2:])
    found = jaxpr_audit._sharding_findings(
        bad, jax.make_jaxpr(ma.fn)(*ma.args))
    assert found and found[0].rule == "sharding-drift", found


def test_seeded_scatter_contract_violation_is_flagged():
    _mesh_and_shardings()  # skip on 1-device hosts for parity

    def overwrite(tbl, idx, vals):
        return tbl.at[idx].set(vals)  # plain scatter, no unique claim

    def accumulate(tbl, idx, vals):
        return tbl.at[idx].add(vals)

    args = (jnp.zeros(16), jnp.arange(4), jnp.ones(4))
    ow = jax.make_jaxpr(overwrite)(*args)
    acc = jax.make_jaxpr(accumulate)(*args)

    def spec_for(fn, jx, allowed):
        return ProgramSpec(name="seeded_scatter", fn=fn, args=args,
                           carry_out_leaves=0,
                           scatter_allowed=allowed), jx

    # a commutative scatter-add matching the declaration: clean
    s, jx = spec_for(accumulate, acc, (("scatter-add", (0,)),))
    assert not jaxpr_audit._scatter_findings(s, jx)
    # an UNDECLARED form is a finding even when commutative
    s, jx = spec_for(accumulate, acc, (("scatter-add", (1,)),))
    found = jaxpr_audit._scatter_findings(s, jx)
    assert found and found[0].rule == "scatter-contract", found
    # a declared OVERWRITE scatter without unique_indices is order-
    # dependent under collisions: finding
    s, jx = spec_for(overwrite, ow, (("scatter", (0,)),))
    found = jaxpr_audit._scatter_findings(s, jx)
    assert found and "unique_indices" in found[0].message, found
    # the unique-indices spelling of the same overwrite passes
    def overwrite_unique(tbl, idx, vals):
        return tbl.at[idx].set(vals, unique_indices=True)

    ju = jax.make_jaxpr(overwrite_unique)(*args)
    s, jx = spec_for(overwrite_unique, ju, (("scatter", (0,)),))
    assert not jaxpr_audit._scatter_findings(s, jx)


# -- the CLI gate -------------------------------------------------------------


def test_cli_lint_gate_exits_zero():
    from kubernetes_tpu.analysis.__main__ import main

    assert main(["--lint-only"]) == 0


def test_cli_json_mode_emits_machine_readable_rows(capsys, tmp_path):
    """--json: one JSON object per finding, uniform across lint, jaxpr
    audit, and merged race-witness artifacts (the CI upload format)."""
    from kubernetes_tpu.analysis.__main__ import main

    # seed a race artifact the CLI must merge and fail on
    report = tmp_path / "races.jsonl"
    try:
        with races.instrumented(reset=True):
            obj = races.track(_Shared(), "seeded.CLI")

            def t1():
                obj.val = 1

            def t2():
                obj.val = 2

            _run_pair(t1, t2)
            assert races.dump_jsonl(str(report)) >= 1
    finally:
        races.reset()

    rc = main(["--lint-only", "--json", "--race-report", str(report)])
    out = capsys.readouterr().out
    rows = [json.loads(line) for line in out.splitlines() if line]
    assert rc == 1  # the merged unsuppressed race fails the gate
    assert all({"pass", "rule", "where", "message", "suppressed"}
               <= set(r) for r in rows)
    assert any(r["pass"] == "races" and r["rule"] == "data-race"
               for r in rows)
    # an empty artifact gates clean
    empty = tmp_path / "none.jsonl"
    empty.write_text("")
    assert main(["--lint-only", "--json", "--race-report",
                 str(empty)]) == 0
    capsys.readouterr()


def test_bench_refuses_armed_sanitizers(monkeypatch):
    """Perf runs must hard-fail with a sanitizer armed — an
    instrumented headline number is worse than no number."""
    import importlib.util as u
    import os

    monkeypatch.setenv("KUBERNETES_TPU_RACE_SANITIZER", "1")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    spec = u.spec_from_file_location("_bench_under_test", path)
    mod = u.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # SystemExit, not AssertionError: the guard must survive python -O
    with pytest.raises(SystemExit, match="RACE_SANITIZER"):
        mod.main()  # the guard is the first statement: no heavy work


def test_cli_malformed_race_report_fails_the_gate(tmp_path):
    from kubernetes_tpu.analysis.__main__ import main

    bad = tmp_path / "corrupt.jsonl"
    bad.write_text("this is not json\n")
    assert main(["--lint-only", "--race-report", str(bad)]) == 1


def test_findings_report_shape():
    rep = render_report([
        Finding("lint", "host-sync", "a.py:1", "x", suppressed=False),
        Finding("lint", "host-sync", "b.py:2", "y", suppressed=True),
    ], "t:")
    assert "1 finding(s), 1 suppressed" in rep
    assert "a.py:1" in rep
    # suppressed rows stay visible, marked — allowance drift is
    # auditable from the report itself
    assert "[suppressed lint/host-sync] b.py:2" in rep


def test_nondaemon_thread_rule_ignores_path_and_str_joins():
    """os.path.join / ', '.join must NOT satisfy the thread-join
    heuristic — only a plausible Thread.join() does."""
    base = '''\
import os
import threading


def f():
    p = os.path.join("a", "b")
    s = ", ".join(["x", "y"])
    threading.Thread(target=print).start()
    return p, s
'''
    found = lint.lint_sources({"kubernetes_tpu/client/_seeded5.py": base})
    assert any(f.rule == "nondaemon-thread" for f in found), found
    joined = base.replace(
        "    return p, s",
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    t.join()\n"
        "    return p, s",
    )
    found2 = lint.lint_sources(
        {"kubernetes_tpu/client/_seeded5.py": joined})
    assert not any(f.rule == "nondaemon-thread" for f in found2), found2
