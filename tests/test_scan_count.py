"""The scan's trip count is an operand (models/batch.scan_backlog).

`jit_batch_scan` was a `lax.scan` over its whole pod bucket: a wave of
1,030 pods ran 2,048 steps, each padded one the whole step on a pod
that fits nowhere. Now the loop ends at the wave's real count. Held
here, on the 48-node cuts of the two term deployments and on a zoned
and an unzoned spread shape: (a) with `count = n` over a padded bucket
the picks of the first n pods and the final carry are the whole-bucket
scan's bit for bit, and the rest of `chosen` is -1; (b) with `count = P`
`BatchScheduler.schedule` is that scan; (c) every count shares the
bucket's one program, as long as it goes in as `np.int32`; (d) the wave
driver counts the steps the loop ran (`scan_steps`, by the loop's own
counter) and the steps its buckets hold (`scan_bucket_steps`), here and
on /debug/traces, and so does the optimizing profile's remainder.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.models.batch import (
    BatchScheduler,
    SchedulerConfig,
    _scan_fn,
    interpod_views,
)
from kubernetes_tpu.models.wave import PATHS, SCAN_COUNTERS, gather_batch
from kubernetes_tpu.ops import interpod as IP
from kubernetes_tpu.snapshot.pad import pad_batch
from kubernetes_tpu.trace import profile

BUCKET = 64  # the smallest pod bucket (WaveScheduler.pod_floor)
SHAPES = ("podaffinity", "antiaffinity", "spread-zoned", "spread-unzoned")
COUNTS = (1, BUCKET // 2 + 1, BUCKET - 1, BUCKET)


def _whole_bucket_scan(config, num_zones, num_values, static, carry, pods):
    """`scan_backlog` as it was before its trip count was an operand: a
    `lax.scan` over the padded axis -> (final carry, chosen[P])."""
    views = interpod_views(config, static, carry)
    dom_lt = None if views is None else IP.lt_domains(
        static["ip_u_topo"], static["ip_topo_dom"], static["ip_lt_u"])
    step = functools.partial(
        _scan_fn, config, num_zones, num_values, static, dom_lt)
    (final, _), chosen = jax.lax.scan(step, (carry, views), pods)
    return final, chosen


@functools.lru_cache(maxsize=None)
def _shape(name):
    """-> (snap, batch of BUCKET + 1 pods or more, counter, sched, static,
    carry, the whole-bucket scan and the counted one, both jitted)."""
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder
    from tests.test_interpod_views import _deployment
    from tests.test_wave_paths import _controllers, _dealt_in_turn, _nodes

    if name.startswith("spread"):
        state = ClusterState.build(
            _nodes(30, "abc" if name == "spread-zoned" else ""),
            controllers=_controllers(12))
        waiting, counter = _dealt_in_turn(12, 6), 7
    else:
        make_state, waiting, counter = _deployment(
            name, 2 ** 31 + 47, backlog=BUCKET + 6)
        state = make_state()
    assert len(waiting) > BUCKET
    snap, batch = SnapshotEncoder(state, waiting).encode()
    sched = BatchScheduler(SchedulerConfig())
    static = {f: jnp.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    num_zones = max(int(snap.zone_id.max()) + 1, 1)
    whole = jax.jit(functools.partial(
        _whole_bucket_scan, sched.config, num_zones,
        int(snap.svc_num_values)))
    counted = sched._compiled(num_zones, int(snap.svc_num_values))
    return (snap, batch, counter, sched, static,
            sched.initial_carry(snap, counter), whole, counted)


def _padded(batch, n):
    """The first n pods, padded to the bucket as `flush` pads a wave."""
    seg = pad_batch(gather_batch(batch, np.arange(n)), BUCKET)
    return seg, {f: jnp.asarray(getattr(seg, f))
                 for f in BatchScheduler.POD_FIELDS}


def _same_leaves(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) == 17
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, i
        assert np.array_equal(np.asarray(g), np.asarray(w)), i


@pytest.mark.parametrize("n", COUNTS)
@pytest.mark.parametrize("name", SHAPES)
def test_the_loop_ends_at_the_count_with_the_whole_buckets_picks_and_carry(
        name, n):
    snap, batch, _counter, _sched, static, carry, whole, counted = \
        _shape(name)
    _seg, pods = _padded(batch, n)
    want_final, want = whole(static, carry, pods)
    final, chosen, steps = counted(static, carry, pods, np.int32(n))
    want, chosen = np.asarray(want), np.asarray(chosen)
    assert int(steps) == n
    assert chosen.dtype == want.dtype == np.int32
    assert np.array_equal(chosen[:n], want[:n])
    # a padded step answered -1 when the loop still ran it
    assert (chosen[n:] == -1).all() and (want[n:] == -1).all()
    _same_leaves(final, want_final)
    if n == BUCKET:
        # the shape decides something: pods are placed, the carry moves
        assert (chosen >= 0).sum() > BUCKET // 2
        assert int(final[BatchScheduler.LAST_IDX]) \
            == int(carry[BatchScheduler.LAST_IDX]) + (chosen >= 0).sum()
        if name in ("podaffinity", "antiaffinity"):
            assert snap.ip_lt_u.shape[0] >= 5
            assert any(not np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(final[4:9], carry[4:9]))


@pytest.mark.parametrize("name", SHAPES)
def test_schedule_runs_every_pod_as_the_whole_scan_did(name):
    """(b) the library path: `count = P`, padding and all."""
    snap, batch, counter, sched, static, carry, whole, _counted = \
        _shape(name)
    seg, pods = _padded(batch, BUCKET - 5)
    want_final, want = whole(static, carry, pods)
    chosen, final = sched.schedule(snap, seg, last_node_index=counter)
    assert np.array_equal(chosen, np.asarray(want))
    assert (chosen[BUCKET - 5:] == -1).all()
    _same_leaves(final, want_final)


@pytest.mark.parametrize("name", SHAPES)
def test_every_count_of_a_bucket_is_one_program(name):
    """(c) the count is traced: the calls above and these three, and
    `schedule`'s, all ran the one entry of the jitted function's
    cache."""
    _snap, batch, _counter, _sched, static, carry, _whole, counted = \
        _shape(name)
    _seg, pods = _padded(batch, BUCKET)
    for n in (2, 17, 40):
        _final, chosen, steps = counted(static, carry, pods, np.int32(n))
        assert int(steps) == n and (np.asarray(chosen)[n:] == -1).all()
    assert counted._cache_size() == 1


def test_a_count_of_another_dtype_is_another_program():
    """Why every caller passes `np.int32`: a Python int is a weakly
    typed int64 under x64, and the warmed program would not be the
    live one."""
    profile.install_compile_listener()
    _snap, batch, _counter, sched, static, carry, _whole, _counted = \
        _shape("spread-unzoned")
    fresh = BatchScheduler(sched.config)._compiled(1, 0)
    _seg, pods = _padded(batch, 9)
    built = profile.compile_count()
    a = fresh(static, carry, pods, np.int32(9))
    assert profile.compile_count() == built + 1
    fresh(static, carry, pods, np.int32(4))
    assert profile.compile_count() == built + 1
    b = fresh(static, carry, pods, 9)
    assert profile.compile_count() == built + 2
    assert all("batch_scan" in c["program"]
               for c in profile.recent_compiles()[-2:])
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_a_count_over_the_bucket_runs_the_bucket():
    _snap, batch, _counter, _sched, static, carry, whole, counted = \
        _shape("spread-zoned")
    _seg, pods = _padded(batch, BUCKET)
    _final, chosen, steps = counted(static, carry, pods,
                                    np.int32(BUCKET + 9))
    assert int(steps) == BUCKET
    assert np.array_equal(np.asarray(chosen),
                          np.asarray(whole(static, carry, pods)[1]))


# -- (d) the counters, through the wave driver -------------------------------


#: a case of tests/test_wave_paths.CASES: the buckets its two waves (the
#: backlog, then its last seven pods) take their scans in
WAVE_BUCKETS = {"dealt-in-turn-zoned": [128, 64],
                "dealt-in-turn-unzoned": [128, 64],
                "short-rows-zoned": [64, 64],
                "rows-then-turns": [64, 64]}


@pytest.mark.parametrize("case", sorted(WAVE_BUCKETS))
def test_a_wave_counts_the_steps_it_ran_and_the_steps_its_buckets_hold(case):
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from kubernetes_tpu.trace.httpd import render_traces
    from tests.test_wave_paths import CASES, _controllers, _nodes

    nodes, zones, controllers, backlog, _only = CASES[case]
    buckets = WAVE_BUCKETS[case]
    state = ClusterState.build(_nodes(nodes, zones),
                               controllers=_controllers(controllers))
    algo = TPUScheduleAlgorithm()
    stats = algo._wave.stats
    assert all(stats[k] == 0 for k in SCAN_COUNTERS)
    shown_before = render_traces({"limit": "1"})["wave"]
    held = 0
    for wave, bucket in zip((backlog, backlog[-7:]), buckets):
        algo.schedule_backlog(wave, state)
        held += bucket
        scanned = stats["pods_by_path"]["scan"]
        assert 0 < scanned <= sum(stats["pods_by_path"][p] for p in PATHS)
        # the loop's own counter: a step a pod, none for the padding
        assert stats["scan_steps"] == scanned
        assert stats["scan_bucket_steps"] == held > scanned
    assert stats["dispatches_by_kind"]["scan"] == 2
    shown = render_traces({"limit": "1"})["wave"]
    assert shown == profile.wave_totals()
    for k in SCAN_COUNTERS:
        assert shown[k] - shown_before[k] == stats[k]


@pytest.mark.parametrize("seed", range(2))
def test_the_optimizing_profiles_remainder_counts_its_scan(seed):
    import random

    from kubernetes_tpu.scheduler.tpu_algorithm import TPUScheduleAlgorithm
    from tests.test_conformance import random_scenario

    rng = random.Random(2000 + seed)
    state, pending = random_scenario(
        rng, n_nodes=8, n_existing=6, n_pending=20,
        interpod_p=0.3, volumes_p=0.3)
    algo = TPUScheduleAlgorithm(profile="optimizing")
    algo.schedule_backlog(pending, state)
    stats = algo._wave.stats
    assert stats["dispatches_by_kind"]["scan"] == 1
    assert 0 < stats["scan_steps"] <= len(pending)
    assert stats["scan_bucket_steps"] == BUCKET
