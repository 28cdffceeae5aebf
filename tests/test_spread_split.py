"""ops/priorities: selector spread as two functions.

PR 31 split `selector_spread` into `spread_counts` (the contraction
over class_count: what models/batch.py, models/probe.py and
parallel/mesh.py call) and `spread_score` (everything from the counts
on), and `spread_score` sums the zones by a masked reduction
(`zone_sums`, the mesh scan's too) where the one function
scatter-added by zone_id. `_one_function` below is that function as it
stood, kept as the reference: on every case of the ported upstream
tables, under several fit masks, the two give the same int64 scores,
the NaN branch (zoned nodes, no matching pod in any zone: 0/0 in
float32, Go's int(NaN) == minInt64) included.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.models.batch import SELECTOR_SPREAD, SchedulerConfig
from kubernetes_tpu.ops import priorities as R
from kubernetes_tpu.snapshot.encode import SnapshotEncoder

from tests.test_corpus import load, priority_state

MIN_INT64 = -(2 ** 63)


def _one_function(pod_has_selectors, pod_spread_match, class_count, zone_id,
                  num_zones, fit_mask):
    """ops/priorities.selector_spread at commit c4109ae, verbatim."""
    counts = (
        class_count.astype(jnp.int32) @ pod_spread_match.astype(jnp.int32)
    ).astype(jnp.int64)
    counts = jnp.where(fit_mask, counts, 0)
    max_count = counts.max(where=fit_mask, initial=0)
    zcounts = jnp.zeros((num_zones,), jnp.int64).at[zone_id].add(counts)
    zone_seen = jnp.zeros((num_zones,), jnp.int32).at[zone_id].add(
        (fit_mask & (zone_id > 0)).astype(jnp.int32)
    )
    have_zones = jnp.any(zone_seen > 0)
    max_zone = jnp.where(jnp.arange(num_zones) > 0, zcounts, 0).max(initial=0)
    f = jnp.full(counts.shape, jnp.float32(R.MAX_PRIORITY))
    f = jnp.where(
        max_count > 0,
        jnp.float32(R.MAX_PRIORITY)
        * ((max_count - counts).astype(jnp.float32)
           / max_count.astype(jnp.float32)),
        f,
    )
    node_zcount = zcounts[zone_id]
    zone_score = jnp.float32(R.MAX_PRIORITY) * (
        (max_zone - node_zcount).astype(jnp.float32)
        / max_zone.astype(jnp.float32)
    )
    blended = (f * jnp.float32(1.0 / 3.0)
               + jnp.float32(2.0 / 3.0) * zone_score)
    f = jnp.where(have_zones & (zone_id > 0), blended, f)
    f = jnp.where(pod_has_selectors, f, jnp.float32(R.MAX_PRIORITY))
    return jnp.where(jnp.isnan(f), jnp.int64(MIN_INT64), f.astype(jnp.int64))


def _split(has_selectors, spread_match, class_count, zone_id, num_zones, fit):
    counts = R.spread_counts(class_count, spread_match)
    assert counts.dtype == jnp.int64
    return R.spread_score(has_selectors, counts, zone_id, num_zones, fit)


def _encoded(state, pod):
    config = SchedulerConfig(predicates=(),
                             priorities=((SELECTOR_SPREAD, 1),))
    snap, batch = SnapshotEncoder(state, [pod], config=config).encode()
    num_zones = max(int(snap.zone_id.max()) + 1 if snap.zone_id.size else 1, 1)
    return (jnp.asarray(batch.has_selectors[0]),
            jnp.asarray(batch.spread_match[0]),
            jnp.asarray(snap.class_count), jnp.asarray(snap.zone_id),
            num_zones), snap


def _fit_masks(n):
    """All nodes; every other node; one node; none."""
    yield np.ones(n, bool)
    yield np.arange(n) % 2 == 0
    yield np.arange(n) == n - 1
    yield np.zeros(n, bool)


def _cases():
    for fixture in ("selector_spread", "zone_selector_spread"):
        for i, case in enumerate(load(fixture)["cases"]):
            yield pytest.param(fixture, i, id=f"{fixture}-{i}")


@pytest.mark.parametrize("fixture,index", list(_cases()))
def test_split_spread_equals_the_one_function_on_the_tables(fixture, index):
    case = load(fixture)["cases"][index]
    state, pod = priority_state(case)
    args, snap = _encoded(state, pod)
    for fit in _fit_masks(snap.num_nodes):
        fit = jnp.asarray(fit)
        want = np.asarray(_one_function(*args, fit))
        got = np.asarray(_split(*args, fit))
        assert got.dtype == np.int64
        assert np.array_equal(got, want), (case["test"], got, want)
    # and with every node fit, what the upstream table expects
    got = _split(*args, jnp.ones(snap.num_nodes, bool))
    assert dict(zip(snap.node_names, np.asarray(got).tolist())) \
        == case["expected"]


@pytest.mark.parametrize("zones", [[1, 2, 1, 2], [0, 1, 0, 2], [3, 3, 3, 3]],
                         ids=["two-zones", "some-unzoned", "one-zone"])
def test_nan_branch_is_min_int64_in_both(zones):
    """Zoned nodes fit and no zone holds a matching pod: max_zone 0,
    0/0, minInt64 on every zoned node, 10 on an unzoned one."""
    zone_id = jnp.asarray(zones, jnp.int32)
    n = len(zones)
    class_count = jnp.zeros((n, 3), jnp.int64)
    spread_match = jnp.asarray([1, 0, 1], jnp.int64)
    fit = jnp.ones(n, bool)
    args = (jnp.asarray(True), spread_match, class_count, zone_id, 4)
    want = np.asarray(_one_function(*args, fit))
    got = np.asarray(_split(*args, fit))
    assert np.array_equal(got, want)
    assert got.tolist() == [MIN_INT64 if z else 10 for z in zones]
