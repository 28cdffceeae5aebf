"""The served path's programs, compiled for a DESCRIBED TPU v5e.

The TPU's compiler is installed in the CPU sandbox and compiles for a
chip that is described and not attached (the on-chip-measurement
guide, section 2, rehearsal 3). These tests keep its answers: every
program the single-chip drivers dispatch compiles at the widths the
deployments really run — the 1,024-node bucket (scheduler_perf
density, 1,000 nodes) and the 8,192-node bucket (BASELINE.json config
5 pads 5,000 nodes up) — with shapes taken from the drivers' own
bucketing through the analysis registry. A compile that passes is not
a chip run; what it rules out is a program the chip would refuse.

Only one process may load the TPU library, so the topology is
described inside a module-scoped fixture of THIS file (never at
import, never in conftest), the compiles run in the test's own
process, and no other test file may describe a topology.
"""

import os

import numpy as np
import pytest

#: (real node count, longest template run): density test B
#: (scheduler_test.go:31-33) and BASELINE.json config 5
WIDTHS = {1024: (1000, 30_000), 8192: (5000, 50_000)}

SERVED = ("scan", "probe", "probe_fused_same", "group_probe_G8",
          "apply", "apply_group", "zreplay", "zreplay_group")

#: the 8,192-bucket programs whose compile takes 10-20 s each: five of
#: them would push the file past its tier-1 budget (<= 12 compiles)
HEAVY = {"scan", "probe", "probe_fused_same", "zreplay", "zreplay_group"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described device, with the persistent compile cache off for
    the module: a compile for a described chip is written to the cache
    but cannot be read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def registry():
    """name -> ProgramSpec per node bucket, built once per width."""
    from kubernetes_tpu.analysis.programs import build_programs

    built = {}

    def get(bucket):
        if bucket not in built:
            nodes, run = WIDTHS[bucket]
            specs = {s.name: s for s in build_programs(
                include_mesh=False, num_nodes=nodes, run_length=run)}
            # the registry padded the node axis as the drivers do
            assert specs["apply"].args[3].shape == (bucket,)
            built[bucket] = specs
        return built[bucket]

    return get


def _shapes(args, sharding):
    import jax
    import jax.numpy as jnp

    def leaf(a):
        dtype = a.dtype if hasattr(a, "dtype") else jnp.asarray(a).dtype
        return jax.ShapeDtypeStruct(np.shape(a), dtype, sharding=sharding)

    return jax.tree.map(leaf, args)


def _params():
    for bucket in WIDTHS:
        for name in SERVED:
            slow = bucket == 8192 and name in HEAVY
            yield pytest.param(
                bucket, name, id=f"{name}-{bucket}",
                marks=[pytest.mark.slow] if slow else [])


@pytest.fixture(scope="module")
def served(registry, one_chip):
    """(bucket, name) -> the program compiled for the described chip,
    once a module: a test that reads the compiled text shares the
    compile with the one that made it."""
    import jax

    made = {}

    def get(bucket, name):
        if (bucket, name) not in made:
            spec = registry(bucket)[name]
            fn = spec.fn if hasattr(spec.fn, "lower") else jax.jit(spec.fn)
            made[bucket, name] = fn.lower(
                *_shapes(spec.args, one_chip)).compile()
        return made[bucket, name]

    return get


@pytest.mark.parametrize("bucket,name", list(_params()))
def test_served_program_compiles_for_v5e(bucket, name, served):
    mem = served(bucket, name).memory_analysis()
    # one program's arguments + temporaries, against a 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30


def _loop_bodies(text):
    """The compiled text's `while` loops: [(the loop's name, its body's
    instruction lines)], and every computation's lines by name."""
    import re

    computations, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head and not line.startswith(" "):
            lines = computations.setdefault(head.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line)
    loops = [(m.group(1), computations[m.group(2)])
             for body in computations.values() for line in body
             for m in [re.search(
                 r"%?([\w.\-]+) = .* while\(.*body=%?([\w.\-]+)", line)]
             if m]
    return loops, computations


def test_a_pick_of_the_grouped_replay_costs_what_it_changes(served):
    """What only the chip's compiler shows, read from the program the
    test above compiled (described-v5e text, no chip run): the pick step
    of `jit_zreplay_group`, its innermost loop, holds the spread blend,
    the maximum, the tie-break and the bump: 18 fusions. Before the
    score without its spread term was carried from step to step it held
    44, 21 of them LeastRequested's two emulated-int64 divisions over
    every node; and `L % ties` in int64 was 1,800 scalar instructions,
    which the body's length pins. No loop of the program scatters: a
    run's first zone sums and its commit counts back in node order were
    two emulated-int64 scatters a run slot, 0.53 ms on the chip."""
    import re

    loops, computations = _loop_bodies(served(1024, "zreplay_group")
                                       .as_text())
    # run slots > epochs > pick steps
    assert len(loops) == 3, [name for name, _ in loops]
    innermost = [body for _, body in loops
                 if not any(" while(" in line for line in body)]
    assert len(innermost) == 1
    step, = innermost
    fusions = [line for line in step if " fusion(" in line]
    assert len(fusions) <= 24, len(fusions)
    assert len(step) < 800, len(step)
    for name, body in loops:
        for line in body:
            called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
            if called:
                assert not any(
                    " scatter(" in op
                    for op in computations[called.group(1)]), (name, line)


@pytest.mark.slow
def test_the_scans_step_gathers_no_nodes_domain_under_terms(one_chip):
    """`jit_batch_scan` at podaffinity-2k's own widths (2,000 nodes in
    2,048 slots, 2 combos, 10 logical terms, 2,000 domains), compiled
    for the described v5e (some 20 s): the step carries the inter-pod
    tables' views and adds each pick to them, so its body holds no
    gather wider than a term row but `select_host`'s `pred[2048]`.
    Before, seven gathers of 20,480 single values (each some 250 us on
    the chip: 1.7 of a step's 1.93 ms) and three of `[10, 2048]` rows
    stood in it. tests/test_interpod_views.py holds the same on the
    step's jaxpr, in tier 1."""
    import re

    import jax

    from benchmark import deploy
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder
    from kubernetes_tpu.snapshot.pad import next_pow2, pad_batch

    cfg = deploy.load_config("podaffinity-2k")
    scheme = rest.default_scheme
    bound = []
    for i in range(200):
        pod = scheme.decode(deploy.pod(cfg, i % 10, name=f"held-{i}"))
        pod.spec.node_name = deploy.node_name(cfg, i * 7 % 2000)
        bound.append(pod)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    waiting = [scheme.decode(deploy.pod(cfg, i % 10, name=f"new-{i}"))
               for i in range(64)]
    snap, batch = SnapshotEncoder(state, waiting).encode()
    snap = _pad_snapshot(snap, next_pow2(snap.num_nodes, 64))
    batch = pad_batch(batch, 64)
    assert snap.num_nodes == 2048 and snap.ip_lt_u.shape == (10, 1)
    assert snap.ip_own_anti.shape == (10, 1, 2000)
    sched = BatchScheduler(SchedulerConfig())
    args = ({f: np.asarray(getattr(snap, f))
             for f in BatchScheduler.STATIC_FIELDS},
            sched.initial_carry(snap),
            {f: np.asarray(getattr(batch, f))
             for f in BatchScheduler.POD_FIELDS},
            np.int32(batch.num_pods))
    text = sched._compiled(3, 0).lower(
        *_shapes(args, one_chip)).compile().as_text()
    loops, computations = _loop_bodies(text)
    (_, step), = loops
    gathered = []
    for line in step:
        called = re.search(r"= (\S+) fusion\(.*calls=%?([\w.\-]+)", line)
        if called and any(" gather(" in op
                          for op in computations[called.group(2)]):
            gathered.append(called.group(1))
    wide = [shape for shape in gathered
            if "2048" in shape and not shape.startswith("pred[2048]")
            or "20480" in shape]
    assert wide == [], wide
    assert len(gathered) <= 8, gathered
    assert len([line for line in step if " fusion(" in line]) <= 170


def _index_vectors(op):
    """How many index vectors a compiled `gather` reads or a `scatter`
    writes: the result's elements over a slice's (a gather), or the
    elements of its scalar updates' index array (a scatter)."""
    import re

    sizes = lambda dims: int(np.prod([int(d) for d in dims.split(",") if d]
                                     or [1]))  # noqa: E731
    shape = re.search(r"= \(?\w+\[([\d,]*)\]", op).group(1)
    if " gather(" in op:
        return sizes(shape) // sizes(
            re.search(r"slice_sizes=\{([\d,]*)\}", op).group(1))
    return None


def test_the_run_slot_of_the_grouped_replay_gathers_no_view_under_terms(
        one_chip):
    """`jit_zreplay_group` at mixed-5k's own term widths (2 combos, 4
    logical terms of one slot, a domain a node) on the deployment cut to
    1,000 nodes (1,024 slots; 8 run slots of 64 picks), compiled for the
    described v5e (some 15 s). The five inter-pod views are gathered
    once a dispatch, before the run-slot loop (two gathers of `LT x N`
    index vectors), and ride it: no gather in
    the loop's body reads `LT x N` index vectors, where seven did (each
    some 0.35 ms at 8,192 slots on the chip: PERF.md section 5), and no
    scatter writes a table by node: a run's fold writes its picks,
    `LT x 64` updates, where five scatters of `LT x N` stood (the two
    emulated-int64 ones 2.26 ms each at 8,192 slots). The views are
    advanced by a loop of its own inside the run slot, a pass per 64
    picks."""
    import functools
    import re

    import jax

    from benchmark import deploy
    from kubernetes_tpu.client import rest
    from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig
    from kubernetes_tpu.models.wave import WaveScheduler, group_buffer
    from kubernetes_tpu.models.zreplay import _zreplay_group_fn
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.parallel.mesh import _pad_snapshot
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder
    from kubernetes_tpu.snapshot.pad import next_pow2

    cfg = deploy.load_config("mixed-5k")
    cfg["nodes"]["count"] = 1000
    scheme = rest.default_scheme
    bound = []
    for i in range(100):
        pod = scheme.decode(deploy.pod(cfg, i % 10, name=f"held-{i}"))
        pod.spec.node_name = deploy.node_name(cfg, i * 7 % 1000)
        bound.append(pod)
    state = ClusterState.build(
        [scheme.decode(d) for d in deploy.nodes(cfg)], bound,
        controllers=[scheme.decode(d) for d in deploy.controllers(cfg)])
    waiting = [scheme.decode(deploy.pod(cfg, t, name=f"new-{t}"))
               for t in range(10)]
    snap, batch = SnapshotEncoder(state, waiting).encode()
    snap = _pad_snapshot(snap, next_pow2(snap.num_nodes, 64))
    N, (LT, E) = snap.num_nodes, snap.ip_lt_u.shape
    assert (N, LT, E) == (1024, 4, 1)
    assert snap.ip_own_anti.shape == (4, 1, 1000)
    config = SchedulerConfig()
    static = {f: np.asarray(getattr(snap, f))
              for f in BatchScheduler.STATIC_FIELDS}
    wave = WaveScheduler(config)
    # the plain template and the vetoed one, four run slots of each
    G, layout, buf = group_buffer(batch, [0, 2] * 4)
    K = 64
    args = (static, BatchScheduler(config).initial_carry(snap),
            np.zeros(0, np.uint8), np.zeros(0, np.int64), np.asarray(buf),
            np.zeros(N, np.int32), np.zeros((G, N), bool), np.ones(G, bool),
            np.full(G, 32, np.int64), np.full(G, K, np.int32),
            np.int32(G), np.int64(0))
    text = jax.jit(functools.partial(
        _zreplay_group_fn, config, 2, 0, 128, K, G, layout, wave._apply_fn,
        None, None, wave._apply_group_fn)).lower(
            *_shapes(args, one_chip)).compile().as_text()
    loops, computations = _loop_bodies(text)
    # run slots > epochs > pick steps, and the views' advance beside the
    # epochs
    assert len(loops) == 4, [name for name, _ in loops]
    slot, = [body for _, body in loops
             if sum(" while(" in line for line in body) == 2]

    def fused(body, kind):
        """The `kind` ops of a loop body, in its fusions or bare."""
        found = [line for line in body if f" {kind}(" in line]
        for line in body:
            called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
            if called:
                found += [op for op in computations[called.group(1)]
                          if f" {kind}(" in op]
        return found

    gathers = fused(slot, "gather")
    assert gathers and max(map(_index_vectors, gathers)) <= N, [
        g.strip()[:160] for g in gathers if _index_vectors(g) > N]
    # by the node axis: the name-desc permutation of the header rows and
    # the commit counts back (as without terms), never by LT x N
    assert sum(_index_vectors(g) == N for g in gathers) <= 4
    scatters = fused(slot, "scatter")
    assert 3 <= len(scatters) <= 5  # the five tables' (int64: a pair each)
    for op in scatters:
        # scalar updates at (term, slot, domain): LT x E x K of them
        indices = re.search(r"scatter\(([^)]*)\)", op).group(1).split(", ")
        name = indices[len(indices) // 2].lstrip("%")
        shape, = [re.search(r"= \w+\[([\d,]*)\]", line).group(1)
                  for body in computations.values() for line in body
                  if re.match(rf"\s*(ROOT )?%?{re.escape(name)} = ", line)]
        assert int(np.prod([int(d) for d in shape.split(",")])) \
            <= 3 * LT * E * K, op.strip()[:200]
    # before the loop, once a dispatch: the views' own gathers, one of
    # `ip_term_count`'s single values and one of the four owner tables'
    # entries side by side, six 32-bit words an index vector
    outside = [op for name, body in computations.items()
               for op in body if " gather(" in op
               and _index_vectors(op) == LT * N]
    assert len(outside) == 2, len(outside)
    assert sum("slice_sizes={1,1,1,6}" in op for op in outside) == 1


#: seconds the chip's compiler may take over one shipment's unpack
#: program. The chip's host compiles about three times slower than this
#: sandbox and the deployment asks for under 30 s there; the uint8
#: buffer this guards against took 175 s here at the second size
UNPACK_COMPILE_S = 10.0


@pytest.mark.parametrize("pods,controllers", [(1024, 1), (4096, 500)],
                         ids=["density-1k", "spread-3k"])
def test_shipment_unpack_compiles_for_v5e_in_seconds(pods, controllers,
                                                     one_chip):
    """models/pack's program over a wave's pod rows, at the widths the
    two deployments ship: one template, and 500 controllers whose
    spread_match is i64[4096, 500] (17 MB)."""
    import time

    import jax

    from kubernetes_tpu.models.pack import pack_arrays, unpack

    rows = {"spread_match": np.zeros((pods, controllers), np.int64),
            "class_id": np.zeros(pods, np.int32),
            "zero_req": np.zeros(pods, np.bool_),
            "port_mask": np.zeros((pods, 2), np.uint32),
            "pref_num": np.zeros((pods, 1, 1), np.float64),
            "req_mem": np.zeros(pods, np.int64)}
    layout, buf = pack_arrays(rows)
    began = time.monotonic()
    compiled = jax.jit(lambda b: unpack(layout, b)).lower(
        jax.ShapeDtypeStruct(buf.shape, buf.dtype,
                             sharding=one_chip)).compile()
    took = time.monotonic() - began
    assert took < UNPACK_COMPILE_S, f"{took:.1f}s for {buf.nbytes} bytes"
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * buf.nbytes


#: the sharded driver's programs as analysis/programs registers them
#: (jit_mesh_scan, jit_mesh_probe, ... in a trace), served by the
#: benchmark's mesh-20k.fill cell and chip_smoke.py --mesh
MESH_SERVED = ("mesh_scan", "mesh_probe", "mesh_group_probe",
               "mesh_apply", "mesh_apply_group")

#: the two whose compile at the 32,768 bucket takes over 20 s here
MESH_HEAVY = {"mesh_scan", "mesh_probe"}


@pytest.fixture(scope="module")
def mesh_registry(topo, one_chip):
    """name -> ProgramSpec of the mesh driver at the --mesh phase's
    size (20,000 nodes -> the 32,768 bucket, 8,192 per shard), on a
    mesh of the described 2x2. The benchmark's cell serves the same
    programs at 1,280 slots a shard (5,000 nodes in 5,120 slots)."""
    import jax

    from kubernetes_tpu.analysis import programs

    # the registry builds its mesh from jax.devices(): hand it the
    # described chips (steered here, in the test — not an option of
    # the program). The resident-scatter entry places real arrays,
    # which a described device cannot hold; it is not under test.
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
        patch.setattr(
            programs, "_resident_scatter_program",
            lambda *a, **k: programs.ProgramSpec(name="-", fn=None,
                                                 args=()))
        return {s.name: s for s in programs.build_programs(
            include_mesh=True, num_nodes=20_000, run_length=500)}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=[pytest.mark.slow] if n in MESH_HEAVY else [])
    for n in MESH_SERVED])
def test_mesh_program_compiles_for_v5e_2x2(name, mesh_registry):
    """Each program of the mesh driver, for the described 2x2. The
    donated folds guard mesh._carry_out_shardings: with the result
    shardings of the carry's zero-size int64 leaves declared, libtpu
    0.0.34 does not raise — it ABORTS the process
    (import_shardy_attrs.cc: funcResultSharding.getNumOperands() == 1),
    so a regression here shows as a crashed test worker."""
    import jax

    spec = mesh_registry[name]
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        spec.args)
    compiled = spec.fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
    assert "jit_" + name in compiled.as_text()[:400]
    if name in ("mesh_apply", "mesh_apply_group"):
        # the fold mutates the resident carry in place: every byte of
        # the node-sharded carry aliases, per device
        assert mem.alias_size_in_bytes > 6 * 8 * (32_768 // 4)
