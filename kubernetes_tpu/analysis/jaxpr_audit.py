"""Jaxpr auditor: machine-checked lowering/transfer contracts.

Traces every registered device program (analysis/programs) with
``jax.make_jaxpr`` — no device execution — and walks the jaxpr tree
(recursing through pjit / scan / while / cond / shard_map sub-jaxprs)
enforcing:

* ``denylisted-primitive`` — primitives known to lack a TPU lowering in
  a hot program. The founding member is the 64-bit-integer
  ``dot_general`` (the PR 3 incident: an s64 matmul traced fine on CPU
  and exploded at TPU lowering time); the grouped folds use
  elementwise-mul + reduce instead, and this pass keeps it that way.
* ``host-callback`` — ``pure_callback`` / ``debug_callback`` /
  ``io_callback`` et al. have no place in a hot program: each is a
  device->host round trip per dispatch (or worse, per scan step).
* ``dynamic-shape`` — every aval must have concrete integer dims; shape
  polymorphism would defeat the compile-cache reuse the wave drivers
  key on.
* ``f64-upcast`` — float64 (or complex128) appearing in a program not
  registered as deliberately float64 (the scan/zreplay score
  normalizers mirror the reference's float64 math and are allowed; the
  probe/apply/transfer programs must stay integer/f32 — a weak-type
  Python-float upcast there silently doubles table width and, on real
  TPU, rides the slow f64 emulation path).
* ``transfer-contract`` — the statically counted device->host transfer
  budget per dispatch: each registered program's non-carry output leaf
  count must equal its declaration. The grouped wave's O(1)-dispatch
  property is checked structurally: the grouped probe ships exactly ONE
  host-bound array at BOTH registered G values (probe=1 per wave), and
  the apply folds ship ZERO (the apply dispatch's outputs are all
  carry) — so a wave costs one probe transfer + one fold dispatch no
  matter how many templates rode it.
* ``donation-contract`` / ``donation-unusable`` — the resident-state
  programs (mesh folds, sharded scan, row scatter) declare
  ``donate_argnums``; the auditor lowers each one and requires every
  donated input leaf to carry an input/output alias
  (``tf.aliasing_output``) and no donation to be dropped with a
  warning.  A donated carry XLA silently copies would re-allocate
  O(nodes) buffers per wave — that is a CI failure here, not a perf
  mystery in production.
* ``sharding-drift`` — for every registered pjit program that declares
  ``arg_shardings``/``out_shardings_decl`` (built from
  ``parallel.resident.carry_specs()``/``static_specs()``, the single
  source the placement shares), the in/out shardings the driver's jit
  wrapper actually carries must match leaf-for-leaf.  A program whose
  carry drifts to a different PartitionSpec than the resident
  placement would silently reshard O(nodes) buffers on EVERY dispatch.
* ``dtype-contract`` — the narrow-placement width contract
  (ops/narrow): programs registered with ``narrow_dtypes`` must
  receive each declared table AT its narrow dtype and must never widen
  a node-axis narrow integer to int32/int64 in-program (gather/scatter
  index feeds exempt) — a silent upcast reads the full-width bytes the
  narrow placement exists to save.
* ``scatter-contract`` — the scatter-form commit programs (PR 6's
  O(picks) shipment) are correct only because their updates commute:
  the registry declares the exact (primitive, scatter dims) forms each
  may contain, and any other scatter — in particular a plain
  overwrite ``scatter`` without ``unique_indices`` — is a finding.
  Collision-freedom is the host's job (deduped indices); this keeps
  the device side order-independent so that contract is sufficient.
"""

from __future__ import annotations

from typing import Any, Iterable, List

from kubernetes_tpu.analysis import Finding
from kubernetes_tpu.analysis.programs import ProgramSpec, build_programs

#: primitive names that are host callbacks in disguise
CALLBACK_PRIMITIVES = {
    "pure_callback", "debug_callback", "io_callback", "callback",
    "outside_call", "host_callback_call",
}

#: (primitive name, why) entries denied on any 64-bit integer operand
INT64_DENYLIST = {
    "dot_general": "64-bit integer dot_general has no TPU lowering "
                   "(use elementwise-mul + reduce)",
    "conv_general_dilated": "64-bit integer convolution has no TPU "
                            "lowering",
}

#: primitives that merely MOVE f64 data. The snapshot legitimately
#: carries float64 vocab tables (numeric label values for Gt/Lt
#: selector ops ride as f64 by reference semantics), so f64 flowing
#: through unpack bitcasts / gathers / selects is data plumbing; the
#: f64-upcast rule fires only on f64-PRODUCING arithmetic, which is
#: the signature of a weak-type Python-float promotion.
F64_MOVEMENT_PRIMITIVES = {
    "bitcast_convert_type", "reshape", "broadcast_in_dim", "squeeze",
    "transpose", "gather", "dynamic_slice", "dynamic_update_slice",
    "slice", "concatenate", "select_n", "scatter", "scatter-add",
    "pad", "rev", "copy", "device_put", "stop_gradient",
    # comparisons CONSUME f64 and emit bool; they never appear here
    # (output-dtype gated) but the container prims do:
    "jit", "closed_call", "core_call", "scan", "while", "cond",
    "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint",
    "shard_map", "xla_call",
}

#: source files whose f64 arithmetic is reference-exact BY CONTRACT
#: (priorities.go float64 fraction/normalizer math, mirrored
#: operation-for-operation so truncations agree). An f64-producing
#: equation whose trace provenance passes through one of these is the
#: documented math; anywhere else it is a weak-type upcast.
ALLOWED_F64_SOURCES = (
    "kubernetes_tpu/ops/priorities.py",
    "kubernetes_tpu/ops/interpod.py",
)


#: scatter primitives whose update function commutes (order-independent
#: under colliding indices); plain `scatter` (overwrite) is NOT here —
#: it is only safe with unique indices
COMMUTATIVE_SCATTER = {"scatter-add", "scatter-mul", "scatter-min",
                       "scatter-max"}


def _f64_provenance_ok(eqn) -> bool:
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return False
    try:
        frames = tb.frames
    except Exception:
        return False
    for fr in frames:
        fname = getattr(fr, "file_name", "") or ""
        if any(src in fname for src in ALLOWED_F64_SOURCES):
            return True
    return False


def _subjaxprs(eqn) -> Iterable[Any]:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(v):
        if isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from walk(x)
        elif isinstance(v, dict):
            for x in v.values():
                yield from walk(x)

    for val in eqn.params.values():
        yield from walk(val)


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Depth-first over every equation including sub-jaxprs (scan
    bodies, branches, pjit calls, shard_map bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub)


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            yield aval


def _is_i64(dtype) -> bool:
    import numpy as np

    return np.issubdtype(dtype, np.integer) and np.dtype(dtype).itemsize == 8


def audit_jaxpr(name: str, jaxpr, allow_f64: bool = False
                ) -> List[Finding]:
    """Walk one closed jaxpr against the primitive/dtype/shape rules."""
    import numpy as np

    findings: List[Finding] = []
    f64_hits: List[str] = []
    for eqn in iter_eqns(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr")
                         else jaxpr):
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMITIVES:
            findings.append(Finding(
                "jaxpr", "host-callback", name,
                f"{prim} inside a hot device program (a host round "
                "trip per dispatch)",
            ))
        deny = INT64_DENYLIST.get(prim)
        if deny is not None and any(
            _is_i64(getattr(a, "dtype", np.float32)) for a in _avals(eqn)
        ):
            findings.append(Finding(
                "jaxpr", "denylisted-primitive", name,
                f"{prim} on 64-bit integers: {deny}",
            ))
        for aval in _avals(eqn):
            shape = getattr(aval, "shape", ())
            if not all(isinstance(d, int) for d in shape):
                findings.append(Finding(
                    "jaxpr", "dynamic-shape", name,
                    f"{prim} has a non-static dim {shape} — defeats "
                    "the compile-cache keying the wave drivers rely on",
                ))
                break
        if not allow_f64 and prim not in F64_MOVEMENT_PRIMITIVES:
            for v in eqn.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and dt in (
                    np.dtype(np.float64), np.dtype(np.complex128),
                ) and not _f64_provenance_ok(eqn):
                    f64_hits.append(prim)
                    break
    if f64_hits:
        findings.append(Finding(
            "jaxpr", "f64-upcast", name,
            f"float64 values flow through {len(f64_hits)} equation(s) "
            f"(first: {f64_hits[0]}) in a program registered as "
            "f64-free — a weak-type upcast fattens tables/transfers "
            "and hits TPU f64 emulation",
        ))
    return findings


def _transfer_findings(spec: ProgramSpec) -> List[Finding]:
    """The statically-counted transfer budget: non-carry output leaves
    must match the declaration."""
    import jax

    if spec.expected_host_leaves is None:
        return []
    out = jax.eval_shape(spec.fn, *spec.args)
    n_out = len(jax.tree_util.tree_leaves(out))
    host = n_out - spec.carry_out_leaves
    if host != spec.expected_host_leaves:
        return [Finding(
            "jaxpr", "transfer-contract", spec.name,
            f"{host} host-bound output leaf(s) per dispatch, contract "
            f"says {spec.expected_host_leaves} — an extra device->host "
            "transfer crept into the wave hot path "
            f"({n_out} outputs total, {spec.carry_out_leaves} carry)",
        )]
    return []


def _donation_findings(spec: ProgramSpec) -> List[Finding]:
    """The donation contract: every donated input leaf must alias an
    output in the lowered program.  A resident-state program whose
    donated buffer is silently copied (un-donatable layout, shape/dtype
    drift between carry-in and carry-out) re-allocates O(nodes) memory
    per wave — a CI failure here, not a perf mystery in production."""
    import warnings

    if not spec.donate_argnums:
        return []
    import jax

    expected = spec.donated_leaves
    if expected is None:
        # zero-size leaves hold no buffer to mutate: the mesh driver
        # leaves their result shardings unspecified
        # (mesh._carry_out_shardings), so jax marks them buffer donors
        # without a fixed alias — the contract is about leaves with
        # bytes
        import numpy as np

        expected = sum(
            1
            for i in spec.donate_argnums
            for leaf in jax.tree_util.tree_leaves(spec.args[i])
            if np.size(leaf)
        )
    findings: List[Finding] = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        txt = spec.fn.lower(*spec.args).as_text()
    for w in caught:
        msg = str(w.message)
        if "donat" in msg.lower():
            findings.append(Finding(
                "jaxpr", "donation-unusable", spec.name,
                f"jax dropped a donation while lowering: {msg[:160]}",
            ))
    aliased = txt.count("tf.aliasing_output")
    if aliased != expected:
        findings.append(Finding(
            "jaxpr", "donation-contract", spec.name,
            f"{aliased} input leaf(s) alias an output, contract says "
            f"{expected} — a donated resident-state buffer is being "
            "silently copied instead of mutated in place",
        ))
    return findings


def _normspec(spec) -> tuple:
    """PartitionSpec -> canonical tuple (trailing Nones stripped, so
    P('nodes') == P('nodes', None) the way placement treats them)."""
    t = tuple(spec)
    while t and t[-1] is None:
        t = t[:-1]
    return t


def _flatten_decl(decl) -> List[Any]:
    """Flatten a declared sharding pytree with PartitionSpec leaves in
    the same order jax flattens the matching argument."""
    import jax
    from jax.sharding import PartitionSpec

    return jax.tree_util.tree_leaves(
        decl, is_leaf=lambda x: x is None or isinstance(x, PartitionSpec)
    )


def _pjit_eqn(jaxpr):
    """The top-level jit equation carrying concrete shardings (the
    primitive jax.jit traces to is named ``jit``)."""
    from jax.sharding import NamedSharding

    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "jit":
            shardings = eqn.params.get("in_shardings", ())
            if any(isinstance(s, NamedSharding) for s in shardings):
                return eqn
    return None


def _sharding_findings(spec: ProgramSpec, jaxpr) -> List[Finding]:
    """The sharding-spec drift audit: the in/out shardings the driver's
    jit wrapper carries must equal the PartitionSpecs the resident
    placement declares (resident.carry_specs()/static_specs())."""
    import jax
    from jax.sharding import NamedSharding

    if spec.arg_shardings is None and spec.out_shardings_decl is None:
        return []
    findings: List[Finding] = []
    eqn = _pjit_eqn(jaxpr)
    if eqn is None:
        return [Finding(
            "jaxpr", "sharding-drift", spec.name,
            "program declares expected shardings but traces to no pjit "
            "equation with concrete shardings — the driver stopped "
            "declaring in_shardings/out_shardings",
        )]

    def compare(kind, actual, expected_flat, label_of, avals):
        if len(actual) != len(expected_flat):
            findings.append(Finding(
                "jaxpr", "sharding-drift", spec.name,
                f"{kind}: {len(actual)} sharded leaf(s) in the traced "
                f"program, declaration covers {len(expected_flat)} — "
                "the registry declaration drifted from the driver",
            ))
            return
        for i, (act, exp) in enumerate(zip(actual, expected_flat)):
            if exp is None:
                continue  # leaf explicitly unaudited
            if (not isinstance(act, NamedSharding)
                    and getattr(avals[i], "size", 1) == 0):
                # a zero-size leaf has no bytes to reshard; the mesh
                # driver leaves its result sharding unspecified on
                # purpose (mesh._carry_out_shardings: the TPU compiler
                # aborts on declared shardings of empty i64 results)
                continue
            if not isinstance(act, NamedSharding):
                findings.append(Finding(
                    "jaxpr", "sharding-drift", spec.name,
                    f"{kind} leaf {i} ({label_of(i)}): expected "
                    f"PartitionSpec{tuple(exp)} but the program leaves "
                    "the sharding unspecified — pjit would choose its "
                    "own and reshard the resident buffer per dispatch",
                ))
            elif _normspec(act.spec) != _normspec(exp):
                findings.append(Finding(
                    "jaxpr", "sharding-drift", spec.name,
                    f"{kind} leaf {i} ({label_of(i)}): program uses "
                    f"PartitionSpec{tuple(act.spec)}, resident declares "
                    f"PartitionSpec{tuple(exp)} — an O(nodes) reshard "
                    "rides every dispatch until these agree",
                ))

    if spec.arg_shardings is not None:
        expected: List[Any] = []
        labels: List[str] = []
        for argnum, decl in enumerate(spec.arg_shardings):
            n_leaves = len(jax.tree_util.tree_leaves(spec.args[argnum]))
            if decl is None:
                expected.extend([None] * n_leaves)
                labels.extend(
                    [f"arg{argnum}[{j}]" for j in range(n_leaves)])
                continue
            flat = _flatten_decl(decl)
            if len(flat) != n_leaves:
                findings.append(Finding(
                    "jaxpr", "sharding-drift", spec.name,
                    f"arg {argnum}: declaration has {len(flat)} spec "
                    f"leaf(s) for {n_leaves} array leaf(s) — a field "
                    "was added/removed without updating the declared "
                    "PartitionSpecs",
                ))
                expected.extend([None] * n_leaves)
            else:
                expected.extend(flat)
            labels.extend([f"arg{argnum}[{j}]" for j in range(n_leaves)])
        compare("in_shardings", tuple(eqn.params["in_shardings"]),
                expected, lambda i: labels[i],
                [v.aval for v in eqn.invars])
    if spec.out_shardings_decl is not None:
        flat_out = _flatten_decl(spec.out_shardings_decl)
        compare("out_shardings", tuple(eqn.params["out_shardings"]),
                flat_out, lambda i: f"out[{i}]",
                [v.aval for v in eqn.outvars])
    return findings


def _scatter_findings(spec: ProgramSpec, jaxpr) -> List[Finding]:
    """The commit-fold commutativity contract: every scatter-family
    equation must be one of the registry-declared (primitive, dims)
    forms, and non-commutative forms must assert unique indices."""
    if spec.scatter_allowed is None:
        return []
    allowed = {(p, tuple(d)) for p, d in spec.scatter_allowed}
    findings: List[Finding] = []
    seen: set = set()
    for eqn in iter_eqns(jaxpr.jaxpr):
        prim = eqn.primitive.name
        if not prim.startswith("scatter"):
            continue
        dn = eqn.params.get("dimension_numbers")
        dims = tuple(dn.scatter_dims_to_operand_dims) \
            if dn is not None else ()
        key = (prim, dims)
        if key in seen:
            continue
        seen.add(key)
        if key not in allowed:
            findings.append(Finding(
                "jaxpr", "scatter-contract", spec.name,
                f"{prim} on operand dims {dims} is not in this "
                f"program's declared scatter forms {sorted(allowed)} — "
                "a new scatter crept into a commit fold; prove it "
                "commutative/collision-free and add it to the registry "
                "declaration",
            ))
        elif prim not in COMMUTATIVE_SCATTER \
                and not eqn.params.get("unique_indices"):
            findings.append(Finding(
                "jaxpr", "scatter-contract", spec.name,
                f"overwrite {prim} on dims {dims} without "
                "unique_indices: colliding indices make the result "
                "order-dependent — the serial-oracle equivalence the "
                "scatter-form commits rely on breaks",
            ))
    return findings


#: operand positions that are INDEX feeds (exempt from the widening
#: rule: jax converts index arrays to int32 internally, which is the
#: one legitimate narrow->wide convert of a table-derived value)
_INDEX_OPERANDS = {
    "gather": (1,), "scatter": (1,), "scatter-add": (1,),
    "scatter-mul": (1,), "scatter-min": (1,), "scatter-max": (1,),
}

#: prims index values legitimately flow THROUGH on their way to a
#: gather/scatter operand (jax's index normalization: wrap negatives,
#: reshape to the indices layout); outputs inherit the index-only
#: obligation
_INDEX_PLUMBING = {
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "slice",
    "concatenate", "select_n", "add", "sub", "mul", "rem", "clamp",
    "min", "max",
}

#: comparisons consume the value into a bool guard — one byte out, no
#: widened table materialized
_INDEX_GUARDS = {"lt", "le", "gt", "ge", "eq", "ne"}

_NARROW_INTS = ("int8", "int16")
_WIDE_INTS = ("int32", "int64")


def _dtype_findings(spec: ProgramSpec, jaxpr) -> List[Finding]:
    """The quantized-placement dtype contract: every declared-narrow
    static table must ARRIVE at its narrow dtype, and no node-axis
    narrow integer may be widened to int32/int64 inside the program
    except to feed gather/scatter indices. A silent in-program upcast
    reads the full-width bytes the narrow placement exists to avoid —
    and on a mesh it materializes a widened copy of a sharded table
    per dispatch."""
    import jax
    import numpy as np

    if not spec.narrow_dtypes:
        return []
    decl = {name: np.dtype(dt) for name, dt in spec.narrow_dtypes}
    findings: List[Finding] = []

    # 1. arrival check: the program input leaf for each declared field
    # (located by its pytree path key) carries the narrow dtype
    leaves = jax.tree_util.tree_leaves_with_path(spec.args)
    avals = list(jaxpr.in_avals)
    node_dims = set()
    for i, (path, _leaf) in enumerate(leaves):
        name = None
        for p in path:
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
        if name not in decl or i >= len(avals):
            continue
        aval = avals[i]
        if np.dtype(aval.dtype) != decl[name]:
            findings.append(Finding(
                "jaxpr", "dtype-contract", spec.name,
                f"input table {name!r} arrives as {aval.dtype}, "
                f"declared narrow placement is {decl[name]} — the "
                "driver stopped placing the quantized copy",
            ))
        shape = getattr(aval, "shape", ())
        if shape:
            node_dims.add(shape[0])

    # 2. widening check: narrow-int -> wide-int converts of node-axis
    # arrays, with the gather/scatter index exemption
    from jax.extend.core import Literal

    def scan(jx):
        uses: dict = {}
        for eqn in jx.eqns:
            for pos, v in enumerate(eqn.invars):
                if not isinstance(v, Literal) and hasattr(v, "aval"):
                    uses.setdefault(v, []).append((eqn, pos))
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type":
                iv = eqn.invars[0]
                aval = getattr(iv, "aval", None)
                if aval is None:
                    continue
                in_dt = np.dtype(aval.dtype)
                out_dt = np.dtype(eqn.outvars[0].aval.dtype)
                shape = getattr(aval, "shape", ())
                if (in_dt.name in _NARROW_INTS
                        and out_dt.name in _WIDE_INTS
                        and shape and shape[0] in node_dims):
                    # transitive index-feed walk: the converted value
                    # may flow through jax's index normalization
                    # (negative-wrap add/select, broadcast to the
                    # indices layout) before the gather/scatter; every
                    # terminal use must be an index operand or a bool
                    # guard
                    work = [eqn.outvars[0]]
                    seen: set = set()
                    index_only = bool(uses.get(eqn.outvars[0]))
                    while work and index_only:
                        v = work.pop()
                        if v in seen:
                            continue
                        seen.add(v)
                        for c, pos in uses.get(v, ()):
                            cp = c.primitive.name
                            if pos in _INDEX_OPERANDS.get(cp, ()):
                                continue
                            if cp in _INDEX_GUARDS:
                                continue
                            if cp in _INDEX_PLUMBING:
                                work.extend(c.outvars)
                                continue
                            index_only = False
                            break
                    if not index_only:
                        findings.append(Finding(
                            "jaxpr", "dtype-contract", spec.name,
                            f"{in_dt.name}->{out_dt.name} widening of "
                            f"a node-axis array (shape {shape}) inside "
                            "a quantized program — a declared-narrow "
                            "table is being upcast in-program; consume "
                            "it via ops/narrow's narrow_eq/narrow_matvec "
                            "instead",
                        ))
            for sub in _subjaxprs(eqn):
                scan(sub)

    scan(jaxpr.jaxpr)
    return findings


def audit_program(spec: ProgramSpec) -> List[Finding]:
    import jax

    jaxpr = jax.make_jaxpr(spec.fn)(*spec.args)
    findings = audit_jaxpr(spec.name, jaxpr, allow_f64=spec.allow_f64)
    findings.extend(_transfer_findings(spec))
    findings.extend(_donation_findings(spec))
    findings.extend(_sharding_findings(spec, jaxpr))
    findings.extend(_scatter_findings(spec, jaxpr))
    findings.extend(_dtype_findings(spec, jaxpr))
    return findings


_PROGRAM_CACHE: dict = {}  # include_mesh -> [ProgramSpec]


def registered_programs(include_mesh: bool = True) -> List[ProgramSpec]:
    progs = _PROGRAM_CACHE.get(include_mesh)
    if progs is None:
        progs = build_programs(include_mesh=include_mesh)
        _PROGRAM_CACHE[include_mesh] = progs
    return progs


def audit_all(include_mesh: bool = True) -> List[Finding]:
    """Trace + audit every registered program (the CI pass body)."""
    findings: List[Finding] = []
    specs = registered_programs(include_mesh=include_mesh)
    if include_mesh and not any(s.name.startswith("mesh_")
                                for s in specs):
        # asked-for coverage that cannot be delivered must be a loud
        # finding, never a silent shrink: on a 1-device host the five
        # mesh programs drop out
        import jax

        findings.append(Finding(
            "jaxpr", "mesh-unavailable", "programs",
            f"mesh shard_map variants not auditable here "
            f"({len(jax.devices())} visible device(s)); start python "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(before any backend initializes), or pass --no-mesh to "
            "accept the reduced coverage explicitly",
        ))
    for spec in specs:
        findings.extend(audit_program(spec))
    return findings
