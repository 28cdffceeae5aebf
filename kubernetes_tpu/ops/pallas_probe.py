"""Hand-written Pallas probe kernel (round 19, KUBERNETES_TPU_KERNEL=pallas).

The per-wave resource section of models/probe._probe_rows — the fit
frontier plus the weighted LeastRequested/BalancedAllocation j-table —
is a dense [J, N] sweep: for every prospective commit depth j and node
n, recompute PodFitsResources and the two resource scores at usage +
j * the pod's commit vector. XLA compiles that sweep from lax ops; this
module expresses it as ONE Pallas kernel over a blocked j-grid so the
TPU lowering controls its own tiling (each grid step streams the node
tables once and emits a [BJ, N] tab block plus a frontier partial).

Contract: bit-identical to the lax build. The kernel body calls the
SAME score/predicate kernels (ops/priorities, ops/predicates) the lax
path uses — in interpret mode those jnp ops execute directly, so
equality is by construction. The BA score's f64 reference math rides
into the kernel (this file is on the auditor's f64 allowlist for
exactly that reason).

Interpret mode is the CALLER'S explicit choice (`interpret=True`,
reached through WaveProbe(kernel="pallas-interpret")): tests, the
analysis registry and the bench's CPU A/B ask for it by name. The
default is the compiled lowering, which the TPU compiler REFUSES for
this kernel today ("64-bit types are not supported": its refs, iota
and accumulators are int64/f64 — tests/test_chip_compile.py pins the
refusal), and which no other backend has. So KUBERNETES_TPU_KERNEL=
pallas fails when the probe is constructed, with the compiler's
reason, instead of quietly interpreting.

Gating: the kernel is DEFAULT OFF. models/probe routes the resource
section here only when the probe was built with kernel="pallas" or
"pallas-interpret" (WaveProbe reads KUBERNETES_TPU_KERNEL at
construction; the environment can only ask for the compiled build).
Consumers
that leave the j-table dead (the grouped header probe, the device
replay) stay on the lax build unconditionally — a pallas_call is
opaque to XLA's dead-code elimination, so routing them here would
compute tables nobody reads.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubernetes_tpu.ops import predicates as P
from kubernetes_tpu.ops import priorities as R

ENV = "KUBERNETES_TPU_KERNEL"

# pod scalar vector layout (one i64[9] ships instead of nine scalars)
_POD_SCALARS = (
    "req_mcpu", "req_mem", "req_gpu", "zero_req",
    "commit_mcpu", "commit_mem", "commit_gpu", "nz_mcpu", "nz_mem",
)


def requested() -> bool:
    """True when the environment asks for the Pallas kernel."""
    return os.environ.get(ENV, "").strip().lower() == "pallas"


def _block_j(J: int) -> int:
    """j-block height: J is a pow2 >= 16 on the probe path, so a pow2
    block always divides it. 8 rows keeps a [BJ, N] f64 intermediate
    under ~0.4 MB at N=5120 — comfortably inside VMEM next to the
    node tables."""
    return min(8, J)


def _kernel(pod_ref, a_cpu_ref, a_mem_ref, a_gpu_ref, a_pods_ref,
            u_cpu_ref, u_mem_ref, u_gpu_ref, u_nzc_ref, u_nzm_ref,
            u_cnt_ref, frontier_ref, tab_ref, *, BJ, terms, wants_res,
            bf16):
    jb = pl.program_id(0)
    # 2-D iota (TPU requires >= 2 dims); (BJ, 1) broadcasts over nodes
    j = (jax.lax.broadcasted_iota(jnp.int64, (BJ, 1), 0)
         + jnp.int64(BJ) * jb.astype(jnp.int64))
    pv = pod_ref[...]
    a_cpu = a_cpu_ref[...]
    a_mem = a_mem_ref[...]
    if wants_res:
        res_fit = P.pod_fits_resources(
            pv[0], pv[1], pv[2], pv[3] != 0,
            a_cpu, a_mem, a_gpu_ref[...], a_pods_ref[...],
            u_cpu_ref[...][None, :] + j * pv[4],
            u_mem_ref[...][None, :] + j * pv[5],
            u_gpu_ref[...][None, :] + j * pv[6],
            u_cnt_ref[...][None, :] + j,
        )
    else:
        res_fit = jnp.ones((BJ, a_cpu.shape[0]), bool)

    @pl.when(jb == 0)
    def _init():
        frontier_ref[...] = jnp.zeros_like(frontier_ref)

    # the grid is sequential, so the frontier accumulates across j-blocks
    frontier_ref[...] += res_fit.sum(0, dtype=jnp.int64)

    nzj_cpu = u_nzc_ref[...][None, :] + j * pv[7]
    nzj_mem = u_nzm_ref[...][None, :] + j * pv[8]
    acc_dt = jnp.bfloat16 if bf16 else jnp.int64
    tab = jnp.zeros(res_fit.shape, acc_dt)
    for kind, weight in terms:
        score = (R.least_requested if kind == "lr"
                 else R.balanced_resource_allocation)(
            pv[7], pv[8], nzj_cpu, nzj_mem, a_cpu, a_mem)
        term = jnp.int64(weight) * score
        tab = tab + (term.astype(acc_dt) if bf16 else term)
    if bf16:
        tab = tab.astype(jnp.int32).astype(jnp.int64)
    tab_ref[...] = tab


def resource_probe(J: int, alloc, usage, pod, terms, *,
                   wants_res: bool = True, bf16: bool = False,
                   interpret: bool = False):
    """-> (frontier i64[N], tab i64[J, N]) for a run-of-identical probe.

    alloc: (alloc_mcpu, alloc_mem, alloc_gpu, alloc_pods) node tables;
    usage: the carry's (req_mcpu, req_mem, req_gpu, nz_mcpu, nz_mem,
    pod_count) resource block; pod: the pod dict (scalars listed in
    _POD_SCALARS are consumed); terms: (("lr"|"ba", weight), ...) —
    the config's LR/BA priorities in declaration order (accumulation
    order matters for the bf16 profile's rounding parity with the lax
    build). interpret=False is the compiled lowering, whatever the
    backend; interpret=True runs the kernel body as jnp ops.
    """
    a_cpu, a_mem, a_gpu, a_pods = alloc
    N = a_cpu.shape[0]
    BJ = _block_j(J)
    pod_vec = jnp.stack(
        [jnp.asarray(pod[f]).astype(jnp.int64) for f in _POD_SCALARS])
    kern = functools.partial(_kernel, BJ=BJ, terms=tuple(terms),
                             wants_res=wants_res, bf16=bf16)
    node_spec = pl.BlockSpec((N,), lambda jb: (0,))
    frontier, tab = pl.pallas_call(
        kern,
        grid=(J // BJ,),
        in_specs=[pl.BlockSpec((len(_POD_SCALARS),), lambda jb: (0,))]
        + [node_spec] * 10,
        out_specs=[node_spec, pl.BlockSpec((BJ, N), lambda jb: (jb, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((N,), jnp.int64),
            jax.ShapeDtypeStruct((J, N), jnp.int64),
        ],
        interpret=interpret,
    )(pod_vec, a_cpu, a_mem, a_gpu, a_pods, *usage)
    return frontier, tab


def check_compiled_lowering() -> None:
    """Lower the compiled (non-interpret) kernel once at a tiny shape
    for the default backend; raises what that backend's compiler
    raises. WaveProbe calls this at construction so asking for the
    kernel where it cannot compile fails up front, not mid-wave."""
    J, N = 16, 128
    i64 = jax.ShapeDtypeStruct((N,), jnp.int64)
    pod = {f: jax.ShapeDtypeStruct((), jnp.int64) for f in _POD_SCALARS}
    jax.jit(functools.partial(
        resource_probe, J, terms=(("lr", 1), ("ba", 1)),
    )).lower((i64,) * 4, (i64,) * 6, pod)
