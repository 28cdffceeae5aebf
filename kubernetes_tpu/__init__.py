"""kubernetes_tpu — a TPU-native cluster-scheduling framework.

A from-scratch re-design of the reference Kubernetes scheduler stack
(plugin/pkg/scheduler in the reference tree) around a pure, batched
(pending_pods x nodes) tensor program executed by XLA on TPU:

- predicates  -> boolean mask kernels over a struct-of-arrays ClusterSnapshot
- priorities  -> integer score matrices (0..10 per priority, reference math)
- selection   -> deterministic argmax replicating generic_scheduler.selectHost
                 (score desc, host-name desc, round-robin among ties)
- the backlog -> a jitted loop, a step a pod, that threads resource
                 commitments through the batch so results are
                 bit-identical to the serial Go loop

The event-driven shell around the tensor core (list/watch caches, optimistic
assume with TTL expiry, binding, backoff, events, metrics, leader election)
lives in host-side modules under `cache/`, `client/`, `utils/`.

Layout:
  api/       core object schema: Quantity, labels/selectors, Pod/Node types
             (reference: pkg/api/types.go, pkg/api/resource, pkg/labels)
  snapshot/  columnar ClusterSnapshot + host-side dictionary encoders
             (reference: plugin/pkg/scheduler/schedulercache/node_info.go)
  ops/       predicate masks and priority score kernels
             (reference: plugin/pkg/scheduler/algorithm/{predicates,priorities})
  models/    scheduling algorithms: batched generic scheduler, providers
             (reference: plugin/pkg/scheduler/generic_scheduler.go,
              plugin/pkg/scheduler/algorithmprovider)
  parallel/  device-mesh sharding of the (pods x nodes) program (pjit/shard_map)
  cache/     scheduler cache state machine (assume/add/expire)
  client/    FIFO/watch/reflector-style feeds and fake control planes
  oracle/    pure-Python sequential reference oracle (Go semantics) used as
             the conformance corpus generator/checker
  utils/     workqueue, backoff, trace, metrics, events
  audit/     apiserver audit log (who-did-what ring + /debug/audit)

Integer semantics note: the reference computes scores with int64 arithmetic
(e.g. `((capacity-requested)*10)/capacity` in priorities.go:33); memory is
int64 bytes. We therefore enable jax x64 so device arithmetic matches
bit-for-bit. The heavy mask work stays int32/uint32.
"""

import gc
import os
import sys as _sys_mod

# Cycle-GC pacing for control-plane workloads: the default gen-0
# threshold (700 allocations) makes the collector scan an ever-growing
# heap every ~700 objects, which measured 23us of overhead PER DECODED
# WATCH EVENT once informer stores retain tens of thousands of pods.
# The API object graphs are acyclic dataclass trees — refcounting frees
# them promptly — so the cycle collector exists only as a leak backstop
# and can run 100x less often. Opt out with KUBERNETES_TPU_DEFAULT_GC.
if not os.environ.get("KUBERNETES_TPU_DEFAULT_GC"):
    gc.set_threshold(100_000, 50, 50)

# GIL switch pacing: daemon processes run a handful of CPU-bound threads
# (request handlers, watch streamers, ingest); the 5ms default forces
# ~200 handoffs/s of pure overhead between them. A longer slice trades
# intra-process fairness nobody needs for throughput. Overridable.
_gil = os.environ.get("KUBERNETES_TPU_GIL_SWITCH_INTERVAL")
if _gil != "":  # explicit empty string opts out entirely
    import sys as _sys

    try:
        _sys.setswitchinterval(float(_gil) if _gil else 0.02)
    except (TypeError, ValueError) as _e:
        import warnings as _warnings

        _warnings.warn(
            f"ignoring invalid KUBERNETES_TPU_GIL_SWITCH_INTERVAL="
            f"{_gil!r} ({_e}); running at the interpreter default"
        )

# JAX configuration WITHOUT importing jax: the import costs ~1.1s, and
# half the control plane (apiserver, creator, kubectl, hollow kubelets)
# never touches a tensor. Environment-variable config is jax's own
# first-class mechanism — jax.config reads JAX_ENABLE_X64 /
# JAX_COMPILATION_CACHE_DIR / JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS
# at import, so processes that DO use jax get exactly the old settings
# the moment they import it, and everyone else skips the 1.1s tax.
#
# - x64: the reference computes scores with int64 arithmetic
#   (priorities.go:33) and memory is int64 bytes, so device arithmetic
#   must match bit-for-bit.
# - persistent compile cache: a fresh daemon facing a large cluster
#   pays seconds of compile per (node, pod, width) bucket; caching on
#   disk makes every start after the first warm. The directory is
#   JAX_COMPILATION_CACHE_DIR where the environment sets it, else a
#   FIXED path inside this checkout (the path is part of jax's cache
#   key, so a directory that moves never hits) — never the home
#   directory, which every checkout on a box would share. Opt out with
#   KUBERNETES_TPU_NO_XLA_CACHE.
# forced, not setdefault: an ambient JAX_ENABLE_X64=false would
# silently break the bit-for-bit int64 contract the old
# jax.config.update enforced unconditionally
os.environ["JAX_ENABLE_X64"] = "true"
if not os.environ.get("KUBERNETES_TPU_NO_XLA_CACHE"):
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".xla_cache",
        ),
    )
    # persist even fast compiles: the small pack/unpack and apply
    # programs add up to seconds per process start
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
if "jax" in _sys_mod.modules:
    # jax beat us to import: env vars were already read — apply the
    # same settings through the live config instead. Read the POST-
    # setdefault environment, not our defaults, so an ambient
    # JAX_COMPILATION_CACHE_DIR wins here exactly as it does on the
    # env-var path (cache selection must not depend on import order).
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)
    if not os.environ.get("KUBERNETES_TPU_NO_XLA_CACHE"):
        _jax.config.update(
            "jax_compilation_cache_dir",
            os.environ["JAX_COMPILATION_CACHE_DIR"])
        _jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ[
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))

__version__ = "0.1.0"
