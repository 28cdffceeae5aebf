"""End-to-end scheduling trace & device-phase profiling.

The reference ships scheduler latency histograms plus /metrics and
/healthz on every daemon (plugin/pkg/scheduler/metrics/metrics.go,
server.go:92-108). This package grows that into per-phase attribution
for the TPU wire path:

  * spans.py   — lightweight span API: ``span(name, **attrs)`` context
    manager, thread-safe in-memory ring buffer, parent/child
    propagation via a context var, and a trace-id pod
    annotation that rides the TLV wire, so one pod's journey
    apiserver -> scheduler -> bind is a single trace across processes.
    Each wave of the scheduler is one trace in the ring too:
    ``scheduler.wave`` with the stage children wave.gather / .prepare
    / .algorithm / .assume / .bind (scheduler/core._WaveTrace).
  * profile.py — per-phase histograms (encode / probe / score / replay
    / transfer / wire / bind / prepare / assume / ingest) on one
    exclusive timeline (``exclusive_totals()``) that also holds the
    two idle states queue_wait / gather (``idle_totals()``); beside it
    a ledger per thread (``thread_totals()``: each thread's own wall
    and CPU seconds a phase under the role its owner declared with
    ``thread_role``, and ``device_wait()`` round the host reads that
    block on the device), served as ``threads`` on /debug/traces and as
    scheduler_thread_phase_seconds_total on /metrics; the
    annotation switch (``set_annotations``: each timer also opens a
    ``jax.profiler.TraceAnnotation("sched/<phase>")`` while a profiler
    runs, so host phases and device operations share one clock); and
    XLA compile-vs-execute attribution via jax.monitoring
    (scheduler_xla_compile_seconds, ``recent_compiles()``).
  * httpd.py   — the component observability mux (/healthz, /metrics,
    /configz, /debug/traces with the last compiles, /debug/profile to
    trace the daemon's own process) the scheduler daemon serves, the
    reference's own-:10251-mux idiom.
  * slo.py     — a watchdog sampling e2e scheduling latency against a
    configurable objective, emitting API Events on breach.

Everything span-shaped is gated on one process-global switch
(KUBERNETES_TPU_TRACE, default on; ``span.set_enabled`` flips it at
runtime): disabled, every hook is a no-op costing one attribute read.
"""

from kubernetes_tpu.trace.spans import (
    BUFFER,
    TRACE_ID_ANNOTATION,
    TraceBuffer,
    current_trace_id,
    enabled,
    event_span,
    extract,
    inject,
    new_trace_id,
    record_span,
    set_enabled,
    span,
)

__all__ = [
    "BUFFER",
    "TRACE_ID_ANNOTATION",
    "TraceBuffer",
    "current_trace_id",
    "enabled",
    "event_span",
    "extract",
    "inject",
    "new_trace_id",
    "record_span",
    "set_enabled",
    "span",
]
