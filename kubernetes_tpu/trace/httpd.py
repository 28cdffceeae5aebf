"""Component observability mux.

The reference scheduler runs its own :10251 mux serving /healthz and
prometheus /metrics (plugin/cmd/kube-scheduler/app/server.go:92-108);
in this framework only the apiserver's shared mux rendered the registry
until now. This module is that per-daemon mux: a tiny threaded HTTP
server any component can hang its /healthz, /metrics, /configz,
/debug/traces?limit=N, /debug/profile?seconds=N and /debug/audit
endpoints on. The scheduler daemon serves it by
default (scheduler/server.py); the kubelet reuses render_traces() on
its existing node-API server.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional
from urllib.parse import parse_qs, urlparse


def render_traces(query: Dict[str, str]) -> dict:
    """The /debug/traces payload: most-recent spans, newest first.
    ?limit=N bounds the span count (default 256); ?trace=<id> filters
    to one trace. Shared by every daemon's frontend."""
    from kubernetes_tpu.trace import spans as _span

    try:
        limit = int(query.get("limit", "256"))
    except ValueError:
        limit = 256
    items = _span.BUFFER.snapshot(
        limit=max(1, min(limit, 4096)),
        trace_id=query.get("trace") or None,
    )
    from kubernetes_tpu.trace import profile as _profile

    return {
        "kind": "TraceList",
        "enabled": _span.enabled(),
        "totalRecorded": _span.BUFFER.total_recorded,
        "items": items,
        # the last programs built here: which step recompiled
        "compiles": _profile.recent_compiles(),
        # whether cache deltas reach the snapshot a batch at a time
        "encoder": _profile.encoder_totals(),
        # whether a wave's pending-pod rows are reused or encoded anew
        "pending_rows": _profile.pending_row_totals(),
        # which path decided the pods, and what was launched for them
        "wave": _profile.wave_totals(),
        # whose time it was: each role's threads' own wall and CPU
        # seconds a phase, and the loop's waits for the device
        "threads": _profile.thread_totals(),
    }


PROFILE_MAX_SECONDS = 10.0
_profile_lock = threading.Lock()


def run_profile(query: Dict[str, str]) -> tuple:
    """The /debug/profile handler: run jax.profiler in THIS process for
    ?seconds=N (at most PROFILE_MAX_SECONDS), with the phase timers'
    annotations on, and answer with the directory the trace was written
    to. How an operator traces a daemon that is its own OS process: the
    .xplane.pb holds the device's lines and the host's `sched/<phase>`
    annotations on one clock. -> (status, payload)."""
    from kubernetes_tpu.trace import profile as _profile

    try:
        seconds = float(query.get("seconds", "2"))
    except ValueError:
        return 400, {"message": "seconds must be a number"}
    if not 0 < seconds <= PROFILE_MAX_SECONDS:
        return 400, {"message": "seconds must be over 0 and at most "
                                f"{PROFILE_MAX_SECONDS:g}"}
    if not _profile_lock.acquire(blocking=False):
        return 409, {"message": "a profile is already running"}
    try:
        import jax

        log_dir = tempfile.mkdtemp(prefix="kubernetes-tpu-profile-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the annotations, not every call
        was_on = _profile.set_annotations(True)
        try:
            jax.profiler.start_trace(log_dir, profiler_options=options)
            time.sleep(seconds)
            jax.profiler.stop_trace()
        finally:
            _profile.set_annotations(was_on)
        return 200, {"kind": "Profile", "seconds": seconds,
                     "directory": log_dir}
    finally:
        _profile_lock.release()


def start_component_server(
    host: str = "127.0.0.1",
    port: int = 0,
    healthz: Optional[Callable[[], bool]] = None,
    name: str = "component",
):
    """Serve the observability mux on (host, port); port 0 binds an
    ephemeral port. Returns (server, bound_port); server.shutdown()
    stops it. `healthz` (optional) turns /healthz into a real probe —
    falsy/raising answers 500."""

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet, like the other muxes
            pass

        def _send(self, code: int, payload,
                  content_type: str = "application/json") -> None:
            if isinstance(payload, (dict, list)):
                data = json.dumps(payload).encode()
            elif isinstance(payload, str):
                data = payload.encode()
            else:
                data = payload
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            parsed = urlparse(self.path)
            query = {
                k: v[0] for k, v in parse_qs(parsed.query).items() if v
            }
            path = parsed.path.rstrip("/") or "/"
            try:
                if path == "/healthz":
                    ok = True
                    if healthz is not None:
                        try:
                            ok = bool(healthz())
                        except Exception:
                            ok = False
                    self._send(200 if ok else 500,
                               "ok" if ok else "unhealthy", "text/plain")
                    return
                if path == "/metrics":
                    from kubernetes_tpu.metrics import registry

                    self._send(200, registry.render(),
                               "text/plain; version=0.0.4")
                    return
                if path == "/configz":
                    from kubernetes_tpu.utils import configz

                    self._send(200, configz.snapshot())
                    return
                if path == "/debug/traces":
                    self._send(200, render_traces(query))
                    return
                if path == "/debug/profile":
                    self._send(*run_profile(query))
                    return
                if path == "/debug/audit":
                    from kubernetes_tpu.audit import render_audit

                    self._send(200, render_audit(query))
                    return
                if path == "/debug/telemetry/query":
                    from kubernetes_tpu import telemetry

                    self._send(*telemetry.handle_query(query))
                    return
                if path == "/debug/telemetry/alerts":
                    from kubernetes_tpu import telemetry

                    self._send(*telemetry.handle_alerts(query))
                    return
                if path == "/debug/flightrecorder":
                    from kubernetes_tpu import telemetry

                    self._send(*telemetry.handle_flight(query))
                    return
                self._send(404, {"message": f"unknown path {parsed.path}"})
            except Exception as e:  # a broken probe must not kill the mux
                try:
                    self._send(500, {"message": str(e)})
                except OSError:
                    pass

    class Server(ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    server = Server((host, port), Handler)
    threading.Thread(
        target=server.serve_forever,
        name=f"{name}-observability",
        daemon=True,
    ).start()
    return server, server.server_address[1]
