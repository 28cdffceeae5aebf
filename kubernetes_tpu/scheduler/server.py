"""Scheduler daemon assembly.

Reference: plugin/cmd/kube-scheduler/app/{server.go,options/options.go}.
Run() wires: client, factory + informers, event broadcaster, config from
provider or policy file, optional leader election, then the scheduling
loop. Healthz/metrics ride the shared apiserver mux in this framework
(the reference runs its own :10251 mux, server.go:92-108).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from kubernetes_tpu.client.leaderelection import LeaderElector
from kubernetes_tpu.client.record import EventBroadcaster, EventSink
from kubernetes_tpu.client.rest import RESTClient
from kubernetes_tpu.scheduler import algorithmprovider  # registers providers
from kubernetes_tpu.scheduler.core import Scheduler
from kubernetes_tpu.scheduler.factory import (
    DEFAULT_SCHEDULER_NAME,
    ConfigFactory,
)
from kubernetes_tpu.scheduler.policy import load_policy

log = logging.getLogger(__name__)


@dataclass
class SchedulerServerOptions:
    """options.go:31 SchedulerServer (KubeSchedulerConfiguration knobs)."""

    algorithm_provider: str = algorithmprovider.DEFAULT_PROVIDER_NAME
    policy_config_file: str = ""
    scheduler_name: str = DEFAULT_SCHEDULER_NAME
    hard_pod_affinity_symmetric_weight: int = 1
    failure_domains: List[str] = field(
        default_factory=lambda: [
            "kubernetes.io/hostname",
            "failure-domain.beta.kubernetes.io/zone",
            "failure-domain.beta.kubernetes.io/region",
        ]
    )
    kube_api_qps: float = 50.0
    kube_api_burst: int = 100
    # the daemon's own observability mux (server.go:92-108 runs the
    # reference's on :10251): /healthz, /metrics, /configz,
    # /debug/traces. Port 0 binds ephemeral (the bound port lands on
    # .health_address); None disables the listener entirely.
    serve_address: str = "127.0.0.1"
    serve_port: Optional[int] = 0
    # SLO watchdog (trace/slo.py): objective <= 0 disables; on breach a
    # Warning Event is emitted through the scheduler's recorder
    slo_objective_seconds: float = 0.0
    slo_check_interval: float = 10.0
    leader_elect: bool = False
    leader_elect_identity: str = ""
    lock_object_namespace: str = "kube-system"
    lock_object_name: str = "kube-scheduler"
    # lease timing (leaderelection.go defaults); the HA soak shrinks
    # these so a killed holder's standby takes over inside a CI-sized
    # SLO instead of the production 15s
    leader_elect_lease_duration: float = 15.0
    leader_elect_renew_deadline: float = 10.0
    leader_elect_retry_period: float = 2.0
    # AI-cluster workloads: path to a JSON throughput matrix
    # {workload_class: {accel_type: normalized_throughput}} feeding the
    # gang director's Gavel-style placement score; node accelerator
    # types come from the `accel_label_key` node label
    throughput_matrix_file: str = ""
    accel_label_key: str = "accelerator"

    @classmethod
    def from_component_config(cls, cfg) -> "SchedulerServerOptions":
        """Build options from a versioned KubeSchedulerConfiguration
        (apis/componentconfig.py) — options.go:31's embed, as a
        conversion. Flags-as-API-object is the configuration contract;
        this dataclass stays the daemon-internal form."""
        return cls(
            algorithm_provider=cfg.algorithm_provider,
            policy_config_file=cfg.policy_config_file,
            scheduler_name=cfg.scheduler_name,
            hard_pod_affinity_symmetric_weight=(
                cfg.hard_pod_affinity_symmetric_weight
            ),
            failure_domains=list(cfg.failure_domains),
            kube_api_qps=cfg.kube_api_qps,
            kube_api_burst=cfg.kube_api_burst,
            leader_elect=cfg.leader_election.leader_elect,
            lock_object_namespace=cfg.lock_object_namespace,
            lock_object_name=cfg.lock_object_name,
        )

    @classmethod
    def from_config_file(cls, path: str) -> "SchedulerServerOptions":
        from kubernetes_tpu.apis.componentconfig import (
            load_component_config,
        )

        return cls.from_component_config(
            load_component_config(path, "KubeSchedulerConfiguration")
        )


class SchedulerServer:
    """app.Run (server.go:71)."""

    def __init__(self, client: RESTClient, options: Optional[SchedulerServerOptions] = None):
        self.options = options or SchedulerServerOptions()
        self.client = client
        self.factory: Optional[ConfigFactory] = None
        self.scheduler: Optional[Scheduler] = None
        self._elector: Optional[LeaderElector] = None
        self._thread: Optional[threading.Thread] = None
        self._health_server = None
        self._slo = None
        self._telemetry = None
        self._telemetry_owned = False
        #: (host, port) of the daemon's observability mux once serving
        self.health_address: Optional[tuple] = None
        # set once the scheduling loop is open for business (informers
        # synced + run-path warmup done). Callers that want steady-state
        # behavior (the perf harness, local-up readiness) wait on this;
        # pods arriving earlier still just queue.
        self.ready = threading.Event()
        #: why a device-backed daemon will never become ready (backend
        #: init or warmup failed); waiters poll it to fail fast
        self.start_error: Optional[BaseException] = None
        self._backend_thread: Optional[threading.Thread] = None

    def _device_ok(self) -> bool:
        """A device-backed algorithm may open its loop only once the
        backend came up; False (already logged at error) otherwise."""
        self._backend_thread.join()
        return self.start_error is None

    def start(self) -> "SchedulerServer":
        opts = self.options
        # config introspection (server.go:72-76: configz.New +
        # InstallHandler; served at the shared mux's /configz)
        from kubernetes_tpu.utils import configz

        configz.install("componentconfig", opts)
        # compile-vs-execute attribution must be listening before the
        # first jit fires (warmup included)
        from kubernetes_tpu.trace import profile as trace_profile

        trace_profile.install_compile_listener()
        # the daemon's own mux (reference :10251): metrics/healthz no
        # longer depend on riding the apiserver's shared mux
        if opts.serve_port is not None:
            from kubernetes_tpu.trace.httpd import start_component_server

            try:
                self._health_server, bound = start_component_server(
                    opts.serve_address, opts.serve_port, name="scheduler"
                )
                self.health_address = (opts.serve_address, bound)
            except OSError as e:
                # a sandbox that forbids socket binding must not turn
                # the optional metrics mux into a daemon boot failure
                log.warning("observability mux failed to bind: %s", e)
                self._health_server = None
        # start device-backend initialization NOW: it costs seconds and
        # otherwise lands serially inside the first warmup/wave; the
        # thread spends its time in backend calls (GIL released), so it
        # overlaps informer sync and watch ingest. The platform is
        # whatever JAX_PLATFORMS says from outside. The daemon says
        # which device it got, and a backend that fails to initialize
        # keeps a device-backed daemon from ever reporting ready
        # (_device_ok) — it is never a debug line.
        def _init_backend():
            try:
                import jax

                devs = jax.devices()
                log.info(
                    "scheduler device backend: platform=%s "
                    "device_kind=%s count=%d",
                    devs[0].platform, devs[0].device_kind, len(devs))
            except Exception as e:
                self.start_error = e
                log.error("device backend init failed", exc_info=True)

        self._backend_thread = threading.Thread(
            target=_init_backend, daemon=True, name="sched-backend-init"
        )
        self._backend_thread.start()
        matrix = None
        if opts.throughput_matrix_file:
            import json as _json

            try:
                with open(opts.throughput_matrix_file) as f:
                    matrix = _json.load(f)
            except (OSError, ValueError):
                log.warning("unreadable throughput matrix %r; gangs "
                            "schedule without the heterogeneity term",
                            opts.throughput_matrix_file)
        self.factory = ConfigFactory(
            self.client,
            scheduler_name=opts.scheduler_name,
            hard_pod_affinity_weight=opts.hard_pod_affinity_symmetric_weight,
            failure_domains=opts.failure_domains,
            throughput_matrix=matrix,
            accel_label_key=opts.accel_label_key,
        )
        self.factory.run_components()

        # createConfig (server.go:163): policy file wins over provider
        if opts.policy_config_file:
            config = self.factory.create_from_config(
                load_policy(opts.policy_config_file)
            )
        else:
            config = self.factory.create_from_provider(opts.algorithm_provider)

        # event broadcaster -> apiserver (server.go:117-120)
        self._broadcaster = EventBroadcaster()
        self._broadcaster.start_recording_to_sink(EventSink(self.client))
        config.recorder = self._broadcaster.new_recorder("scheduler")

        # SLO watchdog: e2e latency sampled against the objective, with
        # breaches emitted as Warning Events through the same recorder
        if opts.slo_objective_seconds > 0:
            from kubernetes_tpu.trace.slo import SLOWatchdog

            self._slo = SLOWatchdog(
                config.recorder,
                opts.slo_objective_seconds,
                interval=opts.slo_check_interval,
            ).run()

        # continuous telemetry (telemetry/): the process collector
        # behind this mux's /debug/telemetry endpoints. ensure_default
        # is idempotent — whoever attached first owns shutdown.
        from kubernetes_tpu import telemetry
        from kubernetes_tpu.telemetry import scrape as telemetry_scrape

        if telemetry.enabled() and self._health_server is not None:
            self._telemetry_owned = telemetry_scrape.default() is None
            self._telemetry = telemetry_scrape.ensure_default(
                "scheduler",
                slo_seconds=(opts.slo_objective_seconds
                             if opts.slo_objective_seconds > 0 else 5.0),
                recorder=config.recorder,
            )

        self.scheduler = Scheduler(config)
        if not opts.leader_elect:
            # compile the TPU wave programs off the hot path: wait for
            # the node informer to sync (cluster size sets the program
            # shapes), warm up, then open the scheduling loop. Pods
            # arriving meanwhile queue in the FIFO.
            def _warm_then_run():
                algo = config.algorithm
                if hasattr(algo, "warmup"):
                    if not self._device_ok():
                        return
                    # run_components() already waited for informer sync,
                    # so an empty lister means a genuinely empty cluster:
                    # open the loop immediately and compile on demand
                    # rather than stalling queued pods on a made-up shape.
                    # Same when a backlog is ALREADY waiting: the first
                    # real wave compiles exactly the shapes it needs, so
                    # a synthetic warmup would only delay it
                    # the queue check must see the reflector's initial
                    # list, not race it
                    self.factory.unassigned_reflector.wait_for_sync(
                        timeout=10
                    )
                    nodes = self.factory.node_lister.list()
                    n = len(nodes)
                    # warmup only pays off for a genuinely idle daemon:
                    # if work arrives within the grace window, the first
                    # real wave compiles/loads exactly the shapes it
                    # needs (persistently cached across restarts) and a
                    # synthetic warmup would just delay it while
                    # competing for the interpreter
                    idle = True
                    if n:
                        # short grace: warmup now opens the loop after
                        # its first (run-path) phase, so the cost of a
                        # wrong "idle" guess shrank from the whole
                        # program set to the template-path slice — and
                        # every 100ms spent waiting here is 100ms the
                        # cold-start doesn't overlap with pod creation
                        deadline = time.time() + 0.3
                        while time.time() < deadline:
                            if len(self.factory.pod_queue) > 0:
                                idle = False
                                break
                            time.sleep(0.05)
                    if n and idle:
                        try:
                            algo.warmup(n, phase="run", nodes=nodes)
                        except Exception as e:
                            # the programs every wave needs did not
                            # compile or run on this device: never
                            # report ready over a broken device path
                            # (logged before a waiter can see it)
                            log.error("warmup failed; scheduler will "
                                      "not report ready", exc_info=True)
                            self.start_error = e
                            return

                        def _scan_phase():
                            # the scan-path programs only matter for
                            # heterogeneous backlogs; warm them only
                            # after SUSTAINED idleness — warmup holds
                            # the algorithm lock for the whole compile,
                            # and firing in the momentary gap between
                            # loop-open and the first wave blocked that
                            # wave ~10s behind a scan compile it didn't
                            # need. "Idle" = queue empty AND no wave in
                            # flight (a drained wave leaves the queue
                            # empty while still computing).
                            import time as _t

                            lock = getattr(algo, "_sched_lock", None)
                            idle_since = _t.monotonic()
                            stop = self.scheduler.config.stop_everything
                            while not stop.is_set():
                                busy = len(self.factory.pod_queue) > 0
                                if not busy and lock is not None:
                                    if lock.acquire(blocking=False):
                                        lock.release()
                                    else:
                                        busy = True  # wave in flight
                                if busy:
                                    idle_since = _t.monotonic()
                                elif _t.monotonic() - idle_since >= 5.0:
                                    try:
                                        algo.warmup(n, phase="scan",
                                                    nodes=nodes)
                                    except Exception:
                                        log.error(
                                            "scan warmup failed",
                                            exc_info=True,
                                        )
                                    return
                                time.sleep(0.5)

                        threading.Thread(
                            target=_scan_phase, daemon=True,
                            name="sched-warmup-scan",
                        ).start()
                self._thread = self.scheduler.run()
                self.ready.set()

            threading.Thread(
                target=_warm_then_run, daemon=True, name="sched-warmup"
            ).start()
            return self

        # leader election (server.go:140-157): run() schedules only while
        # holding the lease; losing it stops the world (crash-restart)
        def _lead():
            if hasattr(config.algorithm, "warmup") and not self._device_ok():
                return
            self._thread = self.scheduler.run()
            self.ready.set()

        identity = opts.leader_elect_identity or f"scheduler-{id(self):x}"
        self._elector = LeaderElector(
            self.client,
            opts.lock_object_namespace,
            opts.lock_object_name,
            identity,
            lease_duration=opts.leader_elect_lease_duration,
            renew_deadline=opts.leader_elect_renew_deadline,
            retry_period=opts.leader_elect_retry_period,
            on_started_leading=_lead,
            on_stopped_leading=self._lost_lease,
        )
        threading.Thread(target=self._elector.run, daemon=True).start()
        return self

    def _lost_lease(self) -> None:
        log.error("lost leader lease; stopping scheduler (restart to rejoin)")
        if self.scheduler is not None:
            self.scheduler.stop()

    def is_leader(self) -> bool:
        return self._elector is None or self._elector.is_leader()

    def stop(self) -> None:
        from kubernetes_tpu.utils import configz

        configz.delete("componentconfig")
        if self._slo is not None:
            self._slo.stop()
        if self._telemetry is not None and self._telemetry_owned:
            from kubernetes_tpu.telemetry import scrape as telemetry_scrape

            telemetry_scrape.release_default(self._telemetry)
            self._telemetry = None
        if self._health_server is not None:
            self._health_server.shutdown()
            self._health_server.server_close()
            self._health_server = None
        if self._elector is not None:
            self._elector.stop()
        if self.scheduler is not None:
            self.scheduler.stop()
        if self.factory is not None:
            self.factory.stop()
        if getattr(self, "_broadcaster", None) is not None:
            self._broadcaster.shutdown()
