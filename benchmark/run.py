"""One run of one cell of BENCHMARK.json, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

This process holds the chip: the scheduler daemon runs inside it, as
chip_smoke.phase_served / harness.perf.schedule_pods_separate run it
(proved on the chip by PR 22), and in a traced run so does the
profiler. The apiserver (`hyperkube apiserver`) and the load generator
(benchmark/loadgen.py) are children that never import jax. A cell is
data: its deployment is benchmark/configs/<config>.json, its traffic
benchmark/traffic/<traffic>.json, and each per-layer metric
`<reader>.<suffix>` is read by benchmark/layers/<reader>.py. Nothing
here names a cell, a deployment or a metric.

The last line of stdout is the result; everything else goes to stderr
or under .bench_out/. There is no flag that lets a run pass without a
TPU: tests call `serve` directly at tiny sizes.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check, deploy, trace_reduce  # noqa: E402

#: the profiler traces this share of the window, starting a quarter in
TRACE_SHARE, TRACE_MAX_S = 0.3, 4.0


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_PROCESS:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def require_tpu(chips: int) -> dict:
    """-> the device as JAX reports it; SystemExit unless it is a TPU
    with the chips the cell asks for (chip_smoke.require_tpu, copied)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < chips:
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s), JAX found {device}; "
            "a device number never comes from anything else")
    return device


class CompileCounter:
    """XLA programs built in this process, and how many of them the
    persistent cache served (chip_smoke.CompileCounter, copied)."""

    def __init__(self):
        from jax import monitoring

        self.built = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.built += 1

    def _event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self):
        return self.built, self.hits


class GcWatch:
    """Pauses of this process's garbage collector, which stop the
    daemon's threads with everything else in it: (generation, start,
    seconds) per collection."""

    def __init__(self):
        import gc

        self.pauses = []
        self._began = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._began = time.time()
        else:
            self.pauses.append((info["generation"], self._began,
                                time.time() - self._began))


def scrape_counters(client) -> dict:
    """The apiserver's counters from its /metrics text, summed over
    label sets (harness.perf._scrape_counters, copied; every counter
    is kept, a reader picks its own)."""
    try:
        code, payload = client.transport.request("GET", "/metrics")
    except Exception:
        return {}
    text = ""
    if isinstance(payload, dict):
        text = payload.get("text") or payload.get("message") or ""
    if code != 200 or not text:
        return {}
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            name_part, value = line.rsplit(" ", 1)
            out_name = name_part.split("{", 1)[0]
            out[out_name] = out.get(out_name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def find_cell(manifest: dict, workload: str) -> tuple:
    """-> (cell, path of its deployment file, path of its traffic file)"""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    return (cell, os.path.join(ROOT, files[cell["config"]]),
            deploy.traffic_path(cell["traffic"]))


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def load_readers(metrics: list, layers_dir: str) -> dict:
    """metric name -> its reader module: <layers_dir>/<name up to the
    first dot>.py."""
    readers = {}
    for m in metrics:
        base = m["name"].split(".", 1)[0]
        spec = importlib.util.spec_from_file_location(
            "benchmark.layers." + base,
            os.path.join(layers_dir, base + ".py"))
        readers[m["name"]] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return readers


def _stop(proc) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def _wait_ready(sched, timeout: float = 1100.0) -> float:
    t0 = time.time()
    while not sched.ready.wait(0.1):
        if sched.start_error is not None:
            raise RuntimeError("scheduler daemon failed to start") \
                from sched.start_error
        if time.time() - t0 > timeout:
            raise RuntimeError(f"scheduler not ready after {timeout:.0f}s")
    return time.time() - t0


def _bulk(client, resource_client, objs, step: int = 2000) -> None:
    for i in range(0, len(objs), step):
        for r in resource_client.create_many(objs[i:i + step]):
            if r.get("status") != "Success":
                raise RuntimeError(f"create failed: {r.get('message', r)}")


def _trace_slice(run: dict, out_dir: str, t0: float, t1: float) -> None:
    """Profile one slice inside the window; it is reduced once the
    run's record is in (`_reduce_trace`)."""
    import jax

    length = min(TRACE_MAX_S, TRACE_SHARE * (t1 - t0))
    begin = t0 + 0.25 * (t1 - t0)
    log_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    time.sleep(max(0.0, begin - time.time()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    started = time.time()
    time.sleep(length)
    stopped = time.time()
    jax.profiler.stop_trace()
    run["trace_pending"] = {"log_dir": log_dir, "t_start": started,
                            "t_stop": stopped}


def _reduce_trace(run: dict, out_dir: str) -> None:
    pending = run.pop("trace_pending")
    trace = trace_reduce.load_xplane(
        trace_reduce.find_xplane(pending["log_dir"]),
        keep=trace_reduce.is_device)
    reduced = trace_reduce.reduce(trace)
    t0 = run["window"]["t0"]
    lo, hi = pending["t_start"] - t0, pending["t_stop"] - t0
    reduced["window_s"] = pending["t_stop"] - pending["t_start"]
    reduced["bound_in_slice"] = sum(
        1 for s in run["loadgen"]["bind_seen"] if lo <= s < hi)
    run["trace"] = reduced
    with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
        json.dump({"describe": trace_reduce.describe(trace),
                   "reduced": reduced}, f)


def serve(cell: dict, cfg_path: str, traffic_path: str, seed: int,
          seconds: float, trace: bool, manifest: dict,
          t_process: float = None,
          layers_dir: str = os.path.join(HERE, "layers")) -> dict:
    """Everything of a run below the look for a chip. -> the result
    object without its `device` key."""
    t_process = time.time() if t_process is None else t_process
    cfg = deploy.load_json(cfg_path)
    traffic = deploy.load_json(traffic_path)
    os.environ.update(cfg["scheduler"].get("env", {}))
    out_dir = os.path.join(ROOT, ".bench_out",
                           f"{cell['name']}-{seed}-{int(trace)}")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(cfg_path, os.path.join(out_dir, "config.json"))

    from kubernetes_tpu.client.rest import RESTClient
    from kubernetes_tpu.client.transport import HTTPTransport
    from kubernetes_tpu.scheduler.server import (
        SchedulerServer,
        SchedulerServerOptions,
    )

    counter = CompileCounter()
    collector = GcWatch()
    per_layer = metrics_of(manifest, "per_layer", cell["name"]) \
        if trace else []
    readers = load_readers(per_layer, layers_dir)
    run = {"cell": cell, "config": cfg, "traffic": traffic, "snapshots": {}}
    api = gen = sched = None
    api_err = open(os.path.join(out_dir, "apiserver.stderr"), "w")
    try:
        api = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.hyperkube", "apiserver"]
            + cfg["apiserver"]["args"],
            stdout=subprocess.PIPE, stderr=api_err, text=True, cwd=ROOT)
        url = api.stdout.readline().strip().rsplit(" ", 1)[-1]
        # the control plane's own identity: node set-up, the daemon and
        # the scrapes never queue behind the creators' flow
        client = RESTClient(HTTPTransport(url, binary=True, timeout=180.0,
                                          user="system:kube-scheduler"))
        deadline = time.time() + 30
        while not client.healthz():
            if time.time() > deadline or api.poll() is not None:
                raise RuntimeError(f"apiserver at {url!r} never came up")
            time.sleep(0.05)
        _bulk(client, client.nodes(), deploy.nodes(cfg))
        _bulk(client, client.resource("replicationcontrollers", "default"),
              deploy.controllers(cfg))
        log(f"apiserver up at {url}: {cfg['nodes']['count']} nodes, "
            f"{cfg['controllers']['count']} controllers")

        sched = SchedulerServer(client, SchedulerServerOptions(
            algorithm_provider=cfg["scheduler"]["provider"])).start()
        ready_s = _wait_ready(sched)
        built, hits = counter.snapshot()
        log(f"daemon ready in {ready_s:.2f}s; programs {built} "
            f"(compiled {built - hits}, from cache {hits})")

        record_path = os.path.join(out_dir, "loadgen.json")
        if os.path.exists(record_path):
            os.remove(record_path)
        gen = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             "--server", url, "--config", cfg_path,
             "--traffic", traffic_path, "--seed", str(seed),
             "--seconds", repr(float(seconds)), "--out", record_path],
            stdout=subprocess.PIPE, text=True, cwd=ROOT)
        window = []
        for line in gen.stdout:
            if line.startswith("WINDOW "):
                window = [float(x) for x in line.split()[1:3]]
                break
        if not window:
            raise RuntimeError(f"load generator exited {gen.wait()} before "
                               "its window")
        t0, t1 = window
        run["window"] = {"t0": t0, "t1": t1, "seconds": t1 - t0}
        setup_s = t0 - t_process

        def snapshot() -> dict:
            ctx = {"sched": sched, "compiles": counter.snapshot(),
                   "gc_pauses": list(collector.pauses),
                   "api_metrics": scrape_counters(client)}
            return {name.split(".", 1)[0]: mod.snapshot(ctx)
                    for name, mod in readers.items()
                    if hasattr(mod, "snapshot")}

        time.sleep(max(0.0, t0 - time.time()))
        compiled_before = counter.snapshot()
        before = snapshot()
        if trace:
            _trace_slice(run, out_dir, t0, t1)
        time.sleep(max(0.0, t1 - time.time()))
        after = snapshot()
        compiled_after = counter.snapshot()
        run["snapshots"] = {k: (before[k], after[k]) for k in before}
        log("window closed; programs built in it: "
            f"{compiled_after[0] - compiled_before[0]} (from cache "
            f"{compiled_after[1] - compiled_before[1]}); collector pauses "
            "over 50 ms in it (generation, at, s): " + str(
                [(g, round(at - t0, 2), round(s, 3))
                 for g, at, s in collector.pauses
                 if t0 <= at < t1 and s > 0.05]))

        for line in gen.stdout:  # drains until the child closes it
            pass
        if gen.wait(timeout=300) != 0:
            raise RuntimeError(f"load generator exited {gen.returncode}")
        run["loadgen"] = deploy.load_json(record_path)
        log("load generator done")
        import jax

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
    finally:
        _stop(gen)
        _stop(api)
        api_err.close()
        if sched is not None:
            t = time.time()
            sched.stop()
            log(f"daemon stopped in {time.time() - t:.2f}s")
        log("daemon and children stopped")

    record = run["loadgen"]
    verdict = check.decide(record, cfg)
    log(f"prefill {record['prefill_s']:.1f}s, drain {record['drain_s']:.2f}s"
        f", check batch {record['check']['seconds']:.2f}s; bound in window "
        f"{record['bound_in_window']}, by tenth {record['bound_by_tenth']}; "
        f"generator errors {record['errors']}")
    log("the generator's numbers: " + json.dumps(
        {k: v for k, v in record.items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}))
    if trace:
        _reduce_trace(run, out_dir)

    measured = {"setup_s": setup_s}
    for m in metrics_of(manifest, "end_to_end", cell["name"]):
        if m["name"] in record:
            measured[m["name"]] = record[m["name"]]
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    if trace:
        metrics = {}
        for m in per_layer:
            run["metric"] = m["name"]  # a reader may serve several names
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = value
        log("end to end, under the profiler: " + json.dumps(measured))
    else:
        metrics = {k: v for k, v in measured.items() if k in units}
    result = {
        "correct": verdict["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": {"memory_peak_bytes": peak},
        "ready_s": ready_s,
        "numbers": verdict["numbers"],
    }
    if trace:
        tr = run["trace"]
        result["device"].update(busy_s=tr["busy_s"],
                                window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = deploy.load_manifest(ROOT)
    cell, cfg_path, traffic_path = find_cell(manifest, args.workload)
    # the deployment's settings for the program, before it is imported
    os.environ.update(deploy.load_json(cfg_path)["scheduler"].get("env", {}))
    import kubernetes_tpu  # noqa: F401  (x64 + compile cache, before jax)

    device = require_tpu(int(cell["chips"]))
    log(f"device {device}; compile cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    result = serve(cell, cfg_path, traffic_path, args.seed, args.seconds,
                   bool(args.trace), manifest, t_process=T_PROCESS)
    result["device"] = {**device, **result["device"]}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
