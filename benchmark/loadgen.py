"""The load generator: one child process that creates pods, watches them
get bound, and deletes the oldest to hold the population.

    python3 benchmark/loadgen.py --server URL --config FILE \
        --traffic FILE --seed N --seconds S --out RECORD.json

Copied from harness/soak.py's creator / observer / churn loops and
harness/creator.py's bulk create, with three changes: it is a process
of its own (the soak ran them as threads beside the scheduler, under
its GIL), everything random comes from --seed, and the open loop times
a pod from the tick it was DUE at, not from when it was sent.

It imports the program's REST client and nothing else of the program:
no jax, no scheduler, no apiserver (a test pins this). Two modes, from
the traffic file:

  closed  `workers` creators send `chunk`-pod bulk creates back to
          back, and wait while the unbound backlog is at `backlog_cap`
  open    arrivals at `rate_per_s` in `tick_ms` bulk ticks on a fixed
          schedule; the tick sizes are the Poisson law's quantiles, the
          same multiset for every seed, in an order from the seed

Phases: prefill to the population through the scheduler, `warm_s` of
the cell's own traffic, the window, a drain, then the check batch.
The parent learns the window from one line on stdout,
`WINDOW <t0> <t1>` (epoch seconds), printed before t0.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import deploy  # noqa: E402

PODS_PATH = "/api/v1/namespaces/default/pods"
#: after the window an acknowledged pod has this long to get bound
DRAIN_S = 15.0
#: and the cluster this long after that to come to rest for the check
SETTLE_S = 60.0


def percentile(sorted_values, q: float):
    """Nearest rank on values already sorted; None of no values."""
    if not sorted_values:
        return None
    k = max(0, min(len(sorted_values) - 1,
                   int(-(-q * len(sorted_values) // 1)) - 1))
    return sorted_values[k]


def tick_sizes(rate_per_s: float, tick_ms: int, seed: int,
               block: int) -> list:
    """The arrivals of one 10 s block, as pods per tick: the Poisson
    law's own quantiles at the midpoints of as many equal shares as the
    block has ticks, scaled to the rate exactly and shuffled by
    (--seed, block). Every seed offers the same multiset of ticks, in
    an order of its own: a seed changes when a burst comes, never how
    much work a run has."""
    ticks = int(10_000 // tick_ms)
    mean = rate_per_s * tick_ms / 1000.0
    sizes, n, below, log_p = [], 0, 0.0, -mean
    for i in range(ticks):
        # smallest n with P(X <= n) >= (i + 0.5) / ticks
        while below + math.exp(log_p) < (i + 0.5) / ticks:
            below += math.exp(log_p)
            n += 1
            log_p += math.log(mean / n)
        sizes.append(n)
    want = int(round(rate_per_s * 10))
    i = 0
    while sum(sizes) != want:  # spread the rounding error, one pod a tick
        step = 1 if sum(sizes) < want else -1
        if sizes[i % ticks] + step >= 0:
            sizes[i % ticks] += step
        i += 1
    random.Random(seed * 1_000_003 + block).shuffle(sizes)
    return sizes


def template_order(cfg: dict, seed: int) -> list:
    """The order the controllers take turns in, from the seed."""
    order = list(range(deploy.num_templates(cfg)))
    random.Random(seed).shuffle(order)
    return order


def note(msg: str) -> None:
    print(f"[loadgen] {msg}", file=sys.stderr, flush=True)


def check_backlog(cfg: dict, check: dict, seed: int) -> list:
    """The seeded batch `correct` is decided on: template numbers."""
    rng = random.Random(seed ^ 0x5EED)
    n = deploy.num_templates(cfg)
    return [rng.randrange(n) for _ in range(int(check["pods"]))]


class Generator:
    def __init__(self, url: str, cfg: dict, traffic: dict, seed: int,
                 seconds: float):
        from kubernetes_tpu.client.rest import RESTClient
        from kubernetes_tpu.client.transport import HTTPTransport

        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.population = int(cfg["pods"]["population"])
        self.drain_s = float(traffic.get("drain_s", DRAIN_S))
        # the closed loop's gate; an open loop's prefill goes through
        # the same one, at the same sizes
        self.cap = int(traffic["backlog_cap"])
        # bound pods are held at the population less the backlog cap,
        # so that bound and unbound together never pass the population
        self.hold = self.population - self.cap
        self.chunk = int(traffic.get("chunk", 1500))
        self.order = template_order(cfg, seed)

        def client(user, binary=True):
            return RESTClient(HTTPTransport(url, binary=binary,
                                            timeout=180.0, user=user))

        # a named tenant flow for the workload (the apiserver may queue
        # it); the watch and the read-back are the measuring apparatus
        self.creators = [client("perf-creator")
                         for _ in range(int(traffic.get("workers", 4)))]
        self.watcher = client("system:kube-scheduler")
        self.churner = client("perf-creator")
        self.plain = client("system:kube-scheduler", binary=False)

        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.creating = threading.Event()   # creators may send
        self.churning = threading.Event()   # the churn may delete
        self.churning.set()
        self.prefilled = threading.Event()  # closed workers of an open
        #                                     loop's prefill may leave
        self.limit = None                   # closed loop: pods to send
        self.sends = []       # (sent_at, pods) per request that left
        self.refusals = []    # (sent_at, pods) refused or errored
        self.serial = 0                     # pods handed to a request
        self.sent = 0                       # pods in requests that left
        self.acked = 0
        self.refused = 0                    # per-item failures + errors
        self.bound = 0
        self.deleted = 0
        self.double_bound = 0
        self.waiting = {}     # name -> (due, template): acked, unbound
        self.early = {}       # name -> (seen, node): bound before its ack
        self.node_of = {}     # name -> node, every live bound pod
        self.bound_order = collections.deque()  # (name) oldest first
        self.bind_times = []  # (seen, due) per bound pod
        self.acks = []        # (sent_at, round_trip_s, pods) per request
        self.late = []        # (due, sent_at - due) per open-loop tick
        self.errors = []
        self.relists = 0
        self.events = 0
        self._stream = None   # the observer's live watch

    # -- creating -------------------------------------------------------------

    def _body(self, start: int, n: int):
        """The bulk-create body for pods start..start+n: template by
        turn, named by serial (a generated name's five characters
        collide at these populations, and a collision is a refused
        create)."""
        from kubernetes_tpu.runtime import binary as bin_codec

        turns = len(self.order)
        items = []
        for j in range(start, start + n):
            t = self.order[j % turns]
            items.append(deploy.pod(self.cfg, t,
                                    name=f"p-t{t}-{j:08d}"))
        return bin_codec.encode({"kind": "List", "items": items})

    def create(self, client, start: int, n: int, due: float) -> list:
        """One bulk create of pods start..start+n, all due at `due`.
        -> the names the server gave them."""
        turns = len(self.order)
        body = self._body(start, n)
        sent_at = time.time()
        with self.lock:
            self.sent += n
            self.sends.append((sent_at, n))
        try:
            payload = client.do_raw("POST", PODS_PATH, body=body)
        except Exception as e:  # the generator must outlive a bad reply
            with self.lock:
                self.refused += n
                self.refusals.append((sent_at, n))
                self.errors.append(f"create: {e!r}"[:300])
            return []
        now = time.time()
        names = []
        with self.lock:
            self.acks.append((sent_at, now - sent_at))
            for j, r in enumerate(payload.get("items", [])):
                if r.get("status") != "Success":
                    self.refused += 1
                    self.refusals.append((sent_at, 1))
                    self.errors.append(
                        f"create item: {r.get('message', r)}"[:300])
                    continue
                name = r["name"]
                names.append(name)
                t = self.order[(start + j) % turns]
                self.acked += 1
                seen = self.early.pop(name, None)
                if seen is not None:
                    self._bound(name, seen[1], seen[0], due)
                else:
                    self.waiting[name] = (due, t)
        return names

    def backlog(self) -> int:
        """Pods sent and neither bound nor refused yet."""
        return self.sent - self.refused - self.bound

    def in_system(self) -> int:
        """Pods sent and not deleted yet. The closed loop holds it at
        the population, so that a churn that lags pauses the creators
        instead of filling the cluster: a pod that fits nowhere is a
        failed operation, and the traffic has none. The daemon learns
        of a delete some tenths of a second late, so the population has
        to stay thousands of pods under what the nodes hold."""
        return self.sent - self.refused - self.deleted

    def closed_worker(self, client) -> None:
        cap, chunk = self.cap, min(self.chunk, self.cap)
        while not (self.stop.is_set() or self.prefilled.is_set()):
            if (not self.creating.is_set() or self.backlog() + chunk > cap
                    or self.in_system() + chunk > self.population):
                time.sleep(0.002)
                continue
            limit = self.limit
            with self.lock:
                room = chunk if limit is None else min(
                    chunk, limit - self.serial)
                if room <= 0:
                    start = None
                else:
                    start, self.serial = self.serial, self.serial + room
            if start is None:
                time.sleep(0.002)
                continue
            self.create(client, start, room, time.time())

    def open_clock(self, origin: float) -> None:
        """Ticks on a fixed schedule from `origin`; each tick's pods go
        to a sender thread, so a slow reply delays no later tick."""
        import queue

        tick_s = int(self.traffic["tick_ms"]) / 1000.0
        work = queue.Queue()

        def sender(client):
            while True:
                item = work.get()
                if item is None:
                    return
                start, n, due = item
                sent_at = time.time()
                with self.lock:
                    self.late.append((due, sent_at - due))
                self.create(client, start, n, due)

        senders = [threading.Thread(target=sender, args=(c,), daemon=True)
                   for c in self.creators]
        for s in senders:
            s.start()
        k = 0
        per_block = int(10_000 // int(self.traffic["tick_ms"]))
        sizes = []
        while not self.stop.is_set() and self.creating.is_set():
            if k % per_block == 0:
                sizes = tick_sizes(
                    float(self.traffic["rate_per_s"]),
                    int(self.traffic["tick_ms"]), self.seed,
                    k // per_block)
            due = origin + k * tick_s
            delay = due - time.time()
            if delay > 0:
                time.sleep(delay)
            if not self.creating.is_set():
                break
            n = sizes[k % per_block]
            if n:
                with self.lock:
                    start, self.serial = self.serial, self.serial + n
                work.put((start, n, due))
            k += 1
        for _ in senders:
            work.put(None)
        for s in senders:
            s.join(timeout=self.drain_s)

    # -- observing ------------------------------------------------------------

    def _bound(self, name, node, seen, due) -> None:
        # caller holds the lock
        self.bound += 1
        self.node_of[name] = node
        self.bound_order.append(name)
        self.bind_times.append((seen, due))

    def _saw(self, name: str, node: str, seen: float) -> None:
        # caller holds the lock
        had = self.node_of.get(name)
        if had is not None:
            if had != node:
                self.double_bound += 1
            return
        entry = self.waiting.pop(name, None)
        if entry is not None:
            self._bound(name, node, seen, entry[0])
        elif name not in self.early:
            self.early[name] = (seen, node)

    def observer(self) -> None:
        """The created->bound probe: one watch of bound pods
        (spec.nodeName!=), which shows a pod the moment its binding
        commits. A broken stream relists, as the soak's did."""
        pods = self.watcher.pods()
        first = True
        while not self.stop.is_set():
            try:
                if not first:
                    self.relists += 1
                objs, rv = pods.list(field_selector="spec.nodeName!=")
                now = time.time()
                with self.lock:
                    for p in objs:
                        self._saw(p.metadata.name, p.spec.node_name, now)
                first = False
                stream = pods.watch(resource_version=rv,
                                    field_selector="spec.nodeName!=")
                self._stream = stream
                for ev_type, obj in stream:
                    if self.stop.is_set():
                        return
                    self.events += 1
                    if ev_type == "DELETED":
                        continue
                    now = time.time()
                    with self.lock:
                        self._saw(obj.metadata.name, obj.spec.node_name,
                                  now)
            except Exception as e:
                if self.stop.is_set():
                    return
                self.errors.append(f"observer: {e!r}"[:300])
                time.sleep(0.2)

    # -- churning -------------------------------------------------------------

    def churn(self) -> None:
        """Delete the oldest bound pods, through the batch door, down
        to `hold`. And further, while the pods in the system are over
        the population: an open loop
        cannot wait, so when the scheduler stalls for a few seconds
        the cluster is kept from filling by taking bound pods out (a
        full cluster sends every later wave into the program's
        per-pod failure path, and the run never recovers). The closed
        loop's own gate keeps it under that line."""
        while not self.stop.is_set():
            victims = []
            if not self.churning.is_set():
                time.sleep(0.05)
                continue
            with self.lock:
                extra = max(len(self.bound_order) - self.hold,
                            self.in_system() - self.population)
                while extra > 0 and len(victims) < 2048:
                    victims.append(self.bound_order.popleft())
                    extra -= 1
            if not victims:
                time.sleep(0.05)
                continue
            try:
                self.churner.commit_batch([
                    {"op": "delete", "resource": "pods",
                     "namespace": "default", "name": nm} for nm in victims])
                with self.lock:
                    self.deleted += len(victims)
                    for nm in victims:
                        self.node_of.pop(nm, None)
            except Exception as e:
                self.errors.append(f"churn: {e!r}"[:300])
                time.sleep(0.2)

    # -- the run --------------------------------------------------------------

    def wait_for(self, cond, timeout: float) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if cond():
                return True
            time.sleep(0.01)
        return cond()

    def read_back(self) -> dict:
        """Every pod as the apiserver holds it, over plain JSON HTTP:
        name -> node ('' while unbound)."""
        payload = self.plain.do_raw("GET", PODS_PATH)
        return {i["metadata"]["name"]: i.get("spec", {}).get("nodeName", "")
                or "" for i in payload.get("items", [])}

    def run(self, say) -> dict:
        closed = self.traffic["loop"] == "closed"
        threads = [threading.Thread(target=self.observer, daemon=True),
                   threading.Thread(target=self.churn, daemon=True)]
        # prefill is always a closed loop: the population goes through
        # the scheduler as fast as it takes it
        self.limit = self.hold
        workers = [threading.Thread(target=self.closed_worker, args=(c,),
                                    daemon=True) for c in self.creators]
        for t in threads + workers:
            t.start()
        t_start = time.time()
        self.creating.set()
        if not self.wait_for(lambda: self.bound >= self.hold,
                             float(self.traffic.get("prefill_timeout_s",
                                                    300))):
            raise RuntimeError(
                f"prefill stalled: {self.bound}/{self.hold} bound; "
                f"{self.errors[-3:]}")
        prefill_s = time.time() - t_start
        note(f"prefilled {self.bound} pods in {prefill_s:.2f}s")
        warm = float(self.traffic.get("warm_s", 3.0))
        t0 = time.time() + warm
        t1 = t0 + self.seconds
        clock = None
        if closed:
            self.limit = None  # the workers run on, now without an end
        else:
            self.creating.clear()
            self.prefilled.set()
            # the open loop starts on the schedule the window is cut from
            clock = threading.Thread(
                target=self.open_clock, args=(time.time() + 0.05,),
                daemon=True)
            self.creating.set()
            clock.start()
        say(f"WINDOW {t0!r} {t1!r}")
        time.sleep(max(0.0, t1 - time.time()))
        self.creating.clear()
        if clock is not None:
            clock.join(timeout=self.drain_s)
        # drain: everything acknowledged gets its binding, or the
        # deadline passes and it counts as failed
        drained = self.wait_for(
            lambda: self.backlog() <= 0 and not self.waiting, self.drain_s)
        drain_s = time.time() - t1
        with self.lock:
            unbound_at_deadline = dict(self.waiting)
            bind_times = list(self.bind_times)
            acks, late = list(self.acks), list(self.late)
            sends, refusals = list(self.sends), list(self.refusals)
        # the check wants a cluster at rest: a pod that missed the
        # deadline has failed, and may still get its binding; then the
        # churn comes down to its line and stops
        settled = self.wait_for(
            lambda: self.backlog() <= 0 and not self.waiting, SETTLE_S) \
            and self.wait_for(
                lambda: len(self.bound_order) <= self.hold
                and self.deleted + len(self.bound_order) >= self.bound,
                SETTLE_S)
        self.churning.clear()
        note(f"drained={drained} in {drain_s:.2f}s, at rest={settled} "
             f"{time.time() - t1:.2f}s after the window; unbound at the "
             f"deadline {len(unbound_at_deadline)}, backlog "
             f"{self.backlog()}, waiting {len(self.waiting)}, bound "
             f"{self.bound}, deleted {self.deleted}, live "
             f"{len(self.bound_order)}")
        record = self.reduce(t0, t1, bind_times, acks, late,
                             unbound_at_deadline, sends, refusals)
        record.update(prefill_s=prefill_s, drain_s=drain_s,
                      drained=bool(drained), at_rest=bool(settled))
        record["check"] = self.check()
        self.stop.set()
        if self._stream is not None:
            self._stream.stop()
        record.update(errors=self.errors[:20], relists=self.relists,
                      watch_events=self.events, acked=self.acked,
                      refused=self.refused, bound=self.bound,
                      deleted=self.deleted, double_bound=self.double_bound,
                      live=dict(self.node_of))
        return record

    def reduce(self, t0, t1, bind_times, acks, late, unbound, sends,
               refusals) -> dict:
        """The window's numbers, all from this process's own clock."""
        closed = self.traffic["loop"] == "closed"
        bound_in = sum(1 for seen, _ in bind_times if t0 <= seen < t1)
        due_in = [(seen, due) for seen, due in bind_times if t0 <= due < t1]
        lat = sorted((seen - due) * 1000.0 for seen, due in due_in)
        unbound_in = sum(1 for due, _ in unbound.values() if t0 <= due < t1)
        refused_in = sum(n for at, n in refusals if t0 <= at < t1)
        if closed:
            attempted = sum(n for at, n in sends if t0 <= at < t1)
            unbound_in = sum(1 for due, _ in unbound.values() if due < t1)
        else:
            attempted = len(due_in) + unbound_in + refused_in
        # a pod that missed the drain deadline has no binding to time:
        # it waited to the deadline at the least, and takes that place
        lat_all = sorted(lat + [(t1 + self.drain_s - due) * 1000.0
                                for due, _ in unbound.values()
                                if t0 <= due < t1])
        rtts = sorted(rt * 1000.0 for at, rt in acks if t0 <= at < t1)
        lates = sorted(d * 1000.0 for due, d in late if t0 <= due < t1)
        bind_seen = [round(seen - t0, 4) for seen, _ in bind_times
                     if t0 <= seen < t1]
        by_tenth = collections.Counter(
            min(int(10 * s / (t1 - t0)), 9) for s in bind_seen)
        return {
            "t0": t0, "t1": t1, "loop": self.traffic["loop"],
            "bound_in_window": bound_in,
            "pods_bound_per_s": bound_in / (t1 - t0),
            "attempted": attempted,
            "failed": refused_in + unbound_in,
            "unbound_at_deadline": unbound_in,
            "latency_samples": len(lat_all),
            "bind_latency_p50_ms": percentile(lat_all, 0.50),
            "bind_latency_p95_ms": percentile(lat_all, 0.95),
            "bind_latency_p99_ms": percentile(lat_all, 0.99),
            "bind_latency_max_ms": lat_all[-1] if lat_all else None,
            "create_ack_p50_ms": percentile(rtts, 0.50),
            "create_requests": len(rtts),
            "loadgen_late_p99_ms": percentile(lates, 0.99),
            "bind_seen": bind_seen,
            # how evenly the window's bindings came: a count per tenth
            "bound_by_tenth": [by_tenth.get(k, 0) for k in range(10)],
        }

    def check(self) -> dict:
        """After the drain, with nothing else in flight: read the
        cluster back, send the seeded check batch through the same
        door, and read back where the daemon put it."""
        spec = self.traffic["check"]
        before = self.read_back()
        backlog = check_backlog(self.cfg, spec, self.seed)
        # named outright and created in one request: the daemon meets
        # them in this order
        items = [deploy.pod(self.cfg, t, name=f"check-{i:05d}")
                 for i, t in enumerate(backlog)]
        sent_at = time.time()
        payload = self.creators[0].do_raw(
            "POST", PODS_PATH, body={"kind": "List", "items": items})
        ok = [r.get("status") == "Success"
              for r in payload.get("items", [])]
        names = [f"check-{i:05d}" for i in range(len(backlog))]
        with self.lock:
            for nm, t in zip(names, backlog):
                seen = self.early.pop(nm, None)
                self.acked += 1
                self.sent += 1
                if seen is not None:
                    self._bound(nm, seen[1], seen[0], sent_at)
                else:
                    self.waiting[nm] = (sent_at, t)
        self.wait_for(lambda: not self.waiting, 2 * self.drain_s)
        after = self.read_back()
        return {
            "backlog": backlog,
            "created": sum(ok),
            "seconds": time.time() - sent_at,
            "before": before,
            "after": after,
            "picks": [after.get(nm, "") for nm in names],
            "names": names,
        }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--server", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    def say(line: str) -> None:
        print(line, flush=True)

    gen = Generator(args.server, deploy.load_json(args.config),
                    deploy.load_json(args.traffic), args.seed, args.seconds)
    record = gen.run(say)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, args.out)
    say("DONE")


if __name__ == "__main__":
    main()
