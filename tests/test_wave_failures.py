"""A wave's unschedulable pods on a full cluster: explained once per
template, re-queued by one worker. A door stall of seconds lets an open
loop overfill the cluster; a pass of the host predicates per failed pod
and a thread per failed pod then held the loop while the deletes that
would have made room waited (PERF.md section 6, PR 27)."""

import threading
import time

import pytest

from kubernetes_tpu.api import types as t
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client import LocalTransport, RESTClient
from kubernetes_tpu.oracle.scheduler import FitError, GenericScheduler
from kubernetes_tpu.oracle.state import ClusterState
from kubernetes_tpu.scheduler import core
from kubernetes_tpu.scheduler.factory import ConfigFactory

from conftest import wait_until  # noqa: E402


def pod(name, node="", cpu="100m", labels=None):
    return t.Pod(
        metadata=t.ObjectMeta(name=name, namespace="default",
                              labels=labels or {"name": "sched-perf"}),
        spec=t.PodSpec(
            node_name=node,
            containers=[t.Container(
                name="pause", requests={"cpu": cpu, "memory": "500Mi"})],
        ),
    )


def node(name, cpu="1"):
    return t.Node(
        metadata=t.ObjectMeta(name=name),
        status=t.NodeStatus(
            allocatable={"cpu": cpu, "memory": "32Gi", "pods": "110"},
            conditions=[t.NodeCondition("Ready", "True")],
        ),
    )


def full_state(nodes=8):
    """Every node full in cpu but the last, which the affinity
    predicate therefore reaches."""
    ns = [node(f"n{i}") for i in range(nodes)]
    assigned = [pod(f"a{i}-{j}", node=f"n{i}")
                for i in range(nodes - 1) for j in range(10)]
    return ClusterState.build(ns, assigned)


class _NothingFits:
    def schedule_backlog(self, pods, state):
        return [None] * len(pods)


def _scheduler(**kw):
    return core.Scheduler(core.SchedulerConfig(
        algorithm=_NothingFits(), **kw))


class TestExplainedOncePerTemplate:
    def test_one_pass_per_feature_key(self, monkeypatch):
        passes = []
        real = GenericScheduler.find_nodes_that_fit

        def counted(self, p, state):
            passes.append(p.metadata.name)
            return real(self, p, state)

        monkeypatch.setattr(GenericScheduler, "find_nodes_that_fit", counted)
        state = full_state()
        wave = ([pod(f"p{i}") for i in range(50)]
                + [pod(f"q{i}", cpu="200m") for i in range(50)]
                + [pod("r0", labels={"name": "other"})])
        hosts, errors = _scheduler()._schedule_wave(wave, state)
        assert hosts == [None] * 101
        assert passes == ["p0", "q0", "r0"]  # the first of each template
        assert sorted(errors) == list(range(101))

    @pytest.mark.parametrize("cpu", ["100m", "200m", "2"])
    def test_errors_equal_a_pass_per_pod(self, cpu):
        state = full_state()
        wave = [pod(f"p{i}", cpu=cpu) for i in range(5)]
        sched = _scheduler()
        _, errors = sched._schedule_wave(wave, state)
        for i, p in enumerate(wave):
            alone = sched._explain_failure(p, state, {})  # a pass of its own
            assert isinstance(errors[i], FitError)
            assert errors[i].pod is p
            assert errors[i].failed_predicates == alone.failed_predicates
            assert str(errors[i]) == str(alone)
            assert f"pod ({p.name})" in str(errors[i])

    def test_detail_is_the_message_less_its_first_line(self):
        failed = {"n1": "b", "n0": "a"}
        e = FitError(pod("x"), failed)
        assert e.detail == ("fit failure on node (n0): a\n"
                            "fit failure on node (n1): b")
        again = FitError(pod("y"), failed, e.detail)
        assert str(again) == str(e).replace("(x)", "(y)")

    def test_assigned_pods_listed_once_a_pass(self):
        state = full_state()
        calls = []
        real = state.all_assigned_pods

        def counted():
            calls.append(1)
            return real()

        state.all_assigned_pods = counted
        once = core._AssignedOnce(state)
        fits, failed = GenericScheduler().find_nodes_that_fit(pod("p"), once)
        want_fits, want_failed = GenericScheduler().find_nodes_that_fit(
            pod("p"), full_state())
        assert (fits, failed) == (want_fits, want_failed)
        assert fits == ["n7"] and len(calls) == 1
        assert once.node_infos is state.node_infos


class TestRequeueWorker:
    def _factory(self):
        server = APIServer()
        client = RESTClient(LocalTransport(server))
        factory = ConfigFactory(client)
        factory.pod_backoff.initial = 0.05
        return client, factory

    def test_a_wave_of_failures_starts_one_thread(self):
        client, factory = self._factory()
        handle = factory._make_error_handler()
        pods = [pod(f"p{i}") for i in range(300)]
        for p in pods[:200]:  # the last hundred are deleted meanwhile
            client.pods().create(p)
        client.pods().create(pod("bound", node="n0"))
        before = threading.active_count()
        t0 = time.monotonic()
        for p in pods + [pod("bound")]:
            handle(p, RuntimeError("fits nowhere"))
        assert time.monotonic() - t0 < 1.0
        assert threading.active_count() <= before + 1
        try:
            assert wait_until(lambda: len(factory.pod_queue) == 200,
                              timeout=20.0), len(factory.pod_queue)
            time.sleep(0.2)
            queued = {p.metadata.name for p in factory.pod_queue.list()}
            assert queued == {f"p{i}" for i in range(200)}
        finally:
            factory._stopped = True

    def test_backoff_doubles_and_orders_the_queue(self):
        client, factory = self._factory()
        factory.pod_backoff.initial = 0.3
        handle = factory._make_error_handler()
        slow, fast = pod("slow"), pod("fast")
        client.pods().create(slow)
        client.pods().create(fast)
        factory.pod_backoff.next_("default/slow")  # failed once already
        try:
            handle(slow, RuntimeError("x"))  # due in 0.6 s
            handle(fast, RuntimeError("x"))  # due in 0.3 s: first out
            assert wait_until(lambda: len(factory.pod_queue) == 1,
                              timeout=10.0)
            assert factory.pod_queue.list()[0].metadata.name == "fast"
            assert wait_until(lambda: len(factory.pod_queue) == 2,
                              timeout=10.0)
        finally:
            factory._stopped = True

    def test_nothing_queued_after_stop(self):
        client, factory = self._factory()
        handle = factory._make_error_handler()
        client.pods().create(pod("p"))
        factory._stopped = True
        handle(pod("p"), RuntimeError("x"))
        time.sleep(0.3)
        assert len(factory.pod_queue) == 0


class TestOverfullClusterRecovers:
    def test_every_pod_bound_once_room_is_made(self):
        """Three times what the nodes hold, then the bound pods deleted
        as a churn would: the daemon keeps binding what fits, and the
        pods that fitted nowhere come back and are bound."""
        from kubernetes_tpu.scheduler.server import SchedulerServer

        server = APIServer()
        client = RESTClient(LocalTransport(server))
        for i in range(2):
            client.nodes().create(node(f"n{i}"))  # 10 pods of 100m each
        srv = SchedulerServer(client).start()
        try:
            before = threading.active_count()
            for i in range(60):
                client.pods().create(pod(f"p{i:02d}"))
            seen = set()

            def churn():
                for p in client.pods().list()[0]:
                    if p.spec.node_name:
                        seen.add(p.metadata.name)
                        client.pods().delete(p.metadata.name)
                return len(seen) == 60

            assert wait_until(churn, timeout=60.0), sorted(seen)
            assert client.pods().list()[0] == []
            # one re-queue worker, whatever the number of failures
            assert threading.active_count() <= before + 4
        finally:
            srv.stop()
