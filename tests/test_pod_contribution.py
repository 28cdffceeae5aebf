"""A pod's scheduling contribution (oracle/state.py pod_contribution):
what NodeInfo and the incremental snapshot take from an assigned pod,
derived once per template and shared. The plain functions it is built
from (_calculate_resource, pod_nonzero_request, has_pod_affinity,
get_affinity) stay the reference here."""

import copy
import random
import threading

import pytest

from kubernetes_tpu.api.types import (
    AFFINITY_ANNOTATION,
    Container,
    ContainerPort,
    ObjectMeta,
    Pod,
    PodSpec,
    get_affinity,
    has_pod_affinity,
    pod_nonzero_request,
)
from kubernetes_tpu.metrics import scheduler_pod_contribution_lookups_total
from kubernetes_tpu.oracle import state as oracle_state
from kubernetes_tpu.oracle.state import (
    NodeInfo,
    _calculate_resource,
    pod_contribution,
)

from tests.test_conformance import random_scenario


def _lookups():
    c = scheduler_pod_contribution_lookups_total
    return c.get(result="hit"), c.get(result="miss")


def _expected_terms(pod):
    """PodTerms from the parsed affinity, term by term: the namespaces
    a term resolves to for its owner, its selector's content, its key."""
    if not has_pod_affinity(pod):
        return None
    try:
        aff = get_affinity(pod)
    except Exception:
        return (False, (), (), (), (), False, False)

    def term(t):
        names = {pod.namespace} if t.namespaces is None else set(t.namespaces)
        sel = t.label_selector
        canon = None if sel is None else (
            tuple(sorted(sel.match_labels.items())),
            tuple((e.key, e.operator, tuple(e.values))
                  for e in sel.match_expressions))
        return (frozenset(names), canon, t.topology_key)

    def side(s):
        if s is None:
            return (), ()
        return (
            tuple(term(t) for t in
                  s.required_during_scheduling_ignored_during_execution),
            tuple((term(w.pod_affinity_term), w.weight) for w in
                  s.preferred_during_scheduling_ignored_during_execution))

    return (True, *side(aff.pod_affinity), *side(aff.pod_anti_affinity),
            aff.pod_affinity is not None, aff.pod_anti_affinity is not None)


def _expected(pod):
    return (
        *_calculate_resource(pod),
        *pod_nonzero_request(pod),
        tuple(p.host_port for c in pod.spec.containers for p in c.ports
              if p.host_port != 0),
        (pod.namespace, frozenset(pod.metadata.labels.items()),
         pod.metadata.deletion_timestamp is not None),
        _expected_terms(pod),
    )


def _fuzzed_pods(seed):
    """The pods of tests/test_wave.py's feature-key property: one or two
    containers, absent and zero requests, host ports, inter-pod terms as
    annotations, volumes, and both assigned and pending ones."""
    rng = random.Random(seed)
    state, pending = random_scenario(
        rng, n_nodes=6, n_existing=8, n_pending=20,
        interpod_p=0.3, volumes_p=0.3,
    )
    pods = list(pending)
    for info in state.node_infos.values():
        pods.extend(info.pods)
    if pods:
        gone = copy.deepcopy(pods[0])
        gone.metadata.deletion_timestamp = "2026-01-01T00:00:00Z"
        pods.append(gone)
    return pods


@pytest.mark.parametrize("seed", [1234, 1235, 1236, 1237])
def test_contribution_equals_the_plain_functions(seed):
    pods = _fuzzed_pods(seed)
    assert len(pods) > 20
    for pod in pods:
        got = pod_contribution(pod)
        assert tuple(got) == _expected(pod), pod.metadata.name
        # and again from the memo
        assert tuple(pod_contribution(pod)) == _expected(pod)
        for field in got[:5]:
            assert type(field) is int


def test_spec_affinity_is_derived_not_memoised():
    from kubernetes_tpu.api.types import get_affinity

    pods = [p for p in _fuzzed_pods(1234) if has_pod_affinity(p)]
    assert pods, "the fuzz made no inter-pod pod"
    pod = copy.deepcopy(pods[0])
    pod.spec.affinity = get_affinity(pod)  # the direct field: unhashable
    pod.metadata.annotations.pop(AFFINITY_ANNOTATION, None)
    _, misses = _lookups()
    assert tuple(pod_contribution(pod)) == _expected(pod)
    assert pod_contribution(pod).terms.parsed
    assert _lookups()[1] == misses + 2


def _template(name="a", node="", **requests):
    return Pod(
        metadata=ObjectMeta(name=name, labels={"name": "sched-perf"}),
        spec=PodSpec(
            node_name=node,
            containers=[Container(
                requests=requests or {"cpu": "100m", "memory": "500Mi"})],
        ),
    )


def test_name_and_node_are_not_in_the_key():
    first = pod_contribution(_template("pod-1", "node-1", cpu="70m"))
    held = len(oracle_state._CONTRIBUTIONS)
    hits, misses = _lookups()
    for i in range(50):
        again = pod_contribution(_template(f"pod-{i}", f"node-{i}", cpu="70m"))
        assert again is first  # one shared object, one memo entry
    assert len(oracle_state._CONTRIBUTIONS) == held
    assert _lookups() == (hits + 50, misses)


@pytest.mark.parametrize("mutate", [
    lambda p: p.spec.containers[0].requests.__setitem__("cpu", "300m"),
    lambda p: p.spec.containers[0].requests.pop("memory"),
    lambda p: p.metadata.labels.__setitem__("tier", "be"),
    lambda p: setattr(p.metadata, "deletion_timestamp", "2026-01-01T00:00:00Z"),
    lambda p: p.spec.containers[0].ports.append(ContainerPort(host_port=80)),
    lambda p: p.spec.containers.append(Container(requests={"cpu": "1"})),
    lambda p: setattr(p.metadata, "namespace", "other"),
])
def test_a_pod_mutated_after_a_lookup_gets_no_stale_answer(mutate):
    """The key is rebuilt from the pod's fields on every call, so the
    memo never answers for what the pod held before."""
    pod = _template(cpu="111m", memory="64Mi")
    before = pod_contribution(pod)
    assert tuple(before) == _expected(pod)
    mutate(pod)
    after = pod_contribution(pod)
    assert tuple(after) == _expected(pod)
    assert after != before


def test_memo_stays_under_its_bound_over_distinct_pods():
    bound = oracle_state._CONTRIBUTIONS_MAX
    hot = _template("hot", cpu="123m")
    shared = pod_contribution(hot)
    hits, misses = _lookups()
    pod = _template("cold")
    pod.metadata.labels["flood"] = "yes"  # no other test's template
    for i in range(100_000):
        pod.spec.containers[0].requests["cpu"] = f"{i}m"
        c = pod_contribution(pod)
        assert c.cpu == i
        assert len(oracle_state._CONTRIBUTIONS) <= bound
    assert len(oracle_state._CONTRIBUTIONS) == bound
    assert _lookups() == (hits, misses + 100_000)
    # the oldest went first: the template of before the flood is derived
    # again, to an equal contribution
    again = pod_contribution(hot)
    assert again == shared and again is not shared


def test_concurrent_lookups_lose_no_count_and_keep_the_bound():
    """Informer threads (NodeInfo under the cache lock) and the loop's
    thread (the encoder, outside it) look up at once."""
    import sys

    n_threads, per_thread = 8, 4000
    hits, misses = _lookups()
    errors = []

    def work(t):
        try:
            pod = _template(f"t{t}")
            for i in range(per_thread):
                # half shared across threads, half this thread's own
                cpu = f"{i % 50}m" if i % 2 else f"{t}{i:05d}m"
                pod.spec.containers[0].requests["cpu"] = cpu
                if tuple(pod_contribution(pod)) != _expected(pod):
                    errors.append((t, i))
        except Exception as e:  # surfaced below, not lost in the thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    got_hits, got_misses = _lookups()
    assert (got_hits - hits) + (got_misses - misses) == n_threads * per_thread
    assert len(oracle_state._CONTRIBUTIONS) <= oracle_state._CONTRIBUTIONS_MAX


@pytest.mark.parametrize("seed", [1234, 1235])
def test_node_info_add_then_remove_returns_every_sum_to_zero(seed):
    pods = []
    for i, pod in enumerate(_fuzzed_pods(seed)):
        pod = copy.deepcopy(pod)
        pod.metadata.name = f"pod-{i}"  # remove_pod finds a pod by name
        pods.append(pod)
    info = NodeInfo()
    for pod in pods:
        info.add_pod(pod)
    assert len(info.pods) == len(pods)
    assert info.requested_milli_cpu == sum(
        _calculate_resource(p)[0] for p in pods) > 0
    assert info.requested_memory == sum(
        _calculate_resource(p)[1] for p in pods) > 0
    assert info.nonzero_milli_cpu == sum(
        pod_nonzero_request(p)[0] for p in pods)
    assert info.nonzero_memory == sum(pod_nonzero_request(p)[1] for p in pods)
    random.Random(seed).shuffle(pods)
    for pod in pods:
        info.remove_pod(pod)
    assert (info.requested_milli_cpu, info.requested_memory,
            info.requested_gpu, info.nonzero_milli_cpu,
            info.nonzero_memory, info.pods) == (0, 0, 0, 0, 0, [])
    with pytest.raises(KeyError):
        info.remove_pod(pods[0])


@pytest.mark.parametrize("seed", range(6))
def test_node_info_finds_the_pod_to_remove_whatever_happened_to_the_list(
        seed):
    """remove_pod goes by where add_pod put the pod, and by a walk when
    that is not so: after a clone (which starts without places), after
    `pods` was edited from outside, and with two pods of one name."""
    rng = random.Random(4200 + seed)
    info, held, seq = NodeInfo(), [], 0
    for step in range(400):
        op = rng.random()
        if op < 0.5 or not held:
            seq += 1
            name = f"pod-{seq}" if rng.random() < 0.9 else "twin"
            pod = _template(name, cpu=f"{rng.randrange(1, 9) * 50}m")
            info.add_pod(pod)
            held.append(pod)
        elif op < 0.9:
            pod = held.pop(rng.randrange(len(held)))
            # the cache removes by an equal pod, not always the same object
            arg = copy.deepcopy(pod) if rng.random() < 0.5 else pod
            info.remove_pod(arg)
        elif op < 0.95:
            info = info.clone()
        else:
            rng.shuffle(info.pods)  # a caller that edits the public list
        assert sorted(p.name for p in info.pods) == sorted(
            p.name for p in held), step
        assert info.requested_milli_cpu == sum(
            _calculate_resource(p)[0] for p in held), step
        assert info.nonzero_memory == sum(
            pod_nonzero_request(p)[1] for p in held), step
    with pytest.raises(KeyError):
        info.remove_pod(_template("never-added"))
