"""The programs a PR says it leaves alone, held to the text they lower
to: `jit_batch_scan`, `jit_wave_probe`, `jit_probe_fused_same`,
`jit_group_probe`, the commit folds and the mesh's scan, probes and fold,
through the analysis registry on two small clusters, one whose bound and
pending pods own inter-pod terms of every kind and one without a term.
`tests/lowered_programs.json` keeps a SHA-256 of each program's
lowered text (`jax.jit(...).lower(...).as_text()`, which carries no
source location) as the commit before PR 50 lowered it, with the jax
version it was made under: PR 50 gave `models/probe._probe_rows` and
`models/wave.WaveScheduler._apply_fn` an optional argument for the
device replay, and every caller that passes none must lower to the
program it lowered to. Since PR 52 the two device replay programs
(`jit_zreplay_run`, `jit_zreplay_group`) are held too, as PR 52 left them
(it gave each one more result, the nodes that fit at a run's probe): the
eleven digests before them are the ones the file held.

A PR that means to change one of these programs makes the file anew, on
its own tree, and says so:

    JAX_PLATFORMS=cpu python tests/test_lowered_programs.py \\
        > tests/lowered_programs.json
"""

import hashlib
import json
import os
import sys

import pytest

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "lowered_programs.json")
PROGRAMS = ("scan", "probe", "probe_fused_same", "group_probe_G8", "apply",
            "apply_group", "mesh_scan", "mesh_probe", "mesh_group_probe",
            "mesh_apply", "mesh_apply_group", "zreplay", "zreplay_group")
HOSTNAME = "kubernetes.io/hostname"
ZONE = "failure-domain.beta.kubernetes.io/zone"
LONG = {"required": "requiredDuringSchedulingIgnoredDuringExecution",
        "preferred": "preferredDuringSchedulingIgnoredDuringExecution"}


def _term(app, topo, weight=None):
    term = {"labelSelector": {"matchLabels": {"app": app}},
            "topologyKey": topo, "namespaces": []}
    return term if weight is None else {
        "weight": weight, "podAffinityTerm": term}


def _with_terms(num_nodes=13):
    """The registry's own scenario (`analysis/programs._scenario`: 13
    zoned nodes, templates alpha x 24 and beta x 20, so rows 0 and 24
    are the two templates) with terms of all four kinds among the bound
    pods and on both templates."""
    from kubernetes_tpu.api.types import (
        Container, Node, NodeCondition, NodeStatus, ObjectMeta, Pod, PodSpec,
    )
    from kubernetes_tpu.oracle import ClusterState
    from kubernetes_tpu.snapshot.encode import SnapshotEncoder

    def pod(name, app, affinity, node=None, cpu="100m"):
        made = Pod(metadata=ObjectMeta(name=name, labels={"app": app}),
                   spec=PodSpec(node_name=node, containers=[Container(
                       requests={"cpu": cpu, "memory": "200Mi"})]))
        if affinity:
            made.metadata.annotations = {
                "scheduler.alpha.kubernetes.io/affinity": json.dumps({
                    kind: {LONG[when]: terms for when, terms in body.items()}
                    for kind, body in affinity.items()})}
        return made

    nodes = [Node(
        metadata=ObjectMeta(name=f"audit-n{i:02d}", labels={
            HOSTNAME: f"audit-n{i:02d}", ZONE: "abc"[i % 3]}),
        status=NodeStatus(
            allocatable={"cpu": "8", "memory": "32Gi", "pods": "110"},
            conditions=[NodeCondition("Ready", "True")]))
        for i in range(num_nodes)]
    owned = [
        {"podAffinity": {"required": [_term("alpha", ZONE)]}},
        {"podAffinity": {"preferred": [_term("beta", HOSTNAME, 5)]}},
        {"podAntiAffinity": {"preferred": [_term("alpha", ZONE, 3)]}},
        {"podAntiAffinity": {"required": [_term("beta", HOSTNAME)]}},
        None, None]
    existing = [pod(f"audit-e{i}", "web", owned[i],
                    node=f"audit-n{i % num_nodes:02d}", cpu="500m")
                for i in range(6)]
    alpha = {"podAntiAffinity": {"required": [_term("beta", HOSTNAME)],
                                 "preferred": [_term("web", ZONE, 2)]}}
    beta = {"podAffinity": {"preferred": [_term("web", HOSTNAME, 4)]},
            "podAntiAffinity": {"required": [_term("beta", HOSTNAME)]}}
    pending = [pod(f"audit-alpha-{i:03d}", "alpha", alpha)
               for i in range(24)] \
        + [pod(f"audit-beta-{i:03d}", "beta", beta, cpu="250m")
           for i in range(20)]
    state = ClusterState.build(nodes, assigned_pods=existing)
    snap, batch = SnapshotEncoder(state, pending).encode()
    assert snap.ip_lt_u.shape[0] >= 4 and snap.ip_rev_pref.any()
    return snap, batch


def digests():
    """{"jax": version, cluster: {program: sha256 of its lowered text}}."""
    import jax

    from kubernetes_tpu.analysis import programs

    out = {"jax": jax.__version__}
    plain = programs._scenario
    for cluster, scenario in (("terms", _with_terms), ("no-terms", plain)):
        programs._scenario = scenario
        try:
            specs = {s.name: s for s in programs.build_programs(
                include_mesh=True)}
        finally:
            programs._scenario = plain
        out[cluster] = {}
        for name in PROGRAMS:
            spec = specs[name]
            fn = spec.fn if hasattr(spec.fn, "lower") else jax.jit(spec.fn)
            out[cluster][name] = hashlib.sha256(
                fn.lower(*spec.args).as_text().encode()).hexdigest()
    return out


@pytest.fixture(scope="module")
def lowered():
    import jax

    with open(PINS) as f:
        pins = json.load(f)
    if pins["jax"] != jax.__version__:
        pytest.skip(f"the digests were made under jax {pins['jax']}; "
                    f"this is {jax.__version__}: make them anew")
    return pins, digests()


@pytest.mark.parametrize("name", PROGRAMS)
@pytest.mark.parametrize("cluster", ["terms", "no-terms"])
def test_the_program_lowers_to_the_text_it_lowered_to(cluster, name,
                                                      lowered):
    pins, now = lowered
    assert now[cluster][name] == pins[cluster][name], (
        f"{name} on the {cluster} cluster lowers to another program than "
        "the one kept in tests/lowered_programs.json")


def test_the_two_clusters_lower_to_different_programs(lowered):
    """The terms are live in the programs that read them: a file of
    digests that never saw a term would hold nothing."""
    pins, _ = lowered
    for name in ("scan", "probe", "group_probe_G8", "apply", "mesh_scan"):
        assert pins["terms"][name] != pins["no-terms"][name], name


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_"
                               "force_host_platform_device_count=8").strip()
    import kubernetes_tpu  # noqa: F401  (x64 before any other jax use)

    print(json.dumps(digests(), indent=1))
