"""How `correct` is decided, from what the load generator read back.

Outside the window, with the cluster at rest: the drain deadline has
passed (a pod unbound at it has failed, and is waited for all the
same), the churn is down to its line and stopped:

  (a) read back over plain HTTP: every pod the apiserver acknowledged
      and the generator did not delete is there, bound, to the node the
      watch showed (bound once); no node over allocatable in cpu,
      memory or pod count;
  (b) the seeded check batch went through the same door, daemon and
      compiled programs as the window's traffic, onto the cluster as
      the window left it; every pick is held against the plain
      reference the deployment names (`reference` in its file), pod
      for pod.

Every number compared is an exact count with the limit 0.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys

from benchmark import deploy

_TEMPLATE = re.compile(r"^[a-z]+-t(\d+)-")


def load_reference(cfg: dict):
    """The plain reference a deployment names, as a module."""
    path = os.path.join(deploy.ROOT, cfg["reference"])
    name = "benchmark." + os.path.splitext(os.path.basename(path))[0]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    sys.modules[name] = module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cluster(reference, cfg: dict, bound: dict, check_templates: dict):
    """The reference's cluster holding name -> node. -> (cluster,
    pods whose node the deployment does not have)."""
    made = reference.Cluster(cfg)
    strays = 0
    for name, node in bound.items():
        if not node:
            continue
        t = check_templates.get(name)
        if t is None:
            m = _TEMPLATE.match(name)
            t = int(m.group(1)) if m else 0
        i = made.index.get(node)
        if i is None:
            strays += 1
            continue
        made.bind(t, i)
    return made, strays


def decide(record: dict, cfg: dict, out=sys.stderr) -> dict:
    """-> {"correct": bool, "numbers": {name: (value, limit)},
    "counter": the round-robin counter the picks agree on}"""
    reference = load_reference(cfg)
    chk = record["check"]
    backlog, names = chk["backlog"], chk["names"]
    before, after = chk["before"], chk["after"]
    templates = dict(zip(names, backlog))
    live = record["live"]

    lost = sum(1 for name in live if name not in after)
    moved = sum(1 for name, node in live.items()
                if after.get(name, node) != node)
    unbound = sum(1 for node in after.values() if not node)
    filled, strays = cluster(reference, cfg, after, templates)
    start, strays_before = cluster(reference, cfg, before, templates)
    picks = [start.index.get(after.get(name, "")) for name in names]
    held = reference.verify(start, backlog, picks)

    numbers = {
        "check_pods_not_created": (len(backlog) - chk["created"], 0),
        "acknowledged_pods_lost": (lost, 0),
        "cluster_not_at_rest": (int(not record["at_rest"]), 0),
        "pods_unbound_read_back": (unbound, 0),
        "pods_bound_twice": (record["double_bound"] + moved, 0),
        "pods_on_unknown_nodes": (strays + strays_before, 0),
        "nodes_over_allocatable": (filled.over_allocatable(), 0),
        "picks_off_reference": (held["mismatches"], 0),
    }
    correct = all(value <= limit for value, limit in numbers.values())
    for name, (value, limit) in numbers.items():
        print(f"correct: {name} = {value} (limit {limit})", file=out)
    print(f"correct: {correct}; {held['checked']} picks held against the "
          f"reference on {len(before)} bound pods", file=out)
    return {"correct": correct, "numbers": numbers,
            "counter": held["counter"][0]}
