"""A deployment file (benchmark/configs/<name>.json) turned into the
objects it describes, as plain wire dicts: nodes, replication
controllers and pods. Plain data only — the load generator and the
plain reference both read it, and neither may lean on the program's
own types.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_MEM = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
        "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9, "T": 10 ** 12}


def milli_cpu(q: str) -> int:
    """'100m' -> 100, '4' -> 4000."""
    q = str(q)
    if q.endswith("m"):
        return int(q[:-1])
    return int(q) * 1000


def mem_bytes(q: str) -> int:
    """'500Mi' -> 524288000, '32Gi' -> 34359738368, '123' -> 123."""
    q = str(q)
    for suffix in ("Ki", "Mi", "Gi", "Ti", "k", "M", "G", "T"):
        if q.endswith(suffix):
            return int(q[:-len(suffix)]) * _MEM[suffix]
    return int(q)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, root: str = ROOT) -> dict:
    """The deployment a cell names, found through BENCHMARK.json."""
    for c in load_manifest(root)["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"BENCHMARK.json has no configuration {name!r}")


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", name + ".json")


def node_name(cfg: dict, i: int) -> str:
    return cfg["nodes"]["name_format"].format(i=i)


def node_labels(cfg: dict, i: int) -> dict:
    """Each label value is a format over the node's index `i` and its
    zone letter `zone` (zones are dealt round-robin, as bench.py does)."""
    n = cfg["nodes"]
    zones = n.get("zones") or []
    zone = zones[i % len(zones)] if zones else ""
    return {k: v.format(i=i, zone=zone, name=node_name(cfg, i))
            for k, v in (n.get("labels") or {}).items()}


def nodes(cfg: dict) -> list:
    alloc = dict(cfg["nodes"]["allocatable"])
    return [{
        "kind": "Node", "apiVersion": "v1",
        "metadata": {"name": node_name(cfg, i),
                     "labels": node_labels(cfg, i)},
        "status": {"capacity": alloc, "allocatable": alloc,
                   "conditions": [{"type": "Ready", "status": "True"}]},
    } for i in range(cfg["nodes"]["count"])]


def num_templates(cfg: dict) -> int:
    return max(int(cfg["controllers"]["count"]), 1)


def template_labels(cfg: dict, t: int) -> dict:
    return {k: v.format(t=t) for k, v in cfg["pods"]["labels"].items()}


def controllers(cfg: dict) -> list:
    """One ReplicationController per pod template; its selector is the
    template's labels."""
    c = cfg["controllers"]
    return [{
        "kind": "ReplicationController", "apiVersion": "v1",
        "metadata": {"name": c["name_format"].format(t=t),
                     "namespace": "default"},
        "spec": {"selector": template_labels(cfg, t),
                 "replicas": c["replicas"]},
    } for t in range(int(c["count"]))]


def pod(cfg: dict, t: int, prefix: str = "", name: str = "") -> dict:
    """A pod of template `t`: named outright, or left to the server's
    generateName as a replication manager leaves it."""
    meta = {"namespace": "default", "labels": template_labels(cfg, t)}
    if name:
        meta["name"] = name
    else:
        meta["generateName"] = f"{prefix}t{t}-"
    p = cfg["pods"]
    return {
        "kind": "Pod", "apiVersion": "v1", "metadata": meta,
        "spec": {"containers": [{
            "name": p["container"]["name"], "image": p["container"]["image"],
            "requests": dict(p["requests"])}]},
    }
