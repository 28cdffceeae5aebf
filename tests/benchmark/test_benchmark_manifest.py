"""BENCHMARK.json against the contract, and against the files it names."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_and_units_use_the_allowed_characters(manifest):
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in _metrics(manifest)]
             + [k for c in manifest["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.match(name), name
    for m in _metrics(manifest):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in manifest[group]]
        assert len(seen) == len(set(seen)), group
    for text in ([w["why"] for w in manifest["workloads"]]
                 + [c["why"] for c in manifest["configs"]]
                 + [c["source"] for c in manifest["configs"]]
                 + [m["layer"] for m in manifest["per_layer"]]
                 + manifest["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_config_traffic_and_reader_resolves_to_a_file(manifest):
    paths = manifest["paths"]
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in ("guarantees", "assumed", "measured"):
            assert key in cfg
        # its plain reference, under `paths` like everything of the yardstick
        assert any(cfg["reference"].startswith(p + "/") for p in paths)
        assert os.path.isfile(os.path.join(REPO, cfg["reference"]))
    configs = {c["name"] for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        used.add(w["config"])
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    assert used == configs
    for m in manifest["per_layer"]:
        reader = m["name"].split(".", 1)[0] + ".py"
        assert os.path.isfile(os.path.join(REPO, "benchmark", "layers",
                                           reader)), reader


def test_every_cell_reports_what_the_contract_asks(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert all(0 < m["bound"] <= 0.25 for m in e2e.values())
    cells = [w["name"] for w in manifest["workloads"]]
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2, cell
        layered = [m for m in manifest["per_layer"]
                   if cell in m.get("workloads", cells)]
        assert layered, cell
        for m in layered:  # a per-layer metric moves what its cell reports
            assert m["moves"] in reported, (cell, m["name"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 2)


def test_the_run_length_fits_a_full_check_of_24_cells(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_are_keyed_by_device_kind():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
