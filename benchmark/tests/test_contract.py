"""BENCHMARK.json against the limits of the benchmark's contract that a
file can be checked for without a run, and the files it names."""

import json
import os
import re

import pytest

from benchmark.harness.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return Spec.load(ROOT)


def test_top_level(spec):
    doc = spec.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["command"] == ["python3", "benchmark/run.py"]
    assert doc["paths"] == ["benchmark"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(spec):
    doc = spec.doc
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in doc[group]]
        assert len(names) == len(set(names))
        for e in doc[group]:
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e and group in ("configs", "workloads", "per_layer") \
                        and not (group == "per_layer" and key == "source"):
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    metrics = doc["end_to_end"] + doc["per_layer"]
    for m in metrics:
        assert m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_and_workloads(spec):
    doc = spec.doc
    cells = {w["name"] for w in doc["workloads"]}
    configs = {c["name"] for c in doc["configs"]}
    assert {w["config"] for w in doc["workloads"]} == configs
    assert len({(w["config"], w["traffic"]) for w in doc["workloads"]}) \
        == len(cells)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        cfg = spec.config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and "assumed" in cfg
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        mix = spec.traffic(w["traffic"])
        assert os.path.exists(os.path.join(
            spec.dir, "generators", mix["generator"] + ".py"))
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= \
        max(1, len(cells) // 2)


def test_metrics(spec):
    doc = spec.doc
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            spec.dir, "layer_metrics", m["name"] + ".py")), m["name"]
        movers = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", movers)) <= movers
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_for("per_layer", cell, reported)


def test_peaks_and_kernel_cost(spec):
    peaks = spec.peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"]["value"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("a device nobody has entered")
    cost = spec.kernel_cost("verify_kernel_msg32")
    assert cost.operations(2048) == 2 * cost.operations(1024)
    f = cost.field_ops_per_signature()
    # the ladder alone: 127 steps of 15 multiplies and 8 squarings
    assert f["multiplies"] >= 127 * 15 and f["squarings"] >= 127 * 8
    least, bound = cost.least_seconds(65536, peaks)
    assert bound == "int32 vector operations" and 0.01 < least < 1.0
