"""BENCHMARK.json against the contract's limits, and every file it
names."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= m["run_seconds"] <= 51
    assert m["paths"] == ["benchmark"]
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_sources():
    m = manifest()
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(e["unit"]) and e["source"] in SOURCES
        assert e["moves"] in e2e
        if e["name"].endswith("_roofline") or "mfu" in e["name"]:
            assert e["unit"] == "%"
    cells = {w["name"] for w in m["workloads"]}
    for e in m["end_to_end"] + m["per_layer"]:
        assert set(e.get("workloads", ())) <= cells


def test_every_named_file_exists():
    m = manifest()
    here = os.path.join(ROOT, "benchmark")
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
        for kind, key in (("builders", "builder"),
                          ("reference", "reference")):
            assert os.path.exists(os.path.join(here, kind,
                                               body[key] + ".py"))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    pairs = set()
    for w in m["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(here, "workloads", w["name"] + ".json")) as f:
            body = json.load(f)
        assert body["config"] == w["config"]
        assert body["chips"] == w["chips"]
        assert body["traffic"] == w["traffic"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert os.path.exists(os.path.join(here, "metrics",
                                           e["name"] + ".py")), e["name"]
    assert {c["name"] for c in m["configs"]} == {
        w["config"] for w in m["workloads"]}
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
