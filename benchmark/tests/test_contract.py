"""BENCHMARK.json against the contract's letter, and against the files
the harness finds by name."""

import json
import os
import re

import pytest
from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with 24 cells has to fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


def test_configs_and_cells(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        on_disk = _load("configs", c["name"])
        assert on_disk["reduced"] == c["reduced"]
        assert on_disk["source"] == c["source"]
        assert os.path.exists(
            os.path.join(BENCH, "configs", c["name"] + ".py")
        ), "a configuration has its plain reference beside it"
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        cell = _load("cells", w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert w["config"] in configs
        assert _load("traffic", w["traffic"])["name"] == w["traffic"]
        assert configs[w["config"]] and _load(
            "configs", w["config"])["chips"] == w["chips"]
    assert four <= max(1, len(cells) // 2)
    assert {w["config"] for w in bench["workloads"]} == set(configs)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"
        layers.add(m["layer"])
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
        spec = _load("metrics", m["name"])
        assert os.path.exists(
            os.path.join(BENCH, "metrics", spec["reader"] + ".py"))
        for k in ("unit", "layer", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    # every cell reports set-up, another end-to-end metric and a layer
    for c in cells:
        assert sum(c in m.get("workloads", cells)
                   for m in bench["end_to_end"]) >= 2
        assert any(c in m.get("workloads", cells) for m in bench["per_layer"])
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(d, f), REPO))
