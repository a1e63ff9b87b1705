"""BENCHMARK.json keeps the benchmark's contract, and every name in it
finds its files."""
from __future__ import annotations

import json
import re

import pytest

from bench.common import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.spec()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    size = len(json.dumps(bench).encode())
    assert size <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith("bench/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bench["workloads"]:
        files = harness.cell_files(w["name"], bench)
        mine = {m["name"] for m in files["end_to_end"]}
        assert "setup_s" in mine and len(mine) >= 2
        assert files["per_layer"]


def test_each_per_layer_metric_moves_a_metric_its_cells_report(bench):
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        for cell in m.get("workloads", [w["name"]
                                        for w in bench["workloads"]]):
            files = harness.cell_files(cell, bench)
            assert m["moves"] in {e["name"] for e in files["end_to_end"]}
        layers.setdefault(m["layer"], []).append(m["name"])
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_every_name_finds_its_files(bench):
    for w in bench["workloads"]:
        files = harness.cell_files(w["name"], bench)
        harness.driver(files["traffic"]["kind"]).Driver
        assert files["limits"]
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_configs_are_each_used_once_per_file(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
