"""``BENCHMARK.json`` against the shape the harness and its checkers need,
and every entry against the files the harness finds by name."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_sources():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.load_cell(BENCH, cell)
    assert c["chips"] in (1, 4)
    assert {c["config"]} <= {x["name"] for x in BENCH["configs"]}
    mf = c["model_file"]
    assert mf["name"] == c["config"]
    assert set(mf["reduced"]) <= set(mf["published"])
    mix = c["mix"]
    geo = c["engine"]
    assert set(mix) == {"about", "shape_seed", "prompt", "output"}
    assert c["rate_per_s"] > 0 and c["check"]["limits"]
    assert (traffic.max_length(mix["prompt"])
            + traffic.max_length(mix["output"])
            <= geo["max_blocks_per_seq"] * geo["block_size"])
    assert harness.metrics_for(BENCH, cell, "per_layer")
    assert len(harness.metrics_for(BENCH, cell, "end_to_end")) >= 2


def test_program_config_matches_each_file():
    for c in BENCH["configs"]:
        mf = json.loads((ROOT / c["file"]).read_text())
        assert c["reduced"] == mf["reduced"] and c["source"] == mf["source"]
        harness.program_config(mf)
