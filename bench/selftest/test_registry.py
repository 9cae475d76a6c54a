"""Everything ``BENCHMARK.json`` names loads by name from its own files, and
the file keeps the benchmark's format."""

import json
import math
import re

import pytest

from bench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_a_full_check_of_24_cells_fits_its_time():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_by_name(cell):
    c = harness.load_cell(cell)
    assert c.workload["chips"] in (1, 4)
    assert c.config["chips"] == c.workload["chips"]
    assert c.config["shards"] == c.workload["chips"]
    assert harness.load_module("drivers", c.config["driver"])
    assert c.traffic["ops"]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=lambda e: e["name"])
def test_each_configuration(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert one_line(entry["source"]) and one_line(entry["why"])
    assert entry["file"].startswith("bench/configs/")
    with open(harness.ROOT / entry["file"]) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in ("source", "deployment", "assumed", "guarantees"):
        assert cfg[key]
    assert {"consistency", "commit_interval_ticks",
            "durability"} <= set(cfg["guarantees"])
    assert len(cfg["source"]) <= 200
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_workload_entry(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["traffic"]) and one_line(w["why"])
    assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_each_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_loads_by_name(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES and one_line(m["layer"])
    ends = {e["name"]: e for e in BENCH["end_to_end"]}
    assert m["moves"] in ends
    for cell in m["workloads"]:
        assert cell in ends[m["moves"]].get("workloads", CELLS)
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"
    assert callable(harness.load_module("metrics", m["name"]).read)


def test_layers_of_one_name_are_spelled_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert {x.lower() for x in layers} == layers


def test_a_new_cell_is_new_files_and_entries(tmp_path, monkeypatch):
    """A cell added as new data files loads without touching old files."""
    traffic = tmp_path / "bench" / "traffic"
    traffic.mkdir(parents=True)
    mix = dict(harness.load_cell(CELLS[0]).traffic, block_ticks=3)
    (traffic / "new-mix.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(BENCH["workloads"][0],
                                   name="kv1.new-mix", traffic="new-mix"))
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    monkeypatch.setattr(harness, "ROOT", harness.ROOT)
    cell = harness.load_cell("kv1.new-mix", bench)
    assert cell.traffic["block_ticks"] == 3
    assert math.isclose(cell.traffic["queue_ticks"], mix["queue_ticks"])
