"""BENCHMARK.json and the data files the harness finds by name."""

import json
import os
import re

import pytest

import run as harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def listed(kind, ext):
    return sorted(f[:-len(ext)] for f in
                  os.listdir(os.path.join(harness.HERE, kind))
                  if f.endswith(ext))


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for entry in metrics + bench["workloads"] + bench["configs"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    names = [e["name"] for e in metrics]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert one_line(c["source"]) and one_line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(bench)) < 64 * 1024


def test_end_to_end_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in bench["workloads"]:
        mine = harness.cell_metrics(bench, "end_to_end", w["name"])
        assert len(mine) >= 2 and "setup_s" in [m["name"] for m in mine]


def test_every_cell_resolves_to_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    assert len({c["source"] for c in configs.values()}) == len(configs)
    for w in bench["workloads"]:
        cell, config, traffic, queries, _ = harness.load_cell(w["name"])
        entry = configs[w["config"]]
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert config["chips"] == w["chips"]
        assert config["guarantees"] and config["assumed"]
        assert set(queries) == set(traffic["queries"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)


@pytest.mark.parametrize("name", listed("configs", ".json"))
def test_config_file_loads(name):
    config = harness.load_json(harness.HERE, "configs", f"{name}.json")
    assert config["name"] == name
    for key in ("source", "scale_factor", "rows", "format", "codec",
                "row_groups", "platform", "chips", "engine_confs",
                "guarantees", "reduced", "assumed"):
        assert key in config, key
    assert config["engine_confs"]["spark.rapids.sql.test.enabled"] is True
    assert one_line(config["source"])


@pytest.mark.parametrize("name", listed("traffic", ".json"))
def test_traffic_file_loads_and_its_queries_ship(name):
    traffic = harness.load_json(harness.HERE, "traffic", f"{name}.json")
    assert NAME.match(name)
    assert traffic["loop"] in ("closed", "open")
    assert traffic["entry"] in ("collect", "served")
    assert traffic["clients"] >= 1
    for q in traffic["queries"]:
        mod = harness.load_module("queries", q)
        assert callable(mod.build) and callable(mod.reference)
        assert callable(mod.bytes_read) and mod.READS


def test_the_loops_of_the_open_questions_ship():
    assert {"q1-loop", "q3-loop", "q5-loop", "q6-loop"} \
        <= set(listed("traffic", ".json"))


def test_schedule_gives_every_seed_the_same_queries():
    traffic = {"queries": ["a", "b", "b", "c"]}
    for seed in (0, 7, 2**31 + 3):
        order = harness.schedule(traffic, seed)
        cycle = [next(order) for _ in range(4)]
        assert sorted(cycle) == ["a", "b", "b", "c"]
        assert [next(order) for _ in range(4)] == cycle
        again = harness.schedule(traffic, seed)
        assert [next(again) for _ in range(4)] == cycle


@pytest.mark.parametrize("name", listed("layer_metrics", ".json"))
def test_layer_metric_file_agrees_with_benchmark_json(name, bench):
    spec = harness.load_json(harness.HERE, "layer_metrics", f"{name}.json")
    assert spec["name"] == name and NAME.match(name)
    assert spec["per"] in ("execution", "window")
    assert callable(harness.load_module("readers", spec["reader"]).read)
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry, f"{name} is not in BENCHMARK.json per_layer"
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[0][key] == spec[key], key
    assert one_line(spec["layer"])


def test_every_per_layer_metric_has_its_file_and_moves_a_reported_metric(
        bench):
    have = set(listed("layer_metrics", ".json"))
    for m in bench["per_layer"]:
        assert m["name"] in have
        cells = m.get("workloads", [w["name"] for w in bench["workloads"]])
        for cell in cells:
            reported = [e["name"] for e in
                        harness.cell_metrics(bench, "end_to_end", cell)]
            assert m["moves"] in reported, (m["name"], cell)
    rooflines = [m for m in bench["per_layer"] if "roofline" in m["name"]]
    assert all(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in rooflines)


def test_peaks_name_their_source():
    peaks = harness.load_json(harness.HERE, "peaks.json")
    for kind, row in peaks.items():
        assert row["hbm_bytes_per_s"] > 0 and row["source"], kind


def test_a_device_not_in_the_table_or_not_the_accelerator_is_refused(
        capsys):
    config = harness.load_json(harness.HERE, "configs", "tpch-sf1.json")
    with pytest.raises(SystemExit) as e:  # the tests run on the CPU
        harness.resolve_devices(config, rehearse=False)
    assert "no accelerator" in str(e.value)
