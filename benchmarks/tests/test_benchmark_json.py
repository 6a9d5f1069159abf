"""``BENCHMARK.json`` against the contract's limits that can be checked
without a chip, and against the benchmark's own files."""

import os
import re

import pytest

from benchmarks import spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_names_are_plain_and_used_once():
    names = [e["name"] for section in
             ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[section]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for section in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in BENCH[section])


def test_workloads_name_their_files_and_few_take_four_chips():
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_configs_hold_their_files():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        held = spec.load_json(os.path.join(ROOT, c["file"]))
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        # reduced names scale only, never a width
        assert not set(c["reduced"]) & {"features", "max_bin", "num_leaves"}
        for key in ("features", "max_bin", "num_leaves"):
            assert held["params"].get(key, held[key]) == held[key]
    assert len({c["source"] for c in BENCH["configs"]}) \
        == len(BENCH["configs"])


def test_metrics_are_declared_as_the_contract_says():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        mine = {m["name"] for m in
                spec.metrics_for(BENCH, "end_to_end", w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        assert spec.metrics_for(BENCH, "per_layer", w["name"])


def _listed_and_held_back():
    held = spec.load_json(os.path.join(ROOT, "benchmarks", "held_back.json"))
    return BENCH["per_layer"] + held["per_layer"]


@pytest.mark.parametrize("entry", _listed_and_held_back(),
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_of_its_own(entry):
    # the driver refuses a layer that is not a plain name (no spaces)
    assert LAYER.match(entry["layer"]), entry["layer"]
    reader = spec.load_module("layers", entry["name"])
    named = re.match(r"Layer: ([A-Za-z0-9_.\-]+)", reader.__doc__ or "")
    assert named and named.group(1).rstrip(".") == entry["layer"]
    # a reader that finds nothing to read returns nothing
    assert reader.read({}) is None


def test_every_mix_names_a_kind_and_every_config_a_generator():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(BENCH, w["name"])
        kind = spec.load_module("kinds", cell.traffic["kind"])
        assert callable(kind.run)
        gen = spec.load_module("generators",
                               cell.config["generator"]["name"])
        assert callable(gen.make)


def test_a_full_check_fits_the_limit_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
