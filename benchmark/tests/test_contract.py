"""BENCHMARK.json against the files it names: the harness finds every
cell, configuration, traffic mix, family and per-layer metric by name,
so the names have to agree, letter for letter."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def spec():
    return load(ROOT, "BENCHMARK.json")


def test_keys_names_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in spec["workloads"] + spec["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    assert {m["name"]: m["bound"] for m in spec["end_to_end"]}[
        "setup_s"] == 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in spec["end_to_end"])


def test_cells_configs_traffic_and_families_are_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cell = load(BENCH, "workloads", w["name"] + ".json")
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        assert load(BENCH, "traffic", w["traffic"] + ".json")["chips"] \
            == w["chips"]
        assert w["config"] in configs
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in spec["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = load(ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in ("assumed", "departures", "deployment", "tolerances"):
            assert cfg[key]
        family = importlib.import_module(
            f"benchmark.families.{cfg['family']}")
        for attr in ("THROUGHPUT", "TINY", "init", "loss_fn", "optimizer",
                     "host_batch", "flops_per_unit", "reference_loss",
                     "grad_leaves"):
            assert hasattr(family, attr), (cfg["family"], attr)
        assert any(w["config"] == c["name"] for w in spec["workloads"])


def test_every_per_layer_metric_is_a_reader_of_its_own(spec):
    listed = {m["name"]: m for m in spec["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py") and not f.startswith("_")}
    assert files == set(listed)
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    for name, m in listed.items():
        mod = importlib.import_module(f"benchmark.metrics.{name}")
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
            m["unit"], m["layer"], m["moves"])
        assert m["moves"] in end_to_end and callable(mod.read)


def test_throughput_metric_of_each_cell_is_the_family_s(spec):
    by_name = {m["name"]: m for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        cfg = load(BENCH, "configs", w["config"] + ".json")
        family = importlib.import_module(
            f"benchmark.families.{cfg['family']}")
        name, unit = family.THROUGHPUT
        assert by_name[name]["unit"] == unit
        assert w["name"] in by_name[name]["workloads"]


def test_peaks_table_names_its_source():
    peaks = load(BENCH, "peaks.json")
    assert "Google Cloud" in peaks["source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_throughput_rides_out_a_lone_pause_not_stalls_that_come_back():
    from benchmark import run
    steady = [0.351] * 56
    assert abs(run.steady_interval_s(steady) - 0.351) < 1e-12
    # The host pauses for 1.143 s and then catches up with the queue.
    paused = steady[:10] + [1.143, 0.12, 0.351] + steady[13:]
    assert abs(run.steady_interval_s(paused) - 0.351) < 1e-12
    # A loader that is late on every fourth step stays in.
    starved = [0.351, 0.351, 0.351, 0.5] * 14
    assert run.steady_interval_s(starved) > 0.38
    # A window of a few steps is not trimmed to nothing.
    assert abs(run.steady_interval_s([0.3, 0.4, 0.5]) - 0.4) < 1e-12
    assert abs(run.steady_interval_s([0.4]) - 0.4) < 1e-12
