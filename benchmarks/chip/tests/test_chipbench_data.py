"""The benchmark's data files: every name in BENCHMARK.json resolves to
its file, the files keep to their schemas, and a cell defined only in a
temporary directory loads beside them (so a later cell needs only new
files and one ``workloads`` entry)."""

import json
import re

import pytest

from chipbench_fixtures import CHIP, TINY_SERVE, write_tiny

import harness  # noqa: E402  (path set by chipbench_fixtures)

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
CELLS = sorted(p.name[:-5] for p in (CHIP / "cells").glob("*.json"))
CONFIGS = sorted(p.name[:-5] for p in (CHIP / "configs").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:     # each cell reports what it moves
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2 and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert any(w["name"] in m["workloads"] for m in BENCH["per_layer"])


def test_workloads_match_their_cell_files():
    cat = harness.Catalog()
    for w in BENCH["workloads"]:
        cell = cat.cell(w["name"])
        assert (cell["config"], cell["mix"]) == (w["config"], w["traffic"])
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cat = harness.Catalog()
    cell = cat.cell(name)
    config = cat.config(cell["config"])
    cfg = harness.build_arch(config, cell.get("blocks_per_job"))
    mix = cat.mix(cell["mix"])
    assert hasattr(cat.driver(mix["driver"]), "run")
    if "serve" in cell:
        assert cfg.num_layers == config["num_hidden_layers"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files(name):
    config = harness.Catalog().config(name)
    harness.build_arch(config)          # published keys match the arch
    for c in BENCH["configs"]:
        if c["name"] == name:
            assert config["source"] == c["source"]
            assert set(c["reduced"]) == set(config["reduced"])


def test_every_metric_has_a_reader():
    cat = harness.Catalog()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(cat.metric(m["name"]).read), m["name"]


def test_peaks_known_and_unknown():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.DataError):
        harness.peaks("TPU v99")


def test_cell_in_temp_dir_loads(tmp_path):
    write_tiny(tmp_path)
    cat = harness.Catalog([tmp_path, CHIP])
    cell = cat.cell("tiny.chat")
    assert cell["serve"] == TINY_SERVE
    assert cat.mix(cell["mix"])["driver"] == "open_loop"
    cfg = harness.build_arch(cat.config(cell["config"]))
    assert cfg.d_model == 64
    # the benchmark's own cells are still found through the same catalog
    assert cat.cell(BENCH["workloads"][0]["name"])
    assert [m["name"] for m in harness.metrics_for(
        {"end_to_end": [{"name": "setup_s"},
                        {"name": "x", "workloads": ["other"]}]},
        "tiny.chat", False)] == ["setup_s"]


@pytest.mark.parametrize("kind,edit", [
    ("cells", lambda o: o.update(colour="red")),
    ("cells", lambda o: o["serve"].update(max_batchh=3)),
    ("mixes", lambda o: o.update(speed=1)),
    ("mixes", lambda o: o["prompt"].update(mean=3)),
    ("configs", lambda o: o.update(hidden=3)),
])
def test_unknown_key_fails_loudly(tmp_path, kind, edit):
    write_tiny(tmp_path)
    name = {"cells": "tiny.chat", "mixes": "tchat", "configs": "tiny"}[kind]
    p = tmp_path / kind / f"{name}.json"
    obj = json.loads(p.read_text())
    edit(obj)
    p.write_text(json.dumps(obj))
    cat = harness.Catalog([tmp_path, CHIP])
    with pytest.raises(harness.DataError, match="unknown key"):
        getattr(cat, kind[:-1] if kind != "mixes" else "mix")(name)


def test_config_that_disagrees_with_arch_fails(tmp_path):
    write_tiny(tmp_path)
    p = tmp_path / "configs" / "tiny.json"
    obj = json.loads(p.read_text())
    obj["hidden_size"] = 65
    p.write_text(json.dumps(obj))
    cat = harness.Catalog([tmp_path, CHIP])
    with pytest.raises(harness.DataError, match="hidden_size"):
        harness.build_arch(cat.config("tiny"))


def test_missing_file_fails():
    with pytest.raises(harness.DataError, match="no cells/nope.json"):
        harness.Catalog().cell("nope")
