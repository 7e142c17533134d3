"""Every entry of BENCHMARK.json resolves to its files by name, and the file
keeps to the limits the benchmark's readers rely on."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(conf):
    from chipbench.harness import load_module

    assert NAME.match(conf["name"])
    assert conf["file"].startswith("chipbench/configs/")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert set(conf["reduced"]) <= set(data)
    ref = load_module(ROOT / "chipbench" / data["reference"])
    for fn in ("reference", "bytes_moved"):
        assert callable(getattr(ref, fn))
    assert ref.bytes_moved(data["x"], data["y"]) > 0
    assert data["reference_platform"] in ("tpu", "cpu")
    assert data["inputs"] >= 1
    assert set(data["limits"]) == {
        "out_err", "program_mismatches", "input_flaws",
        "screen_rejects", "record_mismatches", "store_mismatches",
    }


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    from chipbench.harness import load_cell

    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    loaded = load_cell(cell["name"])
    assert loaded.traffic["name"] == cell["traffic"]
    assert loaded.traffic["compile_caches"] == "cold"
    names = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves(metric):
    from chipbench.harness import load_module

    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["moves"] in E2E
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    reader = load_module(ROOT / "chipbench" / "metrics" / f"{metric['name']}.py")
    assert callable(reader.read)


def test_metric_names_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25


def test_layers_spelled_alike_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for m in BENCH["per_layer"]:
        assert f"**{m['layer']}**" in perf, m["layer"]
