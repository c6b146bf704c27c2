"""BENCHMARK.json against the letter of its contract, and against the files
it names."""

import json
import re

import pytest

from tests.benchmark.conftest import REPO

BM = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)
    assert len(json.dumps(BM)) < 64 * 1024
    assert BM["command"] == ["python3", "benchmarks/run.py"]
    assert all((REPO / p).is_dir() for p in BM["paths"])
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_and_cells():
    names = [c["name"] for c in BM["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BM["workloads"]}
    assert used == set(names)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["source"].startswith("https://")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
        assert cfg["source"] == c["source"] and "assumed" in cfg
        # widths as published (Mistral-7B / Mixtral-8x7B config.json)
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["vocab_size"]) == (4096, 14336, 32, 8, 32000)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 4)
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (REPO / "benchmarks" / "traffic" / f"{w['traffic']}.json").exists()


def test_metrics():
    cells = {w["name"] for w in BM["workloads"]}
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert set(e2e) == {"train_tokens_per_s_chip", "serve_tokens_per_s",
                        "setup_s"}
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(set(names)) == len(names)
    layers = set()
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert (REPO / "benchmarks" / "metrics" / f"{m['name']}.py").exists()
        layers.add(m["layer"])
    perf = (REPO / "PERF.md").read_text()
    assert all(layer in perf for layer in layers)


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_reports_setup_one_more_and_a_layer_metric(cell):
    from benchmarks.harness.cell import Cell

    c = Cell.find(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert hasattr(c.reader(m["name"]), "read")
