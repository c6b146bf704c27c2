"""The harness end to end at a tiny size on the CPU: one run per traffic
kind, the form of the last line, and what a CPU run may not print."""

import json

import pytest

from tests.benchmark.conftest import (OTHER, PUBLISHED, add_configuration,
                                      other_configuration, run_cell,
                                      write_root)

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.dense-batch",
                                      "tiny.batch", "tiny.other-batch"])
@pytest.mark.parametrize("trace", [0, 1])
def test_each_kind_runs_and_prints_the_contract_line(
        tiny_root, capsys, monkeypatch, workload, trace):
    rc, lines = run_cell(tiny_root, workload, capsys, monkeypatch, trace=trace)
    assert rc == 0
    line = json.loads(lines[-1])
    assert list(line) == KEYS         # the contract's keys; ``checks`` last
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    # Every compared number is printed beside its limit, and the in-window
    # compile count is printed in every run and reads 0.
    assert any(l.startswith("check: ") and "limit" in l for l in lines)
    assert [f"check: {name} = {c['value']!r} (limit {c['limit']!r})"
            for name, c in line["checks"].items()] == [
        l for l in lines if l.startswith("check: ")]
    assert "compiles_in_window: 0" in lines
    # A CPU run prints counts only: no time, rate, share or utilisation
    # under a metric's name.
    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    source = {m["name"]: m["source"]
              for m in bm["end_to_end"] + bm["per_layer"]}
    assert all(source[n] == "program_counter" for n in line["metrics"])
    if trace:
        assert any(n.startswith("compiles_in_window.")
                   for n in line["metrics"])


def test_without_an_accelerator_it_prints_no_result(tiny_root, capsys):
    from benchmarks import run as bench_run

    rc = bench_run.main(["--workload", "tiny.train", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "no accelerator" in out.err


def test_an_unknown_workload_is_refused(tiny_root):
    from benchmarks import run as bench_run

    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "nope", "--seed", "1", "--seconds",
                        "1"], root=tiny_root)


def test_configuration_mix_and_metric_are_added_as_new_files_only(
        tmp_path, capsys, monkeypatch):
    """A later PR's cell: one configuration file with its source's published
    keys, one traffic file, one reader module and entries in BENCHMARK.json;
    no file that is there is edited, and the harness finds all of them by
    name."""
    dummy = {"name": "requests_in_window.dummy", "unit": "count",
             "better": "higher", "source": "program_counter",
             "layer": "benchmark", "moves": "serve_tokens_per_s",
             "workloads": ["dummy.cell"]}
    root = write_root(tmp_path / "root")
    bench = root / "benchmarks"
    cfg = json.loads((bench / "configs" / "tiny-serve.json").read_text())
    published = json.loads((root / PUBLISHED / "tiny-serve.json").read_text())
    cfg.update(num_hidden_layers=1, reduced=["num_hidden_layers"],
               published={"num_hidden_layers": 2})
    cfg["orion"]["overrides"].append("model.n_layers=1")
    entry = add_configuration(root, "dummy-config", cfg, published)
    mix = json.loads((bench / "traffic" / "tiny-batch.json").read_text())
    mix["clients"] = 2
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "metrics").mkdir()
    (bench / "metrics" / "requests_in_window.dummy.py").write_text(
        "def read(obs):\n    return obs['requests']\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(entry)
    bm["workloads"].append({"name": "dummy.cell", "config": "dummy-config",
                            "traffic": "dummy-mix", "chips": 1, "why": "t"})
    for m in bm["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("dummy.cell")
    bm["per_layer"].append(dummy)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    rc, lines = run_cell(root, "dummy.cell", capsys, monkeypatch, trace=1)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["requests_in_window.dummy"]["value"] > 0


def test_a_configuration_that_disagrees_with_the_program_is_refused(tiny_root):
    from benchmarks.harness.cell import program_config
    from tests.benchmark.conftest import CONFIGS

    bad = dict(CONFIGS["tiny-serve"], hidden_size=128)
    with pytest.raises(SystemExit, match="hidden_size"):
        program_config(bad)


def _other_root(tmp_path, change):
    """The tiny root with the configuration that is not Mistral changed by
    ``change(cfg, published)`` before its files are written."""
    root = write_root(tmp_path / "root")
    cfg, published = other_configuration()
    change(cfg, published)
    add_configuration(root, OTHER, cfg, published)
    return root


def _wider(cfg, published):
    """A width cut: the file and the program agree on 192, the source says
    160, and ``reduced`` does not (and may not) list it."""
    cfg["ffn_hidden_size"] = 192
    cfg["orion"]["overrides"].append("model.d_ff=192")


def _one_more_size(cfg, published):
    """A size the source publishes and nothing checks."""
    published["conv_kernel"] = cfg["conv_kernel"] = 4


def _depth_as_published_unstated(cfg, published):
    """A reduced key whose published value the file does not state."""
    del cfg["published"]["num_layers"]


@pytest.mark.parametrize("change, key", [
    (_wider, "ffn_hidden_size"), (_one_more_size, "conv_kernel"),
    (_depth_as_published_unstated, "num_layers")])
def test_a_configuration_that_leaves_its_source_is_refused_by_the_key(
        tmp_path, capsys, monkeypatch, change, key):
    root = _other_root(tmp_path, change)
    with pytest.raises(SystemExit, match=key):
        run_cell(root, "tiny.other-batch", capsys, monkeypatch)


def test_without_its_published_keys_a_configuration_does_not_run(
        tmp_path, capsys, monkeypatch):
    root = write_root(tmp_path / "root")
    (root / PUBLISHED / f"{OTHER}.json").unlink()
    with pytest.raises(SystemExit, match=f"{OTHER}.json"):
        run_cell(root, "tiny.other-batch", capsys, monkeypatch)
