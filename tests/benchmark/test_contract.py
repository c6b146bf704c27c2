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


# What ``reduced`` may cut, by the field of the program that the key sizes
# (the key itself is named as the source names it): depth, the routed experts
# held here, the rows of the vocabulary held here. Never a width.
MAY_BE_REDUCED = {"n_layers", "n_experts", "vocab_size"}


def assert_configuration_keeps_to_its_source(entry, cfg, published):
    """One entry of ``configs``, its file and its source's keys as published.
    No model's sizes are written here: each configuration is held to the
    file of ITS source."""
    from benchmarks.harness.cell import widths

    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert cfg["source"] == entry["source"] and "assumed" in cfg
    # ``reduced`` may be empty: a model that one chip holds whole cuts
    # nothing. Its file then has no ``published`` group (or an empty one),
    # states every published key as published (the loop below) and is asked
    # for no ``deployment.chips_sharing_a_layer`` (nothing is shared).
    reduced = cfg["reduced"]
    assert isinstance(reduced, list) and reduced == entry["reduced"]
    assert len(set(reduced)) == len(reduced)
    assert set(cfg.get("published", {})) == set(reduced)
    for key, value in published.items():
        if key in reduced:
            # cut, and the file says from what
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key        # no width is ever cut
    field_of = widths(cfg)
    for key in reduced:
        assert key in published, key
        field = field_of.get(key)
        assert field in MAY_BE_REDUCED, (key, field)
        if field == "n_layers":
            assert 1 <= cfg[key] < published[key]
            continue
        # a chip's share of a layer: the file says of how many chips
        chips = cfg["deployment"]["chips_sharing_a_layer"]
        assert isinstance(chips, int) and chips >= 2
        assert cfg[key] < published[key] <= cfg[key] * chips, key
        if field == "n_experts":
            assert cfg[key] >= 8, key
        if field == "vocab_size":
            assert 8 * cfg[key] >= published[key], key


def test_configs_and_cells():
    from benchmarks.harness.cell import PUBLISHED

    names = [c["name"] for c in BM["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BM["workloads"]}
    assert used == set(names)
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    for c in BM["configs"]:
        assert c["source"].startswith("https://")
        assert_configuration_keeps_to_its_source(
            c, json.loads((REPO / c["file"]).read_text()),
            json.loads((REPO / PUBLISHED / f"{c['name']}.json").read_text()))
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
        # every per-layer metric names its cells: a cell that a later PR
        # adds gets the metrics it lists itself under, and no others
        assert m["workloads"] and set(m["workloads"]) <= cells
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


def test_the_configuration_that_is_not_mistral_keeps_to_its_source_too(
        tiny_root):
    from tests.benchmark.conftest import OTHER, other_configuration

    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    entry = next(c for c in bm["configs"] if c["name"] == OTHER)
    cfg, published = other_configuration()
    assert len(cfg["reduced"]) == 2 and cfg["reference"] != "model"
    assert_configuration_keeps_to_its_source(entry, cfg, published)
    # and the same assertions refuse a width that is cut, a cut the file
    # does not own up to, and a share with no deployment behind it
    for change in (lambda c: c.update(ffn_hidden_size=80),
                   lambda c: c["published"].pop("padded_vocab_size"),
                   lambda c: c.update(deployment="an eighth, say"),
                   lambda c: c.update(padded_vocab_size=128),
                   lambda c: c.update(reduced=c["reduced"] + ["kv_channels"],
                                      kv_channels=16)):
        bad = json.loads(json.dumps(cfg))
        change(bad)
        with pytest.raises((AssertionError, TypeError, KeyError)):
            assert_configuration_keeps_to_its_source(
                dict(entry, reduced=bad["reduced"]), bad, published)


# -- a configuration that cuts nothing (PR 43) -----------------------------------
# The configuration that is not Mistral again, of a source that publishes the
# depth and the vocabulary the tiny program runs: ``reduced`` is empty, and
# the file has no ``published`` group and no deployment to state.
UNCUT, UNCUT_CELL = "tiny-uncut-serve", "tiny.uncut-batch"


def uncut_configuration() -> tuple[dict, dict]:
    from tests.benchmark.conftest import other_configuration

    cfg, published = other_configuration()
    published.update({key: cfg[key] for key in cfg["reduced"]})
    del cfg["published"], cfg["deployment"]
    cfg["reduced"] = []
    return cfg, published


def uncut_root(root, cfg, published):
    """The tiny root with the uncut configuration and a cell of it, listed
    under what ``tiny.other-batch`` is listed under; its ``configs`` entry."""
    from tests.benchmark.conftest import add_configuration, write_root

    write_root(root)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    entry = add_configuration(root, UNCUT, cfg, published)
    bm["configs"].append(entry)
    bm["workloads"].append({"name": UNCUT_CELL, "config": UNCUT,
                            "traffic": "tiny-batch", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny.other-batch" in m.get("workloads", ()):
            m["workloads"].append(UNCUT_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return entry


def test_a_configuration_may_cut_nothing(tmp_path, capsys, monkeypatch):
    """An empty ``reduced`` all the way: the ``configs`` entry, ``Cell.find``,
    ``program_config`` against the source's published keys, the contract's
    assertions, and ``run.py`` itself on the CPU."""
    from benchmarks.harness.cell import Cell
    from tests.benchmark.conftest import run_cell

    cfg, published = uncut_configuration()
    root = tmp_path / "root"
    entry = uncut_root(root, cfg, published)
    assert entry["reduced"] == [] == cfg["reduced"]
    assert "published" not in cfg and "deployment" not in cfg
    cell = Cell.find(UNCUT_CELL, root=root)
    assert cell.config["reduced"] == [] and cell.published == published
    model = cell.program_config().model
    assert (model.n_layers, model.vocab_size) == (
        published["num_layers"], published["padded_vocab_size"])
    assert_configuration_keeps_to_its_source(entry, cfg, published)
    assert_configuration_keeps_to_its_source(      # an empty group is none
        entry, dict(cfg, published={}), published)
    rc, lines = run_cell(root, UNCUT_CELL, capsys, monkeypatch, trace=1)
    line = json.loads(lines[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0


def _cut_and_not_listed(cfg, entry):
    cfg["num_layers"] = 1
    cfg["orion"]["overrides"].append("model.n_layers=1")


def _listed_and_not_cut(cfg, entry):
    cfg.update(reduced=["num_layers"],
               published={"num_layers": cfg["num_layers"]})
    entry["reduced"] = ["num_layers"]


def _listed_in_the_entry_alone(cfg, entry):
    entry["reduced"] = ["num_layers"]


def _listed_in_the_file_alone(cfg, entry):
    cfg.update(reduced=["num_layers"], num_layers=1,
               published={"num_layers": 2})


def _a_published_group_with_nothing_cut(cfg, entry):
    cfg["published"] = {"num_layers": cfg["num_layers"]}


def _no_list_at_all(cfg, entry):
    del cfg["reduced"]


def _none_for_a_list(cfg, entry):
    cfg["reduced"] = entry["reduced"] = None


def _a_width_cut_and_not_listed(cfg, entry):
    cfg["ffn_hidden_size"] = 80


def _a_width_cut_and_listed(cfg, entry):
    cfg.update(reduced=["kv_channels"], kv_channels=16,
               published={"kv_channels": 32})
    entry["reduced"] = ["kv_channels"]


def _a_share_with_no_deployment(cfg, entry):
    cfg.update(reduced=["padded_vocab_size"], padded_vocab_size=128,
               published={"padded_vocab_size": 256})
    entry["reduced"] = ["padded_vocab_size"]


@pytest.mark.parametrize("change", [
    _cut_and_not_listed, _listed_and_not_cut, _listed_in_the_entry_alone,
    _listed_in_the_file_alone, _a_published_group_with_nothing_cut,
    _no_list_at_all, _none_for_a_list, _a_width_cut_and_not_listed,
    _a_width_cut_and_listed, _a_share_with_no_deployment])
def test_an_uncut_configuration_is_still_held_to_its_source(change):
    """The assertions that take an empty ``reduced`` refuse what they
    refused: a key that is cut and not listed, a listed key that is not cut,
    a list the entry and the file do not share, a width, a share of a layer
    with no deployment behind it."""
    cfg, published = uncut_configuration()
    entry = {"name": UNCUT, "source": cfg["source"], "reduced": [],
             "why": "test", "file": f"benchmarks/configs/{UNCUT}.json"}
    assert_configuration_keeps_to_its_source(entry, cfg, published)
    change(cfg, entry)
    with pytest.raises((AssertionError, TypeError, KeyError)):
        assert_configuration_keeps_to_its_source(entry, cfg, published)


def test_the_program_refuses_a_key_cut_under_an_empty_list(tmp_path):
    """``program_config`` from the other side: an uncut file whose depth is
    not the source's is refused by the key, and names ``reduced``."""
    from benchmarks.harness.cell import Cell

    cfg, published = uncut_configuration()
    _cut_and_not_listed(cfg, {})
    uncut_root(tmp_path / "root", cfg, published)
    cell = Cell.find(UNCUT_CELL, root=tmp_path / "root")
    with pytest.raises(SystemExit, match="num_layers.*not in 'reduced'"):
        cell.program_config()


# What each cell reported before every per-layer metric named its cells
# (``Cell.find`` at dc729ef), by name; the serve cell has since gained
# ``prefill_expert_roofline.batch``.
TRAIN_METRICS = {"compile_s", "train_step_ms.train", "train_mfu_pct.train",
                 "flash_attn_roofline.train", "device_idle_pct.train",
                 "compiles_in_window.train"}
PER_LAYER = {
    "mistral-7b.train-8k": TRAIN_METRICS,
    "mistral-7b.train-8k-fsdp4": TRAIN_METRICS,
    "mixtral-8x7b.serve-batch": {
        "compile_s", "decode_step_ms.batch", "moe_ffn_roofline.batch",
        "engine_host_ms_per_step.batch", "device_idle_pct.batch",
        "prefill_share_pct.batch", "slot_occupancy_pct.batch",
        "ttft_p50_ms.batch", "itl_p50_ms.batch", "compiles_in_window.batch",
        "admit_ms_per_step.batch", "engine_gap_ms_per_step.batch",
        "prefill_pad_pct.batch", "prefill_device_ms_per_ktoken.batch",
        "paged_decode_roofline.batch", "idle_unattributed_pct.batch",
        "prefill_expert_rows_per_token.batch",
        "prefill_expert_roofline.batch"},
}


# The per-layer entries accepted when this table was last read against the
# file (``test_scopes.FIRST + len(ACCEPTED)``; this module imports no jax): the
# driver holds each to its place, so a later PR's entry stands behind them.
ACCEPTED_ENTRIES = 50


@pytest.mark.parametrize("cell", sorted(PER_LAYER))
def test_a_cell_reports_the_metrics_it_reported(cell):
    """Of the accepted entries exactly those, so that no accepted entry's
    list gains or loses the cell; an entry behind the last accepted one that
    names the cell is an addition, not an edit, and is taken."""
    from benchmarks.harness.cell import Cell

    accepted = [m["name"] for m in BM["per_layer"][:ACCEPTED_ENTRIES]]
    assert len(accepted) == ACCEPTED_ENTRIES
    reported = [m["name"] for m in Cell.find(cell).per_layer]
    assert {n for n in reported if n in accepted} == PER_LAYER[cell]
