"""A WHOLE addition against the real ``BENCHMARK.json``, as a ``model_config``
PR makes one, rehearsed on a copy of the tree: new files and appended entries
only, then the copy's own tests that read the file. PR 43 read the tests' pins
one by one and left two that any sixth serving cell and any 51st per-layer
entry turned red (twelve tests), because nothing performed the whole thing: a
PR that writes a test over ``BENCHMARK.json`` finds the pin it plants here.

The tests find their root from ``__file__`` (``conftest.REPO``,
``harness/cell.ROOT``), so a copy is its own root. What the addition stands
for is the next configuration's (an uncut configuration on one chip, 256 slots
of short chat, kind ``serve_rows``, two kernel shares and a cache-bytes
counter); what it runs is tiny: the copy's tests hold the bookkeeping, and no
cell of the real benchmark runs here.

The rehearsal's own names are names NO PR brings (``REHEARSAL``): this file is
the benchmark's, a later PR may not edit it, and a name that PR also wrote
would be the pin this file is there to find. The names the next PR has
announced (``ANNOUNCED``, ISSUE 44) are used once, to make a copy that already
holds them, and only where the tree does not: the rehearsal is made behind
them too."""

import collections
import copy
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from tests.benchmark.conftest import OTHER_FILES, PUBLISHED, REPO
from tests.benchmark.test_contract import uncut_configuration
from tests.benchmark.test_scopes import LING

COPIED = ("BENCHMARK.json", "PERF.md", "pytest.ini", "benchmarks",
          "tests/benchmark", "tests/conftest.py")
LINKED = ("orion_tpu", "tools")         # what the copy's tests import beside
LEFT_BEHIND = shutil.ignore_patterns("__pycache__", "*.pyc")

# cell, configuration, traffic file, reference module; two kernel shares and a
# counter, named in that order
Addition = collections.namedtuple(
    "Addition", "cell config traffic reference shares counter")
REHEARSAL = Addition(
    "a-sixth.rehearsed-chat", "a-sixth-rehearsed-1chip", "a-sixth-rehearsed",
    "a_sixth_rehearsed",
    ("a_sixth_decode_roofline.rehearsed", "a_sixth_scan_roofline.rehearsed"),
    "a_sixth_cache_bytes_per_token.rehearsed")
ANNOUNCED = Addition(
    "jamba2-3b.serve-chat-256", "jamba2-3b-serve-1chip", "serve-chat-256",
    "jamba2",
    ("ssm_decode_roofline.chat256", "ssm_scan_roofline.chat256"),
    "state_cache_bytes_per_token.chat256")
SOURCE = "https://example.org/%s/blob/main/config.json"
# the lists Ling's cell stands in that are another model's: the new cell goes
# behind the last name of every other one
ANOTHER_MODELS = ("latent_decode_roofline.longctx",)
# an accepted cell that the counter lists beside the new one: an entry behind
# the last may name a cell that is there, and the tests of that cell's
# per-layer names have to take it
ALSO_LISTED = "mixtral-8x7b.serve-batch"
SHARE = {"unit": "%", "better": "higher", "source": "device_trace",
         "layer": "kernels"}
COUNTER = {"unit": "B", "better": "lower", "source": "program_counter",
           "layer": "scheduler"}
READER = '''"""%s: a reader of the rehearsal's; it finds nothing to read."""


def read(obs):
    return None
'''
MIX = {
    "kind": "serve_rows", "block": 256, "pair_seed": 4421, "clients": 256,
    "why": "closed loop, 256 clients = 256 slots of short chat",
    "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 2048},
    "output": {"median": 512, "sigma": 0.6, "min": 64, "max": 2048},
    "warm_requests": 64, "trace_seconds": 6.0, "probe_windows": 2,
    "probe_prompts": [100, 500, 1200, 2048],
}
# The copy's tests that read BENCHMARK.json, or a tiny root made from it, in
# ONE process (the suite's six workers have the other cores), about a minute.
# Left out, each passing on a grown copy by hand in PR 44:
# ``test_scopes.py``'s eight engines' programs (they read no BENCHMARK.json);
# of ``test_harness.py``'s eight runs of a tiny cell all but the traced one of
# ``tiny.batch``, and ``test_contract.py``'s run of an uncut configuration (a
# tiny root is the real file's metrics over the tiny cells: the train cell's
# line is the ungrown tree's, an untraced line has no per-layer name, and
# the three tiny serving cells all stand for ``ALSO_LISTED``, so the counter
# comes to each one's traced line by the same code); ``test_shapes.py``
# (the tiny root through ``Cell.find`` alone, as ``test_harness.py`` does);
# the planted faults of ``test_ling_cell.py`` (the fixture of the test kept);
# and ``test_reference.py`` with the cells' own files.
CHEAP = ("tests/benchmark/test_contract.py", "tests/benchmark/test_scopes.py",
         "tests/benchmark/test_traffic.py")
SELECTED = CHEAP + (
    "tests/benchmark/test_harness.py",
    "tests/benchmark/test_ling_cell.py::"
    "test_a_tiny_configuration_of_this_kind_runs_end_to_end")
NOT_SELECTED = (
    "not test_a_program_carries_its_name_and_every_matmul_a_part "
    "and not test_a_configuration_may_cut_nothing "
    "and not (test_each_kind_runs_and_prints_the_contract_line "
    "and not 1-tiny.batch)")


def files_under(root: pathlib.Path) -> dict:
    """{relative path: bytes} of what COPIED names under ``root``."""
    out = {}
    for name in COPIED:
        top = root / name
        for path in ([top] if top.is_file() else sorted(top.rglob("*"))):
            if path.is_file() and "__pycache__" not in path.parts:
                out[path.relative_to(root).as_posix()] = path.read_bytes()
    return out


def copy_of_the_tree(dst: pathlib.Path) -> pathlib.Path:
    for name in COPIED:
        (dst / name).parent.mkdir(parents=True, exist_ok=True)
        if (REPO / name).is_dir():
            shutil.copytree(REPO / name, dst / name, ignore=LEFT_BEHIND)
        else:
            shutil.copy(REPO / name, dst / name)
    for name in LINKED:
        (dst / name).symlink_to(REPO / name, target_is_directory=True)
    return dst


def write_new(path: pathlib.Path, text: str) -> None:
    with open(path, "x", encoding="utf-8") as f:    # a file that is there stays
        f.write(text)


def entries_of(names: Addition) -> list:
    return [dict(SHARE, name=n) for n in names.shares] + [
        dict(COUNTER, name=names.counter)]


def grow(bm: dict, names: Addition = REHEARSAL) -> dict:
    """``bm`` with the addition's entries appended: one more configuration
    that cuts nothing, one more serving cell on one chip, its name behind the
    last of ``serve_tokens_per_s``'s list and of the per-layer lists Ling's
    cell stands in that are not another model's, three entries behind the
    last, the third listing an accepted cell too. What ``bm`` has by name
    already (the tree of a PR that brought it) is left as it is."""
    bm = copy.deepcopy(bm)
    if names.config not in [c["name"] for c in bm["configs"]]:
        bm["configs"].append({
            "name": names.config, "source": SOURCE % names.config,
            "reduced": [], "file": f"benchmarks/configs/{names.config}.json",
            "why": "state-space mixers among a few attention layers, dense "
                   "MLPs, a tied head: one chip holds it whole, nothing is "
                   "cut"})
    if names.cell not in [w["name"] for w in bm["workloads"]]:
        bm["workloads"].append({
            "name": names.cell, "config": names.config,
            "traffic": names.traffic, "chips": 1,
            "why": "closed loop, 256 clients = 256 slots, prompts 32-2048, "
                   "outputs 64-2048: decode reads the weights and moves a "
                   "state row a slot a step; latency bypassed"})
        for m in bm["end_to_end"] + bm["per_layer"]:
            if (LING in m.get("workloads", ())
                    and m["name"] not in ANOTHER_MODELS
                    and not m["name"].endswith(".reason128")):
                m["workloads"].append(names.cell)
    there = [m["name"] for m in bm["per_layer"]]
    for e in entries_of(names):
        cells = [names.cell] + [ALSO_LISTED] * (e["name"] == names.counter)
        if e["name"] not in there:
            bm["per_layer"].append(
                dict(e, moves="serve_tokens_per_s", workloads=cells))
    return bm


def make_the_addition(root: pathlib.Path, names: Addition = REHEARSAL,
                      where_absent: bool = False) -> set:
    """New files and appended entries only; the paths it added. A file that
    is there is an error, or with ``where_absent`` is left as it is."""
    cfg, published = uncut_configuration()
    cfg.update(source=SOURCE % names.config, reference=names.reference)
    bench = root / "benchmarks"
    new = {
        bench / "configs" / f"{names.config}.json": json.dumps(cfg, indent=1),
        root / PUBLISHED / f"{names.config}.json":
            json.dumps(published, indent=1),
        bench / "reference" / f"{names.reference}.py":
            (OTHER_FILES / "reference.py").read_text(),
        bench / "traffic" / f"{names.traffic}.json": json.dumps(MIX, indent=1),
    }
    for e in entries_of(names):
        new[bench / "metrics" / f"{e['name']}.py"] = READER % e["name"]
    if where_absent:
        new = {p: text for p, text in new.items() if not p.exists()}
    for path, text in new.items():
        write_new(path, text)
    grown = grow(json.loads((root / "BENCHMARK.json").read_text()), names)
    (root / "BENCHMARK.json").write_text(json.dumps(grown, indent=1) + "\n")
    return {p.relative_to(root).as_posix() for p in new}


def assert_only_appended(old: dict, new: dict) -> None:
    """``BENCHMARK.json`` key by key, as the driver compares a PR that is no
    ``benchmark`` PR: every entry that was there in its place and as it was,
    but for names behind the last name of a list of cells."""
    def same_but_for_cells_behind(a: dict, b: dict) -> None:
        assert set(a) == set(b), a["name"]
        for key in a:
            if key == "workloads":
                assert b[key][:len(a[key])] == a[key], a["name"]
                assert len(set(b[key])) == len(b[key]), a["name"]
            else:
                assert a[key] == b[key], (a["name"], key)

    assert set(old) == set(new)
    for key in ("command", "paths", "run_seconds"):
        assert old[key] == new[key], key
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key], key
    assert len(new["end_to_end"]) == len(old["end_to_end"])
    assert len(new["per_layer"]) >= len(old["per_layer"])
    for a, b in zip(old["end_to_end"] + old["per_layer"],
                    new["end_to_end"]
                    + new["per_layer"][:len(old["per_layer"])]):
        same_but_for_cells_behind(a, b)
    names = [m["name"] for m in new["end_to_end"] + new["per_layer"]]
    assert len(set(names)) == len(names)


def run_the_copys_tests(root: pathlib.Path, selected, *more: str):
    """pytest in a process of its own, the copy its working directory."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    return subprocess.run(
        [sys.executable, "-m", "pytest", *selected, "-q", "-m", "not slow",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         f"--basetemp={root.parent / 'basetemp'}", *more],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


@pytest.fixture(scope="module")
def grown_copy(tmp_path_factory):
    root = copy_of_the_tree(tmp_path_factory.mktemp("addition") / "tree")
    return root, make_the_addition(root)


def test_a_whole_addition_passes_the_copys_tests_with_no_file_edited(
        grown_copy):
    root, added = grown_copy
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    grown = json.loads((root / "BENCHMARK.json").read_text())
    assert (len(grown["configs"]), len(grown["workloads"]),
            len(grown["per_layer"])) == (
        len(real["configs"]) + 1, len(real["workloads"]) + 1,
        len(real["per_layer"]) + 3)
    listed = [m["name"] for m in grown["end_to_end"] + grown["per_layer"]
              if REHEARSAL.cell in m.get("workloads", ())]
    lings = [m["name"] for m in real["end_to_end"] + real["per_layer"]
             if LING in m.get("workloads", ())]
    assert len(listed) == len(lings) - 5 + 3 and "serve_tokens_per_s" in listed
    assert grown["per_layer"][-1]["workloads"] == [REHEARSAL.cell, ALSO_LISTED]
    done = run_the_copys_tests(root, SELECTED, "-k", NOT_SELECTED)
    assert done.returncode == 0, done.stdout[-6000:] + done.stderr[-2000:]
    # not one file that was there differs, and nothing but the addition came
    before, after = files_under(REPO), files_under(root)
    assert set(after) - set(before) == added
    assert set(before) <= set(after)
    differ = [p for p in before if before[p] != after[p]]
    assert differ == ["BENCHMARK.json"]
    assert_only_appended(real, grown)


def test_the_addition_is_taken_behind_the_one_the_next_pr_announced(tmp_path):
    """The copy holds the announced names first, from the tree itself once a
    PR has brought them and from here until then; the rehearsal's own names
    collide with none of them, and the cheap tests of the copy still pass."""
    root = copy_of_the_tree(tmp_path / "tree")
    real = json.loads((root / "BENCHMARK.json").read_text())
    make_the_addition(root, ANNOUNCED, where_absent=True)
    announced = json.loads((root / "BENCHMARK.json").read_text())
    assert_only_appended(real, announced)
    for path in ("traffic/%s.json" % ANNOUNCED.traffic,
                 "configs/%s.json" % ANNOUNCED.config,
                 *("metrics/%s.py" % e["name"] for e in entries_of(ANNOUNCED))):
        assert (root / "benchmarks" / path).is_file(), path
    names = [m["name"] for m in announced["per_layer"]]
    assert all(e["name"] in names for e in entries_of(ANNOUNCED))
    assert ANNOUNCED.cell in [w["name"] for w in announced["workloads"]]
    before = files_under(root)
    added = make_the_addition(root)         # a name twice: FileExistsError
    grown = json.loads((root / "BENCHMARK.json").read_text())
    assert_only_appended(announced, grown)
    assert_only_appended(real, grown)
    assert len(grown["per_layer"]) == len(announced["per_layer"]) + 3
    assert [w["name"] for w in grown["workloads"]][-1] == REHEARSAL.cell
    after = files_under(root)
    assert set(after) - set(before) == added and len(added) == 7
    assert [p for p in before if before[p] != after[p]] == ["BENCHMARK.json"]
    done = run_the_copys_tests(root, CHEAP, "-k", NOT_SELECTED)
    assert done.returncode == 0, done.stdout[-6000:] + done.stderr[-2000:]


# The two lines PR 43 left, planted back into the copy: (file, the line of
# this tree, the line as it was, the test of the copy that then fails).
PINS = {
    "the serving cells written down": (
        "tests/benchmark/test_scopes.py",
        "    today = serving_cells(bm)\n",
        "    today = serving_cells(bm)\n"
        "    assert set(today) == set(UNPINNED + [LING, "
        "'mixtral-8x7b.serve-batch'])\n",
        "tests/benchmark/test_scopes.py::"
        "test_a_by_part_list_grows_by_serving_cells_and_loses_none",
        "14 failed"),
    "Ling's four at the end of the list": (
        "tests/benchmark/test_ling_cell.py",
        "    assert tuple(names[at:at + len(NEW)]) == NEW "
        "and names[-1] == FIFTH\n",
        "    assert names[-5:] == list(NEW) + [FIFTH]\n",
        "tests/benchmark/test_ling_cell.py::"
        "test_a_tiny_configuration_of_this_kind_runs_end_to_end",
        "1 error"),
}


@pytest.mark.parametrize("pin", sorted(PINS))
def test_with_an_old_pin_planted_back_the_rehearsal_fails(grown_copy, pin):
    root, _ = grown_copy
    name, line, as_it_was, test, outcome = PINS[pin]
    text = (root / name).read_text()
    assert text.count(line) == 1
    (root / name).write_text(text.replace(line, as_it_was))
    try:
        done = run_the_copys_tests(root, [test])
    finally:
        (root / name).write_text(text)
    assert done.returncode == 1 and outcome in done.stdout, done.stdout[-3000:]
    assert "passed" not in done.stdout.splitlines()[-1]


# What a list of cells can suffer from a PR that only means to append: the
# comparison above is the driver's, which lives outside the repo, so it is held
# here to the two edits this file's own ``grow`` could make by mistake.
def _a_cell_taken_out_of_a_list(bm):
    bm["per_layer"][6]["workloads"].remove(LING)


def _an_entry_in_front_of_an_accepted_one(bm):
    bm["per_layer"].insert(len(bm["per_layer"]) - 4, bm["per_layer"].pop())


@pytest.mark.parametrize("edit", [_a_cell_taken_out_of_a_list,
                                  _an_entry_in_front_of_an_accepted_one])
def test_the_comparison_takes_the_addition_and_refuses_an_edit(edit):
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    grown = grow(real)
    assert_only_appended(real, real)
    assert_only_appended(real, grown)
    edit(grown)
    with pytest.raises(AssertionError):
        assert_only_appended(real, grown)
