"""A benchmark root at a tiny size, in a temporary directory: its own
BENCHMARK.json, configurations and traffic mixes (new files only), run by the
real harness on the CPU."""

import collections
import copy
import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.harness.cell import PUBLISHED  # noqa: E402  (needs the path)

BASE = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=256, num_hidden_layers=2,
            rope_theta=500000.0, rms_norm_eps=1e-5,
            tie_word_embeddings=False, sliding_window=None)
SERVE = ["inference.max_seq_len=128", "inference.page_size=16",
         "inference.num_pages=64", "inference.max_batch_size=4",
         "inference.prefill_chunk=32", "inference.decode_window=4"]
SERVE_LIMITS = {"logit_rel_err_worst_probe_median_clear": 1e-3,
                "window_kv_rel_err_max": 1e-4, "window_token_gap_max": 1e-3}
CONFIGS = {
    "tiny-serve": dict(
        BASE, role="serve", frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-llama", "overrides": SERVE},
        correct={"limits": SERVE_LIMITS}),
    "tiny-moe-serve": dict(
        BASE, num_local_experts=4, num_experts_per_tok=2, rope_theta=1e6,
        role="serve", frontend={"prefill_token_budget": 128},
        orion={"preset": "tiny-mixtral",
               "overrides": SERVE + ["model.capacity_factor=2.0"]},
        correct={"router_margin_min": 1e-3, "limits": SERVE_LIMITS}),
    "tiny-train": dict(
        BASE, role="train",
        orion={"preset": "tiny-llama",
               "overrides": ["data.seq_len=64", "data.batch_size=2",
                             "train.num_steps=100000"]},
        correct={"grad_leaves": ["blocks.attn.wk", "blocks.mlp.w_gate",
                                 "embed.tokens"],
                 "limits": {"grad_rel_err_max": 1e-3}}),
}


def _mix(**kw):
    return dict(kind="serve", block=8, pair_seed=1, probe_windows=2,
                prompt={"median": 24, "sigma": 0.8, "min": 4, "max": 60},
                output={"median": 8, "sigma": 0.5, "min": 4, "max": 12},
                trace_seconds=0.2, probe_prompts=[5, 40], **kw)


MIXES = {
    "tiny-dense-batch": _mix(clients=3, warm_requests=6),
    "tiny-batch": _mix(clients=4, warm_requests=8),
    "tiny-train": dict(kind="train", seq_len=64, distinct_batches=2,
                       warm_steps=2, max_in_flight=2, trace_steps=2),
}
# A configuration that is not Mistral, as a later PR brings one: its files
# lie under data/tiny_other/ (configuration, the source's published keys, the
# reference its configuration names) and are copied into the root unedited.
OTHER = "tiny-other-serve"
OTHER_FILES = REPO / "tests" / "benchmark" / "data" / "tiny_other"
CELLS = {"tiny.train": ("tiny-train", "tiny-train", "mistral-7b.train-8k"),
         "tiny.dense-batch": ("tiny-serve", "tiny-dense-batch",
                              "mixtral-8x7b.serve-batch"),
         "tiny.batch": ("tiny-moe-serve", "tiny-batch", "mixtral-8x7b.serve-batch"),
         "tiny.other-batch": (OTHER, "tiny-batch", "mixtral-8x7b.serve-batch")}
NOT_SIZES = ("role", "frontend", "orion", "correct")


def add_configuration(root: pathlib.Path, name: str, cfg: dict,
                      published: dict) -> dict:
    """Write a configuration's two files; its BENCHMARK.json entry."""
    (root / "benchmarks" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    (root / PUBLISHED / f"{name}.json").write_text(json.dumps(published))
    return {"name": name, "source": cfg.get("source", "test"),
            "reduced": cfg.get("reduced", []), "why": "test",
            "file": f"benchmarks/configs/{name}.json"}


def other_configuration() -> tuple[dict, dict]:
    return (json.loads((OTHER_FILES / "config.json").read_text()),
            json.loads((OTHER_FILES / "published.json").read_text()))


def write_root(root: pathlib.Path, extra_metrics=()) -> pathlib.Path:
    """The real BENCHMARK.json's metrics over the tiny cells."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmarks" / "configs").mkdir(parents=True)
    (root / "benchmarks" / "traffic").mkdir()
    (root / "benchmarks" / "reference").mkdir()
    (root / PUBLISHED).mkdir(parents=True)
    entries = [add_configuration(
        root, name, cfg,
        {k: v for k, v in cfg.items() if k not in NOT_SIZES})
        for name, cfg in CONFIGS.items()]
    entries.append(add_configuration(root, OTHER, *other_configuration()))
    shutil.copy(OTHER_FILES / "reference.py",
                root / "benchmarks" / "reference" / "biased.py")
    for name, mix in MIXES.items():
        (root / "benchmarks" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    stands_for = collections.defaultdict(list)
    for tiny, (_, _, real_name) in CELLS.items():
        stands_for[real_name].append(tiny)
    bm = copy.deepcopy(real)
    bm["configs"] = entries
    bm["workloads"] = [
        {"name": cell, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for cell, (c, t, _) in CELLS.items()]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"]
                              for t in stands_for[w]]
    bm["per_layer"] += list(extra_metrics)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return write_root(tmp_path_factory.mktemp("tiny_bench"))


def run_cell(root, workload, capsys, monkeypatch, trace=0, seconds=0.5,
             seed=2 ** 31 + 77):
    """The harness's ``main`` in this process; returns (exit code, the lines
    of standard output)."""
    from benchmarks import run as bench_run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / ".cache"))
    rc = bench_run.main([
        "--workload", workload, "--seed", str(seed), "--seconds",
        str(seconds), "--trace", str(trace)], root=root, allow_cpu=True)
    said = capsys.readouterr()
    lines = said.out.strip().splitlines()
    checks = [l for l in lines if l.startswith("check: ")]
    if rc == 0:     # every compared number ends standard error too
        assert said.err.strip().splitlines()[-len(checks):] == checks
    return rc, lines
