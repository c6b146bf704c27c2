"""A cell, found by name: its entry in ``BENCHMARK.json``, its configuration
file, its traffic file, the module of its traffic ``kind`` and the readers of
its per-layer metrics. Nothing a cell needs lives in this file."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from dataclasses import dataclass, field
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
PUBLISHED = pathlib.Path("tests", "benchmark", "data", "published")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict            # the configuration file, as run
    mix: dict               # the traffic file
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    bench_dir: pathlib.Path = BENCH
    published: Optional[dict] = None   # the source's config.json, as published

    @classmethod
    def find(cls, name: str, benchmark: Optional[dict] = None,
             root: pathlib.Path = ROOT) -> "Cell":
        bm = benchmark or load_benchmark(root)
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}"
            )
        w = cells[name]
        files = {c["name"]: c["file"] for c in bm["configs"]}
        with open(root / files[w["config"]], encoding="utf-8") as f:
            config = json.load(f)
        bench_dir = (root / files[w["config"]]).parent.parent
        pub = root / PUBLISHED / f"{w['config']}.json"
        if not pub.exists():
            raise SystemExit(f"{pub}: not there (every configuration brings "
                             f"its source's published keys)")
        with open(pub, encoding="utf-8") as f:
            published = json.load(f)
        from benchmarks.traffic.generator import load_mix

        mix = load_mix(w["traffic"], bench_dir / "traffic")
        e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
        e2e_names = {m["name"] for m in e2e}
        per = [m for m in bm["per_layer"]
               if _applies(m, name) and m["moves"] in e2e_names]
        return cls(name, w["chips"], w["config"], w["traffic"], config, mix,
                   e2e, per, bench_dir, published)

    def program_config(self):
        return program_config(self.config, published=self.published)

    def kind_module(self):
        """``kinds/<kind>.py`` of the traffic file's ``kind``."""
        return self._load("kinds", f"{self.mix['kind']}.py")

    def reader(self, metric_name: str):
        """``metrics/<name>.py``: a module with ``read(obs)``."""
        return self._load("metrics", f"{metric_name}.py")

    def reference(self):
        """``reference/<name>.py`` of the configuration's ``reference``
        (default ``model``): the plain model of this architecture, with
        ``param_spec(hf) -> {path: (shape, kind)}``, ``logits_at(params,
        tokens, at, hf, quant) -> (logits, router_margin)`` and, for a
        configuration that is trained, ``loss(params, inputs, targets, hf,
        quant, ...)``. It reads the configuration file's own keys."""
        return self._load("reference",
                          f"{self.config.get('reference', 'model')}.py")

    def _load(self, sub: str, filename: str):
        """Beside the cell's own configuration first, then in this
        benchmark's directory (a benchmark root elsewhere, as the tests
        make, brings only the files it adds)."""
        for base in (self.bench_dir, BENCH):
            if (base / sub / filename).exists():
                return _load(base / sub / filename)
        raise SystemExit(
            f"{sub}/{filename}: not there (named by BENCHMARK.json)")


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.parent.name + "_"
        + path.stem.replace(".", "_").replace("-", "_"), path
    )
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


# Published key -> the program's ModelConfig field: the default map. A
# configuration whose source names a size otherwise brings ``orion.widths``
# (the same form) in its own file. The configuration file is the only place
# the sizes live; the program's preset must agree with it.
_WIDTHS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
    "sliding_window": "sliding_window", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_token",
    "head_dim": "resolved_head_dim",
}


def widths(config: dict) -> dict:
    """Published key -> ModelConfig field for this configuration: the
    default map, and over it the file's own ``orion.widths``."""
    return {**_WIDTHS, **config["orion"].get("widths", {})}


def key_of(config: dict, field_name: str) -> str:
    """The key under which this configuration's source states the size the
    program calls ``field_name`` (``n_layers``, ``vocab_size`` ...)."""
    keys = [k for k, f in widths(config).items()
            if f == field_name and k in config]
    if len(keys) != 1:
        raise SystemExit(f"the configuration states model.{field_name} "
                         f"under {keys}: one key has to")
    return keys[0]


def program_config(config: dict, extra: tuple = (), published=None):
    """The program's Config for a configuration file, checked key by key
    against the sizes the file states. ``published`` (the source's own
    ``config.json``, ``Cell.published``): each of its keys must be checked
    here or be listed, with the reason it has no field, under
    ``orion.unchecked``, so that a size cannot go unchecked by silence."""
    from orion_tpu.config import get_config

    o = config["orion"]
    cfg = get_config(o["preset"], list(o["overrides"]) + list(extra))
    mapped, unchecked = widths(config), o.get("unchecked", {})
    reduced = config.get("reduced", ())
    for key, value in (published or {}).items():
        if key not in config:
            raise SystemExit(f"the source publishes {key!r} and the "
                             f"configuration file does not state it")
        stated = (config.get("published", {}).get(key) if key in reduced
                  else config[key])
        if stated != value:
            raise SystemExit(
                f"the source publishes {key}={value!r} and the configuration "
                f"file states {stated!r}"
                + ("" if key in reduced else f", with {key!r} not in 'reduced'"))
        if key not in mapped and key not in unchecked:
            raise SystemExit(
                f"the source publishes {key!r} and nothing checks it: map it "
                f"to a field of the program in orion.widths, or give the "
                f"reason it has none in orion.unchecked")
    for key, fld in mapped.items():
        if key not in config:
            continue
        if not hasattr(cfg.model, fld):
            raise SystemExit(f"orion.widths maps {key!r} to model.{fld}, "
                             f"which the program does not have")
        got = getattr(cfg.model, fld)
        if got != config[key]:
            raise SystemExit(
                f"configuration says {key}={config[key]!r} but the program "
                f"would run model.{fld}={got!r}"
            )
    # The head size: a source that names it (``head_dim``, or keys of its own
    # that orion.widths maps to the program's head-size fields) has had it
    # checked by the loop above; one that does not means hidden / heads.
    if not any("head_dim" in f for k, f in mapped.items() if k in config):
        want, rest = divmod(cfg.model.d_model, cfg.model.n_heads)
        if rest:
            raise SystemExit(
                "the source names no head size and hidden_size / "
                "num_attention_heads is not whole: map its head-size keys "
                "in orion.widths")
        if cfg.model.resolved_head_dim != want:
            raise SystemExit("head_dim of the program differs from the file's")
    return cfg


@dataclass
class Outcome:
    """What a kind's ``run`` hands back to the harness."""
    correct: bool
    checks: list                    # (name, value, limit) as compared
    attempted: int
    failed: int
    end_to_end: dict                # name -> value, all this kind measures
    obs: dict = field(default_factory=dict)   # what the readers read
    device_extra: dict = field(default_factory=dict)  # busy_s, window_s
    breakdown: Optional[dict] = None


class Phases:
    """Where set-up goes: seconds since the process started at each mark,
    printed as one line so that a slow set-up names its part."""

    def __init__(self, t_process: float):
        import time

        self._clock, self._t0 = time.monotonic, t_process
        self.marks: list = []

    def mark(self, name: str) -> None:
        self.marks.append((name, round(self._clock() - self._t0, 2)))

    def say(self) -> None:
        print("set-up, seconds since process start at the end of each "
              f"phase: {self.marks}", flush=True)
