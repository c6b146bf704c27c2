"""Lint-rule units (ISSUE 15 layer 2): every rule fires on a synthetic
violation, every suppression round-trips (allow -> suppressed -> removing
the code makes the allow itself a finding), and the repo itself sweeps
clean — the tier-1 CI hook for tools/lint.py."""

import os
import subprocess
import sys
from pathlib import Path

from orion_tpu.analysis import lint

ROOT = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _unsuppressed(findings, rule=None):
    return [
        f for f in findings
        if not f.suppressed and (rule is None or f.rule == rule)
    ]


# ---------------------------------------------------------------------------
# Rule units
# ---------------------------------------------------------------------------


def test_host_sync_rule_fires_and_scopes():
    src = (
        "import jax, numpy as np\n"
        "def _decode_all(self):\n"
        "    return np.asarray(jax.device_get(x))\n"
        "def helper_outside_scope(self):\n"
        "    return x.item()\n"
    )
    fs = lint.lint_source(src, "orion_tpu/infer/engine.py")
    hits = _unsuppressed(fs, "host-sync")
    # _decode_all is a dispatch body (both calls flagged); the helper is
    # outside the engine's scoped hot path.
    assert len(hits) == 2 and all(f.line == 3 for f in hits)

    # runner.py: EVERY function is traced code — the helper now counts.
    fs = lint.lint_source(src, "orion_tpu/infer/runner.py")
    assert len(_unsuppressed(fs, "host-sync")) == 3
    # Outside the dispatch modules the rule is silent.
    fs = lint.lint_source(src, "orion_tpu/train/trainer.py")
    assert _unsuppressed(fs, "host-sync") == []


def test_host_sync_nested_function_reported_once():
    """A call inside a helper nested in a dispatch body is ONE finding
    (the nested frame inherits the hot-path scope; the outer walk does
    not descend into it, so no double report)."""
    src = (
        "import jax\n"
        "def _decode_all(self):\n"
        "    def _inner():\n"
        "        return jax.device_get(x)\n"
        "    return _inner()\n"
    )
    fs = lint.lint_source(src, "orion_tpu/infer/engine.py")
    hits = _unsuppressed(fs, "host-sync")
    assert len(hits) == 1 and hits[0].line == 4


def test_host_sync_suppression_roundtrip():
    src = (
        "import jax\n"
        "def _decode_all(self):\n"
        "    return jax.device_get(x)  # orion: allow[host-sync] ONE fetch\n"
    )
    fs = lint.lint_source(src, "orion_tpu/infer/engine.py")
    assert _unsuppressed(fs) == []
    sup = [f for f in fs if f.suppressed]
    assert len(sup) == 1 and sup[0].reason == "ONE fetch"
    # Comment-above style also covers the next line.
    src2 = (
        "import jax\n"
        "def _decode_all(self):\n"
        "    # orion: allow[host-sync] ONE fetch\n"
        "    return jax.device_get(x)\n"
    )
    assert _unsuppressed(lint.lint_source(
        src2, "orion_tpu/infer/engine.py")) == []


def test_clock_rule_and_scope():
    src = "import time\nt = time.time()\n"
    assert len(_unsuppressed(
        lint.lint_source(src, "orion_tpu/obs/registry.py"), "clock")) == 1
    # tools/ may use wall clocks (bench stamps); the rule scopes to the
    # package.
    assert _unsuppressed(
        lint.lint_source(src, "tools/bench_thing.py"), "clock") == []
    ok = "import time\nt = time.perf_counter()\n"
    assert _unsuppressed(
        lint.lint_source(ok, "orion_tpu/obs/registry.py"), "clock") == []


def test_stats_timing_rule():
    bad = (
        "from dataclasses import dataclass\n"
        "@dataclass\n"
        "class FooStats:\n"
        "    n: int = 0\n"
    )
    fs = lint.lint_source(bad, "orion_tpu/metrics.py")
    assert len(_unsuppressed(fs, "stats-timing")) == 1
    good = bad + "    def as_timing(self):\n        return {}\n"
    # Re-parse: as_timing now inside the class body.
    good = (
        "from dataclasses import dataclass\n"
        "@dataclass\n"
        "class FooStats:\n"
        "    n: int = 0\n"
        "    def as_timing(self):\n"
        "        return {'n': self.n}\n"
    )
    assert _unsuppressed(
        lint.lint_source(good, "orion_tpu/metrics.py"), "stats-timing") == []
    # Non-dataclass *Stats (plain collector classes) are exempt.
    plain = "class BareStats:\n    pass\n"
    assert _unsuppressed(
        lint.lint_source(plain, "orion_tpu/metrics.py"), "stats-timing"
    ) == []


def test_config_validation_rule():
    src = (
        "from dataclasses import dataclass\n"
        "@dataclass(frozen=True)\n"
        "class FooConfig:\n"
        "    n: int = 0\n"
    )
    assert len(_unsuppressed(
        lint.lint_source(src, "orion_tpu/config.py"), "config-validation"
    )) == 1
    with_post = src + "    def __post_init__(self):\n        pass\n"
    assert _unsuppressed(
        lint.lint_source(with_post, "orion_tpu/config.py"),
        "config-validation") == []
    # Other modules' Config classes are out of scope.
    assert _unsuppressed(
        lint.lint_source(src, "orion_tpu/infer/engine.py"),
        "config-validation") == []


def test_fault_except_rule():
    bare = "try:\n    x = 1\nexcept:\n    pass\n"
    # Bare except is flagged everywhere.
    assert len(_unsuppressed(
        lint.lint_source(bare, "tools/somewhere.py"), "fault-except")) == 1
    broad = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert len(_unsuppressed(
        lint.lint_source(broad, "orion_tpu/infer/executor.py"),
        "fault-except")) == 1
    # Overbroad catches outside fault envelopes are allowed (metrics
    # providers etc. contain errors by design).
    assert _unsuppressed(
        lint.lint_source(broad, "orion_tpu/obs/registry.py"),
        "fault-except") == []
    typed = "try:\n    x = 1\nexcept ValueError:\n    pass\n"
    assert _unsuppressed(
        lint.lint_source(typed, "orion_tpu/infer/executor.py"),
        "fault-except") == []


def test_bad_allow_and_unused_allow():
    no_reason = (
        "import jax\n"
        "def _decode_all(self):\n"
        "    return jax.device_get(x)  # orion: allow[host-sync]\n"
    )
    fs = lint.lint_source(no_reason, "orion_tpu/infer/engine.py")
    rules = {f.rule for f in _unsuppressed(fs)}
    # The reasonless allow is itself a finding AND suppresses nothing.
    assert "bad-allow" in rules and "host-sync" in rules

    unknown = "x = 1  # orion: allow[warp-drive] because\n"
    fs = lint.lint_source(unknown, "orion_tpu/foo.py")
    assert [f.rule for f in _unsuppressed(fs)] == ["bad-allow"]

    stale = "x = 1  # orion: allow[clock] leftover reason\n"
    fs = lint.lint_source(stale, "orion_tpu/foo.py")
    assert [f.rule for f in _unsuppressed(fs)] == ["unused-allow"]


def test_unparseable_file_is_a_parse_error_finding(tmp_path):
    fs = lint.lint_source("def broken(:\n", "orion_tpu/x.py")
    assert [f.rule for f in fs] == ["parse-error"]


def test_allow_inside_string_literal_is_inert():
    """Allow-shaped text inside a STRING (a docstring quoting the
    syntax) must neither suppress a neighboring finding nor register as
    an unused allow — only real comment tokens count."""
    src = (
        "import time\n"
        'DOC = "example: # orion: allow[clock] sample reason"\n'
        "t = time.time()\n"
    )
    fs = lint.lint_source(src, "orion_tpu/obs/foo.py")
    assert [f.rule for f in _unsuppressed(fs)] == ["clock"]
    assert not any(f.suppressed for f in fs)


# ---------------------------------------------------------------------------
# The repo itself
# ---------------------------------------------------------------------------


def test_default_targets_exist():
    """A sweep target that was deleted or renamed fails here:
    ``iter_target_files`` passes a missing one by in silence."""
    missing = [t for t in lint.DEFAULT_TARGETS if not (ROOT / t).exists()]
    assert missing == []


def test_repo_sweeps_clean():
    """The acceptance pin: zero unsuppressed findings across orion_tpu/,
    tools/, and the entry scripts — every violation the first full sweep
    surfaced was fixed or justify-suppressed (ISSUE 15)."""
    findings = lint.lint_paths(ROOT)
    unsup = _unsuppressed(findings)
    assert unsup == [], "\n" + "\n".join(str(f) for f in unsup)
    # The suppressed set is the justified inventory: every one carries a
    # reason (bad-allow would have fired otherwise).
    assert all(f.reason for f in findings if f.suppressed)


def test_lint_cli_exit_codes(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py")],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    # --diff scopes to changed files (vs HEAD there may be none — the
    # command must still succeed and report its scope).
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py"), "--diff"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "scope:" in proc.stdout
