"""Repo-native AST lint (ISSUE 15, layer 2).

Host-side discipline the compiled-program contracts can't see: no device
syncs in dispatch hot paths, monotonic clocks in obs, every Stats class
riding the ``reset_timing``/registry protocol, Config dataclasses
validating their fields, and fault envelopes that never swallow blindly.
Each finding is typed and suppressible per-site with a comment of the
form ``# orion: allow[<rule>] <reason>`` on the finding's line or the
line above. The reason is mandatory — an allow comment without one is itself a
finding (``bad-allow``), and an allow that suppresses nothing is flagged
(``unused-allow``) so stale suppressions cannot accumulate. CLI:
``tools/lint.py [--diff [REF]]``.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterable, Optional, Sequence

__all__ = [
    "Finding", "RULES", "lint_source", "lint_paths", "iter_target_files",
    "DEFAULT_TARGETS",
]

# Entry scripts + packages the sweep covers (repo-relative).
DEFAULT_TARGETS = ("orion_tpu", "tools", "train.py", "generate.py")

_ALLOW_RE = re.compile(
    r"#\s*orion:\s*allow\[([a-z0-9_,\s-]+)\]\s*(.*)"
)


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    msg: str
    suppressed: bool = False
    reason: str = ""

    def __str__(self):
        tag = " (suppressed: %s)" % self.reason if self.suppressed else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}{tag}"


@dataclass
class _Allow:
    line: int
    rules: tuple
    reason: str
    used: bool = False


# ---------------------------------------------------------------------------
# Rule helpers
# ---------------------------------------------------------------------------


def _call_name(node: ast.Call) -> str:
    """Dotted best-effort name of a call target: ``jax.device_get`` /
    ``np.asarray`` / ``.item`` (attribute tail for method calls)."""
    f = node.func
    parts = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
        return ".".join(reversed(parts))
    return "." + ".".join(reversed(parts)) if parts else ""


def _enclosing_funcs(tree: ast.AST):
    """Yield (func_node, qualname) for every function (nested ones with
    their full dotted qualname)."""
    funcs = []

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.append((child, tuple(stack) + (child.name,)))
                walk(child, stack + [child.name])
            else:
                walk(child, stack)

    walk(tree, [])
    return funcs


def _walk_own_body(func) -> Iterable[ast.AST]:
    """Walk a function's OWN statements, not descending into nested
    function definitions — each nested def is visited separately by
    ``_enclosing_funcs``, so a call inside it must not be reported twice
    (once per enclosing frame)."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Rule:
    name: str
    doc: str
    fn: Callable[[ast.AST, str, str], list]

    def check(self, tree, src, relpath) -> list:
        return self.fn(tree, src, relpath)


def _rule(name, doc):
    def wrap(fn):
        return Rule(name, doc, fn)
    return wrap


def _is_host_sync(node: ast.Call) -> bool:
    """Host-synchronizing call shapes: ``<x>.item()`` /
    ``<x>.block_until_ready()`` on anything, ``jax.device_get`` /
    ``jax.block_until_ready``, and ``np.asarray`` (forces a device->host
    transfer when handed a device array)."""
    name = _call_name(node)
    tail = name.rsplit(".", 1)[-1]
    if tail in ("item", "block_until_ready"):
        return isinstance(node.func, ast.Attribute) or name.startswith(
            "jax."
        )
    return name in ("jax.device_get", "device_get", "np.asarray",
                    "numpy.asarray")

# Dispatch-body scope per module suffix: None = every function in the
# module is hot (runner/executor are the traced/dispatch layer); a tuple
# of prefixes scopes to the engine's step-loop call tree.
_DISPATCH_SCOPE = {
    "orion_tpu/infer/runner.py": None,
    "orion_tpu/infer/executor.py": None,
    "orion_tpu/infer/engine.py": (
        "step", "_decode", "_denoise", "_mixed", "_verify", "_prefill",
        "_propose",
        "_accept", "_run_dispatch", "_grow_pages", "_roll_window",
        # The other half of a launched prefill (ISSUE 40): its wait and
        # the one fetch of its picks.
        "_finish_prefill",
        # Host-tier copy paths (ISSUE 18): the batched d2h/h2d envelopes
        # run from admission/eviction inside step — their single syncs
        # are the documented one-copy points (justified allows).
        "_spill", "_restore", "_resolve_host", "offload_prefix",
        # Per-request KV paging (ISSUE 19): the batched page-in restore
        # is the same documented one-h2d envelope.
        "_page_in",
        # KV-page migration (ISSUE 20): the gather/scatter copy envelopes
        # run from the router's serving loop — one sync per batch each
        # (justified allows).
        "export_migration", "import_pages",
    ),
}


@_rule(
    "host-sync",
    "host-synchronizing call (.item/device_get/block_until_ready/"
    "np.asarray) inside an engine/runner/executor dispatch body — every "
    "sync in the hot path must be the documented ONE-fetch point",
)
def _host_sync(tree, src, relpath) -> list:
    scope = None
    for suffix, names in _DISPATCH_SCOPE.items():
        if relpath.endswith(suffix):
            scope = (True, names)
            break
    if scope is None:
        return []
    _, prefixes = scope
    out = []
    for func, qual in _enclosing_funcs(tree):
        # A nested helper inherits its enclosing dispatch body's scope:
        # any qualname component matching a hot-path prefix puts the
        # whole frame in scope.
        if prefixes is not None and not any(
            part.startswith(p) for part in qual for p in prefixes
        ):
            continue
        for node in _walk_own_body(func):
            if not isinstance(node, ast.Call) or not _is_host_sync(node):
                continue
            out.append(Finding(
                "host-sync", relpath, node.lineno,
                f"{_call_name(node)}() in dispatch body "
                f"{'.'.join(qual)}",
            ))
    return out


@_rule(
    "clock",
    "time.time() inside orion_tpu — span/duration timing must ride "
    "monotonic clocks (perf_counter/monotonic); wall-clock export "
    "stamps need a justifying allow",
)
def _clock(tree, src, relpath) -> list:
    if not relpath.startswith("orion_tpu/"):
        return []
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "time.time":
            out.append(Finding(
                "clock", relpath, node.lineno,
                "time.time() — use time.perf_counter()/monotonic() for "
                "durations",
            ))
    return out


@_rule(
    "stats-timing",
    "a *Stats dataclass without as_timing()/summary() — every Stats "
    "class must ride the reset_timing drain / registry protocol "
    "(PR 8's unification; an unregistered one silently exports nothing)",
)
def _stats_timing(tree, src, relpath) -> list:
    if not relpath.startswith("orion_tpu/"):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not node.name.endswith("Stats"):
            continue
        is_dc = any(
            (isinstance(d, ast.Name) and d.id == "dataclass")
            or (isinstance(d, ast.Call) and _call_name(d) == "dataclass")
            or (isinstance(d, ast.Attribute) and d.attr == "dataclass")
            for d in node.decorator_list
        )
        if not is_dc:
            continue
        methods = {
            n.name for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        if not methods & {"as_timing", "summary"}:
            out.append(Finding(
                "stats-timing", relpath, node.lineno,
                f"{node.name} defines neither as_timing() nor summary()",
            ))
    return out


@_rule(
    "config-validation",
    "a *Config dataclass in config.py without __post_init__ — domain "
    "validation at construction is what turns a typo'd knob into a "
    "named error instead of a trace-time stack",
)
def _config_validation(tree, src, relpath) -> list:
    if not relpath.endswith("orion_tpu/config.py"):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not node.name.endswith("Config"):
            continue
        methods = {
            n.name for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        if "__post_init__" not in methods:
            out.append(Finding(
                "config-validation", relpath, node.lineno,
                f"{node.name} has no __post_init__ validation",
            ))
    return out


# Fault-envelope modules: catching Exception here is sometimes the whole
# point (contain ANY dispatch failure) — but each catch-all must say so.
_FAULT_ENVELOPES = (
    "orion_tpu/runtime/fault.py", "orion_tpu/infer/executor.py",
    "orion_tpu/infer/engine.py", "orion_tpu/infer/router.py",
    "orion_tpu/ckpt/checkpoint.py",
)


@_rule(
    "fault-except",
    "bare/overbroad except inside a fault envelope — a blind catch "
    "swallows the typed-outcome discipline (PR 6/7); every intentional "
    "catch-all needs a justifying allow",
)
def _fault_except(tree, src, relpath) -> list:
    in_envelope = any(relpath.endswith(m) for m in _FAULT_ENVELOPES)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(Finding(
                "fault-except", relpath, node.lineno,
                "bare except: catches SystemExit/KeyboardInterrupt too",
            ))
            continue
        if not in_envelope:
            continue
        names = []
        types = (
            node.type.elts if isinstance(node.type, ast.Tuple)
            else [node.type]
        )
        for t in types:
            if isinstance(t, ast.Name):
                names.append(t.id)
            elif isinstance(t, ast.Attribute):
                names.append(t.attr)
        if set(names) & {"Exception", "BaseException"}:
            out.append(Finding(
                "fault-except", relpath, node.lineno,
                f"except {'/'.join(names)} in a fault envelope",
            ))
    return out


RULES: tuple[Rule, ...] = (
    _host_sync, _clock, _stats_timing, _config_validation, _fault_except,
)
RULE_NAMES = tuple(r.name for r in RULES) + (
    "bad-allow", "unused-allow", "parse-error",
)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def _iter_comments(src: str):
    """(line, text) for every REAL comment token — allow parsing must not
    read allow-shaped text out of string literals (a docstring quoting
    the syntax could silently suppress a neighboring finding)."""
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                yield tok.start[0], tok.string
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return


def _parse_allows(src: str, relpath: str) -> tuple[list, list]:
    """Collect ``# orion: allow[rule,...] reason`` comments; a missing
    reason is a ``bad-allow`` finding, an unknown rule too."""
    allows: list[_Allow] = []
    findings: list[Finding] = []
    for i, comment in _iter_comments(src):
        m = _ALLOW_RE.search(comment)
        if m is None:
            continue
        rules = tuple(
            r.strip() for r in m.group(1).split(",") if r.strip()
        )
        reason = m.group(2).strip()
        unknown = [r for r in rules if r not in RULE_NAMES]
        if unknown:
            findings.append(Finding(
                "bad-allow", relpath, i,
                f"allow names unknown rule(s) {unknown}; have "
                f"{sorted(set(RULE_NAMES) - {'bad-allow', 'unused-allow'})}",
            ))
            continue
        if not reason:
            findings.append(Finding(
                "bad-allow", relpath, i,
                "allow comment without a reason — justify the site",
            ))
            continue
        allows.append(_Allow(line=i, rules=rules, reason=reason))
    return allows, findings


def lint_source(src: str, relpath: str) -> list:
    """Lint one file's source; returns ALL findings (suppressed ones
    flagged, so callers can render them distinctly)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("parse-error", relpath, e.lineno or 0,
                        f"unparseable: {e.msg}")]
    allows, findings = _parse_allows(src, relpath)
    for rule in RULES:
        findings.extend(rule.check(tree, src, relpath))
    # Apply suppressions: an allow covers its own line and the line
    # directly below (comment-above style).
    by_line: dict[tuple[int, str], _Allow] = {}
    for a in allows:
        for rule in a.rules:
            by_line[(a.line, rule)] = a
            by_line[(a.line + 1, rule)] = a
    for f in findings:
        a = by_line.get((f.line, f.rule))
        if a is not None:
            f.suppressed = True
            f.reason = a.reason
            a.used = True
    for a in allows:
        if not a.used:
            findings.append(Finding(
                "unused-allow", relpath, a.line,
                f"allow[{','.join(a.rules)}] suppresses nothing — remove "
                f"the stale comment",
            ))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def iter_target_files(
    root: Path, targets: Sequence[str] = DEFAULT_TARGETS
) -> Iterable[Path]:
    for t in targets:
        p = root / t
        if p.is_file():
            yield p
        elif p.is_dir():
            yield from sorted(
                q for q in p.rglob("*.py") if "__pycache__" not in q.parts
            )


def lint_paths(
    root: Path, paths: Optional[Iterable[Path]] = None
) -> list:
    """Lint files (default: the full target set) and return findings."""
    root = Path(root)
    if paths is None:
        paths = iter_target_files(root)
    findings: list[Finding] = []
    for p in paths:
        p = Path(p)
        if p.suffix != ".py" or not p.exists():
            continue
        rel = str(p.relative_to(root)) if p.is_absolute() else str(p)
        findings.extend(lint_source(p.read_text(), rel))
    return findings
