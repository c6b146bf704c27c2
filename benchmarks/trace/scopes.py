"""Device time of one trace by program and by part of the model.

The program enters each part of a layer as nested ``jax.named_scope``s
(``orion_tpu.obs.parts.PARTS``: ``attention/qkv``, ``mlp_moe/experts`` ...)
and names the programs it jits (``jit_orion_prefill``,
``jit_orion_decode_window`` ...). A scope reaches every compiled instruction as
its ``op_name`` (``jit(orion_decode_window)/while/body/attention/qkv/
dot_general``). This module reads them out of the ``.xplane.pb`` the run's own
``capture.Trace`` just wrote and gives, for every LEAF operation of the first
device (``reduce.leaves``: a ``while`` holds its body's operations), the
program it ran in (the ``XLA Modules`` event that holds its start), and its
part, and from those the seconds by (program stem, part).

- Where the path comes from (``load``): the profile's ``/host:metadata``
  plane holds the compiled HLO of every program that ran, each instruction
  with its ``op_name``; an event of the ``XLA Ops`` line is joined to it on
  (the id of the program that holds it in time, the instruction's name).
  All from the file the profiler already wrote; nothing else is read.
- A fusion carries the ``op_name`` of its root: a norm the compiler fused into
  the next matmul is booked to the matmul's part. That is the compiler's
  choice, and the reading is still a split of real time.
- ``unscoped`` of a program is its modules' seconds LESS what its operations
  under a part took: the operations under no part (a window program's top, key
  handling, loop bookkeeping) and the time inside a program during which no
  operation ran. So a program's parts add up to its modules' seconds, which
  is what ``decode_step_ms.batch`` and ``prefill_device_ms_per_ktoken.batch``
  divide.
- ``clock_offsets_ns``: by name of ``orion/*/run`` span, the least and the
  median (start of the first program the span launched - start of the
  span). A negative number is the profile's device clock running ahead of
  its host clock (``host_spans`` found 0.7-0.9 ms on the v5e): every idle
  table is good to about that.

``load`` turns the file into the plain dict the test fixture is stored in;
``attribute`` does the arithmetic on that dict and needs nothing of JAX. A
program without the vocabulary (the parent of the PR that added it) names no
program ``orion_*``: ``for_obs`` returns None and the readers leave their
metric out.

    python3 -m benchmarks.trace.scopes [<trace.xplane.pb>]

prints the table of the newest trace under ``.bench_trace/`` (the one a
``--trace 1`` run leaves behind), for the cells whose per-layer set is pinned
and for the training cells.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
from typing import Optional

from benchmarks.trace import host_spans, reduce

UNSCOPED = "unscoped"
BETWEEN = "(no operation running)"
_STEM = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_WRAPPED = re.compile(r"^(?:\w+\()+(.*?)\)+$")


def vocabulary() -> Optional[tuple]:
    """(parts, the train step's own scopes) as the program spells them, or
    None for a program from before it had them."""
    try:
        from orion_tpu.obs import parts
    except ImportError:
        return None
    return parts.PARTS, parts.STEP_SCOPES


@functools.lru_cache(maxsize=None)
def part_of(op_name: Optional[str], parts: tuple, outer: tuple = ()) -> str:
    """The part an ``op_name`` path lies under: the first component that is a
    part's parent, with the first later component that is one of its
    children (``attention`` ... ``kernel`` -> ``attention/kernel``; a parent
    under which no child follows is booked to the parent alone, which no
    metric of a part reads). Under no part, the innermost of the ``outer``
    scopes (the train step's ``optimizer``); else ``unscoped``."""
    if not op_name:
        return UNSCOPED
    # Outside a scan's body a transformation wraps the scope's name:
    # ``transpose(jvp(unembed))`` is the head's backward pass.
    path = [_WRAPPED.sub(r"\1", c) for c in op_name.split("/")]
    children: dict = {}
    for p in parts:
        parent, _, child = p.partition("/")
        children.setdefault(parent, set()).update({child} - {""})
    for i, name in enumerate(path):
        if name in children:
            child = next((c for c in path[i + 1:] if c in children[name]),
                         None)
            return f"{name}/{child}" if child else name
    return next((n for n in reversed(path) if n in outer), UNSCOPED)


def stem(module: str) -> str:
    """``jit_orion_prefill(123)`` -> ``orion_prefill``."""
    m = _STEM.match(module)
    return m.group(1) if m else module


def load(path: str) -> dict:
    """The first device's operations, each with its ``op_name``, its programs,
    and the host's ``orion/`` and ``bench.`` spans. An operation is joined to
    its ``op_name`` on (the id of the program that holds it in time, the
    operation's whole text): ``fusion.11`` is another instruction in every
    program."""
    from jax.profiler import ProfileData

    names = op_names(path)
    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = reduce.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines
                     if line.name in (reduce.MODULES_LINE, reduce.OPS_LINE)}
            modules = sorted(
                ([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                 for ev in lines.get(reduce.MODULES_LINE, [])),
                key=lambda e: e[1])
            starts = [s for _, s, _ in modules]
            ids = [program_id(n) for n, _, _ in modules]
            # A program's operations come again every run and every layer:
            # each distinct text is reduced to its two names once.
            known = functools.lru_cache(maxsize=None)(
                lambda text: (reduce.short_name(text), instruction(text)))
            ops = []
            for ev in lines.get(reduce.OPS_LINE, []):
                s = int(ev.start_ns)
                at = bisect.bisect_right(starts, s) - 1
                short, inst = known(ev.name)
                ops.append([short, s, int(ev.duration_ns),
                            names.get((ids[at], inst)) if at >= 0 else None])
            out["devices"][m.group(1)] = {
                reduce.MODULES_LINE: modules, reduce.OPS_LINE: ops}
        elif plane.name.startswith("/host:"):
            out["host"] += [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for line in plane.lines for ev in line.events
                if ev.name.startswith(host_spans.PREFIXES)
            ]
    return out


def program_id(module: str) -> Optional[int]:
    """``jit_orion_prefill(123)`` -> 123."""
    m = _STEM.match(module)
    return int(m.group(2)[1:-1]) if m and m.group(2) else None


# -- the one thing ``ProfileData`` does not show ------------------------------
# On the chip an operation's event is named by its whole HLO line WITHOUT its
# metadata, and neither the event nor its metadata has a stat that holds the
# ``op_name`` (PR 38's first chip call printed one in full: ``hlo_category``,
# ``program_id``, ``flops``, ``bytes_accessed``, ``shape_with_layout`` ... and
# no ``tf_op``). What the profile does hold is the ``/host:metadata`` plane:
# one event metadata a program that ran, its id the program's id (the number
# in ``jit_orion_prefill(123)``), with ONE stat, ``Hlo Proto``, the bytes of
# the program's ``HloProto`` as compiled: every instruction with its name and
# its ``metadata.op_name``. ``ProfileData`` shows a plane's lines and an
# event's own stats, and that plane has no line, so the table is read here
# from the file's bytes: the protocol-buffer wire format of the messages on
# the way and nothing else of the file. ``XSpace.planes`` = 1; ``XPlane.name``
# = 2, ``.event_metadata`` = 4 (a map: key = 1, value = 2);
# ``XEventMetadata.id`` = 1, ``.stats`` = 5; ``XStat.bytes_value`` = 6;
# ``HloProto.hlo_module`` = 1; ``HloModuleProto.computations`` = 3;
# ``HloComputationProto.instructions`` = 2; ``HloInstructionProto.name`` = 1,
# ``.metadata`` = 7; ``OpMetadata.op_name`` = 2.
METADATA_PLANE = "/host:metadata"


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes
    of a length-delimited field; fixed-width fields are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            val, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in a profile")
        yield key >> 3, val


def _all(buf, number: int):
    return (val for num, val in _fields(buf) if num == number)


def _first(buf, number: int, default=None):
    return next(_all(buf, number), default)


def instruction_names(hlo_proto) -> dict:
    """{instruction's name: its ``op_name``} of one serialized ``HloProto``;
    an instruction the compiler made without metadata (a copy, a slice) is
    not in it."""
    out = {}
    for comp in _all(_first(hlo_proto, 1, b""), 3):
        for inst in _all(comp, 2):
            op_name = _first(_first(inst, 7, b""), 2)
            if op_name:
                out[str(_first(inst, 1, b""), "utf-8")] = str(op_name, "utf-8")
    return out


def op_names(path: str) -> dict:
    """{(program id, instruction's name): its ``op_name``} of every program
    whose HLO one ``.xplane.pb`` holds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for plane in _all(space, 1):
        if bytes(_first(plane, 2, b"")) != METADATA_PLANE.encode():
            continue
        for entry in _all(plane, 4):
            meta = _first(entry, 2, b"")
            program = _first(meta, 1)
            for stat in _all(meta, 5):
                proto = _first(stat, 6)
                if proto is not None:
                    out.update(((program, name), op_name) for name, op_name
                               in instruction_names(proto).items())
    return out


def instruction(text: str) -> str:
    """``%fusion.12 = bf16[8,32]{...} fusion(...)`` -> ``fusion.12``: the
    name the program's HLO knows the operation by."""
    return text.partition(" = ")[0].lstrip("%")


def attribute(events: dict, parts: tuple, outer: tuple = ()) -> Optional[dict]:
    """``by``: {program stem: {part: seconds}}, every program's parts adding
    up to ``module_s[stem]`` (see ``unscoped`` above); ``module_n``: runs;
    ``unscoped_ops``: {stem: {operation: seconds}} of what lies under no part;
    ``clock_offsets_ns``. An operation that starts outside every program is
    dropped. None where the trace holds no program of a name."""
    if not events["devices"]:
        return None
    dev = events["devices"][min(events["devices"])]
    modules = sorted(dev.get(reduce.MODULES_LINE, []), key=lambda m: m[1])
    if not modules:
        return None
    starts = [s for _, s, _ in modules]
    ops = dev.get(reduce.OPS_LINE, [])
    module_s: dict = {}
    module_n: dict = {}
    for name, _, d in modules:
        module_s[stem(name)] = module_s.get(stem(name), 0.0) + d / 1e9
        module_n[stem(name)] = module_n.get(stem(name), 0) + 1
    by: dict = {k: {} for k in module_s}
    unscoped_ops: dict = {k: {} for k in module_s}
    # ``leaves`` keeps (name, start, duration): the name here is the
    # operation's index, so that its scope is found again.
    for i, s, d in reduce.leaves([(i, op[1], op[2]) for i, op in enumerate(ops)]):
        at = bisect.bisect_right(starts, s) - 1
        if at < 0 or s >= modules[at][1] + modules[at][2]:
            continue
        prog = stem(modules[at][0])
        part = part_of(ops[i][3], parts, outer)
        if part == UNSCOPED:
            book = unscoped_ops[prog]
            book[ops[i][0]] = book.get(ops[i][0], 0.0) + d / 1e9
        else:
            by[prog][part] = by[prog].get(part, 0.0) + d / 1e9
    for prog, got in by.items():
        in_ops = sum(unscoped_ops[prog].values())
        left = module_s[prog] - sum(got.values())
        unscoped_ops[prog][BETWEEN] = left - in_ops
        got[UNSCOPED] = left
    return {"by": by, "module_s": module_s, "module_n": module_n,
            "unscoped_ops": unscoped_ops,
            "clock_offsets_ns": clock_offsets(events["host"], modules)}


def clock_offsets(host: list, modules: list) -> dict:
    """{``orion/*/run`` span's name: [least, median, spans]} of (start of the
    first program such a span launched - start of the span), ns; a program
    belongs to the run span it overlaps most, as in
    ``host_spans.attribute``. Only the engine's
    own programs (``orion_*``) count: a key split launched while the step
    was built can still run when the span opens."""
    runs = [(s, s + d, name) for name, s, d in host
            if name.startswith("orion/") and name.endswith("/run")]
    first: dict = {}
    for name, s, d in modules:
        if not stem(name).startswith("orion_"):
            continue
        overlap, span = max(
            ((min(s + d, e1) - max(s, s1), (s1, e1, kind))
             for s1, e1, kind in runs), default=(0, None))
        if overlap > 0:
            first[span] = min(first.get(span, s), s)
    lags: dict = {}
    for (s1, _, kind), s in first.items():
        lags.setdefault(kind, []).append(s - s1)
    return {kind: [min(v), sorted(v)[len(v) // 2], len(v)]
            for kind, v in lags.items()}


def seconds(got: dict, program: str, prefixes: tuple) -> float:
    """Seconds of ``program`` under the parts that are, or lie under, one of
    ``prefixes`` (``("attention/kernel", "attention/cache")``,
    ``("mlp_moe",)``)."""
    return sum(v for part, v in got["by"].get(program, {}).items()
               if any(part == p or part.startswith(p + "/") for p in prefixes))


def rest(got: dict, program: str, prefixes: tuple) -> float:
    """Every other second of ``program``: its modules' less ``seconds``."""
    return got["module_s"].get(program, 0.0) - seconds(got, program, prefixes)


_CACHE: dict = {}


def for_path(path: str) -> Optional[dict]:
    """``attribute`` of one trace file, once a process; prints the table the
    first time."""
    if path not in _CACHE:
        vocab = vocabulary()
        _CACHE[path] = got = (
            None if vocab is None else attribute(load(path), *vocab))
        if got is not None:
            say(got)
    return _CACHE[path]


def for_obs(obs: dict) -> Optional[dict]:
    """``attribute`` of this run's own trace (the newest under
    ``.bench_trace/``), once per process; prints the table of device time by
    program and part the first time, above the result line. None without a
    trace, or where no program carries an ``orion_`` name."""
    if not obs.get("trace"):
        return None
    path = host_spans.newest_trace()
    if path is None:
        return None
    got = for_path(path)
    if got is None or not any(k.startswith("orion_") for k in got["by"]):
        return None
    return got


def decode_ms_per_step(obs: dict, prefixes: Optional[tuple]) -> Optional[float]:
    """Milliseconds a token step (the denominator of ``decode_step_ms.batch``:
    runs of the decode-window program x its window) of the parts under
    ``prefixes``; None for every other second of the program."""
    got = for_obs(obs)
    prog = "orion_decode_window"
    if got is None or not got["module_n"].get(prog):
        return None
    pick = seconds if prefixes is not None else rest
    s = pick(got, prog, prefixes if prefixes is not None else DECODE_PARTS)
    return 1e3 * s / (got["module_n"][prog] * obs["decode_window"])


def prefill_ms_per_ktoken(obs: dict, prefixes: Optional[tuple]
                          ) -> Optional[float]:
    """Milliseconds per 1000 real prompt positions of the traced segment (the
    denominator of ``prefill_device_ms_per_ktoken.batch``) of the prefill
    programs' parts under ``prefixes``; None for every other second of them."""
    got = for_obs(obs)
    prog = "orion_prefill"
    if got is None or not got["module_n"].get(prog):
        return None
    tokens = obs["trace"]["timing"].get("prefill_tokens")
    if not tokens:
        return None
    pick = seconds if prefixes is not None else rest
    s = pick(got, prog, prefixes if prefixes is not None else PREFILL_PARTS)
    return 1e3 * s / (tokens / 1000.0)


# What the four named decode metrics and the three named prefill metrics
# read; the fifth and the fourth read every other second of the program.
DECODE_ATTN_KERNEL = ("attention/kernel", "attention/cache")
DECODE_ATTN_PROJ = ("attention/norm", "attention/qkv", "attention/out")
DECODE_FFN = ("mlp_moe",)
DECODE_HEAD = ("embed", "unembed", "sample")
DECODE_PARTS = DECODE_ATTN_KERNEL + DECODE_ATTN_PROJ + DECODE_FFN + DECODE_HEAD
PREFILL_ATTN = ("attention",)
PREFILL_EXPERTS = ("mlp_moe/experts", "mlp_moe/shared", "mlp_moe/dense")
PREFILL_ROUTE = ("mlp_moe/norm", "mlp_moe/router", "mlp_moe/dispatch")
PREFILL_PARTS = PREFILL_ATTN + PREFILL_EXPERTS + PREFILL_ROUTE


def say(got: dict, top: int = 20) -> None:
    print("device time by program and part of the model "
          "(seconds, share of the program, per run):")
    for prog in sorted(got["by"], key=lambda k: -got["module_s"][k]):
        total, n = got["module_s"][prog], got["module_n"][prog]
        if not total:
            continue
        print(f"  {prog}: {total:.4f} s in {n} runs, "
              f"{1e3 * total / n:.3f} ms a run")
        for part, s in sorted(got["by"][prog].items(), key=lambda kv: -kv[1]):
            print(f"    {part:<20s} {s:9.4f} s {100 * s / total:6.1f} % "
                  f"{1e3 * s / n:9.3f} ms")
        named = sorted(got["unscoped_ops"][prog].items(),
                       key=lambda kv: -kv[1])[:top]
        if len(got["by"][prog]) > 1 and named:
            print(f"    the largest of {UNSCOPED} "
                  f"({len(got['unscoped_ops'][prog]) - 1} operations):")
            for name, s in named:
                print(f"      {name:<64s} {s:9.4f} s {1e3 * s / n:9.3f} ms")
    if got["clock_offsets_ns"]:
        print("clock: the first program of a run span starts, least / median, "
              + ", ".join(f"{lo / 1e6:+.3f} / {mid / 1e6:+.3f} ms into {kind} "
                          f"({n} spans)" for kind, (lo, mid, n)
                          in sorted(got["clock_offsets_ns"].items()))
              + " (negative: the profile's device clock runs ahead of its "
              "host clock)", flush=True)


if __name__ == "__main__":
    trace = sys.argv[1] if len(sys.argv) > 1 else host_spans.newest_trace()
    if trace is None:
        sys.exit("no trace under .bench_trace/: run a cell with --trace 1")
    print(trace)
    if for_path(trace) is None:
        sys.exit("the program has no vocabulary of parts, or the trace no "
                 "program")
