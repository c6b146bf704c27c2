"""Traffic kind ``serve``: requests through ``orion_tpu.infer.InferenceEngine``
in a closed loop (N clients, each sends its next request when its last
completes).

From the program this takes the engine and its ``reset_timing`` spans; the
weights, the requests, the clock, the front end's admission budget, the
output check and every metric are the benchmark's."""

from __future__ import annotations

import collections
import random
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from benchmarks.harness.cell import Outcome, Phases
from benchmarks.harness.stats import emission_gaps
from benchmarks.kinds import shapes
from benchmarks.traffic import generator

clock = time.monotonic


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclass
class Rec:
    index: int
    due: float                           # its client was free to send it
    n_prompt: int
    max_new: int
    prompt: list
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    seen: int = 0
    emissions: list = field(default_factory=list)
    req: object = None


class Driver:
    """The clients and the front end: one thread, the engine's own step
    loop. ``turn()`` is one pass: every free client queues its next request,
    the front end hands the engine a burst within the budget, the engine
    runs one step, and what it emitted is observed."""

    def __init__(self, engine, mix: dict, icfg, budget: int, stream):
        self.engine, self.stream = engine, stream
        self.chunk, self.max_seq = icfg.prefill_chunk, icfg.max_seq_len
        self.slots, self.budget = icfg.max_batch_size, budget
        self.fifo: collections.deque = collections.deque()
        self.live: dict = {}
        self.records: list = []
        self.idle_clients = mix["clients"]
        self.steps = 0

    def _arrivals(self, now: float) -> None:
        while self.idle_clients > 0:
            self.idle_clients -= 1
            p = next(self.stream)
            rec = Rec(p.index, now, len(p.prompt), p.max_new, p.prompt)
            self.fifo.append(rec)
            self.records.append(rec)

    def _submit(self) -> None:
        if not self.fifo or len(self.engine.waiting):
            return
        n = shapes.take_burst(
            [r.n_prompt for r in self.fifo], self.slots - len(self.live),
            self.chunk, self.max_seq, self.budget,
        )
        for _ in range(n):
            rec = self.fifo.popleft()
            rec.req = self.engine.submit_request(rec.prompt, rec.max_new)
            rec.prompt = None
            self.live[rec.req.rid] = rec

    def _observe(self, now: float, done: list) -> None:
        for rec in self.live.values():
            n = len(rec.req.generated) - rec.seen
            if n > 0:
                rec.emissions.append((now, n))
                rec.seen += n
                if rec.t_first is None:
                    rec.t_first = now
        for req in done:
            rec = self.live.pop(req.rid, None)
            if rec is not None:
                rec.t_done = now
                self.idle_clients += 1

    def turn(self) -> None:
        with _annotation("bench.generate"):
            self._arrivals(clock())
            self._submit()
        if not self.engine.has_work():
            return
        with _annotation("bench.engine_step"):
            done = self.engine.step()
        self.steps += 1
        with _annotation("bench.observe"):
            self._observe(clock(), done)

    def run_until(self, stop) -> None:
        while not stop():
            self.turn()


# -- the output check ---------------------------------------------------------


def _kv_at(cache, page_table, slot, positions, n_layers: int,
           n_pages: int, page_size: int):
    """What the cache holds of one slot at ``positions`` in every layer: of
    every leaf of the engine's cache dict in the paged layout ([layers x
    pages, heads, page, width]: K and V, a latent, ...), the entries
    [layers, W, heads, width] in float32, raveled and joined in the order of
    the leaves' names. Leaves of another layout (the scale pools of a
    quantised cache) are left out."""
    import jax.numpy as jnp

    rows = (jnp.arange(n_layers)[:, None] * n_pages
            + page_table[slot, positions // page_size][None, :])
    offset = (positions % page_size)[None, :]
    paged = [name for name in sorted(cache) if cache[name].ndim == 4
             and cache[name].shape[0] == n_layers * n_pages
             and cache[name].shape[2] == page_size]
    if not paged:
        raise ValueError(f"no leaf of the cache {sorted(cache)} has the "
                         f"paged layout")
    return jnp.concatenate([
        cache[name][rows, :, offset, :].astype(jnp.float32).ravel()
        for name in paged])


class LogitTap:
    """Reads the engine's own dispatches while a probe runs.

    Prefill: the program's logits as they come. Decode: the fused window
    program hands back only the tokens it sampled and the KV it wrote, so
    each of its steps is run again by the program's one-step decode body on
    the same token, position and pool. That gives the step's logits (for the
    reference) and two numbers that tie the WINDOW program to them: how far
    the KV it wrote is from what the one-step body writes in its place, and
    how far below the top logit the token it sampled lies (greedy: 0).
    ``break_link`` feeds the one-step body another token: the control of
    those two numbers. Installed for the probes only."""

    def __init__(self, engine, break_link: bool = False):
        import jax

        from orion_tpu.infer import runner

        self.engine, self.break_link = engine, break_link
        self.prefill: list = []
        self.decode: list = []      # per window: (logits [W, V], kv, gap)
        mcfg, mesh = engine.mcfg, engine.mesh
        self._pool = (mcfg.n_layers, engine.icfg.num_pages,
                      engine.icfg.page_size)
        self._core = jax.jit(
            lambda p, c, tok, pos, pt: runner._decode_core(
                p, c, tok, pos, pt, mcfg, mesh),
            donate_argnums=(1,),
        )
        self._kv = jax.jit(_kv_at, static_argnums=(4, 5, 6))
        self._orig = engine._executor.run

    def __enter__(self):
        self.engine._executor.run = self._run
        return self

    def __exit__(self, *exc):
        self.engine._executor.run = self._orig

    def _run(self, path, name, *args, **kwargs):
        import jax.numpy as jnp

        out = self._orig(path, name, *args, **kwargs)
        if path == "prefill":
            self.prefill.append(np.asarray(out[0], np.float32))
        elif path == "decode":
            params, _, last_token, seq_lens, page_table, mask = args[:6]
            toks, cache = out[0], out[-1]
            W = toks.shape[0]
            slot = int(np.argmax(np.asarray(mask)))     # the probe is alone
            at = seq_lens[slot] + jnp.arange(W)
            wrote = np.asarray(self._kv(cache, page_table, slot, at,
                                        *self._pool))
            steps = []
            for j in range(W):                  # step j read token j - 1
                tok = last_token if j == 0 else toks[j - 1]
                if self.break_link:
                    tok = tok + 1
                logits, cache = self._core(
                    params, cache, tok, seq_lens + j, page_table)
                steps.append(np.asarray(logits[slot], np.float32))
            again = np.asarray(self._kv(cache, page_table, slot, at,
                                        *self._pool))
            logits = np.stack(steps)
            picked = logits[np.arange(W), np.asarray(toks)[:, slot]]
            gap = (logits.max(axis=-1) - picked) / logits.std(axis=-1)
            self.decode.append((logits, _rel_err(wrote, again),
                                float(gap.max())))
            out = (*out[:-1], cache)
        return out


def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def probe_numbers(engine, ref, hf: dict, mix: dict, seed: int,
                  control: Optional[str] = None,
                  break_link: bool = False) -> dict:
    """Each probe prompt goes ALONE through the engine's prefill and
    ``probe_windows`` decode windows. Returns, for every compared position
    (the last prompt position and every decode step), the relative L2 error
    of the logits against the float32 reference ``ref`` (the cell's
    ``reference()``) on the same tokens (``err``) with the reference's own router margin there (``margin``), and
    for every window the two numbers of ``LogitTap``. With ``control``,
    ``control_err`` holds the errors of the CONTROL in the program's place
    (the reference at that lower precision): the run that has to fail."""
    import jax
    import jax.numpy as jnp

    n_new = mix["probe_windows"] * engine.decode_window
    rng = random.Random(seed * 7919 + 13)
    out = {"probe": [], "err": [], "margin": [], "control_err": [],
           "window_kv_rel_err": [], "window_token_gap": []}
    for i, n in enumerate(mix["probe_prompts"]):
        prompt = [rng.randrange(1, engine.mcfg.vocab_size) for _ in range(n)]
        with LogitTap(engine, break_link) as tap:
            req = engine.submit_request(prompt, n_new + 1)
            while engine.has_work():
                engine.step()
        if req.outcome != "completed" or len(req.generated) != n_new + 1:
            raise RuntimeError(
                f"probe of {n} tokens ended {req.outcome!r} with "
                f"{len(req.generated)} tokens"
            )
        got = np.concatenate(
            [tap.prefill[0][:1]] + [logits for logits, _, _ in tap.decode])
        tokens = jnp.asarray(prompt + list(req.generated[:n_new]), jnp.int32)
        at = jnp.arange(n - 1, n + n_new)
        fn = jax.jit(lambda p, t, a, q=None: ref.logits_at(p, t, a, hf, q),
                     static_argnums=(3,))
        want, margin = (np.asarray(x) for x in fn(engine.params, tokens, at))
        out["probe"] += [i] * len(got)
        out["err"] += [_rel_err(g, w) for g, w in zip(got, want)]
        out["margin"] += [float(m) for m in margin]
        out["window_kv_rel_err"] += [kv for _, kv, _ in tap.decode]
        out["window_token_gap"] += [gap for _, _, gap in tap.decode]
        if control is not None:
            low = np.asarray(fn(engine.params, tokens, at, control)[0])
            out["control_err"] += [_rel_err(g, w) for g, w in zip(low, want)]
    return out


def judged(numbers: dict, margin_min: float, errs: str = "err") -> dict:
    """The numbers the limits are set on. The logit error is, for each
    probe, the MEDIAN over its compared positions whose router margin, in
    the float32 reference and in every layer, is at least ``margin_min`` (a
    dense model: all of them), and of those the WORST probe's. Nearer a tie
    than that, rounding in any precision picks another expert and the
    position reads 0.3-1.2: such positions say nothing about the program and
    are left out by a rule that asks the reference alone. A single position
    does not tell bfloat16 from the int8 control (0.03-0.08 against 0.09-0.2),
    a probe's median does; a probe with no position left has no number and
    fails.

    Two steadier numbers beside it, for a configuration whose ``limits``
    name them (PR 43; a cell is held to the numbers its file names and to no
    other). Where experts are many and a position's margin is of the size of
    the rounding in a bfloat16 score, no ``margin_min`` leaves positions
    over, a fifth of them picks another last expert and reads 0.03-0.09, and
    they come in RUNS (the positions behind one long prompt route alike): 12
    of one probe's 17 in a sound run, which moves its median into the
    control's range. The positions that did not flip tell the precisions
    apart, so: ``..._worst_probe_octile_clear``, each probe's LOWER OCTILE
    (of 17 positions the third smallest: it moves only if 15 do, as when
    every decode position is wrong) and of those the worst probe's; and
    ``..._all_probes_median_clear``, the median over all probes' clear
    positions together, which a fault in part of EVERY probe moves and a run
    of flips in one probe does not."""
    by_probe = collections.defaultdict(list)
    for probe, err, margin in zip(numbers["probe"], numbers[errs],
                                  numbers["margin"]):
        by_probe[probe] += [err] if margin >= margin_min else []
    clear = list(by_probe.values())
    if all(clear):
        median = max(float(np.median(v)) for v in clear)
        octile = max(float(np.quantile(v, 0.125)) for v in clear)
        pooled = float(np.median([e for v in clear for e in v]))
    else:       # a probe with nothing clear: no number at all
        median = octile = pooled = float("nan")
    return {
        "logit_rel_err_worst_probe_median_clear": median,
        "logit_rel_err_worst_probe_octile_clear": octile,
        "logit_rel_err_all_probes_median_clear": pooled,
        "window_kv_rel_err_max": max(numbers["window_kv_rel_err"]),
        "window_token_gap_max": max(numbers["window_token_gap"]),
        "clear_positions_per_probe": [len(v) for v in by_probe.values()],
    }


def decide(numbers: dict, correct: dict):
    stats = judged(numbers, correct.get("router_margin_min", 0.0))
    limits = correct["limits"]
    checks = [(name, stats[name], limits[name]) for name in sorted(limits)]
    ok = all(lim is not None and np.isfinite(v) and v <= lim
             for _, v, lim in checks)
    print(f"compared positions clear of a router tie, per probe: "
          f"{stats['clear_positions_per_probe']} of "
          f"{len(numbers['probe']) // len(stats['clear_positions_per_probe'])}",
          flush=True)
    return ok, checks


# -- set-up -------------------------------------------------------------------


def build_engine(cell, seed: int):
    from benchmarks.reference import weights
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.runtime import initialize

    cfg = cell.program_config()
    initialize(cfg.runtime)
    params = weights.for_cell(cell, cfg, seed)
    return cfg, InferenceEngine(cfg, params, seed=seed % (2 ** 31))


def cell_prefill_shapes(cell, icfg) -> list:
    """The cell's closed set of prefill shapes, from its two files alone."""
    return shapes.prefill_shapes(
        [p for p, _ in generator.length_table(cell.mix)],
        cell.mix["probe_prompts"], icfg.prefill_chunk, icfg.max_seq_len,
        cell.config["frontend"]["prefill_token_budget"], icfg.max_batch_size,
    )


def warm_shapes(engine, cell, cfg) -> list:
    """Compile every enumerated prefill shape through the engine's own
    prefill program (inputs that write only the scratch page), and the
    sampler at each row count."""
    import jax.numpy as jnp

    icfg = cfg.inference
    todo = cell_prefill_shapes(cell, icfg)
    for nb, s_pad in todo:
        logits, engine.cache = engine._run_dispatch(
            "prefill", "prefill", engine.params, engine.cache,
            jnp.zeros((nb, s_pad), jnp.int32), jnp.ones((nb,), jnp.int32),
            jnp.zeros((nb, s_pad // icfg.page_size), jnp.int32),
            jnp.zeros((nb,), jnp.int32), jnp.zeros((nb, 0), jnp.int32),
        )
        engine._sample(logits)
    return todo


# -- a run --------------------------------------------------------------------


def run(cell, dev, *, seed: int, seconds: float, trace: bool,
        t_process: float, compiles) -> Outcome:
    import jax

    mix, hf = cell.mix, cell.config
    phases = Phases(t_process)
    phases.mark("imports")
    cfg, engine = build_engine(cell, seed)
    jax.block_until_ready(engine.params)
    phases.mark("weights+engine")
    numbers = probe_numbers(engine, cell.reference(), hf, mix, seed)
    phases.mark("output check")
    correct, checks = decide(numbers, hf["correct"])
    warmed = warm_shapes(engine, cell, cfg)
    phases.mark("warm shapes")
    print(f"warmed prefill shapes (rows, tokens): {warmed}", flush=True)

    stream = generator.request_stream(mix, seed, cfg.model.vocab_size)
    drv = Driver(engine, mix, cfg.inference,
                 hf["frontend"]["prefill_token_budget"], stream)
    # Warm phase: the same load, uncounted, so that the window opens on a
    # system in its steady state: every client busy and out of step with
    # the others.
    want = mix["warm_requests"]
    drv.run_until(
        lambda: sum(r.t_done is not None for r in drv.records) >= want)
    phases.mark("warm traffic")
    phases.say()
    engine.reset_timing()
    n_setup, compile_s = compiles.take()
    steps0 = drv.steps
    t_open = clock()
    setup_s = t_open - t_process

    drv.run_until(lambda: clock() - t_open >= seconds)
    t_close = clock()
    timing = engine.reset_timing()
    n_window, _ = compiles.take()
    steps = drv.steps - steps0

    device_extra, breakdown, trace_obs = {}, None, None
    if trace and dev.platform != "cpu":   # a CPU has no device trace
        from benchmarks.trace import capture

        engine.reset_timing()
        with capture.Trace(cell) as tr:
            t_end = clock() + mix["trace_seconds"]
            drv.run_until(lambda: clock() >= t_end)
        trace_obs = tr.reduced(dev)
        device_extra = {"busy_s": trace_obs["busy_s"],
                        "window_s": trace_obs["window_s"]}
        breakdown = trace_obs["breakdown"]
        trace_obs["timing"] = engine.reset_timing()

    mine = [r for r in drv.records
            if r.t_done is not None and t_open < r.t_done <= t_close]
    failed = sum(r.req.outcome != "completed"
                 or len(r.req.generated) != r.max_new for r in mine)
    window_s = t_close - t_open
    tokens = sum(n for r in drv.records for t, n in r.emissions
                 if t_open < t <= t_close)
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": tokens / window_s}
    obs = {
        "window_s": window_s, "steps": steps, "timing": timing,
        "tokens": tokens, "requests": len(mine),
        "ttft_s": [r.t_first - r.due for r in mine],
        "gaps_s": [g for r in drv.records
                   for t, g in emission_gaps(r.emissions)
                   if t_open < t <= t_close],
        "compile_s": compile_s, "compiles_setup": n_setup,
        "compiles_in_window": n_window, "slots": cfg.inference.max_batch_size,
        "decode_window": timing["decode_window"], "trace": trace_obs,
        "config": hf, "peaks": dev.peaks if dev.platform != "cpu" else None,
    }
    print(f"window: {window_s:.3f}s {steps} engine steps, {len(mine)} "
          f"requests, {tokens} tokens, prefill_s {timing['prefill_s']:.3f} "
          f"device_s {timing['device_s']:.3f} host_s {timing['host_s']:.3f}",
          flush=True)
    engine.close()
    return Outcome(correct, checks, len(mine), failed, e2e, obs,
                   device_extra, breakdown)
