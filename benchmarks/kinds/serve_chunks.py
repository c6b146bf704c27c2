"""Traffic kind ``serve_chunks``: ``serve_rows`` for an engine whose prompts
enter in CHUNKS that resume from the cache, and whose sparse layers select
the pages they read (``ModelConfig.resumes_prefill``: MiniCPM-SALA).

What differs from ``kinds/serve.py`` and ``kinds/serve_rows.py``:

- a probe's prompt is several prefill dispatches (the engine's own chunks,
  as the timed path runs them); the logits compared are the LAST one's;
- the closed set of prefill shapes is ONE row of one chunk and of each of
  its tail buckets, whatever the prompts' lengths, each resuming over the
  slot's whole page-table row (``prefill_shapes``);
- the window link reads the leaves of this cache: K and V one head a row,
  the compressed keys of the pages a window touches, and the state rows
  (``RowTap``'s);
- ONE NUMBER MORE, which logits cannot hold under seeded weights (attention
  is close to a mean of values there, and a wrong block moves a logit less
  than bfloat16 does): the program hands back the block ids it selected at
  every compared position (``runner.SELECTED``, an extra leaf of the cache a
  probe's dispatches are given), and the reference scores them
  (``reference/sala.regret``): ``selection_regret_max``, 0 in exact
  arithmetic and whatever the ties;
- the reference of a 65,536-token probe does not fit beside the pool: the
  probes all run first, then the (idle) engine's cache is freed while the
  references are computed and made anew, zeros, as the engine made it.
"""

from __future__ import annotations

import contextlib
import random
from typing import Optional

import numpy as np

from benchmarks.kinds import serve, serve_rows


def _cache_at(cache, page_table, slot, positions, n_layers: int,
              n_pages: int, page_size: int):
    """What the paged leaves hold of one slot around ``positions``, raveled
    and joined: K and V [layers, W, heads, width] there (a leaf that keeps
    one head a row is read a head at a time), and every compressed key of
    the pages those positions lie in and of the page before them (whose last
    kernel a window may complete)."""
    import jax.numpy as jnp

    pages = page_table[slot, positions // page_size]                 # [W]
    rows = jnp.arange(n_layers)[:, None] * n_pages + pages[None]    # [L, W]
    offset = (positions % page_size)[None, :]
    out = []
    for name in ("k", "v"):
        per = cache[name].shape[0] // (n_layers * n_pages)
        at = rows[..., None] * per + jnp.arange(per)
        out.append(cache[name][at, 0, offset[..., None]])
    before = page_table[slot, jnp.maximum(positions // page_size - 1, 0)]
    both = jnp.concatenate([pages, before])
    out.append(cache["ck"][
        jnp.arange(n_layers)[:, None] * n_pages + both[None]])
    return jnp.concatenate([a.astype(jnp.float32).ravel() for a in out])


class ChunkTap(serve_rows.RowTap):
    def __init__(self, engine, break_link: bool = False):
        import jax
        import jax.numpy as jnp

        from orion_tpu.infer import runner

        super().__init__(engine, break_link)
        mcfg, mesh = engine.mcfg, engine.mesh
        self._kv = jax.jit(_cache_at, static_argnums=(4, 5, 6))
        self.selected: list = []        # per compared position [Ls, K, T]
        shape = lambda rows: (
            mcfg.n_paged_layers, rows, mcfg.n_kv_heads,
            min(mcfg.sparse.topk, engine.pages_per_seq))
        self._ids = lambda rows: jnp.zeros(shape(rows), jnp.int32)
        core = jax.jit(
            lambda p, c, tok, pos, pt: runner._decode_core(
                p, {**c, runner.SELECTED: self._ids(tok.shape[0])}, tok, pos,
                pt, mcfg, mesh),
            donate_argnums=(1,))
        self._steps: list = []

        def one_step(p, c, tok, pos, pt):
            logits, c = core(p, c, tok, pos, pt)
            c = dict(c)
            self._steps.append(c.pop(runner.SELECTED))
            return logits, c

        self._core = one_step

    def _run(self, path, name, *args, **kwargs):
        from orion_tpu.infer import runner

        if path == "prefill":
            params, cache, tokens = args[:3]
            logits, cache = self._orig(
                path, name, params,
                {**cache, runner.SELECTED: self._ids(tokens.shape[0])},
                *args[2:], **kwargs)
            cache = dict(cache)
            ids = np.asarray(cache.pop(runner.SELECTED))[:, 0]
            # The last chunk's are the prompt's.
            self.prefill = [np.asarray(logits, np.float32)]
            self.selected = [ids]
            return logits, cache
        if path != "decode":
            return self._orig(path, name, *args, **kwargs)
        slot = int(np.argmax(np.asarray(args[5])))      # the probe is alone
        self._steps = []
        out = super()._run(path, name, *args, **kwargs)
        self.selected += [np.asarray(ids)[:, slot] for ids in self._steps]
        return out


def probe_numbers(engine, ref, hf: dict, mix: dict, seed: int,
                  control: Optional[str] = None,
                  break_link: bool = False) -> dict:
    """``serve.probe_numbers`` (each probe alone through the engine's
    chunked prefill and ``probe_windows`` decode windows; logits against the
    float32 reference) and, for every compared position, the regret of the
    program's selection by the reference's scores (``regret``, the worst
    sparse layer's and K/V head's)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.infer.kv_cache import init_cache

    n_new = mix["probe_windows"] * engine.decode_window
    rng = random.Random(seed * 7919 + 13)
    out = {"probe": [], "err": [], "margin": [], "control_err": [],
           "window_kv_rel_err": [], "window_token_gap": [], "regret": [],
           "control_regret": []}
    ran = []
    for n in mix["probe_prompts"]:
        prompt = [rng.randrange(1, engine.mcfg.vocab_size) for _ in range(n)]
        with ChunkTap(engine, break_link) as tap:
            req = engine.submit_request(prompt, n_new + 1)
            while engine.has_work():
                engine.step()
        if req.outcome != "completed" or len(req.generated) != n_new + 1:
            raise RuntimeError(
                f"probe of {n} tokens ended {req.outcome!r} with "
                f"{len(req.generated)} tokens")
        got = np.concatenate(
            [tap.prefill[0][:1]] + [logits for logits, _, _ in tap.decode])
        ran.append((prompt + list(req.generated[:n_new]), got,
                    np.stack(tap.selected, 1)))            # [Ls, R, K, T]
        out["window_kv_rel_err"] += [kv for _, kv, _ in tap.decode]
        out["window_token_gap"] += [gap for _, _, gap in tap.decode]
    # The references, in the pool's place (the engine is idle: every probe
    # has completed and nothing of the cache is read again).
    for leaf in engine.cache.values():
        leaf.delete()
    engine.cache = None
    fn = jax.jit(lambda p, t, a, q=None: ref.selection_at(p, t, a, hf, q),
                 static_argnums=(3,))
    regret = jax.jit(lambda s, i, a: ref.regret(s, i, a, hf))
    chosen = jax.jit(lambda s, a: ref.chosen_ids(s, a, hf))
    for i, (tokens, got, ids) in enumerate(ran):
        n = len(tokens) - n_new
        tokens = jnp.asarray(tokens, jnp.int32)
        at = jnp.arange(n - 1, n + n_new)
        want, scores = fn(engine.params, tokens, at)
        want = np.asarray(want)
        out["probe"] += [i] * len(got)
        out["err"] += [serve._rel_err(g, w) for g, w in zip(got, want)]
        out["margin"] += [0.0] * len(got)   # (every position counts)
        out["regret"] += [float(r) for r in np.asarray(
            regret(scores, jnp.asarray(ids), at)).max(axis=(0, 2))]
        if control is not None:
            # The control in the program's place: its logits, and the
            # selection its scores make, by the float32 scores.
            low, low_scores = fn(engine.params, tokens, at, control)
            out["control_err"] += [serve._rel_err(g, w)
                                   for g, w in zip(np.asarray(low), want)]
            out["control_regret"] += [float(r) for r in np.asarray(regret(
                scores, chosen(low_scores, at), at)).max(axis=(0, 2))]
        del want, scores
    engine.cache = init_cache(engine.mcfg, engine.icfg)
    return out


def judged(numbers: dict, margin_min: float, errs: str = "err") -> dict:
    """``serve.judged``'s numbers (every position counts: the margin here is
    the selection's, which the regret is proof against) and the worst
    position's selection regret."""
    regrets = numbers["control_regret" if errs == "control_err" else "regret"]
    return {**serve.judged(numbers, float("-inf"), errs),
            "selection_regret_max": max(regrets)}


def decide(numbers: dict, correct: dict):
    stats = judged(numbers, 0.0)
    limits = correct["limits"]
    checks = [(name, stats[name], limits[name]) for name in sorted(limits)]
    ok = all(lim is not None and np.isfinite(v) and v <= lim
             for _, v, lim in checks)
    return ok, checks


def prefill_shapes(icfg) -> list:
    """(rows, tokens): one row of a whole chunk and of each tail bucket."""
    step, chunk = icfg.prefill_chunk, icfg.prefill_chunk_tokens
    return [(1, s) for s in range(step, chunk + 1, step)]


def cell_prefill_shapes(cell, icfg) -> list:
    return prefill_shapes(icfg)


def warm_shapes(engine, cell, cfg) -> list:
    """Compile every shape through the engine's own prefill program as it
    launches it (a chunk that resumes over the slot's whole page-table row;
    inputs that write only the scratch page and the scratch state row), and
    the sampler."""
    import jax.numpy as jnp

    icfg = cfg.inference
    todo = prefill_shapes(icfg)
    for nb, s_pad in todo:
        logits, engine.cache = engine._run_dispatch(
            "prefill", "prefill", engine.params, engine.cache,
            jnp.zeros((nb, s_pad), jnp.int32), jnp.ones((nb,), jnp.int32),
            jnp.zeros((nb, s_pad // icfg.page_size), jnp.int32),
            jnp.zeros((nb,), jnp.int32),
            jnp.zeros((nb, engine.pages_per_seq), jnp.int32),
        )
        engine._sample(logits)
    return todo


@contextlib.contextmanager
def chunked():
    """``serve``'s run with this kind's probes, verdict and warm-up."""
    names = ("probe_numbers", "decide", "warm_shapes", "cell_prefill_shapes")
    keep = {name: getattr(serve, name) for name in names}
    for name in names:
        setattr(serve, name, globals()[name])
    try:
        yield
    finally:
        for name, fn in keep.items():
            setattr(serve, name, fn)


def run(cell, dev, **kw):
    with chunked():
        return serve.run(cell, dev, **kw)


# What ``benchmarks/tools.py calibrate`` asks a serving kind for.
build_engine = serve.build_engine
