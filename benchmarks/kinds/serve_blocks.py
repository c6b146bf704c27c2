"""Traffic kind ``serve_blocks``: ``serve`` for an engine that generates by
diffusion over blocks (``model.block_length``): a dispatch is ONE block
program for every live slot, ``denoising_steps`` forwards that each decide
some of a block's positions and a commit forward that leaves its K/V, and it
yields up to a block of tokens a slot.

The run, the clients and the front end (``serve.run``, ``serve.Driver``), the
warm-up (``serve.warm_shapes``), the logit numbers (``serve.judged``) and the
verdict (``serve.decide``) are ``kinds/serve.py``'s; the tap and
``probe_numbers`` are this file's. ``serve.LogitTap`` runs a decode window's
steps again by the one-step decode body; here every FORWARD of a probe's
block program is run again by the program's one-forward body
(``runner.block_forward``) on the block AS THE PROGRAM FED IT, which the
program's own record of the forward that decided each position gives back
exactly. Compared are

(i) those logits, at the block's positions, against the float32 reference on
    (the committed prefix + the block as fed), and the prefill program's
    logits at the last whole-block position: relative L2 error a position;
(ii) the link between the block program and that body: the K/V rows the
    program's commit left against the rows the body writes from the final
    tokens (``block_kv_rel_err``); how far below the body's top logit each
    token the program decided lies, in standard deviations of the logits
    (greedy: 0; ``block_token_gap``); and by how much the confidence of a
    position the program left undecided exceeds that of one it decided at
    the same forward, as a share of the latter (the static rule decides the
    surest: 0; ``block_order_excess``);
(iii) the mask inside a block, which (i) barely sees (three rows among a
    context's hundreds move a position's logits by a few hundredths, about
    what bfloat16 does): on the SHORTEST probe, where a block's own rows are
    the largest share of what a query sees, at every row of a denoising
    forward that has a later row in its block, the error against the
    reference over the error against the reference's mask control
    (``causal_from`` = the probe's first generated block: no query sees a
    later row of its block), the median over those rows
    (``block_mask_side``: well under 1 if the queries see each other, well
    over 1 if they are a chain).

``break_link`` feeds the body other tokens: the control of (ii); the control
of (iii) is the planted fault itself (``tools/sdar_fault_probe.py``)."""

from __future__ import annotations

import contextlib
import random
from typing import Optional

import numpy as np

from benchmarks.kinds import serve


class BlockTap:
    """Reads the engine's own dispatches while a probe runs alone."""

    def __init__(self, engine, break_link: bool = False):
        import jax

        from orion_tpu.infer import runner

        self.engine, self.break_link = engine, break_link
        self.prefill: list = []
        self.blocks: list = []    # a block: (start, [(fed, logits)], numbers)
        mcfg, icfg, mesh = engine.mcfg, engine.icfg, engine.mesh
        self._pool = (mcfg.n_layers, icfg.num_pages, icfg.page_size)
        self._forward = jax.jit(
            lambda p, c, fed, sl, pt, act: runner.block_forward(
                p, c, fed, sl, pt, act, mcfg, icfg.max_seq_len, mesh),
            donate_argnums=(1,))
        self._kv = jax.jit(serve._kv_at, static_argnums=(4, 5, 6))
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
            return out
        if path != "decode":
            return out
        params, _, _, _, seq_lens, page_table, mask = args[:7]
        toks, at, cache = (np.asarray(out[0]), np.asarray(out[1]), out[-1])
        mcfg = self.engine.mcfg
        L, S = mcfg.block_length, self.engine.icfg.denoising_steps
        slot = int(np.argmax(np.asarray(mask)))         # the probe is alone
        start = int(np.asarray(seq_lens)[slot])
        pos = start + jnp.arange(L)
        wrote = np.asarray(self._kv(cache, page_table, slot, pos, *self._pool))

        def forward(cache, fed):
            if self.break_link:
                fed = (fed + 1) % mcfg.vocab_size
            logits, cache = self._forward(
                params, cache, jnp.asarray(fed, jnp.int32), seq_lens,
                page_table, mask)
            return np.asarray(logits[slot], np.float32), cache

        fed_logits, gap, excess = [], 0.0, 0.0
        for s in range(S):
            fed = np.where(at < s, toks, mcfg.mask_token_id)
            logits, cache = forward(cache, fed)
            fed_logits.append((fed[slot], logits))
            chosen, left = at[slot] == s, at[slot] > s
            z = logits - logits.max(axis=-1, keepdims=True)
            p = np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True)
            picked = logits[np.arange(L), toks[slot]]
            if chosen.any():
                gap = max(gap, float((
                    (logits.max(axis=-1) - picked) / logits.std(axis=-1)
                )[chosen].max()))
            if chosen.any() and left.any():
                least = float(p[np.arange(L), toks[slot]][chosen].min())
                excess = max(excess, float(
                    p.max(axis=-1)[left].max() - least) / least)
        _, cache = forward(cache, toks)                 # the commit, again
        again = np.asarray(self._kv(cache, page_table, slot, pos, *self._pool))
        self.blocks.append((start, fed_logits, {
            "block_kv_rel_err": serve._rel_err(wrote, again),
            "block_token_gap": gap, "block_order_excess": max(excess, 0.0)}))
        return (*out[:-1], cache)


def probe_numbers(engine, ref, hf: dict, mix: dict, seed: int,
                  control: Optional[str] = None,
                  break_link: bool = False) -> dict:
    """Each probe prompt goes ALONE through the engine's prefill and
    ``probe_blocks`` block programs. Returns, for every compared position
    (the prefill's last whole-block position and the L positions of every
    denoising forward), ``err`` against the reference ``ref`` on the same
    tokens as fed, with the reference's own router margin there, and for
    every block the three numbers of ``BlockTap``. With ``control``,
    ``control_err`` holds the errors of the CONTROL in the program's place."""
    import jax
    import jax.numpy as jnp

    g = hf["generation"]
    L, mask_id = g["block_length"], g["mask_token_id"]
    rng = random.Random(seed * 7919 + 13)
    out = {"probe": [], "err": [], "margin": [], "control_err": [],
           "block_kv_rel_err": [], "block_token_gap": [],
           "block_order_excess": [], "block_mask_side": []}
    fn = jax.jit(lambda p, t, a, q=None, c=None: ref.logits_at(
        p, t, a, hf, q, c), static_argnums=(3,))
    shortest = min(mix["probe_prompts"])
    for i, n in enumerate(mix["probe_prompts"]):
        prompt = [rng.randrange(1, engine.mcfg.vocab_size) for _ in range(n)]
        whole = n - n % L
        n_new = mix["probe_blocks"] * L - (n - whole)
        with BlockTap(engine, break_link) as tap:
            req = engine.submit_request(prompt, n_new)
            while engine.has_work():
                engine.step()
        if (req.outcome != "completed" or len(req.generated) != n_new
                or len(tap.blocks) != mix["probe_blocks"]):
            raise RuntimeError(
                f"probe of {n} tokens ended {req.outcome!r} with "
                f"{len(req.generated)} tokens in {len(tap.blocks)} blocks")
        final = np.array(prompt + list(req.generated), np.int64)
        # One shape a probe: the whole final length, blocks that are not yet
        # there fed as the mask token (invisible under the mask).
        compared = [(np.where(np.arange(len(final)) < whole, final, mask_id),
                     np.arange(whole - L, whole), tap.prefill[0][:1], 1)]
        for start, forwards, numbers in tap.blocks:
            for fed, logits in forwards:
                seq = np.where(np.arange(len(final)) < start, final, mask_id)
                seq[start:start + L] = fed
                compared.append((seq, np.arange(start, start + L), logits, L))
            for name, value in numbers.items():
                out[name].append(value)
        for seq, at, got, keep in compared:
            args = (engine.params, jnp.asarray(seq, jnp.int32),
                    jnp.asarray(at))
            want, margin = (np.asarray(x)[-keep:] for x in fn(*args))
            out["probe"] += [i] * keep
            errs = [serve._rel_err(a, b) for a, b in zip(got, want)]
            out["err"] += errs
            out["margin"] += [float(m) for m in margin]
            if n == shortest and keep == L:
                chain = np.asarray(fn(*args, None, jnp.int32(whole))[0])
                out["block_mask_side"] += [
                    e / max(serve._rel_err(a, b), 1e-30)
                    for e, a, b in zip(errs[:-1], got, chain)]
            if control is not None:
                low = np.asarray(fn(*args, control)[0])[-keep:]
                out["control_err"] += [
                    serve._rel_err(a, b) for a, b in zip(low, want)]
    return out


_LINK = ("block_kv_rel_err", "block_token_gap", "block_order_excess")
_serve_judged = serve.judged    # (``_as_blocks`` swaps the module's name)


def judged(numbers: dict, margin_min: float, errs: str = "err") -> dict:
    """``serve.judged``'s logit numbers (each probe's median over its
    compared positions and of those the worst probe's; the median over all
    probes' positions together), the largest of each link number, and the
    median of the mask's."""
    stats = _serve_judged({
        **numbers, "window_kv_rel_err": [0.0], "window_token_gap": [0.0],
    }, margin_min, errs)
    for name in ("window_kv_rel_err_max", "window_token_gap_max"):
        del stats[name]
    stats.update({name + "_max": max(numbers[name]) for name in _LINK})
    stats["block_mask_side_median"] = float(
        np.median(numbers["block_mask_side"]))
    return stats


@contextlib.contextmanager
def _as_blocks():
    """``serve``'s run and verdict read this kind's probes and numbers."""
    keep = serve.probe_numbers, serve.judged
    serve.probe_numbers, serve.judged = probe_numbers, judged
    try:
        yield
    finally:
        serve.probe_numbers, serve.judged = keep


def run(cell, dev, **kw):
    with _as_blocks():
        return serve.run(cell, dev, **kw)


def decide(numbers: dict, correct: dict):
    with _as_blocks():
        return serve.decide(numbers, correct)


# What ``benchmarks/tools.py calibrate`` asks a serving kind for.
build_engine = serve.build_engine
