"""Traffic kind ``serve_rows``: ``serve`` for an engine whose decode step
ADVANCES rows that are a slot's (a recurrence's state, a convolution's tail)
beside what it writes into pages, and whose paged leaves are sized over some
of the layers only.

Everything is ``kinds/serve.py``'s but the probe's tap. ``serve.LogitTap``
runs each step of a window again, by the program's one-step body, on the
cache the window handed back: right where a step only ADDS a position to
pages, wrong where it moves a state on (the second run would start from the
window's last state). ``RowTap`` keeps a copy of the probe's slot rows from
before the window, puts it back before the steps are run again, and holds
the window to the one-step body on those rows too: the link's number is the
larger of the paged leaves' and the slot rows' relative error."""

from __future__ import annotations

import contextlib

import numpy as np

from benchmarks.kinds import serve


class RowTap(serve.LogitTap):
    def __init__(self, engine, break_link: bool = False):
        import jax

        super().__init__(engine, break_link)
        mcfg = engine.mcfg
        # The paged leaves' own layers (a program from before it could say:
        # all of them).
        self._pool = (getattr(mcfg, "n_paged_layers", mcfg.n_layers),
                      *self._pool[1:])
        rows = engine.icfg.max_batch_size + 1
        self._slot_leaves = [
            name for name in sorted(engine.cache)
            if engine.cache[name].ndim >= 3
            and engine.cache[name].shape[1] == rows]
        self._take = jax.jit(lambda cache, row: {
            name: cache[name][:, row] for name in self._slot_leaves})
        self._put = jax.jit(
            lambda cache, row, saved: {
                **cache, **{name: cache[name].at[:, row].set(saved[name])
                            for name in self._slot_leaves}},
            donate_argnums=(0,))

    def _flat(self, rows: dict) -> np.ndarray:
        return np.concatenate([
            np.asarray(rows[name], np.float32).ravel()
            for name in self._slot_leaves])

    def _run(self, path, name, *args, **kwargs):
        import jax.numpy as jnp

        if path != "decode" or not self._slot_leaves:
            return super()._run(path, name, *args, **kwargs)
        params, cache, last_token, seq_lens, page_table, mask = args[:6]
        slot = int(np.argmax(np.asarray(mask)))         # the probe is alone
        before = self._take(cache, slot + 1)
        out = self._orig(path, name, *args, **kwargs)
        toks, cache = out[0], out[-1]
        W = toks.shape[0]
        at = seq_lens[slot] + jnp.arange(W)
        wrote = np.asarray(self._kv(cache, page_table, slot, at, *self._pool))
        after = self._take(cache, slot + 1)
        wrote_rows = self._flat(after)
        cache = self._put(cache, slot + 1, before)
        steps = []
        for j in range(W):                      # step j read token j - 1
            tok = last_token if j == 0 else toks[j - 1]
            if self.break_link:
                tok = tok + 1
            logits, cache = self._core(
                params, cache, tok, seq_lens + j, page_table)
            steps.append(np.asarray(logits[slot], np.float32))
        again = np.asarray(self._kv(cache, page_table, slot, at, *self._pool))
        again_rows = self._flat(self._take(cache, slot + 1))
        # The engine goes on from what its own window left.
        cache = self._put(cache, slot + 1, after)
        logits = np.stack(steps)
        picked = logits[np.arange(W), np.asarray(toks)[:, slot]]
        gap = (logits.max(axis=-1) - picked) / logits.std(axis=-1)
        link = max(serve._rel_err(wrote, again),
                   serve._rel_err(wrote_rows, again_rows))
        self.decode.append((logits, link, float(gap.max())))
        return (*out[:-1], cache)


@contextlib.contextmanager
def tapped():
    """``serve``'s probes read through ``RowTap`` inside this block."""
    keep, serve.LogitTap = serve.LogitTap, RowTap
    try:
        yield
    finally:
        serve.LogitTap = keep


def run(cell, dev, **kw):
    with tapped():
        return serve.run(cell, dev, **kw)


def probe_numbers(*args, **kw):
    with tapped():
        return serve.probe_numbers(*args, **kw)


# What ``benchmarks/tools.py calibrate`` asks a serving kind for.
build_engine, judged, decide = serve.build_engine, serve.judged, serve.decide
