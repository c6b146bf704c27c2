"""Weights from the seed, made by the benchmark and by nothing of the program.

One jitted call draws every leaf on the device, in the dtype it is used in
(``fold_in`` of the leaf's path, so a leaf does not depend on the others).
Which leaves there are is the configuration's reference's to say
(``param_spec(hf)`` of ``reference/<name>.py``: ``{path: (shape, kind)}``,
kind 'normal', 'resid', 'norm' or ``("uniform", lo, hi)``): the tree has the
layout the program's model reads, and the reference reads the same arrays. A
test pins each configuration's layout against
``orion_tpu.models.init_params``.

``("uniform", lo, hi)`` is ``lo + (hi - lo) x U[0, 1)``, drawn in float32 and
cast to the tree's dtype (so within a rounding step of ``[lo, hi)`` in a
narrower one), for the leaves of a recurrence that are time constants and not
matrices: drawn as N(0, 0.02) a state decays within a position or two, and
its carry across a chunk or a fold never reaches the output check (PERF.md
section 6, PRs 33 and 41). Such a leaf takes the range its source initialises
it over, and the reference that names the kind states that source: a
state-space layer's ``A_log`` over ``[0, log d_state]`` and its step bias
over ``[softplus^-1(0.001), softplus^-1(0.1)]`` = ``[-6.907, -2.252]``
(Mamba, arXiv:2312.00752, section 3.6 and its code's ``dt_min``, ``dt_max``,
``A = 1..d_state``) give time constants of ten to a thousand positions."""

from __future__ import annotations

import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp

STD = 0.02
NORM_JITTER = 0.05


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def _draw(spec: dict, n_layers: int, dtype, key):
    """``n_layers`` scales the leaves that write into the residual stream
    ('resid'): N(0, 0.02 / sqrt(2 x layers))."""
    flat = {}
    for path, (shape, kind) in spec.items():
        k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        if isinstance(kind, tuple):
            name, lo, hi = kind
            if name != "uniform" or not lo < hi:
                raise ValueError(f"{'/'.join(path)}: no such kind {kind!r}")
            u = jax.random.uniform(k, shape, jnp.float32)
            flat[path] = (lo + (hi - lo) * u).astype(dtype)
            continue
        z = jax.random.normal(k, shape, dtype)
        if kind == "norm":
            flat[path] = (1.0 + NORM_JITTER * z).astype(dtype)
        else:
            std = STD / (2 * n_layers) ** 0.5 if kind == "resid" else STD
            flat[path] = (std * z).astype(dtype)
    return _nest(flat)


def make_params(spec: dict, n_layers: int, dtype: str, seed: int,
                out_shardings: Optional[Any] = None) -> dict:
    """The whole tree of ``spec`` in ONE jitted call from ``seed``."""
    fn = jax.jit(
        lambda key: _draw(spec, n_layers, jnp.dtype(dtype), key),
        out_shardings=out_shardings,
    )
    return fn(jax.random.key(seed % (2 ** 63)))


def for_cell(cell, cfg, seed: int, out_shardings: Optional[Any] = None) -> dict:
    """The weights of a cell's run: the tree its configuration's reference
    describes, at the depth and in the dtype the program (``cfg``, checked
    against the configuration file) holds its parameters in."""
    return make_params(cell.reference().param_spec(cell.config),
                       cfg.model.n_layers, cfg.model.param_dtype, seed,
                       out_shardings)
