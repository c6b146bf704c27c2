"""The plain reference of a model that generates by diffusion over blocks
(SDAR-30B-A3B-Chat, ``model_type`` sdar_moe): its forward pass and its
generation procedure in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision. No kernel, no cache, no batching: every forward
is the whole sequence so far under a dense boolean mask. It reads the
configuration file's own keys and imports nothing of the program.

**A layer** (all alike; ``decoder_sparse_step`` 1 and ``mlp_only_layers`` []
make every one sparse; ``use_sliding_window`` false). Pre-norm residual
blocks, RMSNorm ``rms_norm_eps``, no bias, untied head.

- ``q = x Wq`` as heads x ``head_dim``, ``k``, ``v`` over the K/V heads.
  RMSNorm over each q and each k head with a learned scale BEFORE the
  rotation (assumed (a): Qwen3's, the family this model is adapted from);
  rotate-half rotary over the whole head at ``rope_theta``; scores
  ``q_i k_j / sqrt(head_dim)``.
- The mask, with block length L (assumed (b), ``generation.block_length``):
  j is visible to i iff ``j // L <= i // L``.
- FFN: ``p = softmax(x Wr)`` over all experts in float32, the
  ``num_experts_per_tok`` largest, gates ``p_e / sum of the chosen``
  (``norm_topk_prob``), ``y = sum g_e Wd_e(silu(Wg_e x) * Wu_e x)``. No
  shared expert, no dropped token: every expert is computed for every token
  and weighted by its gate, zero for those not chosen.
- Final RMSNorm and the head. Assumed (c): NO SHIFT: the logits at position
  i are over the token AT i.

**Generation** (``generate``; the family's public ``generate.py`` as far as
it can be stated without the file, every size under ``generation`` in the
configuration file). Departures, each on purpose: the procedure keeps a
boolean a position and never compares ids with the mask token's (a prompt
token equal to ``mask_token_id`` stays a token; the script compares ids);
the confidence of a drawn token is its float32 softmax probability under the
UNFILTERED logits whatever the temperature; a block that is fully decided
before its last denoising step makes its remaining steps for nothing (no
token changes); layers run under ``lax.scan`` over the stacked weights and
experts under a scan inside it, each matrix upcast where it is used; heads
run under ``lax.map``; logits are taken only at the positions asked for.

``quant="int8"`` is the CONTROL, not a reference: both operands of every
weight matmul rounded to int8 (weights per output channel, activations per
token), the nearest precision below bfloat16, in the program's place.
``causal_from=n`` is the control of the MASK, not a reference either: rows
from position ``n`` on see no later row of their own block (j <= i), which
is what a chain of W queries computes in a block program's place; the check
asks which of the two functions the program's logits lie nearer."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _q(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(x, w, quant: Optional[str]):
    """x [S, D] @ w [D, F]."""
    if quant is None:
        return jnp.matmul(x, w)
    if quant == "int8":
        return jnp.matmul(_q(x, -1), _q(w, 0))
    raise ValueError(f"unknown control precision {quant!r}")


def _up(w):
    return w.astype(F32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [S, n, H]; half-split rotation (HF ``rotate_half``)."""
    H = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, H, 2, dtype=F32) / H))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_mask(S: int, L: int, causal_from=None):
    """[S, S] bool, True = attend: j // L <= i // L (rows from
    ``causal_from`` on, the mask's control: j <= i)."""
    pos = jnp.arange(S)
    blk = pos // L
    mask = blk[None, :] <= blk[:, None]
    if causal_from is None:
        return mask
    return mask & ((pos[None, :] <= pos[:, None])
                   | (pos[:, None] < causal_from))


def _attention(q, k, v, mask):
    """q [S, N, H], k/v [S, K, H] -> [S, N, H]; the full masked softmax, one
    query head at a time."""
    S, N, H = q.shape
    rep = N // k.shape[1]

    def one_head(args):
        qh, kh, vh = args
        s = jnp.matmul(qh, kh.T) / (H ** 0.5)
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(s, axis=-1), vh)

    out = jax.lax.map(one_head, (
        q.transpose(1, 0, 2), jnp.repeat(k, rep, axis=1).transpose(1, 0, 2),
        jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)))
    return out.transpose(1, 0, 2)


def _moe(x, p, hf, quant):
    """-> (y, the router's margin a token: the logit of the last expert
    chosen less that of the first one left out)."""
    k = hf["num_experts_per_tok"]
    router_logits = jnp.matmul(x, _up(p["router"]))
    ranked = jax.lax.top_k(router_logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx
    ].set(top / jnp.sum(top, axis=-1, keepdims=True))

    def one_expert(y, ew):
        w_in, w_gate, w_out, g = ew
        h = jax.nn.silu(_matmul(x, _up(w_gate), quant)) * _matmul(
            x, _up(w_in), quant)
        return y + g[:, None] * _matmul(h, _up(w_out), quant), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["w_in"], p["w_gate"], p["w_out"], gates.T))
    return y, margin


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)} in the layout the program's model reads."""
    D, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    E, F = hf["num_experts"], hf["moe_intermediate_size"]
    return {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
        ("blocks", "attn_norm", "scale"): ((L, D), "norm"),
        ("blocks", "mlp_norm", "scale"): ((L, D), "norm"),
        ("blocks", "attn", "wq"): ((L, D, N * H), "normal"),
        ("blocks", "attn", "wk"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wv"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wo"): ((L, N * H, D), "resid"),
        ("blocks", "attn", "q_norm"): ((L, H), "norm"),
        ("blocks", "attn", "k_norm"): ((L, H), "norm"),
        ("blocks", "moe", "router"): ((L, D, E), "normal"),
        ("blocks", "moe", "w_in"): ((L, E, D, F), "normal"),
        ("blocks", "moe", "w_gate"): ((L, E, D, F), "normal"),
        ("blocks", "moe", "w_out"): ((L, E, F, D), "resid"),
    }


def hidden_states(params, tokens, hf: dict, quant: Optional[str] = None,
                  with_kv: bool = False, causal_from=None):
    """tokens [S] -> (final-norm input [S, D], router margins [layers, S]);
    with ``with_kv`` also every layer's rotated keys and values
    [layers, S, K, H] (what a cache would hold)."""
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    S = tokens.shape[0]
    positions = jnp.arange(S)
    mask = block_mask(S, hf["generation"]["block_length"], causal_from)
    x = params["embed"]["tokens"][tokens].astype(F32)

    def layer(x, bp):
        h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
        a = bp["attn"]
        q = _matmul(h, _up(a["wq"]), quant).reshape(S, N, H)
        k = _matmul(h, _up(a["wk"]), quant).reshape(S, K, H)
        v = _matmul(h, _up(a["wv"]), quant).reshape(S, K, H)
        q = _rope(_rmsnorm(q, _up(a["q_norm"]), eps), positions, theta)
        k = _rope(_rmsnorm(k, _up(a["k_norm"]), eps), positions, theta)
        o = _attention(q, k, v, mask).reshape(S, N * H)
        x = x + _matmul(o, _up(a["wo"]), quant)
        h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
        y, margin = _moe(h, bp["moe"], hf, quant)
        return x + y, (margin, k, v) if with_kv else (margin,)

    x, out = jax.lax.scan(layer, x, params["blocks"])
    return (x, *out)


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None,
              causal_from=None):
    """Float32 logits [len(at), V] of one sequence at positions ``at`` (over
    the token AT each: no shift), and the smallest router margin over the
    layers at each of them."""
    with jax.default_matmul_precision("highest"):
        x, margins = hidden_states(params, tokens, hf, quant,
                                   causal_from=causal_from)
        x = _rmsnorm(x[at], params["final_norm"]["scale"].astype(F32),
                     hf["rms_norm_eps"])
        return (_matmul(x, params["lm_head"].astype(F32), quant),
                margins.min(axis=0)[at])


def kv_of(params, tokens, hf: dict):
    """Rotated keys and values [layers, S, K, H] of a whole forward."""
    with jax.default_matmul_precision("highest"):
        _, _, k, v = hidden_states(params, tokens, hf, with_kv=True)
        return k, v


def schedule(L: int, steps: int) -> list:
    """Positions the static rule decides at each step: L / steps each, the
    remainder to the first steps."""
    each, rest = divmod(L, steps)
    return [each + (s < rest) for s in range(steps)]


def choose(conf: np.ndarray, undecided: np.ndarray, count: int,
           remasking: str, threshold: float) -> np.ndarray:
    """Which undecided positions of a block a step decides (bool [L])."""
    score = np.where(undecided, conf, -1.0)
    order = np.argsort(-score, kind="stable")      # ties: the lower index
    static = np.zeros_like(undecided)
    static[order[:min(count, int(undecided.sum()))]] = True
    if remasking == "low_confidence_static":
        return static
    if remasking != "low_confidence_dynamic":
        raise ValueError(f"unknown remasking {remasking!r}")
    over = undecided & (conf > threshold)
    return static if over.sum() < count else over


def generate(params, prompt, max_new: int, hf: dict, *,
             eos_id: Optional[int] = None,
             draw: Optional[Callable] = None,
             trace: Optional[list] = None) -> list:
    """The tokens at positions n .. n + max_new - 1 of one request, block by
    block, every forward on the whole sequence (no cache).

    ``draw(logits [L, V] float32, block, step) -> x0 [L]`` draws a token at
    every position of the block (default: argmax, temperature 0); a
    position's confidence is the float32 softmax probability of its drawn
    token. ``trace``, if given, receives ``(block, step, fed tokens [L],
    logits [L, V], decided-after [L])`` of every denoising forward."""
    g = hf["generation"]
    L, S, mask_id = g["block_length"], g["denoising_steps"], g["mask_token_id"]
    n = len(prompt)
    n_blocks = -(-(n + max_new) // L)
    seq = np.array(list(prompt) + [0] * (n_blocks * L - n), np.int64)
    decided = np.arange(n_blocks * L) < n
    fn = jax.jit(lambda t, a: logits_at(params, t, a, hf)[0])
    for b in range(n // L, n_blocks):
        lo, hi = b * L, (b + 1) * L
        for s, count in enumerate(schedule(L, S)):
            # The whole sequence, later blocks fed as the mask token: they
            # are invisible to this block under the mask (one shape a
            # request, so one compilation).
            fed = np.where(decided, seq, mask_id)
            logits = np.asarray(
                fn(jnp.asarray(fed, jnp.int32), jnp.arange(lo, hi)), np.float32)
            x0 = (np.argmax(logits, axis=-1) if draw is None
                  else np.asarray(draw(logits, b, s)))
            z = logits - logits.max(axis=-1, keepdims=True)
            p = np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True)
            conf = p[np.arange(L), x0]
            take = choose(conf, ~decided[lo:hi], count, g["remasking"],
                          g["confidence_threshold"])
            seq[lo:hi] = np.where(take, x0, seq[lo:hi])
            decided[lo:hi] |= take
            if trace is not None:
                trace.append((b, s, fed[lo:hi].copy(), logits,
                              decided[lo:hi].copy()))
        assert decided[lo:hi].all()
        # (the commit forward keeps this block's K/V; here nothing is kept)
    out = [int(t) for t in seq[n:n + max_new]]
    if eos_id is not None and eos_id in out:
        out = out[:out.index(eos_id) + 1]
    return out
