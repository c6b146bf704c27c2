"""The plain reference of the Brumby family: a Qwen3-shaped decoder whose
attention layers are POWER RETENTION layers, written out in the quadratic
form, in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.

A layer, from the configuration's own keys:

    h = rmsnorm(x)            q = h Wq [N, H]    k = h Wk [K, H]    v = h Wv
    q = rope(rmsnorm_head(q)) k = rope(rmsnorm_head(k))     (per head, over H)
    g_t = log sigmoid(h_t Wr)         one number a K/V head and position
    A_ij = exp(sum_{j<l<=i} g_l) (q_i . k_j / sqrt(H))^2          (j <= i)
    y_i = sum_j A_ij v_j / sum_j A_ij   (a query head reads its group's K/V
                                         head and gate)
    x = x + y Wo ;  x = x + (silu(h2 Wgate) * (h2 Win)) Wout

Every weight ``A_ij`` of every pair is computed: there is no state, no
chunk, no cache and no kernel here, and nothing is shared with
``orion_tpu/ops/retention.py`` (whose recurrent and chunked forms this one
is the yardstick of). The config has no key for the power (2), the gate's
place, the normaliser or the q/k norm: the configuration file lists them as
``assumed`` and each is ONE function below.

Departures from a textbook transcription, each for memory on a chip that
the engine already fills: layers run under ``lax.scan`` over the stacked
weights, upcasting each matrix where it is used; heads run under
``lax.map`` and a head's queries in blocks of ``ROWS`` rows, so that of the
[S, S] weights one [ROWS, S] block exists at a time (S reaches 8208); the
MLP runs over blocks of rows for the same reason; logits are taken only at
the positions asked for, the head a block of its columns at a time (the
whole head in float32 would be 3.1 GB).

``quant="int8"`` is the CONTROL: the same mathematics with both operands of
every weight matmul rounded to int8 (weights per output channel,
activations per token)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 512
HEAD_BLOCKS = 8


def _q(x, axis):
    """Symmetric int8 rounding along ``axis``: what an int8 matmul sees."""
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


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _head_norm(x, scale, eps):
    """x [S, n, H]: RMSNorm of each head over its H numbers."""
    return _rmsnorm(x, scale, eps)


def _rope(x, positions, theta):
    """x [S, n, H]; half-split rotation (HF ``rotate_half``)."""
    H = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, H, 2, dtype=F32) / H))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _log_gate(h, wr, quant):
    """[S, K]: the log of each K/V head's gate at each position."""
    return jax.nn.log_sigmoid(_matmul(h, wr, quant))


def _row_blocks(x):
    """[S, ...] -> ([blocks, ROWS, ...], S): rows padded with zeros."""
    S = x.shape[0]
    pad = -S % ROWS
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape(-1, ROWS, *x.shape[1:]), S


def _retention(q, k, v, log_g):
    """q [S, N, H], k / v [S, K, H], log_g [S, K] -> [S, N, H]: every pair's
    weight written out, one query head and ROWS queries at a time."""
    S, N, H = q.shape
    rep = N // k.shape[1]
    since = jnp.cumsum(log_g, axis=0)            # [S, K]: sum of g_l, l <= t
    cols = jnp.arange(S)

    def one_head(args):
        qh, kh, vh, bh = args                    # [S, H] x 3, [S]
        qb, _ = _row_blocks(qh)
        bb, _ = _row_blocks(bh)
        rows, _ = _row_blocks(cols)

        @jax.checkpoint
        def one_block(blk):
            qr, br, ir = blk
            s = jnp.matmul(qr, kh.T) / (H ** 0.5)            # [ROWS, S]
            seen = cols[None, :] <= ir[:, None]
            decay = jnp.exp(jnp.where(seen, br[:, None] - bh[None, :],
                                      -jnp.inf))
            a = decay * s * s
            total = jnp.sum(a, axis=-1, keepdims=True)
            # The padding rows of the last block (zero queries) weigh
            # nothing at all: 0 / 1 there, so that no NaN exists even where
            # nothing reads it (a gradient would).
            return jnp.matmul(a, vh) / jnp.where(total > 0, total, 1.0)

        return jax.lax.map(one_block, (qb, bb, rows)).reshape(-1, H)[:S]

    out = jax.lax.map(one_head, (
        q.transpose(1, 0, 2),
        jnp.repeat(k, rep, axis=1).transpose(1, 0, 2),
        jnp.repeat(v, rep, axis=1).transpose(1, 0, 2),
        jnp.repeat(since, rep, axis=1).T))
    return out.transpose(1, 0, 2)


def _mlp(h, p, quant):
    """Gated MLP (silu), a block of rows at a time."""
    w_in, w_gate, w_out = (p[n].astype(F32)
                           for n in ("w_in", "w_gate", "w_out"))
    hb, S = _row_blocks(h)

    def one(rows):
        up = jax.nn.silu(_matmul(rows, w_gate, quant)) * _matmul(
            rows, w_in, quant)
        return _matmul(up, w_out, quant)

    return jax.lax.map(one, hb).reshape(-1, h.shape[-1])[:S]


def param_spec(hf: dict) -> dict:
    """The tree this model reads, in the layout the program's model reads:
    {path: (shape, kind)}; kind is 'normal', 'resid' or 'norm'
    (``weights.py`` draws them)."""
    D, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    F = hf["intermediate_size"]
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
        ("blocks", "attn_norm", "scale"): ((L, D), "norm"),
        ("blocks", "mlp_norm", "scale"): ((L, D), "norm"),
        ("blocks", "attn", "wq"): ((L, D, N * H), "normal"),
        ("blocks", "attn", "wk"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wv"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wo"): ((L, N * H, D), "resid"),
        ("blocks", "attn", "q_norm"): ((L, H), "norm"),
        ("blocks", "attn", "k_norm"): ((L, H), "norm"),
        ("blocks", "attn", "wr"): ((L, D, K), "normal"),
        ("blocks", "mlp", "w_in"): ((L, D, F), "normal"),
        ("blocks", "mlp", "w_gate"): ((L, D, F), "normal"),
        ("blocks", "mlp", "w_out"): ((L, F, D), "resid"),
    }
    if not hf.get("tie_word_embeddings", False):
        spec[("lm_head",)] = ((D, V), "normal")
    return spec


def hidden_states(params, tokens, hf: dict, quant: Optional[str] = None):
    """tokens [S] -> the final norm's input [S, D] in float32."""
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    S = tokens.shape[0]
    positions = jnp.arange(S)
    x = params["embed"]["tokens"][tokens].astype(F32)

    @jax.checkpoint
    def layer(x, bp):
        a = bp["attn"]
        h = _rmsnorm(x, bp["attn_norm"]["scale"].astype(F32), eps)
        q = _matmul(h, a["wq"].astype(F32), quant).reshape(S, N, H)
        k = _matmul(h, a["wk"].astype(F32), quant).reshape(S, K, H)
        v = _matmul(h, a["wv"].astype(F32), quant).reshape(S, K, H)
        q = _rope(_head_norm(q, a["q_norm"].astype(F32), eps), positions,
                  theta)
        k = _rope(_head_norm(k, a["k_norm"].astype(F32), eps), positions,
                  theta)
        log_g = _log_gate(h, a["wr"].astype(F32), quant)
        y = _retention(q, k, v, log_g).reshape(S, N * H)
        x = x + _matmul(y, a["wo"].astype(F32), quant)
        h = _rmsnorm(x, bp["mlp_norm"]["scale"].astype(F32), eps)
        return x + _mlp(h, bp["mlp"], quant), None

    return jax.lax.scan(layer, x, params["blocks"])[0]


def _logits(x, params, hf, quant):
    """x [n, D] -> [n, V], the head a block of its columns at a time."""
    if hf.get("tie_word_embeddings", False):
        head = params["embed"]["tokens"].T
    else:
        head = params["lm_head"]
    D, V = head.shape
    nb = HEAD_BLOCKS if V % HEAD_BLOCKS == 0 else 1
    blocks = head.reshape(D, nb, V // nb).transpose(1, 0, 2)
    out = jax.lax.map(lambda w: _matmul(x, w.astype(F32), quant), blocks)
    return out.transpose(1, 0, 2).reshape(x.shape[0], V)


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    a router margin of ``inf`` at each (there is no router)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, hf, quant)
        x = _rmsnorm(x[at], params["final_norm"]["scale"].astype(F32),
                     hf["rms_norm_eps"])
        return (_logits(x, params, hf, quant),
                jnp.full(x.shape[:1], jnp.inf))
