"""The plain reference of MiniCPM-SALA (openbmb, ``model_type``
``minicpm_sala``): the forward pass in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, no kernel, no cache, no page, no
chunk of a prompt, no batching. Written from the published ``config.json``
(the configuration file's own keys) and the file's ``assumed`` sizes, not
from the program.

``x0 = scale_emb x E[token]``; block l, pre-norm residual under muP's depth
scaling, with the PUBLISHED depth: ``x += (scale_depth / sqrt(32)) x
Mixer_l(RMSNorm(x))``, ``x += (scale_depth / sqrt(32)) x MLP_l(RMSNorm(x))``;
the MLP a SwiGLU of ``intermediate_size``; logits ``(RMSNorm(x) / (hidden_size
/ dim_model_base)) W_head``. ``mixer_types`` says which mixer a layer has:

- ``lightning-attn`` (``lightning_nh`` heads of ``lightning_head_dim``): q,
  k, v = h Wq, h Wk, h Wv; RMSNorm over each head of q and of k
  (``qk_norm``); rotary embedding on q and k (``lightning_use_rope``, all
  of a head's numbers, rotate-half pairing); a head h decays by ``lambda_h =
  exp(-s_h)``, ``s_h = 2^(-8 (h + 1) / heads)``: ``S_t = lambda_h S_{t-1} +
  k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t`` (``lightning_scale``), as
  ``lax.scan`` over positions of the recurrence itself; RMSNorm over the
  concatenated heads of ``o_t`` (``use_output_norm``), times ``sigmoid(h
  Wz)`` elementwise (``use_output_gate``), then Wo.
- ``minicpm4`` (``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads): q, k, v = h Wq, h Wk, h Wv; RMSNorm over
  each head of q and of k; NO rotary embedding (``attn_use_rope`` false).
  Compressed keys ``c_{g,j} = mean(k_{g, stride j .. stride j + kernel -
  1})``. A query at t scores the kernels wholly in its past (``stride j +
  kernel - 1 <= t``) with a softmax a head at ``1 / sqrt(d)``, the heads of
  a K/V group are summed, block b takes the maximum over the kernels that
  overlap it, the first ``init_blocks`` blocks and the ``local_blocks``
  ending with t's own are forced, and the ``topk`` best causal blocks are
  taken, forced ones among them. One softmax at ``1 / sqrt(d)`` over the
  positions <= t of the selected blocks (a MASK over a dense product here),
  times ``sigmoid(h Wz)`` elementwise (``attn_use_output_gate``), then Wo.

ASSUMED (the configuration file's ``assumed`` says what each is and why
seeded weights cannot tell it from its alternative): the selection's sizes
(``assumed.sparse``), the exact softmax over kernels, no dense switch under
a length, the slopes ``s_h`` the same in every layer, rotate-half pairing,
the output norm over the concatenated heads and the gates elementwise.

Departures, each on purpose: weights are upcast where they are used; every
layer runs over blocks of ``ROWS`` positions (a lightning layer carries its
state from block to block, a sparse layer first makes every position's keys
and values and then attends a block of ``sparse.block`` queries at a time
against all of them under the mask), so that a 65,536-token sequence fits
beside its own residual stream; the LAST layer's mixer output and MLP and
the head are computed at the positions asked for alone. ``quant="int8"`` is
the CONTROL (``model._matmul``): both operands of every weight matmul rounded
to int8.

``logits_at`` also hands back, for the cell's second number, what the
selection was made ON at the positions asked for: ``selection_at`` has every
sparse layer's block scores there, by which the benchmark scores the block
ids the PROGRAM selected (``regret``: the reference's worst chosen score less
the lowest reference score among the program's free choices, over the
former; 0 where the two agree or differ only between equal scores)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.model import F32, _matmul, _rmsnorm, _rope, _up

ROWS = 2048     # positions a block of the dense parts


# -- what the config says of each layer ---------------------------------------


def _layers(hf: dict) -> list:
    """``mixer_types`` is the source's whole list; a file that runs a cut in
    depth says with ``first_layer`` (default 0) where in it its layers lie."""
    kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    first = hf.get("first_layer", 0)
    return [kinds[m] for m in
            hf["mixer_types"][first:first + hf["num_hidden_layers"]]]


def _elements(hf: dict) -> list:
    """The program's layout, from the layer list alone: RUNS of equal layers,
    each on its own (``blocks/lead/i``); a run of several layers has one
    leading dimension. -> [(path, leading shape, its layers)]."""
    runs: list = []
    for layer, kind in enumerate(_layers(hf)):
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(layer)
        else:
            runs.append((kind, [layer]))
    return [(("blocks", "lead", str(i)),
             (len(at),) if len(at) > 1 else (), at)
            for i, (_, at) in enumerate(runs)]


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)} in the layout the program's model reads."""
    D, V = hf["hidden_size"], hf["vocab_size"]
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    Nl, Hl = hf["lightning_nh"], hf["lightning_head_dim"]
    F = hf["intermediate_size"]
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
    }
    shared = {
        ("attn_norm", "scale"): ((D,), "norm"),
        ("mlp_norm", "scale"): ((D,), "norm"),
        ("mlp", "w_in"): ((D, F), "normal"),
        ("mlp", "w_gate"): ((D, F), "normal"),
        ("mlp", "w_out"): ((F, D), "resid"),
    }
    attn = {
        "lightning": {
            ("attn", "wq"): ((D, Nl * Hl), "normal"),
            ("attn", "wk"): ((D, hf["lightning_nkv"] * Hl), "normal"),
            ("attn", "wv"): ((D, hf["lightning_nkv"] * Hl), "normal"),
            ("attn", "q_norm"): ((Hl,), "norm"),
            ("attn", "k_norm"): ((Hl,), "norm"),
            ("attn", "o_norm"): ((Nl * Hl,), "norm"),
            ("attn", "wg"): ((D, Nl * Hl), "normal"),
            ("attn", "wo"): ((Nl * Hl, D), "resid"),
        },
        "sparse": {
            ("attn", "wq"): ((D, N * H), "normal"),
            ("attn", "wk"): ((D, K * H), "normal"),
            ("attn", "wv"): ((D, K * H), "normal"),
            ("attn", "q_norm"): ((H,), "norm"),
            ("attn", "k_norm"): ((H,), "norm"),
            ("attn", "wg"): ((D, N * H), "normal"),
            ("attn", "wo"): ((N * H, D), "resid"),
        },
    }
    kinds = _layers(hf)
    for path, lead, at in _elements(hf):
        for leaf, (shape, k) in {**shared, **attn[kinds[at[0]]]}.items():
            spec[path + leaf] = (lead + shape, k)
    return spec


def _block(params, hf: dict, layer: int):
    for path, lead, at in _elements(hf):
        if layer in at:
            node = params
            for part in path:
                node = node[part]
            return (jax.tree.map(lambda a: a[at.index(layer)], node)
                    if lead else node)
    raise ValueError(layer)


# -- blocks of positions ------------------------------------------------------


def _by_rows(fn, x, carry=None, rows: int = ROWS):
    """``fn(carry, x_block, first position) -> (carry, y_block)`` over
    blocks of ``rows`` positions of x [S, ...] -> (carry, y [S, ...])."""
    S = x.shape[0]
    n = -(-S // rows)
    xp = jnp.pad(x, ((0, n * rows - S),) + ((0, 0),) * (x.ndim - 1))

    def step(c, i):
        return fn(c, jax.lax.dynamic_slice_in_dim(xp, i * rows, rows),
                  i * rows)

    carry, y = jax.lax.scan(step, carry, jnp.arange(n))
    return carry, jax.tree.map(
        lambda a: a.reshape(n * rows, *a.shape[2:])[:S], y)


def _swiglu(h, p, quant):
    return _matmul(jax.nn.silu(_matmul(h, _up(p["w_gate"]), quant))
                   * _matmul(h, _up(p["w_in"]), quant), _up(p["w_out"]),
                   quant)


# -- lightning attention ------------------------------------------------------


def slopes(n_heads: int):
    """ASSUMED: ``s_h = 2^(-8 (h + 1) / heads)``, the same in every layer."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=F32) / n_heads)


def _lightning(x, bp, hf: dict, quant, decay: bool = True):
    """x [S, D] (the residual stream) -> the layer's mixer output [S, D]."""
    a, eps = bp["attn"], hf["rms_norm_eps"]
    N, H = hf["lightning_nh"], hf["lightning_head_dim"]
    lam = (jnp.exp(-slopes(N)) if decay else jnp.ones((N,), F32))[:, None,
                                                                   None]

    def rows(state, xb, first):
        R = xb.shape[0]
        pos = first + jnp.arange(R)
        h = _rmsnorm(xb, _up(bp["attn_norm"]["scale"]), eps)
        q, k, v = (_matmul(h, _up(a[w]), quant).reshape(R, N, H)
                   for w in ("wq", "wk", "wv"))
        if hf["qk_norm"]:
            q = _rmsnorm(q, _up(a["q_norm"]), eps)
            k = _rmsnorm(k, _up(a["k_norm"]), eps)
        if hf["lightning_use_rope"]:
            q, k = (_rope(t, pos, hf["rope_theta"]) for t in (q, k))
        q = q * H ** -0.5                       # lightning_scale 1/sqrt(d)

        def step(S_, qkv):
            qt, kt, vt = qkv                                # [N, H]
            S_ = lam * S_ + kt[:, :, None] * vt[:, None, :]
            return S_, jnp.einsum("nk,nkv->nv", qt, S_)

        state, o = jax.lax.scan(step, state, (q, k, v))
        o = o.reshape(R, N * H)
        if hf["use_output_norm"]:
            o = _rmsnorm(o, _up(a["o_norm"]), eps)
        if hf["use_output_gate"]:
            o = o * jax.nn.sigmoid(_matmul(h, _up(a["wg"]), quant))
        return state, _matmul(o, _up(a["wo"]), quant)

    return _by_rows(rows, x, jnp.zeros((N, H, H), F32))[1]


# -- block-sparse attention ---------------------------------------------------


def _sizes(hf: dict):
    s = hf["assumed"]["sparse"]
    return (s["kernel"], s["stride"], s["block"], s["init_blocks"],
            s["local_blocks"], s["topk"])


def block_scores(q, ck, pos, hf: dict, per_head: bool = False,
                 pooled: bool = True):
    """q [R, N, H] at positions pos [R]; ck [J, K, H] every kernel's
    compressed key -> [R, K, blocks]: a block's score (-inf where no kernel
    that overlaps it lies wholly in the query's past). ``per_head`` /
    ``pooled`` false are two of the faults the tests plant (the first: each
    head's own scores, the group's first head deciding; the second: a
    block's own first kernel alone)."""
    kernel, stride, block, *_ = _sizes(hf)
    R, N, H = q.shape
    J, K = ck.shape[0], ck.shape[1]
    kpp = block // stride
    z = jnp.einsum("rkgh,jkh->rkgj", q.reshape(R, K, N // K, H), ck) * (
        H ** -0.5)
    seen = (stride * jnp.arange(J) + kernel - 1)[None] <= pos[:, None]
    z = jnp.where(seen[:, None, None], z, -jnp.inf)
    m = z.max(-1, keepdims=True)
    e = jnp.exp(z - jnp.where(jnp.isfinite(m), m, 0.0))
    total = e.sum(-1, keepdims=True)
    p = e / jnp.where(total == 0.0, 1.0, total)
    r = p[:, :, 0] if per_head else p.sum(2)                    # [R, K, J]
    r = jnp.where(seen[:, None], r, -jnp.inf)
    nb = -(-J // kpp)
    r = jnp.pad(r, ((0, 0), (0, 0), (0, nb * kpp - J)),
                constant_values=-jnp.inf).reshape(R, K, nb, kpp)
    if not pooled:
        return r[..., 0]
    # Block b: kernels kpp b - (kernel / stride - 1) .. kpp b + kpp - 1.
    score = r.max(-1)
    for back in range(1, kernel // stride):
        before = jnp.concatenate(
            [jnp.full_like(r[:, :, :1, 0], -jnp.inf),
             r[:, :, :-1, kpp - back]], axis=2)
        score = jnp.maximum(score, before)
    return score


def forced(pos, n_blocks: int, hf: dict, window: bool = True):
    """[R, blocks] bool: the causal blocks a query takes whatever their
    score. ``window`` false is a fault the tests plant (no local blocks
    but the query's own)."""
    *_, block, init, local, _ = _sizes(hf)
    b, own = jnp.arange(n_blocks)[None], (pos // block)[:, None]
    near = (b > own - local) if window else (b == own)
    return (b <= own) & ((b < init) | near)


def select(score, pos, hf: dict, window: bool = True):
    """-> (chosen [R, K, blocks] bool, free [R, K, blocks] bool: the chosen
    blocks that were not forced, cutoff [R, K]: the lowest score among
    them, +inf where none was chosen)."""
    *_, block, _, _, topk = _sizes(hf)
    nb = score.shape[-1]
    must = forced(pos, nb, hf, window)[:, None]
    causal = (jnp.arange(nb)[None] <= (pos // block)[:, None])[:, None]
    s = jnp.where(causal, jnp.where(must, jnp.inf, score), -jnp.inf)
    vals, ids = jax.lax.top_k(s, min(topk, nb))
    rows = jnp.arange(score.shape[0])[:, None, None]
    heads = jnp.arange(score.shape[1])[None, :, None]
    chosen = jnp.zeros(s.shape, bool).at[rows, heads, ids].set(
        vals > -jnp.inf)
    free = chosen & ~must
    cutoff = jnp.where(free, score, jnp.inf).min(-1)
    return chosen, free, cutoff


def _sparse(x, bp, hf: dict, quant, at, only_at: bool, faults: tuple = ()):
    """x [S, D] -> (the layer's mixer output at every position, or with
    ``only_at`` at positions ``at`` alone; the block scores at positions
    ``at`` [len(at), K, blocks])."""
    a, eps = bp["attn"], hf["rms_norm_eps"]
    N, K, H = (hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["head_dim"])
    kernel, stride, block, *_ = _sizes(hf)
    S = x.shape[0]

    def project(xb, names):
        h = _rmsnorm(xb, _up(bp["attn_norm"]["scale"]), eps)
        out = []
        for w, heads in names:
            t = _matmul(h, _up(a[w]), quant).reshape(xb.shape[0], heads, H)
            if hf["qk_norm"] and w in ("wq", "wk"):
                t = _rmsnorm(t, _up(a["q_norm" if w == "wq" else "k_norm"]),
                             eps)
            out.append(t)
        return h, out

    _, (k, v) = _by_rows(
        lambda c, xb, first: (c, project(xb, (("wk", K), ("wv", K)))[1]), x)
    # Every kernel that is whole inside the sequence.
    J = max((S - kernel) // stride + 1, 0)
    starts = stride * jnp.arange(J)[:, None] + jnp.arange(kernel)[None]
    ck = k[starts].mean(1) if J else jnp.zeros((0, K, H), F32)   # [J, K, H]
    nb = -(-S // block)
    if J < nb * (block // stride):      # so that every block has its columns
        ck = jnp.pad(ck, ((0, nb * (block // stride) - J), (0, 0), (0, 0)))
    keys = jnp.arange(S)

    def scored(xb, pos):
        h, (q,) = project(xb, (("wq", N),))
        return h, q, block_scores(q, ck, pos, hf, "per_head" in faults,
                                  "unpooled" not in faults)

    def attend(xb, pos):
        """Rows ``xb`` [R, D] of the stream at positions ``pos``."""
        R = xb.shape[0]
        h, q, score = scored(xb, pos)
        chosen, _, _ = select(score, pos, hf, "no_window" not in faults)
        live = chosen[:, :, keys // block] & (keys[None] <= pos[:, None])[
            :, None]                                           # [R, K, S]
        z = jnp.einsum("rkgh,skh->rkgs", q.reshape(R, K, N // K, H), k) * (
            H ** -0.5)
        p = jax.nn.softmax(jnp.where(live[:, :, None], z, -jnp.inf), -1)
        o = jnp.einsum("rkgs,skh->rkgh", p, v).reshape(R, N * H)
        if hf["attn_use_output_gate"]:
            o = o * jax.nn.sigmoid(_matmul(h, _up(a["wg"]), quant))
        return _matmul(o, _up(a["wo"]), quant)

    if only_at:
        return attend(x[at], at), scored(x[at], at)[2]
    return _by_rows(
        lambda c, xb, first: (c, attend(xb, first + jnp.arange(block))),
        x, rows=block)[1], scored(x[at], at)[2]


# -- the model ----------------------------------------------------------------


def _forward(params, tokens, at, hf: dict, quant, faults: tuple = ()):
    eps = hf["rms_norm_eps"]
    resid = hf["scale_depth"] / hf["published"]["num_hidden_layers"] ** 0.5
    kinds = _layers(hf)
    scores = []
    with jax.default_matmul_precision("highest"):
        x = hf["scale_emb"] * params["embed"]["tokens"][tokens].astype(F32)
        for layer, kind in enumerate(kinds):
            bp = _block(params, hf, layer)
            last = layer == len(kinds) - 1
            if kind == "lightning":
                y = _lightning(x, bp, hf, quant, "no_decay" not in faults)
                y = y[at] if last else y
            else:
                y, score = _sparse(x, bp, hf, quant, at, last, faults)
                scores.append(score)
            x = (x[at] if last else x) + resid * y
            mlp = lambda c, xb, first: (c, _swiglu(
                _rmsnorm(xb, _up(bp["mlp_norm"]["scale"]), eps), bp["mlp"],
                quant))
            x = x + resid * _by_rows(mlp, x)[1]
        x = _rmsnorm(x, _up(params["final_norm"]["scale"]), eps)
        x = x / (hf["hidden_size"] / hf["dim_model_base"])
        return _matmul(x, _up(params["lm_head"]), quant), jnp.stack(scores, 0)


def selection_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """(logits [len(at), V]; scores [sparse layers, len(at), K, blocks]: what
    each sparse layer's selection at those positions is made on)."""
    return _forward(params, tokens, at, hf, quant)


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None,
              faults: tuple = ()):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the selection's margin there: over the sparse layers and K/V heads, the
    least distance between the lowest score chosen freely and the highest
    score left out, over the former (+inf where every block is taken)."""
    logits, scores = _forward(params, tokens, at, hf, quant, faults)
    margins = []
    for score in scores:
        chosen, free, cutoff = select(score, at, hf)
        block = _sizes(hf)[2]
        causal = (jnp.arange(score.shape[-1])[None] <= (at // block)[:, None])
        out = jnp.where(causal[:, None] & ~chosen, score, -jnp.inf).max(-1)
        margins.append(jnp.where(jnp.isfinite(cutoff) & jnp.isfinite(out),
                                 (cutoff - out) / cutoff, jnp.inf).min(-1))
    return logits, jnp.stack(margins).min(0)


def chosen_ids(scores, at, hf: dict):
    """The block ids a selection on ``scores`` [sparse layers, R, K, blocks]
    takes, as the program hands its own back: [sparse layers, R, K, T]
    ascending, ``blocks`` for the entries a short context leaves unused (the
    CONTROL's selection, from the reference's scores at a lower precision)."""
    nb = scores.shape[-1]
    T = min(_sizes(hf)[5], nb)
    out = []
    for score in scores:
        chosen, _, _ = select(score, at, hf)
        ids = jnp.where(chosen, jnp.arange(nb), nb)
        out.append(jnp.sort(ids, axis=-1)[..., :T])
    return jnp.stack(out)


def regret(scores, ids, at, hf: dict):
    """How much worse the PROGRAM's selection is than the reference's, by the
    reference's own scores. ``scores`` [sparse layers, R, K, blocks] of
    ``selection_at``; ``ids`` [sparse layers, R, K, T] the block ids the
    program selected at those positions (ids >= blocks: unused entries) ->
    [sparse layers, R, K]: (the reference's lowest freely chosen score less
    the lowest reference score among the program's free choices) over the
    former; 0 where the program chose the same blocks or others of equal
    score, 0 where nothing is chosen freely, +inf where the program's list
    lacks a forced block or holds a block twice or a block of the future."""
    out = []
    for score, mine in zip(scores, ids):
        nb = score.shape[-1]
        _, free, cutoff = select(score, at, hf)
        must = forced(at, nb, hf)[:, None]
        picked = jnp.zeros(score.shape, jnp.int32).at[
            jnp.arange(score.shape[0])[:, None, None],
            jnp.arange(score.shape[1])[None, :, None],
            jnp.minimum(mine, nb - 1)].add((mine < nb).astype(jnp.int32))
        block = _sizes(hf)[2]
        causal = (jnp.arange(nb)[None] <= (at // block)[:, None])[:, None]
        sound = ((picked <= 1).all(-1) & (picked * ~causal == 0).all(-1)
                 & ((picked > 0) | ~must).all(-1)
                 & (picked.sum(-1) == (free | must).sum(-1)))
        worst = jnp.where((picked > 0) & ~must, score, jnp.inf).min(-1)
        r = jnp.where(jnp.isfinite(cutoff),
                      jnp.maximum(cutoff - worst, 0.0) / cutoff, 0.0)
        out.append(jnp.where(sound, r, jnp.inf))
    return jnp.stack(out)
