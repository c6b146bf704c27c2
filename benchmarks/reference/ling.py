"""The plain reference of Ling-3.0-flash (inclusionAI, ``model_type``
``bailing_hybrid``): the forward pass in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, no kernel, no cache, no chunk, no
batching. Written from the published ``config.json`` (the configuration
file's own keys), not from the program.

Block l, pre-norm residual: ``x += Attn_l(RMSNorm(x))``, ``x += FFN_l(RMSNorm(x))``.
Layer l is a LATENT layer where (l + 1) % ``layer_group_size`` == 0, else a
Kimi-delta-attention (KDA) layer.

- KDA layer (h the normed input; ``num_attention_heads`` heads of
  ``head_dim`` keys and values; ``conv`` a depthwise causal convolution over
  the last ``short_conv_kernel_size`` positions): q = l2norm(silu(conv(h
  Wq))) / sqrt(head_dim), k = l2norm(silu(conv(h Wk))), v = silu(conv(h Wv))
  (``linear_silu``); the log-decay a key channel g = ``_log_decay``(h Wf)
  (``no_kda_lora``: Wf of full rank), a = exp(g); b = sigmoid(h Wb) a head;
  ``S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T``, o_t =
  S_t^T q_t, as ``lax.scan`` over positions of the recurrence itself; out =
  Wo [RMSNorm_head(o_t) * sigmoid(h Wg)] (Wg of full rank).
- Latent layer: q = h Wq (``q_lora_rank`` null) -> heads x
  (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv | k_pe] = h Wkv_a``,
  c_kv = RMSNorm(c_kv); ``use_qk_norm``: RMSNorm over each head's query and
  over k_pe, before the rotation; the rope parts rotated (ONE rotary key for
  all heads); ``[k_nope | v] = c_kv Wkv_b``; causal softmax of q . k /
  sqrt(nope + rope), the EXPANDED form only; head j's output times
  sigmoid(h Wgate)_j (``gated_attention_proj_granularity_type`` head_wise);
  Wo.
- FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others the routed experts and
  ``num_shared_experts`` shared, each a SwiGLU of ``moe_intermediate_size``;
  router: s = sigmoid(h Wr) in float32 over ``published.num_experts``
  experts in ``n_group`` groups; on s + b (the selection bias) a group's
  score is the sum of its two largest, the best ``topk_group`` groups are
  kept, the ``num_experts_per_tok`` largest of s + b inside them chosen;
  gates s there WITHOUT b, over their sum + 1e-20 (``norm_topk_prob``),
  times ``routed_scaling_factor``. The swiglu limits
  (``expert_swiglu_limit_list``) are 0, no clamp, in every layer run.
- Final RMSNorm, untied head. The multi-token-prediction module is a draft
  head beside the layers and no part of these logits: left out.

ASSUMED, because the config gives a key and not a formula (each ONE function
below and one in the program; the configuration file's ``assumed`` names
both): (a) ``kda_safe_gate`` with ``kda_lower_bound`` -5 is the bounded gate
g = -5 sigmoid(exp(A_log) (h Wf + dt_bias)); the standard form g =
-exp(A_log) softplus(h Wf + dt_bias) is ``_log_decay``'s other branch
(``GATE_FORM``; no program option); (b) ``use_qk_norm`` on a latent layer:
``_qk_norm`` (the query a head, the shared rotary key); the other reading,
ISSUE 41's, a norm over each head's ``nope + rope`` key numbers, is
``_expanded_key`` (``QK_NORM_FORM``; no program option: it is one number a
head and position that the cached row does not hold and the absorbed decode
kernel does not apply, PERF.md section 7); (c) the head-wise gate:
``_head_gate``;
(d) rotate-half pairing on the 64 rotary numbers (``rope_interleave`` is a
fixed permutation of a checkpoint's columns, which seeded weights cannot
tell apart): ``model._rope``.

The chip's share (model-configs guide, section 4), as in
``reference/laguna.py``: the file's ``num_experts`` is how many experts are
HELD (``deployment.experts_held`` = [first, end) of the
``published.num_experts`` the router chooses among); the router keeps its
published width, groups and top-k, the gates are normalised over all chosen
experts, and what the absent experts would add is left out, here and in the
program alike. The vocabulary is the file's (a slice is a smaller
vocabulary).

Departures, each on purpose: weights are upcast where they are used; latent
attention runs one head and one block of 512 queries at a time, the held
experts one at a time under ``lax.scan`` over all positions (a gate of 0
where an expert was not chosen); logits are taken only at the positions
asked for. ``quant="int8"`` is the CONTROL (``model._matmul``): both operands
of every weight matmul rounded to int8; the router's matmul stays float32."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.glm import _head_attention, _swiglu
from benchmarks.reference.model import F32, _matmul, _rmsnorm, _rope, _up

GATE_FORM = "bounded"       # ASSUMED (a); "standard" is the other reading
QK_NORM_FORM = "shared"     # ASSUMED (b); "expanded" is the other reading


# -- what the config says of each layer ---------------------------------------


def _layers(hf: dict) -> list:
    """('kda' | 'latent', 'dense' | 'sparse') of each layer run."""
    G = hf["layer_group_size"]
    return [("latent" if (l + 1) % G == 0 else "kda",
             "dense" if l < hf["first_k_dense_replace"] else "sparse")
            for l in range(hf["num_hidden_layers"])]


def _elements(hf: dict) -> list:
    """The program's layout, from the layer list alone: RUNS of equal
    layers, one behind the other. The leading runs are each on their own
    (``blocks/lead/i``), the others stacked by their position in a period of
    runs (``blocks/period/j``, entry g of it the run of period g), with as
    many leading runs as leave the fewest runs to write down (lead + period
    + a tail of the period's first positions); a run of several layers has
    one more leading dimension. -> [(path, leading shape, [its layers in
    that shape, flat])]."""
    runs: list = []
    for layer, kind in enumerate(_layers(hf)):
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(layer)
        else:
            runs.append((kind, [layer]))
    sig = [(kind, len(at)) for kind, at in runs]

    def periodic(rest):
        p = next((p for p in range(1, len(rest) + 1)
                  if all(rest[i] == rest[i % p] for i in range(len(rest)))),
                 1)
        return p, len(rest) % p

    dense = sum(kind[1] == "dense" for kind, _ in sig)
    lead = min(range(dense, len(runs) + 1),
               key=lambda e: (e + sum(periodic(sig[e:])), e))
    period, _ = periodic(sig[lead:])
    found = [(("blocks", "lead", str(i)), (), [at])
             for i, (_, at) in enumerate(runs[:lead])]
    for j in range(min(period, len(runs) - lead)):
        groups = [at for _, at in runs[lead + j::period]]
        found.append((("blocks", "period", str(j)), (len(groups),), groups))
    return [(path, deep + ((len(groups[0]),) if len(groups[0]) > 1 else ()),
             groups) for path, deep, groups in found]


def _where(hf: dict, layer: int) -> tuple:
    """(the path of the layer's leaves, its index in their leading
    dimensions)."""
    for path, shape, groups in _elements(hf):
        for g, at in enumerate(groups):
            if layer in at:
                index = (() if path[1] == "lead" else (g,)) + (
                    (at.index(layer),) if len(at) > 1 else ())
                return path, index
    raise ValueError(layer)


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)} in the layout the program's model reads. Of
    ``weights.py``'s three kinds the KDA gate's leaves take the pair that
    brings a step's decay nearest 1: ``a_log`` 'norm' (exp(1 + 0.05 z) = 2.7:
    the sigmoid's argument is spread, so that about half of the channels of
    a position decay by less than e^-0.5 and the others by nearly e^-5) and
    ``dt_bias`` 'normal' (0: a positive bias only lowers every decay). The
    convolution's taps are 'norm' (each about 1: a sum of the last four
    rows), the selection bias 'norm' as in ``reference/glm.py``."""
    D, V, N = hf["hidden_size"], hf["vocab_size"], hf["num_attention_heads"]
    H, Kc = hf["head_dim"], hf["short_conv_kernel_size"]
    R, nope, rope, vd = (hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                         hf["qk_rope_head_dim"], hf["v_head_dim"])
    E, Er = hf["num_experts"], hf["published"]["num_experts"]
    Fe = hf["moe_intermediate_size"]
    Fs = hf["num_shared_experts"] * hf["moe_shared_expert_intermediate_size"]
    F = hf["intermediate_size"]
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
    }
    norms = {("attn_norm", "scale"): ((D,), "norm"),
             ("mlp_norm", "scale"): ((D,), "norm")}
    attn = {
        "kda": {
            ("attn", "wq"): ((D, N * H), "normal"),
            ("attn", "wk"): ((D, N * H), "normal"),
            ("attn", "wv"): ((D, N * H), "normal"),
            ("attn", "conv"): ((Kc, 3 * N * H), "norm"),
            ("attn", "wf"): ((D, N * H), "normal"),
            ("attn", "a_log"): ((N,), "norm"),
            ("attn", "dt_bias"): ((N * H,), "normal"),
            ("attn", "wb"): ((D, N), "normal"),
            ("attn", "wg"): ((D, N * H), "normal"),
            ("attn", "o_norm"): ((H,), "norm"),
            ("attn", "wo"): ((N * H, D), "resid"),
        },
        "latent": {
            ("attn", "wq"): ((D, N * (nope + rope)), "normal"),
            ("attn", "wkv_a"): ((D, R + rope), "normal"),
            ("attn", "kv_a_norm"): ((R,), "norm"),
            ("attn", "wkv_b"): ((R, N * (nope + vd)), "normal"),
            ("attn", "q_norm"): ((nope + rope,), "norm"),
            ("attn", "k_norm"): ((rope,), "norm"),
            ("attn", "wg"): ((D, N), "normal"),
            ("attn", "wo"): ((N * vd, D), "resid"),
        },
    }
    ffn = {
        "dense": {("mlp", "w_in"): ((D, F), "normal"),
                  ("mlp", "w_gate"): ((D, F), "normal"),
                  ("mlp", "w_out"): ((F, D), "resid")},
        "sparse": {
            ("moe", "router"): ((D, Er), "normal"),
            ("moe", "router_bias"): ((Er,), "norm"),
            ("moe", "w_in"): ((E, D, Fe), "normal"),
            ("moe", "w_gate"): ((E, D, Fe), "normal"),
            ("moe", "w_out"): ((E, Fe, D), "resid"),
            ("moe", "shared", "w_in"): ((D, Fs), "normal"),
            ("moe", "shared", "w_gate"): ((D, Fs), "normal"),
            ("moe", "shared", "w_out"): ((Fs, D), "resid"),
        },
    }
    kinds = _layers(hf)
    for path, lead, groups in _elements(hf):
        att, kind = kinds[groups[0][0]]
        for leaf, (shape, k) in {**norms, **attn[att], **ffn[kind]}.items():
            spec[path + leaf] = (lead + shape, k)
    return spec


_EXPERTS = ("w_in", "w_gate", "w_out")


def _block(params, hf: dict, layer: int):
    """The layer's weights; of a stacked sparse layer the routed experts'
    three leaves stay whole beside the layer's index (``_moe`` takes one
    expert's matrices out of the stack at a time)."""
    path, at = _where(hf, layer)
    node = params
    for part in path:
        node = node[part]
    take = lambda tree: jax.tree.map(lambda a: a[at], tree)
    if "moe" not in node:
        return take(node)
    moe = node["moe"]
    out = take({**node,
                "moe": {k: v for k, v in moe.items() if k not in _EXPERTS}})
    out["moe"]["experts"] = ({k: moe[k] for k in _EXPERTS}, at)
    return out


# -- Kimi delta attention -----------------------------------------------------


def _conv(x, w):
    """Depthwise causal convolution: x [S, C], w [K, C]; y_t = sum_i w_i
    x_(t - K + 1 + i), zeros before the sequence."""
    K, S = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[i:i + S] * w[i] for i in range(K))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _log_decay(z, a_log, dt_bias, hf: dict, form: Optional[str] = None):
    """ASSUMED (a). z [S, N, H] = h Wf; -> g [S, N, H] <= 0."""
    z = z + dt_bias
    rate = jnp.exp(a_log)[None, :, None]
    if (form or GATE_FORM) == "bounded":
        return hf["kda_lower_bound"] * jax.nn.sigmoid(rate * z)
    return -rate * jax.nn.softplus(z)


def _delta_rule(q, k, v, g, b):
    """The recurrence itself: q, k, g [S, N, H]; v [S, N, H]; b [S, N] ->
    o [S, N, H]."""
    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, :, None]                       # diag(a) S
        u = vt - jnp.einsum("nkv,nk->nv", S, kt)              # v - S^T k
        S = S + (bt[:, None] * kt)[:, :, None] * u[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", S, qt)

    N, H = q.shape[1], q.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((N, H, v.shape[-1]), F32),
                        (q, k, v, g, b))
    return o


def _kda(h, a, hf: dict, quant):
    S, N, H = h.shape[0], hf["num_attention_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    conv = _up(a["conv"])
    xs = jnp.concatenate(
        [_matmul(h, _up(a[w]), quant) for w in ("wq", "wk", "wv")], -1)
    y = jax.nn.silu(_conv(xs, conv)).reshape(S, 3, N, H)
    q, k, v = _l2norm(y[:, 0]) * H ** -0.5, _l2norm(y[:, 1]), y[:, 2]
    g = _log_decay(_matmul(h, _up(a["wf"]), quant).reshape(S, N, H),
                   _up(a["a_log"]), _up(a["dt_bias"]).reshape(N, H), hf)
    b = jax.nn.sigmoid(_matmul(h, _up(a["wb"]), quant))       # [S, N]
    o = _rmsnorm(_delta_rule(q, k, v, g, b), _up(a["o_norm"]), eps)
    gate = jax.nn.sigmoid(_matmul(h, _up(a["wg"]), quant)).reshape(S, N, H)
    return _matmul((o * gate).reshape(S, N * H), _up(a["wo"]), quant)


# -- latent attention ---------------------------------------------------------


def _qk_norm(q, k_pe, a, eps):
    """ASSUMED (b): q [S, N, nope + rope] a head, k_pe [S, 1, rope]."""
    return (_rmsnorm(q, _up(a["q_norm"]), eps),
            _rmsnorm(k_pe, _up(a["k_norm"]), eps))


def _expanded_key(k_nope, k_pe, a, eps):
    """The other reading of (b): RMSNorm over ONE head's whole key, k_nope
    [S, nope] | k_pe [S, rope] (not yet rotated), before the rotation. The
    scale is 1 on the nope numbers (the program's tree has no leaf for
    them) and ``k_norm`` on the rope numbers."""
    scale = jnp.concatenate(
        [jnp.ones(k_nope.shape[-1:], F32), _up(a["k_norm"])])
    return _rmsnorm(jnp.concatenate([k_nope, k_pe], -1), scale, eps)


def _head_gate(h, wg):
    """ASSUMED (c): [S, N] sigmoid gate, one number a head."""
    return jax.nn.sigmoid(jnp.matmul(h, _up(wg)))


def _latent(h, a, positions, hf: dict, quant, form: Optional[str] = None):
    S, N, eps = h.shape[0], hf["num_attention_heads"], hf["rms_norm_eps"]
    R, nope, rope, vd = (hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                         hf["qk_rope_head_dim"], hf["v_head_dim"])
    theta = hf["rope_theta"]
    q = _matmul(h, _up(a["wq"]), quant).reshape(S, N, nope + rope)
    row = _matmul(h, _up(a["wkv_a"]), quant)                  # [S, R + rope]
    c_kv = _rmsnorm(row[:, :R], _up(a["kv_a_norm"]), eps)
    k_pe = row[:, None, R:]
    expanded = (hf["use_qk_norm"]
                and (form or QK_NORM_FORM) == "expanded")
    if hf["use_qk_norm"]:
        q, shared = _qk_norm(q, k_pe, a, eps)
        k_pe = k_pe if expanded else shared
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], positions, theta)], -1)
    if not expanded:
        k_pe = _rope(k_pe, positions, theta)
    k_pe = k_pe[:, 0]                                         # [S, rope]
    wkv = _up(a["wkv_b"]).reshape(R, N, nope + vd).transpose(1, 0, 2)

    def one_head(args):
        q_n, wkv_n = args
        kv = _matmul(c_kv, wkv_n, quant)                      # [S, nope + vd]
        k = jnp.concatenate([kv[:, :nope], k_pe], -1)
        if expanded:
            k = _expanded_key(kv[:, :nope], k_pe, a, eps)
            k = jnp.concatenate([k[:, :nope], _rope(
                k[:, None, nope:], positions, theta)[:, 0]], -1)
        return _head_attention(q_n, k, kv[:, nope:])

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), wkv))    # [N, S, vd]
    o = o.transpose(1, 0, 2) * _head_gate(h, a["wg"])[..., None]
    return _matmul(o.reshape(S, N * vd), _up(a["wo"]), quant)


# -- feed-forward -------------------------------------------------------------


def _router(h, p, hf: dict):
    """-> (gates [S, E_router], zero where an expert was not chosen; the
    margin [S]: on what the choice is made on, the last expert chosen less
    the first left out, or the last group kept less the first dropped where
    that is nearer)."""
    k, G, kg = hf["num_experts_per_tok"], hf["n_group"], hf["topk_group"]
    s = jax.nn.sigmoid(jnp.matmul(h, _up(p["router"])))       # [S, E]
    chosen_on = s + _up(p["router_bias"])[None, :]
    margin = jnp.full(s.shape[:1], jnp.inf)
    if G > 1:
        by_group = chosen_on.reshape(s.shape[0], G, -1)
        score = jax.lax.top_k(by_group, 2)[0].sum(-1)         # [S, G]
        ranked, kept = jax.lax.top_k(score, kg + 1)
        margin = ranked[:, kg - 1] - ranked[:, kg]
        keep = jnp.zeros_like(score).at[
            jnp.arange(s.shape[0])[:, None], kept[:, :kg]].set(1.0)
        chosen_on = jnp.where(keep[:, :, None] > 0, by_group, 0.0).reshape(
            s.shape)
    ranked, idx = jax.lax.top_k(chosen_on, k + 1)
    margin = jnp.minimum(margin, ranked[:, k - 1] - ranked[:, k])
    at = jnp.arange(h.shape[0])[:, None]
    top = s[at, idx[:, :k]]                                   # WITHOUT b
    if hf["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * hf["routed_scaling_factor"]
    return jnp.zeros_like(s).at[at, idx[:, :k]].set(top), margin


def _moe(h, p, hf: dict, quant):
    """(this chip's part of the layer's output, the router's margin)."""
    gates, margin = _router(h, p, hf)
    stack, layer = p["experts"]
    first, end = hf["deployment"]["experts_held"]
    assert end - first == stack["w_in"].shape[len(layer)] == hf["num_experts"]

    def one_expert(y, eg):
        e, g = eg
        out = _swiglu(h, {k: stack[k][(*layer, e)] for k in _EXPERTS}, quant)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(end - first), gates[:, first:end].T))
    return y + _swiglu(h, p["shared"], quant), margin


# -- the model ----------------------------------------------------------------


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the smallest router margin over the sparse layers at each of them."""
    eps = hf["rms_norm_eps"]
    positions = jnp.arange(tokens.shape[0])
    margins = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for layer, (att, ffn) in enumerate(_layers(hf)):
            bp = _block(params, hf, layer)
            h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
            x = x + (_kda(h, bp["attn"], hf, quant) if att == "kda"
                     else _latent(h, bp["attn"], positions, hf, quant))
            h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
            if ffn == "dense":
                x = x + _swiglu(h, bp["mlp"], quant)
            else:
                y, margin = _moe(h, bp["moe"], hf, quant)
                x = x + y
                margins.append(margin)
        x = _rmsnorm(x[at], _up(params["final_norm"]["scale"]), eps)
        margin = (jnp.stack(margins).min(axis=0)[at] if margins
                  else jnp.full(x.shape[:1], jnp.inf))
        return _matmul(x, _up(params["lm_head"]), quant), margin
