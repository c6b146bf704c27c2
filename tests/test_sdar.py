"""A model that generates by diffusion over blocks (SDAR; ``tiny-sdar``: blocks
of 4 under pages of 8, 2 layers, 8 experts top-2): the block mask in the XLA
form, the flash forward and the W-query paged kernel; prefill and the block
program against the plain reference (``benchmarks/reference/sdar.py``, which
imports nothing of the program); THE ENGINE'S TOKENS EQUAL THE REFERENCE'S
(float32, greedy) for both strategies, every step count, every prompt tail,
cut outputs, an EOS inside a block, a batch against each alone, and one
sampled case with the engine's key replayed; what a commit leaves in the
pages, the counters, the refusals by name, and that ``block_length = 0``
traces the parent's statics."""

import dataclasses
import json
import pathlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.infer import InferenceEngine
from orion_tpu.models import init_params
from orion_tpu.ops.attention import attention, attention_mask, attention_xla

REPO = pathlib.Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((REPO / "tests/benchmark/data/published/"
                        "sdar-30b-a3b-serve-1chip.json").read_text())


def _reference():
    from benchmarks.harness import cell

    return cell._load(REPO / "benchmarks" / "reference" / "sdar.py")


def tiny_hf(cfg) -> dict:
    """The tiny preset under the published key names, as the reference reads
    a configuration file."""
    m, i = cfg.model, cfg.inference
    return {
        "hidden_size": m.d_model, "head_dim": m.resolved_head_dim,
        "num_attention_heads": m.n_heads,
        "num_key_value_heads": m.n_kv_heads, "vocab_size": m.vocab_size,
        "num_hidden_layers": m.n_layers, "rms_norm_eps": m.norm_eps,
        "rope_theta": m.rope_theta, "num_experts": m.n_experts,
        "num_experts_per_tok": m.n_experts_per_token,
        "moe_intermediate_size": m.moe_d_ff,
        "generation": {
            "block_length": m.block_length, "mask_token_id": m.mask_token_id,
            "denoising_steps": i.denoising_steps, "remasking": i.remasking,
            "confidence_threshold": i.confidence_threshold}}


def _config(*overrides):
    return get_config("tiny-sdar", list(overrides))


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the matrices ten times the preset's scale: at N(0,
    0.02) one token wins every position, and equal outputs would say little."""
    cfg = _config()
    tree = init_params(cfg.model, jax.random.key(0))
    return jax.tree.map(lambda a: a * 10.0 if a.ndim >= 3 else a, tree)


def _prompt(n: int, seed: int = 0, vocab: int = 255) -> list:
    rng = np.random.default_rng(1000 * seed + n)
    return [int(t) for t in rng.integers(1, vocab, n)]


_ENGINES: dict = {}


def _engine(params, *overrides, **kw):
    """ONE engine a configuration for the whole file (an engine compiles its
    programs anew, 1-2 s each on the CPU): a request meets pages and rows
    beyond its cursor that earlier tests' requests left, which no block may
    read. Tests that replace ``_executor.run`` put it back."""
    key = (overrides, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        _ENGINES[key] = InferenceEngine(_config(*overrides), params,
                                        **{"seed": 0, **kw})
    return _ENGINES[key]


@pytest.fixture(scope="module", autouse=True)
def _close_engines():
    yield
    while _ENGINES:
        _ENGINES.popitem()[1].close()


def _generate(params, requests, *overrides, **kw):
    """``requests``: [(prompt, max_new)] all submitted before the first step;
    returns them and the counters of their steps alone."""
    engine = _engine(params, *overrides, **kw)
    engine.reset_timing()
    reqs = [engine.submit_request(p, m) for p, m in requests]
    while engine.has_work():
        engine.step()
    return reqs, engine.reset_timing()


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# -- the mask -----------------------------------------------------------------


def test_the_preset_is_the_published_model():
    m = get_config("sdar-30b-a3b").model
    assert (m.d_model, m.n_layers, m.n_heads, m.n_kv_heads,
            m.resolved_head_dim, m.vocab_size, m.d_ff, m.moe_d_ff,
            m.n_experts, m.n_experts_per_token) == tuple(PUBLISHED[k] for k in (
                "hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size",
                "intermediate_size", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok"))
    assert (m.rope_theta, m.norm_eps, m.tie_embeddings, m.sliding_window,
            m.attn_bias) == (PUBLISHED["rope_theta"],
                             PUBLISHED["rms_norm_eps"],
                             PUBLISHED["tie_word_embeddings"],
                             PUBLISHED["sliding_window"],
                             PUBLISHED["attention_bias"])
    assert PUBLISHED["decoder_sparse_step"] == 1 and m.n_dense_layers == 0
    assert PUBLISHED["mlp_only_layers"] == [] and m.shared_expert_d_ff == 0
    assert (m.qk_norm, m.router_score, m.activation, m.block_length,
            m.mask_token_id) == (True, "softmax", "swiglu", 4, 151669)
    assert m.capacity_factor == m.n_experts / m.n_experts_per_token
    assert m.layer_plan is None and m.holds_expert_share is False


@pytest.mark.parametrize("length", [8, 30, 130, 256])
def test_block_mask_xla_and_flash_against_the_dense_mask(length):
    """``attention_mask(block=)`` is j // 4 <= i // 4; ``attention_xla`` and
    the flash forward (interpreted; 128-wide tiles, lengths that are and are
    not multiples of the tile; whole rows at 8 and 256, ragged rows at 30
    and 130) attend exactly its pairs."""
    i = np.arange(length)
    dense = (i[None, :] // 4) <= (i[:, None] // 4)
    assert (np.asarray(attention_mask(length, length, block=4)) == dense).all()
    ks = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(ks[0], (2, length, 4, 16))
    k = jax.random.normal(ks[1], (2, length, 2, 16))
    v = jax.random.normal(ks[2], (2, length, 2, 16))
    want = attention_xla(q, k, v, causal=False, mask=jnp.asarray(dense))
    assert _rel(attention_xla(q, k, v, block=4), want) < 1e-6
    tiles = dict(block_q=128, block_kv=128) if length > 128 else {}
    if length in (8, 256):          # whole rows
        assert _rel(attention(q, k, v, block=4, impl="pallas_interpret",
                              **tiles), want) < 1e-6
        return
    lens = jnp.array([length, length - 4])      # ragged rows
    seg = (jnp.arange(length)[None] < lens[:, None]).astype(jnp.int32)
    got = attention(q, k, v, block=4, impl="pallas_interpret", **tiles,
                    q_segment_ids=seg, kv_segment_ids=seg, seg_pad_zero=True)
    ragged = attention_xla(q, k, v, block=4, q_segment_ids=seg,
                           kv_segment_ids=seg)
    real = np.asarray(seg, bool)
    assert _rel(np.asarray(got)[0], want[0]) < 1e-6
    assert _rel(np.asarray(got)[real], np.asarray(ragged)[real]) < 1e-6


def test_block_zero_traces_the_parents_statics_and_the_backward_refuses():
    """``block=0`` is the parent's call (the same ``_Statics``, spelled out);
    a block mask is forward only, refused by name."""
    import importlib

    fa = importlib.import_module("orion_tpu.ops.pallas.flash_attention")
    q = jnp.ones((1, 16, 2, 16))
    seen = []
    kept = fa._flash

    def spy(st, *a):
        seen.append(st)
        return kept(st, *a)

    fa._flash = spy
    try:
        fa.flash_attention(q, q, q, interpret=True)
        fa.flash_attention(q, q, q, interpret=True, block=0)
    finally:
        fa._flash = kept
    assert seen[0] == seen[1] == fa._Statics(
        causal=True, logit_softcap=None, q_offset=0, seq_q=16, seq_kv=16,
        block_q=16, block_kv=16, interpret=True)
    assert seen[0].block == 0
    with pytest.raises(NotImplementedError, match="block mask"):
        jax.grad(lambda x: fa.flash_attention(
            x, q, q, interpret=True, block=4).sum())(q)
    for bad in (dict(window=8), dict(causal=False), dict(q_offset=2)):
        with pytest.raises(ValueError, match="block="):
            fa.flash_attention(q, q, q, interpret=True, block=4, **bad)
    for bad in (3, 32):     # no power of two; wider than an ancestor word
        with pytest.raises(ValueError, match="power of two up to 16"):
            dataclasses.replace(_config().model, block_length=bad)


@pytest.mark.parametrize("start", [8, 12, 20])
def test_the_paged_kernel_with_every_new_row_visible(start):
    """The W-query paged kernel (interpreted) at W = 4 under full ancestor
    words, starts on a page boundary (8), inside a page (12, 20): against
    ``attention_xla`` over [cached | the 4 new rows], all 4 visible to each
    of the 4 queries; the rows land at ``start .. start + 3``."""
    from orion_tpu.ops.pallas.paged_attention import attend

    B, W, N, K, H, psz, P = 2, 4, 4, 2, 16, 8, 4
    ks = jax.random.split(jax.random.key(start), 5)
    q = jax.random.normal(ks[0], (B, W, N, H))
    k_new = jax.random.normal(ks[1], (B, W, K, H))
    v_new = jax.random.normal(ks[2], (B, W, K, H))
    pool_k = jax.random.normal(ks[3], (1 + B * P, K, psz, H))
    pool_v = jax.random.normal(ks[4], (1 + B * P, K, psz, H))
    table = 1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    starts = jnp.array([start, start - 4], jnp.int32)
    out, k2, v2 = attend(
        q, pool_k, pool_v, table, starts, jnp.full((B,), W, jnp.int32),
        layer_base=0, k_new=k_new, v_new=v_new, logit_softcap=None,
        window=None, interpret=True, k_scale=None, v_scale=None,
        tree_mask=jnp.full((B, W), 15, jnp.int32),
        depths=jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (B, W)),
        name="block_paged")
    for b in range(B):
        s = int(starts[b])
        ctx = lambda pool: pool[table[b]].transpose(0, 2, 1, 3).reshape(
            P * psz, K, H)[:s]
        kk = jnp.concatenate([ctx(pool_k), k_new[b]])[None]
        vv = jnp.concatenate([ctx(pool_v), v_new[b]])[None]
        want = attention_xla(q[b][None], kk, vv, causal=False)
        assert _rel(out[b], want[0]) < 1e-5
        wrote = k2[table[b]].transpose(0, 2, 1, 3).reshape(P * psz, K, H)
        assert _rel(wrote[s:s + W], k_new[b]) < 1e-6
        assert (np.asarray(wrote[:s]) == np.asarray(ctx(pool_k))).all()


# -- prefill and the block program against the reference ----------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_prefill_logits_and_pages_against_the_reference(params, kernels):
    """The prefill program on a prompt's whole blocks: its logits are the
    reference's at the last position (over the token AT it) under the block
    mask, and the pages hold the reference's rotated keys and values."""
    from benchmarks.kinds import serve

    cfg = _config(f"model.kernels={kernels}")
    ref, hf = _reference(), tiny_hf(cfg)
    engine = _engine(params, f"model.kernels={kernels}")
    seen, pages = [], []
    run = engine._executor.run

    def tap(path, name, *a, **kw):
        out = run(path, name, *a, **kw)
        if path == "prefill":
            seen.append(np.asarray(out[0], np.float32))
            pages.append(np.asarray(a[4])[0])
        return out

    engine._executor.run = tap
    prompt = _prompt(22)
    req = engine.submit_request(prompt, 0)      # scoring: prefill alone
    while engine.has_work():
        engine.step()
    engine._executor.run = run
    assert req.outcome == "completed" and req.generated == []
    toks = jnp.asarray(prompt[:20], jnp.int32)
    want, _ = ref.logits_at(params, toks, jnp.array([19]), hf)
    assert _rel(seen[0][0], want[0]) < 2e-4
    # a causal mask in its place is another function
    causal = {**hf, "generation": {**hf["generation"], "block_length": 1}}
    assert _rel(ref.logits_at(params, toks, jnp.array([18]), causal)[0],
                ref.logits_at(params, toks, jnp.array([18]), hf)[0]) > 1e-2
    k, v = ref.kv_of(params, toks, hf)                # [layers, 20, K, H]
    got = np.asarray(serve._kv_at(
        engine.cache, jnp.asarray(pages[0][None, :]), 0, jnp.arange(20),
        cfg.model.n_layers, cfg.inference.num_pages,
        cfg.inference.page_size))
    want_kv = np.concatenate([np.asarray(k).ravel(), np.asarray(v).ravel()])
    assert _rel(got, want_kv) < 2e-4


def test_a_block_forward_at_32_rows_an_expert_under_a_skewed_router(
        params, monkeypatch):
    """32 slots x 4 positions x 2 picks over 8 experts, the router leaning on
    two of them: the block forward takes the grouped matmul (interpreted)
    under the row tile its shape gets, 128 and not the widest, and gives
    what the capacity buckets give and what the reference gives a block."""
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq
    from orion_tpu.models import moe as moe_lib
    from orion_tpu.ops import grouped_matmul as gm

    B = 32
    moe = params["blocks"]["moe"]
    lean = moe["router"] * jnp.asarray([3.0, 3.0, 1, 1, 1, 1, 1, 1])
    params = {**params, "blocks": {**params["blocks"],
                                   "moe": {**moe, "router": lean}}}
    fed = jnp.asarray(np.random.default_rng(5).integers(1, 255, (B, 4)),
                      jnp.int32)
    table = jnp.zeros((B, pages_per_seq(_config().inference)), jnp.int32
                      ).at[:, 0].set(1 + jnp.arange(B))

    def forward(*overrides):
        cfg = _config(f"inference.max_batch_size={B}", *overrides)
        logits, _ = runner.block_forward(
            params, init_cache(cfg.model, cfg.inference), fed,
            jnp.zeros((B,), jnp.int32), table, jnp.ones((B,), bool),
            cfg.model, cfg.inference.max_seq_len)
        return cfg, logits

    cfg, buckets = forward()
    m = cfg.model
    _, _, idx = moe_lib._router_topk(
        jax.random.normal(jax.random.key(0), (B, 4, m.d_model)), lean[0], m)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert counts[:2].sum() > 1.5 * counts.sum() / 4     # not an even router
    assert not moe_lib.takes_grouped_path(m, B, 4)
    # the path rule's tile term keeps toy blocks on the buckets
    monkeypatch.setattr(gm, "ROW_TILE", 1)
    assert moe_lib.takes_grouped_path(m, B, 4)
    seen, rule = [], gm._tiles
    monkeypatch.setattr(
        gm, "_tiles",
        lambda *a, **kw: seen.append((a, rule(*a, **kw))) or seen[-1][1])
    _, grouped = forward("model.kernels=pallas_interpret")
    assert {a for a, _ in seen} == {
        (256, 8, m.d_model, m.moe_d_ff), (256, 8, m.moe_d_ff, m.d_model)}
    assert {t[0] for _, t in seen} == {128}
    assert _rel(grouped, buckets) < 2e-5
    ref, hf = _reference(), tiny_hf(cfg)
    for b in (0, 13, 31):
        want, _ = ref.logits_at(params, fed[b], jnp.arange(4), hf)
        assert _rel(grouped[b], want) < 2e-4


CASES = [
    # (strategy, steps, prompt length, max_new)
    ("low_confidence_static", 1, 8, 8),
    ("low_confidence_static", 2, 9, 7),
    ("low_confidence_static", 2, 10, 5),
    ("low_confidence_static", 4, 11, 6),
    ("low_confidence_static", 4, 3, 9),
    ("low_confidence_dynamic", 1, 13, 6),
    ("low_confidence_dynamic", 2, 12, 9),
    ("low_confidence_dynamic", 4, 14, 11),
    ("low_confidence_dynamic", 4, 21, 13),
]


@pytest.mark.parametrize("strategy,steps,n,max_new", CASES)
def test_the_engines_tokens_are_the_references(params, strategy, steps, n,
                                               max_new):
    """Both strategies, S in {1, 2, 4}, prompt tails 0..3 (a prompt shorter
    than a block among them), ``max_new`` that is and is not a multiple of
    4: the engine's tokens equal ``reference.generate``'s, and the counters
    add up. The dynamic rule's threshold (0.05) lies inside the confidences
    these weights give, so both of its branches run."""
    overrides = (f"inference.denoising_steps={steps}",) * (steps != 2)
    if strategy == "low_confidence_dynamic":
        overrides += (f"inference.remasking={strategy}",
                      "inference.confidence_threshold=0.05")
    ref, hf = _reference(), tiny_hf(_config(*overrides))
    prompt = _prompt(n)
    (req,), t = _generate(params, [(prompt, max_new)], *overrides)
    trace = []
    want = ref.generate(params, prompt, max_new, hf, trace=trace)
    assert req.outcome == "completed" and req.generated == want
    assert len(set(want)) > 1           # not one token everywhere
    blocks = -(-(n + max_new) // 4) - n // 4
    # a block program counts as a window and holds ``steps`` + 1 forwards
    assert (t["windows"], t["block_slot_forwards"]) == (
        blocks, blocks * (steps + 1))
    # The committed blocks hold 4 * blocks positions: the prompt's tail (in
    # the first block alone), the tokens emitted, and what lies beyond
    # max_new_tokens in the last block, which is discarded and not emitted.
    assert t["tokens_committed"] == len(req.generated) == max_new
    assert 4 * t["windows"] - n % 4 - len(req.generated) == (
        -(n + max_new) % 4)
    assert t["decode_window"] == 4
    # what the reference fed as the mask token, forward by forward
    assert t["block_positions_undecided_fed"] == sum(
        int((fed == hf["generation"]["mask_token_id"]).sum())
        for _, _, fed, _, _ in trace)
    if strategy == "low_confidence_dynamic" and steps == 4:
        per_forward = [int(after.sum()) for _, _, _, _, after in trace]
        assert max(np.diff([0] + per_forward[:4])) > 1   # above the floor
    cached = sum(n // 4 * 4 + 4 * b for b in range(blocks))
    assert t["block_kv_positions_read"] == (steps + 1) * (cached + 4 * blocks)
    if n % 4 == 0 and max_new % 4 == 0:
        assert t["tokens_committed"] / t["block_slot_forwards"] == (
            4 / (steps + 1))


@pytest.mark.parametrize("strategy,steps,requests", [
    ("low_confidence_static", 2, [(9, 7)]),
    ("low_confidence_static", 4, [(3, 9)]),
    ("low_confidence_dynamic", 4, [(21, 13)]),
    ("low_confidence_static", 2, [(5, 9), (18, 13)]),
])
def test_the_heads_rows_and_how_many_decided_a_token(params, strategy, steps,
                                                     requests):
    """``block_head_rows`` is the rows the head and the choice were given: a
    live slot's ``undecided_bounds`` a block program (4 + 2 at two steps, 4 +
    3 + 2 + 1 at four), whatever its tail; ``block_head_rows_decided`` the
    positions a forward decided, which is every position of the run's blocks
    that did not come as a prompt's tail."""
    from orion_tpu.infer.runner import undecided_bounds

    overrides = (f"inference.denoising_steps={steps}",) * (steps != 2)
    if strategy == "low_confidence_dynamic":
        overrides += (f"inference.remasking={strategy}",
                      "inference.confidence_threshold=0.05")
    reqs, t = _generate(
        params, [(_prompt(n, seed=7), m) for n, m in requests], *overrides)
    assert all(r.outcome == "completed" for r in reqs)
    blocks = [-(-(n + m) // 4) - n // 4 for n, m in requests]
    assert undecided_bounds(4, steps) == {2: (4, 2), 4: (4, 3, 2, 1)}[steps]
    assert t["block_head_rows"] == sum(blocks) * sum(undecided_bounds(4, steps))
    assert t["block_head_rows_decided"] == sum(
        4 * b - n % 4 for b, (n, _) in zip(blocks, requests))
    assert t["block_head_rows_decided"] <= t["block_head_rows"] < (
        4 * steps * sum(blocks) if steps > 1 else 1 + 4 * sum(blocks))
    # every forward's undecided positions were among the rows it was given
    assert t["block_positions_undecided_fed"] <= t["block_head_rows"]


@pytest.mark.parametrize("sampled", [False, True])
def test_a_drawn_tokens_confidence_from_reductions(sampled):
    """``draw_with_confidence`` draws what ``sample`` draws and gives
    ``exp(log_softmax(logits))`` at the drawn column to float32 rounding, on
    rows with a tied maximum, a row of equal logits, a row with one logit far
    above the rest and rows far from zero."""
    from orion_tpu.infer.runner import draw_with_confidence
    from orion_tpu.infer.sampling import sample

    R, V = 12, 517
    logits = 4.0 * jax.random.normal(jax.random.key(3), (R, V), jnp.float32)
    logits = logits.at[0, jnp.array([5, 200])].set(logits[0].max() + 1.0)
    logits = logits.at[1].set(-3.25)                    # all equal
    logits = logits.at[2, 77].set(90.0)                 # one certain token
    logits = logits.at[3].add(1e4).at[4].add(-1e4)
    logits = logits.at[5, jnp.array([0, V - 1])].set(logits[5].max())
    kw = dict(temperature=0.0, top_k=0, top_p=1.0)
    if sampled:
        kw = dict(temperature=jnp.linspace(0.0, 1.5, R),
                  top_k=jnp.full((R,), 50, jnp.int32),
                  top_p=jnp.full((R,), 0.95, jnp.float32))
    key = jax.random.key(11)
    drawn, conf = jax.jit(lambda x: draw_with_confidence(x, key, **kw))(logits)
    assert (np.asarray(drawn) == np.asarray(sample(logits, key, **kw))).all()
    want = jnp.exp(jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), drawn[:, None], axis=-1))[:, 0]
    np.testing.assert_allclose(np.asarray(conf), np.asarray(want), rtol=2e-6,
                               atol=0)
    assert conf.dtype == jnp.float32 and drawn.dtype == jnp.int32
    if not sampled:
        assert list(np.asarray(drawn[:3])) == [5, 0, 77]   # ties: the lower
        assert float(conf[1]) == pytest.approx(1 / V, rel=1e-6)
        assert float(conf[0]) < 0.5 < float(conf[2])


_BLOCK_PROGRAMS: dict = {}

LAYOUTS = {
    # tails a slot, live slots
    "no-tail": ((0, 0, 0, 0), (True, True, True, True)),
    "tails": ((1, 2, 3, 0), (True, True, True, True)),
    "dead-slot": ((3, 0, 2, 1), (True, False, True, True)),
}


@pytest.mark.parametrize("strategy,steps,threshold,layout", [
    ("low_confidence_static", 1, 0.9, "tails"),
    ("low_confidence_static", 2, 0.9, "no-tail"),
    ("low_confidence_static", 2, 0.9, "tails"),
    ("low_confidence_static", 2, 0.9, "dead-slot"),
    ("low_confidence_static", 4, 0.9, "no-tail"),
    ("low_confidence_static", 4, 0.9, "tails"),
    ("low_confidence_static", 5, 0.9, "tails"),  # a forward past the last
    ("low_confidence_dynamic", 2, 0.2, "no-tail"),
    ("low_confidence_dynamic", 2, 0.2, "tails"),
    ("low_confidence_dynamic", 2, 0.2, "dead-slot"),
    ("low_confidence_dynamic", 4, 0.2, "no-tail"),
    ("low_confidence_dynamic", 4, 0.2, "tails"),
])
def test_the_block_program_against_its_body_over_every_row(
        params, strategy, steps, threshold, layout):
    """``denoise_block`` takes the head and the choice at a slot's first
    ``undecided_bounds`` undecided positions; the one-forward body
    (``block_forward``: EVERY row's logits), driven by hand with the whole
    ``log_softmax`` and ``choose_positions``, decides the same tokens at the
    same forwards: over eight cached positions of random K/V, with no
    prompt tail, tails of 1-3 decided positions and a dead slot, every step
    count and both rules (the head twenty times the preset's scale and the
    dynamic rule's threshold inside the confidences that gives, so that
    some slots decide more than the static count and some fall back on it)."""
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    params = {**params, "lm_head": 20.0 * params["lm_head"]}
    cfg = _config()
    m, icfg = cfg.model, cfg.inference
    B, L = icfg.max_batch_size, m.block_length
    tails, live = (jnp.asarray(a) for a in LAYOUTS[layout])
    rng = np.random.default_rng(17)
    tokens = jnp.asarray(rng.integers(1, 255, (B, L)), jnp.int32)
    tokens = jnp.where(jnp.arange(L) < tails[:, None], tokens, 0)
    seq_lens = jnp.full((B,), 8, jnp.int32)
    table = jnp.zeros((B, pages_per_seq(icfg)), jnp.int32
                      ).at[:, :2].set(1 + jnp.arange(2 * B).reshape(B, 2))
    ks = iter(jax.random.split(jax.random.key(2), 4))
    cache = {n: 0.5 * jax.random.normal(next(ks), a.shape, a.dtype)
             if n in "kv" else a for n, a in init_cache(m, icfg).items()}
    kw = dict(cfg=m, max_seq_len=icfg.max_seq_len)
    name = (strategy, steps)
    if name not in _BLOCK_PROGRAMS:
        _BLOCK_PROGRAMS[name] = jax.jit(partial(
            runner.denoise_block, **kw, steps=steps, remasking=strategy,
            threshold=threshold, temperature=0.0, top_k=0, top_p=1.0))
    if "body" not in _BLOCK_PROGRAMS:
        _BLOCK_PROGRAMS["body"] = jax.jit(partial(runner.block_forward, **kw))
    toks, at, _, _ = _BLOCK_PROGRAMS[name](
        params, cache, tokens, tails, seq_lens, table, live,
        jax.random.PRNGKey(0))

    want = tokens
    want_at = jnp.where(jnp.arange(L) < tails[:, None], -1, steps)
    for s, count in enumerate(runner.denoise_schedule(L, steps)):
        decided = want_at < s
        logits, _ = _BLOCK_PROGRAMS["body"](
            params, cache, jnp.where(decided, want, m.mask_token_id),
            seq_lens, table, live)
        drawn = jnp.argmax(logits, axis=-1)
        conf = jnp.exp(jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), drawn[..., None],
            axis=-1))[..., 0]
        take = runner.choose_positions(conf, ~decided, count, strategy,
                                       threshold)
        want, want_at = jnp.where(take, drawn, want), jnp.where(
            take, s, want_at)
        if (strategy, layout, s) == ("low_confidence_dynamic", "no-tail", 0
                                     ) and steps > 1:
            given = np.asarray(take.sum(-1))
            assert given.max() > count == given.min()   # both branches
    keep = np.asarray(live)
    assert (np.asarray(toks)[keep] == np.asarray(want)[keep]).all()
    assert (np.asarray(at)[keep] == np.asarray(want_at)[keep]).all()
    assert (np.asarray(at)[keep] < min(steps, L)).all()    # all decided
    assert len(set(np.asarray(toks)[keep].ravel().tolist())) > 1


def test_a_batch_of_unequal_requests_against_each_alone(params):
    """Four requests of unequal lengths and tails in one engine (4 slots,
    every dispatch one block for every live slot) give what each gives
    alone, which is the reference's; under the interpreted kernels too."""
    ref = _reference()
    requests = [(_prompt(n, seed=7), m)
                for n, m in ((5, 9), (16, 4), (18, 13), (31, 6))]
    together, _ = _generate(params, requests, "model.kernels=pallas_interpret")
    want = [ref.generate(params, p, m, tiny_hf(_config()))
            for p, m in requests]
    assert [r.generated for r in together] == want
    alone = [_generate(params, [r])[0][0].generated for r in requests[:2]]
    assert alone == want[:2]


def test_an_eos_inside_a_block_ends_the_request_there(params):
    cfg = _config()
    ref, hf = _reference(), tiny_hf(cfg)
    prompt = _prompt(10)
    full = ref.generate(params, prompt, 14, hf)
    eos = full[7]                       # inside the third block
    cut = full[:full.index(eos) + 1]
    assert ref.generate(params, prompt, 14, hf, eos_id=eos) == cut
    (req,), t = _generate(params, [(prompt, 14)], eos_id=eos)
    assert req.generated == cut and req.outcome == "completed"
    # It ended in the block that holds the EOS, whose positions behind the
    # EOS were discarded: nothing was emitted once the request was done.
    assert t["windows"] == -(-(10 + len(cut)) // 4) - 10 // 4
    assert t["tokens_committed"] == len(req.generated) == len(cut)
    assert req.generated[-1] == eos and eos not in req.generated[:-1]
    assert 4 * t["windows"] - 10 % 4 - len(cut) == -(10 + len(cut)) % 4 > 0


def test_a_sampled_request_with_the_engines_key_replayed(params):
    """Temperature 1: the engine's key is split as ``decode_window`` splits
    it (key', sub = split(key); one of split(sub, steps) a forward), and a
    forward draws every slot's rows at once: the positions a slot can still
    have undecided (``runner.undecided_bounds``), its undecided ones first;
    the reference, handed those draws, gives the engine's tokens."""
    from orion_tpu.infer.runner import undecided_bounds
    from orion_tpu.infer.sampling import sample

    cfg = _config("inference.temperature=1.0")
    ref, hf = _reference(), tiny_hf(cfg)
    B, L, S = cfg.inference.max_batch_size, 4, cfg.inference.denoising_steps
    prompt = _prompt(9)
    (req,), _ = _generate(params, [(prompt, 11)], "inference.temperature=1.0",
                          seed=5)
    keys, key = {}, jax.random.PRNGKey(5)
    for b in range(9 // 4, -(-(9 + 11) // 4)):
        key, sub = jax.random.split(key)
        keys[b] = jax.random.split(sub, S)
    trace = []

    def draw(logits, b, s):
        # decided before this forward: the prompt's tail, then what the
        # reference's last forward of this block left
        decided = trace[-1][4] if s else np.arange(L) < max(9 - b * L, 0)
        at_rows = np.argsort(decided, kind="stable")[:undecided_bounds(L, S)[s]]
        rows = jnp.zeros((B * len(at_rows), logits.shape[-1])
                         ).at[:len(at_rows)].set(logits[at_rows])
        x0 = np.zeros(L, np.int64)
        x0[at_rows] = np.asarray(sample(
            rows, keys[b][s], temperature=1.0))[:len(at_rows)]
        return x0

    want = ref.generate(params, prompt, 11, hf, draw=draw, trace=trace)
    assert req.generated == want
    assert want != ref.generate(params, prompt, 11, hf)


def test_a_prompt_token_equal_to_the_mask_id_stays_a_token(params):
    cfg = _config()
    ref, hf = _reference(), tiny_hf(cfg)
    mask_id = cfg.model.mask_token_id
    prompt = _prompt(14)
    prompt[3] = prompt[9] = prompt[13] = mask_id     # the last in the tail
    other = list(prompt)
    other[13] = 7
    (a, b), _ = _generate(params, [(prompt, 6), (other, 6)])
    assert a.generated == ref.generate(params, prompt, 6, hf)
    assert b.generated == ref.generate(params, other, 6, hf)
    assert a.generated != b.generated    # the token was read, not a mask


def test_what_a_commit_leaves_and_what_no_later_block_reads(params):
    """After each commit the pages hold what a full forward of the final
    tokens gives. The rows a denoising forward writes lie beyond the cursor
    and are never read by a later block: poisoned (1e3 over everything
    beyond the cursor, before every dispatch; finite, since a masked pair
    still multiplies its value by 0), the tokens do not move."""
    from benchmarks.kinds import serve

    cfg = _config()
    ref, hf = _reference(), tiny_hf(cfg)
    prompt = _prompt(10)
    want = ref.generate(params, prompt, 9, hf)
    engine = _engine(params)
    run = engine._executor.run
    L, psz = 4, cfg.inference.page_size

    def poisoned(path, name, *args, **kw):
        if path == "decode":
            cursor = int(np.asarray(args[4])[0])
            pages = np.asarray(args[5])[0]
            pos = np.arange(cursor, len(pages) * psz)
            pos = pos[pages[pos // psz] > 0]
            rows = (np.arange(cfg.model.n_layers)[:, None]
                    * cfg.inference.num_pages + pages[pos // psz]).ravel()
            cols = np.tile(pos % psz, cfg.model.n_layers)
            cache = {leaf: a.at[rows, :, cols].set(1e3) if leaf in "kv"
                     else a for leaf, a in args[1].items()}
            args = (args[0], cache, *args[2:])
        return run(path, name, *args, **kw)

    engine._executor.run = poisoned
    req = engine.submit_request(prompt, 9)
    table = None
    while engine.has_work():
        engine.step()
        if engine.slots[0] is not None:
            table, cursor = engine.page_table.copy(), int(engine.seq_lens[0])
            final = jnp.asarray((prompt + req.generated)[:cursor], jnp.int32)
            k, v = ref.kv_of(params, final, hf)
            got = np.asarray(serve._kv_at(
                engine.cache, jnp.asarray(table), 0, jnp.arange(cursor),
                cfg.model.n_layers, cfg.inference.num_pages, psz))
            want_kv = np.concatenate(
                [np.asarray(k).ravel(), np.asarray(v).ravel()])
            assert cursor % L == 0 and _rel(got, want_kv) < 2e-4
    engine._executor.run = run
    assert table is not None and req.generated == want


def test_preemption_falls_on_block_boundaries(params):
    """A pool too small for both requests: one is preempted between blocks
    and re-admitted on its context (a whole number of blocks since its
    prompt's first), and both still give the reference's tokens."""
    cfg = _config("inference.num_pages=9", "inference.max_batch_size=2")
    ref, hf = _reference(), tiny_hf(cfg)
    requests = [(_prompt(16, seed=3), 24), (_prompt(14, seed=4), 22)]
    engine = InferenceEngine(cfg, params, seed=0)
    reqs = [engine.submit_request(p, m) for p, m in requests]
    while engine.has_work():
        engine.step()
    assert engine.preemptions > 0
    assert [r.generated for r in reqs] == [
        ref.generate(params, p, m, hf) for p, m in requests]
    engine.close()


# -- refusals -----------------------------------------------------------------


@pytest.mark.parametrize("override,named", [
    ("inference.prefix_cache=true", "inference.prefix_cache"),
    ("inference.host_tier_bytes=1000000", "inference.host_tier_bytes"),
    ("inference.speculative=true", "inference.speculative"),
    ("inference.constrained=true", "inference.constrained"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("inference.kv_quant=int8", "inference.kv_quant"),
    ("model.weight_quant=int8", "model.weight_quant"),
])
def test_what_block_generation_is_not_served_with_is_refused_by_name(
        params, override, named):
    with pytest.raises(ValueError) as e:
        InferenceEngine(_config(override), params)
    assert "generates by diffusion over blocks (model.block_length)" in str(
        e.value)
    assert "the block program only" in str(e.value) and named in str(e.value)


def test_sizes_a_block_would_straddle_and_migration_are_refused(params):
    with pytest.raises(ValueError, match="multiples of model.block_length"):
        InferenceEngine(_config("inference.max_seq_len=126"), params)
    engine = _engine(params)
    req = engine.submit_request(_prompt(9), 8)
    engine.step()
    with pytest.raises(ValueError, match="model.block_length"):
        engine.export_migration_state(req.rid)
    while engine.has_work():
        engine.step()
    from orion_tpu.obs.parts import PROGRAM_NAMES

    assert PROGRAM_NAMES["denoise"] == "orion_denoise_block"
    assert [s for s, n in PROGRAM_NAMES.items() if "decode_window" in n] == [
        "decode"]


def test_a_tp_mesh_is_refused_by_name(params):
    """Weights on a mesh with a live ``tp`` axis under interpreted kernels:
    the block program has not been run per shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 2:
        pytest.skip("one device: no tp axis to refuse on")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    sharded = jax.device_put(params, NamedSharding(mesh, P()))
    with pytest.raises(ValueError, match="served on one device") as e:
        InferenceEngine(_config("model.kernels=pallas_interpret"), sharded)
    assert "model.block_length" in str(e.value)


def test_an_autoregressive_model_traces_what_it_traced():
    """``block_length = 0`` (every other preset): no block program is built,
    the engine reports its own decode window and the block counters stay 0
    (the flash wrapper under ``block=0``: the test of its statics above)."""
    cfg = get_config("tiny-mixtral", ["inference.max_seq_len=64"])
    assert cfg.model.block_length == 0
    p = init_params(cfg.model, jax.random.key(0))
    engine = InferenceEngine(cfg, p)
    assert not hasattr(engine, "_denoise")
    assert engine.decode_window == cfg.inference.decode_window
    t = engine.reset_timing()
    assert t["block_slot_forwards"] == t["tokens_committed"] == 0
    engine.close()
