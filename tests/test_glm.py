"""Latent attention and a sigmoid router under a selection bias
(GLM-4.7-Flash): ``forward`` against the benchmark's expanded float32
reference, prefill then decode through the paged latent rows at every
position, the absorbed kernel under the interpreter against the XLA absorbed
form and the expanded form, the router with a bias that flips choices, what
the engine refuses, and softmax models left as they were. CPU, ``tiny-glm``:
nope 24 != rope 8, values 16 != the row's 48, a leading dense layer, 8
experts top-2 with a shared one, pages of 8. Under the benchmark's N(0, 0.02)
draw attention is close to a mean of the values and a wrong score moves a
logit little, so ``_peaked`` scales the query and key matrices until it is
peaked, and two tests PLANT a fault (a decode that drops ``q_rope . k_pe``;
gates taken from ``s + b``) and see the comparison fail."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

PUBLISHED = json.loads((REPO / "tests/benchmark/data/published/"
                        "glm-4.7-flash-serve-1chip.json").read_text())
HF = dict(hidden_size=64, vocab_size=256, num_hidden_layers=3,
          num_attention_heads=4, q_lora_rank=40, kv_lora_rank=48,
          qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
          intermediate_size=128, first_k_dense_replace=1,
          n_routed_experts=8, n_shared_experts=1, moe_intermediate_size=32,
          num_experts_per_tok=2, norm_topk_prob=True,
          routed_scaling_factor=1.8, rms_norm_eps=1e-5, rope_theta=1e6)
TOL = 5e-5      # float32 on the CPU: the program against the reference


def _reference():
    from benchmarks.reference import glm

    return glm


@pytest.fixture(scope="module")
def tiny():
    """(model config, weights drawn as the benchmark draws them: the bias
    among them, 1 + 0.05 N(0, 1))."""
    from benchmarks.reference import weights

    cfg = get_config("tiny-glm").model
    params = weights.make_params(
        _reference().param_spec(HF), cfg.n_layers, "float32", 5)
    return cfg, params


def _peaked(params, scale=12.0):
    """Attention that is peaked: the query's and the row's up-projections
    times ``scale``, so that a score's spread is a few units and one key
    carries a position, where the benchmark's draw gives a tenth of a unit
    and a mean of the values."""
    p = jax.tree.map(lambda a: a, params)
    for part in ("lead", "period"):
        for name, blk in p["blocks"][part].items():
            blk = dict(blk)
            blk["attn"] = {**blk["attn"],
                           "wq_b": blk["attn"]["wq_b"] * scale,
                           "wkv_b": blk["attn"]["wkv_b"] * scale,
                           "wkv_a": blk["attn"]["wkv_a"].at[..., 48:].multiply(
                               scale)}
            p["blocks"][part] = {**p["blocks"][part], name: blk}
    return p


def _engine(params, overrides=()):
    from orion_tpu.infer import InferenceEngine

    return InferenceEngine(get_config("tiny-glm", list(overrides)), params,
                           seed=0)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# -- the model ------------------------------------------------------------------


def test_the_parameter_tree_is_the_references(tiny):
    from orion_tpu.models.transformer import init_params

    cfg, _ = tiny
    flat = {
        tuple(str(getattr(k, "key", k)) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            init_params(cfg, jax.random.key(0)))[0]}
    assert flat == {k: v[0] for k, v in _reference().param_spec(HF).items()}


def test_forward_is_the_reference(tiny):
    from orion_tpu.models.transformer import forward

    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, 45))
    got, _ = forward(params, tokens[None], cfg)
    want, _ = _reference().logits_at(params, tokens, jnp.arange(45), HF)
    assert _rel(got[0], want) < TOL


def test_forward_is_the_reference_where_attention_is_peaked(tiny):
    from orion_tpu.models.transformer import forward

    cfg, params = tiny[0], _peaked(tiny[1])
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 256, 45))
    got, _ = forward(params, tokens[None], cfg)
    want, _ = _reference().logits_at(params, tokens, jnp.arange(45), HF)
    assert _rel(got[0], want) < TOL


def test_the_loss_differentiates_the_expanded_form(tiny):
    from orion_tpu.train.trainer import loss_fn

    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(2).integers(1, 256, (2, 33)))
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
    assert np.isfinite(float(loss))
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    attn = grads["blocks"]["period"]["0"]["attn"]
    assert all(float(jnp.abs(attn[k]).max()) > 0
               for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))


# -- prefill, then decode through the latent rows ---------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("peaked", [False, True])
def test_prefill_then_decode_is_the_reference_at_every_position(
        tiny, kernels, peaked):
    """A burst of two prompts of unlike lengths (13 and 27 in a bucket of
    32, pages of 8), then 14 decode steps a row, each across page
    boundaries: the logits at every compared position against the
    reference's full forward over the tokens so far."""
    import dataclasses

    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    cfg = dataclasses.replace(tiny[0], kernels=kernels)
    params = _peaked(tiny[1]) if peaked else tiny[1]
    icfg = get_config("tiny-glm").inference
    cache = init_cache(cfg, icfg)
    assert set(cache) == {"latent"} and cache["latent"].shape == (
        3 * icfg.num_pages, 1, 8, 128)
    rng = np.random.default_rng(3)
    lens = [13, 27]
    rows = [list(rng.integers(1, 256, n)) for n in lens]
    tokens = np.zeros((2, 32), np.int32)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    table = np.zeros((2, 16), np.int32)
    table[0, :6] = np.arange(1, 7)
    table[1, :6] = np.arange(7, 13)
    logits, cache = runner.prefill_step(
        params, cache, jnp.asarray(tokens), jnp.asarray(lens),
        jnp.asarray(table[:, :4]), cfg=cfg)
    got = [[np.asarray(logits[i])] for i in range(2)]
    pos = np.array(lens)
    for _ in range(14):
        tok = np.array([int(np.argmax(g[-1])) for g in got])
        for i in range(2):
            rows[i].append(int(tok[i]))
        logits, cache = runner._decode_core(
            params, cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(table), cfg)
        for i in range(2):
            got[i].append(np.asarray(logits[i]))
        pos = pos + 1
    for i in range(2):
        seq = jnp.asarray(rows[i])
        want, _ = _reference().logits_at(
            params, seq, jnp.arange(lens[i] - 1, len(rows[i])), HF)
        errs = [_rel(g, w) for g, w in zip(got[i], want)]
        assert max(errs) < TOL, (i, errs)


def _planted(fault):
    """PLANT a fault in the program before it is traced, as
    ``tools/latent_fault_probe.py`` plants it on the chip: ``rope`` (a
    decode that drops ``q_rope . k_pe``), ``bias`` (gates taken from
    ``s + b``), or ``none``."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "latent_fault_probe", REPO / "tools/latent_fault_probe.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.planted(fault)


@pytest.mark.parametrize("fault", ["none", "rope", "bias"])
def test_the_engine_is_the_reference_and_a_planted_fault_is_seen(tiny, fault):
    """Through the benchmark's own comparison (probes alone through the
    engine's prefill and three decode windows of 4; pages of 8, so every
    probe crosses pages), peaked attention: the engine agrees with the
    reference at every compared position; with a fault planted the worst
    probe's median, the number ``decide`` judges, is 10 times the tolerance
    and more, and the window link (the latent leaf the fused window wrote
    against the one-step body's) stays whole."""
    from benchmarks.kinds import serve

    mix = {"probe_prompts": [5, 13, 22, 31], "probe_windows": 3}
    with _planted(fault):
        eng = _engine(_peaked(tiny[1]))
        numbers = serve.probe_numbers(eng, _reference(), HF, mix, seed=3)
    judged = serve.judged(numbers, 0.0)
    assert len(numbers["err"]) == 4 * 13
    assert judged["window_kv_rel_err_max"] < 1e-6
    if fault == "none":
        assert max(numbers["err"]) < TOL, max(numbers["err"])
    else:
        assert judged["logit_rel_err_worst_probe_median_clear"] > 10 * TOL
    eng.close()


def test_the_window_link_sees_a_latent_leaf_that_is_not_the_one_step_bodys(
        tiny):
    from benchmarks.kinds import serve

    eng = _engine(tiny[1])
    mix = {"probe_prompts": [13], "probe_windows": 2}
    numbers = serve.probe_numbers(eng, _reference(), HF, mix, seed=3,
                                  break_link=True)
    assert max(numbers["window_kv_rel_err"]) > 0.1
    eng.close()


def test_two_bursts_of_unlike_lengths_decode_as_each_alone(tiny):
    """Greedy tokens of four requests in two bursts (lengths 3-40, the
    second admitted while the first decodes) are those of each alone."""
    _, params = tiny
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, 256, n)))
               for n in (3, 40, 17, 9)]
    alone = []
    for p in prompts:
        eng = _engine(params)
        alone.append(list(eng.generate([p], max_new_tokens=20)[0]))
        eng.close()
    eng = _engine(params)
    reqs = [eng.submit_request(p, 20) for p in prompts[:2]]
    for _ in range(2):
        eng.step()
    reqs += [eng.submit_request(p, 20) for p in prompts[2:]]
    while eng.has_work():
        eng.step()
    assert [list(r.generated) for r in reqs] == alone
    eng.assert_page_accounting()
    eng.close()


def test_the_interpreted_kernel_serves_the_engines_tokens(tiny):
    _, params = tiny
    prompt = list(range(1, 20))
    out = []
    for kernels in ("xla", "pallas_interpret"):
        eng = _engine(params, [f"model.kernels={kernels}"])
        out.append(list(eng.generate([prompt], max_new_tokens=12)[0]))
        eng.close()
    assert out[0] == out[1]


# -- the absorbed kernel ----------------------------------------------------------


def _absorbed_inputs(seed=0, B=3, N=4, R=48, rope=8, psz=8, P=6, NP=32):
    ks = jax.random.split(jax.random.key(seed), 4)
    Wd = 128
    pool = jnp.zeros((2 * NP, 1, psz, Wd)).at[..., :R + rope].set(
        jax.random.normal(ks[0], (2 * NP, 1, psz, R + rope)))
    q = jnp.zeros((B, N, Wd)).at[..., :R + rope].set(
        jax.random.normal(ks[1], (B, N, R + rope)))
    new = jnp.zeros((B, Wd)).at[:, :R + rope].set(
        jax.random.normal(ks[2], (B, R + rope)))
    table = jnp.asarray(
        np.random.default_rng(seed).permutation(np.arange(1, NP))[:B * P]
        .reshape(B, P), jnp.int32)
    pos = jnp.asarray([0, 21, 47], jnp.int32)[:B]
    return q, pool, table, pos, new


def _absorbed_xla(q, pool, table, pos, new, base, R, scale):
    psz = pool.shape[2]
    B = q.shape[0]
    pool = pool.at[base + table[jnp.arange(B), pos // psz], 0,
                   pos % psz].set(new)
    rows = pool[base + table][:, :, 0].reshape(B, -1, pool.shape[-1])
    z = jnp.einsum("bnw,btw->bnt", q, rows) * scale
    live = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], z, -jnp.inf), axis=-1)
    return jnp.einsum("bnt,btr->bnr", p, rows[..., :R]), pool


@pytest.mark.parametrize("layer", [0, 1])
def test_the_absorbed_kernel_is_the_xla_absorbed_form(layer):
    from orion_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention,
    )

    q, pool, table, pos, new = _absorbed_inputs()
    want, pool_want = _absorbed_xla(q, pool, table, pos, new, layer * 32,
                                    48, 32 ** -0.5)
    got, pool_got = latent_paged_attention(
        q, pool, table, pos, new, layer_base=layer * 32, value_width=48,
        scale=32 ** -0.5, interpret=True)
    assert _rel(got, want) < 1e-5
    # The new rows landed, and nothing else of the pool moved.
    assert (np.asarray(pool_got) == np.asarray(pool_want)).all()


def test_the_absorbed_form_is_the_expanded_form(tiny):
    """Equation 4 against equation 3 on one layer's weights: the absorbed
    kernel over cached rows against ``latent_attention`` over the same rows
    expanded, at the last position."""
    from orion_tpu.models.transformer import (
        latent_absorb, latent_attention, latent_proj, latent_unabsorb)
    from orion_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention,
    )

    cfg, params = tiny[0], _peaked(tiny[1])
    a = jax.tree.map(lambda x: x[0], params["blocks"]["period"]["0"]["attn"])
    S = 29
    h = jax.random.normal(jax.random.key(1), (1, S, 64))
    positions = jnp.arange(S)[None]
    q, row = latent_proj(h, a, cfg, positions)
    want = latent_attention(q, row, a["wkv_b"], cfg, impl="xla")[0, -1]
    pages = jnp.pad(row[0], ((0, 3), (0, 128 - 56))).reshape(4, 1, 8, 128)
    pool = jnp.zeros((8, 1, 8, 128)).at[1:5].set(pages)
    pool = pool.at[4, 0, 4].set(0.0)        # the kernel writes the new row
    q_lat = jnp.pad(latent_absorb(q[:, -1:], a["wkv_b"], cfg)[:, 0],
                    ((0, 0), (0, 0), (0, 128 - 56)))
    o_lat, pool = latent_paged_attention(
        q_lat, pool, jnp.asarray([[1, 2, 3, 4]], jnp.int32),
        jnp.asarray([S - 1], jnp.int32), jnp.pad(row[:, -1], ((0, 0), (0, 72))),
        layer_base=0, value_width=48, scale=32 ** -0.5, interpret=True)
    got = latent_unabsorb(o_lat[:, None], a["wkv_b"], cfg)[0, 0]
    assert _rel(got, want) < 1e-5
    assert _rel(pool[4, 0, 4, :56], row[0, -1]) == 0.0


# -- the router -------------------------------------------------------------------


def test_the_router_is_the_references_and_the_bias_flips_choices(tiny):
    """Sigmoid scores, the top-2 of score + bias, gates without it. With
    the bias as the benchmark draws it (1 + 0.05 N(0, 1)) the chosen set
    differs from the unbiased one at 15-60 % of positions on these widths
    (the constant moves nothing), and no gate holds the bias: they sum to
    ``routed_scaling_factor``."""
    from orion_tpu.models.moe import _router_topk

    cfg, params = tiny
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["period"]["0"]["moe"])
    x = jax.random.normal(jax.random.key(7), (1, 512, 64))
    _, gate, idx = _router_topk(x, moe["router"], cfg, moe["router_bias"])
    want, _ = _reference()._router(x[0], moe, HF)
    got = jnp.zeros((512, 8)).at[jnp.arange(512)[:, None], idx[0]].set(gate[0])
    assert _rel(got, want) < 1e-6
    assert np.allclose(np.asarray(gate.sum(-1)), 1.8, atol=1e-5)
    _, _, plain = _router_topk(x, moe["router"], cfg, None)
    flipped = float(np.mean(np.sort(np.asarray(idx[0]), -1)
                            != np.sort(np.asarray(plain[0]), -1)))
    assert 0.15 < flipped < 0.6, flipped
    # A bias common to all experts moves nothing.
    _, g1, i1 = _router_topk(x, moe["router"], cfg, jnp.ones((8,)))
    _, g0, i0 = _router_topk(x, moe["router"], cfg, None)
    assert (np.asarray(i1) == np.asarray(i0)).all()
    assert _rel(g1, g0) < 1e-6


def test_a_softmax_router_is_as_it_was():
    """One function, two scorings: a softmax model's gates are the softmax's
    top-k renormalised, whatever the new field says of other models."""
    from orion_tpu.models.moe import _router_topk

    cfg = get_config("tiny-mixtral").model
    x = jax.random.normal(jax.random.key(0), (2, 9, 64))
    w = jax.random.normal(jax.random.key(1), (64, 4))
    probs, gate, idx = _router_topk(x, w, cfg)
    p = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, w), -1)
    top, want = jax.lax.top_k(p, 2)
    assert (np.asarray(idx) == np.asarray(want)).all()
    assert _rel(gate, top / top.sum(-1, keepdims=True)) < 1e-6
    assert _rel(probs, p) == 0.0


@pytest.mark.parametrize("preset, tokens", [
    ("tiny-mixtral", [[100, 114, 93, 242, 188, 93, 242, 188, 40, 36, 14, 76],
                      [241, 149, 32, 198, 234, 35, 89, 229, 22, 73, 131,
                       175]]),
    ("tiny-laguna", [[215, 46, 20, 233, 246, 216, 78, 56, 197, 213, 200, 200],
                     [112, 185, 167, 238, 210, 1, 54, 29, 115, 148, 70,
                      210]]),
])
def test_a_softmax_kv_model_serves_the_parents_tokens(preset, tokens):
    """The tokens the parent commit's engine gave (PR 34, recorded there):
    the K/V backends and the softmax router compute what they computed."""
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models.transformer import init_params

    cfg = get_config(preset)
    eng = InferenceEngine(cfg, init_params(cfg.model, jax.random.key(0)),
                          seed=0)
    out = eng.generate([[7, 8, 9, 7, 8, 3, 4, 5, 6, 7, 8, 9],
                        [5, 4, 3, 2, 1, 9, 9]], max_new_tokens=12)
    assert [list(map(int, o)) for o in out] == tokens
    assert set(eng.cache) == {"k", "v"}
    t = eng.reset_timing()
    for key in ("decode_latent_token_layers", "prefill_attn_pairs",
                "latent_live_page_bytes", "latent_live_tokens"):
        assert t[key] == 0, key
    assert t["decode_kv_token_layers"] > 0
    eng.close()


# -- the engine -------------------------------------------------------------------


def test_the_counters_are_host_arithmetic_on_lengths(tiny):
    _, params = tiny
    eng = _engine(params)
    eng.generate([list(range(1, 12))], max_new_tokens=9)
    t = eng.reset_timing()
    # 11 prompt positions: 11 x 12 / 2 pairs a layer.
    assert t["prefill_attn_pairs"] == 3 * 66
    # The first token comes off the prefill; two windows of 4 steps read
    # 11..18 cached positions a step and layer.
    assert t["windows"] == 2
    assert t["decode_latent_token_layers"] == 3 * sum(range(11, 19))
    # At each window: whole pages of 8 rows of 128 float32 numbers a layer.
    page = 3 * 8 * 128 * 4
    assert t["latent_live_page_bytes"] % page == 0
    assert t["latent_live_tokens"] == 11 + 15
    per = t["latent_live_page_bytes"] / (3 * t["latent_live_tokens"])
    assert 128 * 4 <= per < 2 * 128 * 4
    assert t["decode_kv_tokens"] == t["decode_kv_token_layers"] == 0
    eng.close()


def test_the_counters_reach_the_registry_and_a_flight_dump(tiny, tmp_path):
    """Every key of ``reset_timing()`` is a registry gauge; a NaN
    quarantine's flight dump carries the registry's snapshot and the spans
    around the fault, the latent backend's dispatches among them."""
    import glob

    from orion_tpu.infer import InferenceEngine
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    jsonl = tmp_path / "serve.jsonl"
    eng = InferenceEngine(
        get_config("tiny-glm", [
            "inference.nan_guard=true", "inference.trace=true",
            f"inference.flight_dir={tmp_path}",
            f"inference.metrics_jsonl={jsonl}"]), tiny[1], seed=0,
        fault_injector=FaultInjector([FaultSpec("nan", step=1)]))
    reqs = [eng.submit_request(list(range(1, 12)), 9),
            eng.submit_request(list(range(20, 27)), 9)]
    while eng.has_work():
        eng.step()
    assert sorted(r.outcome for r in reqs) == ["completed", "error:nan"]
    snap = eng.registry.snapshot()
    keys = ("decode_latent_token_layers", "prefill_attn_pairs",
            "latent_live_page_bytes", "latent_live_tokens")
    for key in keys:
        assert snap[f"engine.{key}"] > 0, key
    doc = json.loads(open(glob.glob(
        str(tmp_path / "flight_nan_quarantine_*.json"))[0]).read())
    for key in keys:
        assert doc["metrics"][f"engine.{key}"] > 0, key
    spans = {s["name"] for s in doc["spans"] if s["kind"] == "span"}
    assert {"orion/prefill/run", "orion/decode/run"} <= spans
    t = eng.reset_timing()
    row = json.loads(jsonl.read_text().splitlines()[-1])
    for key in keys:
        assert row[f"serve.{key}"] == t[key] > 0, key
    eng.close()


def test_a_quarantined_request_leaves_no_nan_behind(tiny):
    """NaN quarantine through the helper that knows the paged leaves: the
    victim's rows are poisoned, it errors, its pages are scrubbed and the
    neighbour's tokens are those of a fault-free run."""
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    prompts = [list(range(1, 22)), list(range(30, 40))]
    guard = ["inference.nan_guard=true"]
    eng = _engine(tiny[1], guard)
    want = eng.generate(prompts, max_new_tokens=16)
    eng.close()
    eng = InferenceEngine(
        get_config("tiny-glm", guard), tiny[1], seed=0,
        fault_injector=FaultInjector([FaultSpec("nan", step=1)]))
    reqs = [eng.submit_request(p, 16) for p in prompts]
    while eng.has_work():
        eng.step()
    assert [r.outcome for r in reqs] == ["error:nan", "completed"]
    assert list(reqs[1].generated) == list(want[1])
    assert np.isfinite(np.asarray(eng.cache["latent"])).all()
    eng.assert_page_accounting()
    eng.close()


def test_a_preempted_request_re_prefills_to_the_same_tokens(tiny):
    _, params = tiny
    prompt = [int(x) for x in np.random.default_rng(1).integers(1, 256, 21)]
    eng = _engine(params)
    want = eng.generate([prompt], max_new_tokens=24)[0]
    eng.close()
    eng = _engine(params)
    req = eng.submit_request(prompt, 24)
    while len(req.generated) < 9:
        eng.step()
    eng._preempt(req)
    assert req.slot is None and eng.alloc.free_pages == eng.icfg.num_pages - 1
    while eng.has_work():
        eng.step()
    assert list(req.generated) == list(want)
    eng.assert_page_accounting()
    eng.close()


def test_one_helper_knows_a_caches_paged_leaves(tiny):
    from orion_tpu.infer import kv_cache

    glm, llama = get_config("tiny-glm"), get_config("tiny-llama")
    c = kv_cache.init_cache(glm.model, glm.inference)
    assert kv_cache.page_geometry(c, 3) == (8, glm.inference.num_pages)
    assert kv_cache.host_page_bytes(c, 3) == 3 * 8 * 128 * 4
    c = kv_cache.init_cache(llama.model, llama.inference)
    assert kv_cache.page_geometry(c, 2) == (
        llama.inference.page_size, llama.inference.num_pages)
    assert kv_cache.paged_leaf(c) is c["k"]
    # 512 + 64 -> 640: whole lane tiles.
    assert kv_cache.latent_width(get_config("glm-4.7-flash").model) == 640


@pytest.mark.parametrize("override, named", [
    ("inference.prefix_cache=true", "inference.prefix_cache"),
    ("inference.speculative=true", "inference.speculative"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("inference.kv_quant=int8", "inference.kv_quant"),
    ("inference.constrained=true", "inference.constrained"),
    ("model.weight_quant=int8", "model.weight_quant"),
    ("inference.host_tier_bytes=1048576", "inference.host_tier_bytes"),
])
def test_what_the_latent_backend_does_not_serve_is_refused_by_name(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=named):
        InferenceEngine(get_config("tiny-glm", [override]), tiny[1])


def test_migration_is_refused_by_name(tiny):
    eng = _engine(tiny[1])
    req = eng.submit_request([1, 2, 3, 4, 5], 8)
    eng.step()
    with pytest.raises(ValueError, match="kv_lora_rank"):
        eng.export_migration_state(req.rid)
    eng.close()


def test_a_cached_prefix_is_refused_by_the_prefill_program(tiny):
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    cfg, params = tiny
    icfg = get_config("tiny-glm").inference
    with pytest.raises(ValueError, match="whole prompts"):
        runner.prefill_step(
            params, init_cache(cfg, icfg), jnp.zeros((1, 16), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            cfg=cfg)


def test_the_preset_is_the_published_configuration():
    m, pub = get_config("glm-4.7-flash").model, PUBLISHED
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.vocab_size) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_hidden_layers"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["vocab_size"])
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (
        pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"])
    assert m.resolved_head_dim == (pub["qk_nope_head_dim"]
                                   + pub["qk_rope_head_dim"])
    assert (m.n_experts, m.n_experts_per_token, m.moe_d_ff,
            m.n_shared_experts, m.n_dense_layers, m.router_scale) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["n_shared_experts"],
        pub["first_k_dense_replace"], pub["routed_scaling_factor"])
    assert (m.rope_theta, m.norm_eps, m.tie_embeddings, m.attn_bias) == (
        pub["rope_theta"], pub["rms_norm_eps"], pub["tie_word_embeddings"],
        pub["attention_bias"])
    assert pub["topk_method"] == "noaux_tc" and m.router_score == "sigmoid"
    assert m.router_bias and pub["norm_topk_prob"]
    assert pub["n_group"] == pub["topk_group"] == 1
    assert pub["hidden_act"] == "silu" and m.activation == "swiglu"
    assert pub["rope_scaling"] is None and pub["partial_rotary_factor"] == 1
    assert m.max_seq_len == pub["max_position_embeddings"]
    assert m.is_latent and m.latent_row_width == 576
    plan = m.layer_plan
    assert (plan.lead, plan.period, plan.repeats, plan.tail) == (1, 1, 46, 0)


@pytest.mark.parametrize("converter", [
    "from_hf_llama", "from_hf_mixtral", "to_hf_llama"])
def test_no_converter_has_this_key_set_and_says_so(tiny, converter):
    from orion_tpu.models import convert

    arg = tiny[1] if converter.startswith("to_") else {}
    with pytest.raises(ValueError, match="no converter here"):
        getattr(convert, converter)(arg, tiny[0])
