"""Kimi-delta-attention layers among latent layers in ONE layer plan, under a
group-limited sigmoid router of which a chip holds a share (Ling-3.0-flash):
``forward`` against the benchmark's float32 reference (the recurrence over
positions, the expanded form, the router with its groups), ``tiny-ling``
through the ENGINE (prefill and decode windows, slots of unlike length, a slot
released and reused) through the benchmark's own comparison, both planted
faults seen, the router against the reference's with a bias that flips a
group, the share test (the shares' parts add up to the uncut layer), what the
engine refuses, the preset against the published file. CPU, float32.

Tolerance: 5e-5 relative L2 on logits, float32 on the CPU: the program's
chunked form (chunks of 64 in sub-chunks of 16, a triangular inverse) and the
reference's scan over positions differ by rounding alone; the measured
values are 1e-5 to 2e-5."""

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
                        "ling-3.0-flash-serve-1chip.json").read_text())
HF = dict(hidden_size=64, vocab_size=256, num_hidden_layers=8,
          num_attention_heads=4, head_dim=16, kv_lora_rank=48,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          intermediate_size=128, first_k_dense_replace=2, layer_group_size=6,
          short_conv_kernel_size=4, kda_lower_bound=-5, num_experts=16,
          published={"num_experts": 16}, num_shared_experts=1,
          moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
          num_experts_per_tok=4, n_group=4, topk_group=2, norm_topk_prob=True,
          routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=6e6,
          use_qk_norm=True, deployment={"experts_held": [0, 16]})
TOL = 5e-5


def _reference():
    from benchmarks.reference import ling

    return ling


@pytest.fixture(scope="module")
def tiny():
    """(model config, weights drawn as the benchmark draws them)."""
    from benchmarks.reference import weights

    cfg = get_config("tiny-ling").model
    params = weights.make_params(
        _reference().param_spec(HF), cfg.n_layers, "float32", 5)
    return cfg, params


def _engine(params, overrides=()):
    from orion_tpu.infer import InferenceEngine

    return InferenceEngine(get_config("tiny-ling", list(overrides)), params,
                           seed=0)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _probe(eng, mix, **kw):
    from benchmarks.kinds import serve, serve_rows

    with serve_rows.tapped():
        return serve.probe_numbers(eng, _reference(), HF, mix, seed=3, **kw)


# -- the model ------------------------------------------------------------------


def test_a_layers_attention_is_its_kinds_and_the_plan_is_one():
    m = get_config("ling-3.0-flash").model
    kinds = [k.attention for k in m.layer_kinds]
    assert kinds == ["latent" if (l + 1) % 6 == 0 else "kda"
                     for l in range(42)]
    assert (m.n_layers_of("kda"), m.n_layers_of("latent")) == (35, 7)
    assert not m.is_latent and m.has_latent and m.has_kda
    # the plan's elements are RUNS of equal layers: two dense KDA layers,
    # three sparse ones, then (a latent layer, five KDA layers) six times
    # and the last latent layer: four bodies and a tail
    plan = m.layer_plan
    assert plan == (2, 2, 6, 1, (2, 3, 1, 5))
    assert [plan.layers(e) for e in range(3)] == [
        [0, 1], [2, 3, 4], [5, 11, 17, 23, 29, 35, 41]]
    assert plan.layers(3) == [list(range(6 * g, 6 * g + 5))
                              for g in range(1, 7)]
    cut = get_config("tiny-ling").model.layer_plan      # the cell's eight
    assert cut == (1, 3, 1, 0, (2, 3, 1, 2))
    assert [cut.layers(e) for e in range(4)] == [
        [0, 1], [[2, 3, 4]], [5], [[6, 7]]]
    # a layer's row among the layers of its kind, from (l, its static twin:
    # the first layer of its element)
    twin = lambda l: (0 if l < 2 else 2 if l < 5
                      else 5 if (l + 1) % 6 == 0 else 6)
    rows = [m.cache_layer(l, twin(l)) for l in range(42)]
    seen = {"kda": 0, "latent": 0}
    for l, row in enumerate(rows):
        assert row == seen[kinds[l]]
        seen[kinds[l]] += 1
    # a model of one kind keeps its one answer
    glm, brumby = get_config("tiny-glm").model, get_config("tiny-brumby").model
    assert glm.is_latent and not glm.has_kda and glm.n_paged_layers == 3
    assert {k.attention for k in brumby.layer_kinds} == {"power_retention"}
    assert brumby.layer_plan is None and brumby.cache_layer(2, 0) == 2
    assert get_config("tiny-ling").model.n_paged_layers == 1


def test_the_parameter_tree_is_the_references(tiny):
    from orion_tpu.models.transformer import init_params, param_logical_axes

    cfg, _ = tiny
    tree = init_params(cfg, jax.random.key(0))
    flat = {
        tuple(str(getattr(k, "key", k)) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {k: v[0] for k, v in _reference().param_spec(HF).items()}
    axes = param_logical_axes(cfg)
    assert jax.tree.structure(tree) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


def test_forward_is_the_reference(tiny):
    from orion_tpu.models.transformer import forward

    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 256, 77))
    got, _ = forward(params, tokens[None], cfg)
    want, _ = _reference().logits_at(params, tokens, jnp.arange(77), HF)
    assert _rel(got[0], want) < TOL


def test_the_loss_differentiates_the_chunked_form(tiny):
    from orion_tpu.train.trainer import loss_fn

    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(2).integers(1, 256, (2, 33)))
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
    assert np.isfinite(float(loss))
    attn = grads["blocks"]["lead"]["0"]["attn"]
    assert all(float(jnp.abs(attn[k]).max()) > 0 for k in (
        "wq", "wk", "wv", "conv", "wf", "a_log", "dt_bias", "wb", "wg",
        "o_norm", "wo"))


def test_the_two_readings_of_the_gate_are_apart():
    """ASSUMED (a): the reference keeps the standard gate as its other
    branch; the bounded one never passes its bound, the standard one does,
    and at a zero argument they read -2.5 and -log 2."""
    ref = _reference()
    z = jnp.asarray(np.random.default_rng(0).normal(size=(9, 4, 16)) * 8)
    a_log, dt = jnp.zeros((4,)), jnp.zeros((4, 16))
    hf = {"kda_lower_bound": -5}
    bounded = ref._log_decay(z, a_log, dt, hf)
    standard = ref._log_decay(z, a_log, dt, hf, form="standard")
    assert ref.GATE_FORM == "bounded"
    assert float(bounded.min()) >= -5.0 and float(standard.min()) < -5.0
    zero = jnp.zeros((1, 4, 16))
    assert np.allclose(np.asarray(ref._log_decay(zero, a_log, dt, hf)), -2.5)
    assert np.allclose(np.asarray(ref._log_decay(
        zero, a_log, dt, hf, form="standard")), -np.log(2.0), atol=1e-6)
    # and the program's is the bounded one
    from orion_tpu.ops.kda import safe_log_decay

    assert _rel(safe_log_decay(z, a_log, dt, -5.0), bounded) < 1e-6


def test_the_reference_keeps_the_other_reading_of_the_qk_norm(tiny):
    """ASSUMED (b): the program and the reference norm each head's query and
    the ONE shared rotary key; ISSUE 41's reading, a norm over each head's
    whole key (nope | rope numbers) before the rotation, is the reference's
    other branch (no program option). Under it a head's key has a root mean
    square of 1 whatever ``wkv_b`` brings, which the shared reading's has
    not; the two give unlike layer outputs, and the default is the shared
    one, the one the engine is held to below."""
    ref = _reference()
    _, params = tiny
    a = ref._block(params, HF, 5)["attn"]                   # the latent layer
    assert "wkv_a" in a and ref.QK_NORM_FORM == "shared"
    S, nope = 24, HF["qk_nope_head_dim"]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(S, 64)), jnp.float32)
    pos = jnp.arange(S)
    shared = ref._latent(h, a, pos, HF, None)
    assert _rel(ref._latent(h, a, pos, HF, None, form="shared"), shared) == 0
    expanded = ref._latent(h, a, pos, HF, None, form="expanded")
    assert _rel(expanded, shared) > 0.05
    k_nope = 7.0 * jnp.asarray(
        np.random.default_rng(2).normal(size=(S, nope)), jnp.float32)
    k_pe = jnp.asarray(np.random.default_rng(3).normal(size=(S, 8)), jnp.float32)
    ones = {"k_norm": jnp.ones((8,))}
    k = ref._expanded_key(k_nope, k_pe, ones, 0.0)
    assert np.allclose(np.asarray(jnp.mean(k * k, -1)), 1.0, atol=1e-5)
    # without the norm the switch changes nothing
    off = dict(HF, use_qk_norm=False)
    assert _rel(ref._latent(h, a, pos, off, None, form="expanded"),
                ref._latent(h, a, pos, off, None)) == 0


# -- the engine, through the benchmark's own comparison ---------------------------


def _planted(fault):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "kda_fault_probe", REPO / "tools/kda_fault_probe.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.planted(fault)


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_the_engine_is_the_reference_at_every_position(tiny, kernels):
    """Probes of 2 (shorter than the convolution), 13, 22 and 31 tokens,
    each through the engine's prefill (the chunked form, the expanded form)
    and three decode windows of 4 (the state advanced in place, the
    absorbed form over pages of 8): every compared position against the
    reference; the window link (the latent rows AND the slot's state and
    convolution rows against the one-step body's) bitwise."""
    eng = _engine(tiny[1], [f"model.kernels={kernels}"])
    numbers = _probe(eng, {"probe_prompts": [2, 13, 22, 31],
                           "probe_windows": 3})
    assert len(numbers["err"]) == 4 * 13
    assert max(numbers["err"]) < TOL, max(numbers["err"])
    assert max(numbers["window_kv_rel_err"]) == 0.0
    assert max(numbers["window_token_gap"]) == 0.0
    eng.close()


@pytest.mark.parametrize("fault", ["erase", "groups"])
def test_a_planted_fault_is_seen(tiny, fault):
    """``tools/kda_fault_probe.py``'s two faults on the tiny model: a decode
    that drops the erase term (a gated sum), a router that ignores its
    groups. The judged number is 10 times the tolerance and more; the
    window link stays whole (the window and the one-step body share the
    fault)."""
    from benchmarks.kinds import serve

    with _planted(fault):
        eng = _engine(tiny[1])
        numbers = _probe(eng, {"probe_prompts": [5, 13],
                               "probe_windows": 3})
    judged = serve.judged(numbers, 0.0)
    assert judged["logit_rel_err_worst_probe_median_clear"] > 10 * TOL
    assert judged["window_kv_rel_err_max"] < 1e-6
    eng.close()


def test_the_window_link_sees_rows_that_are_not_the_one_step_bodys(tiny):
    eng = _engine(tiny[1])
    numbers = _probe(eng, {"probe_prompts": [13], "probe_windows": 2},
                     break_link=True)
    assert max(numbers["window_kv_rel_err"]) > 0.1
    eng.close()


def test_slots_of_unlike_length_and_a_slot_reused_decode_as_each_alone(tiny):
    """Greedy tokens of four requests over TWO slots, in bursts of unlike
    lengths (2-40), the later ones taking the slots that the earlier ones
    released (their state and convolution rows are written whole by the
    prefill, from a zero state: what the last tenant left is not read), are
    those of each alone in a fresh engine."""
    _, params = tiny
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, 256, n))) for n in (3, 40, 17, 2)]
    news = [6, 20, 9, 12]
    two = ["inference.max_batch_size=2"]
    alone = []
    eng = _engine(params, two)
    for p, n in zip(prompts, news):      # one engine, one request at a time
        alone.append(list(eng.generate([p], max_new_tokens=n)[0]))
    eng.close()
    eng = _engine(params, two)
    reqs = [eng.submit_request(p, n) for p, n in zip(prompts, news)]
    slots = []
    while eng.has_work():
        eng.step()
        slots.append(tuple(r.slot for r in reqs))
    assert [list(r.generated) for r in reqs] == alone
    first = {s[:2] for s in slots if None not in s[:2]}
    later = {x for s in slots for x in s[2:] if x is not None}
    assert first and later <= {0, 1} and later  # the released slots, reused
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


def test_the_cache_is_two_kinds_in_one_manager(tiny):
    """The latent leaf over the latent layers alone; the KDA leaves a
    slot's, which a scrub of pages passes by; a page's bytes count the
    paged leaf only."""
    from orion_tpu.infer import kv_cache

    cfg = get_config("tiny-ling")
    c = kv_cache.init_cache(cfg.model, cfg.inference)
    assert {k: v.shape for k, v in c.items()} == {
        "latent": (1 * 64, 1, 8, 128),
        "kda_state": (7, 5, 4, 16, 16), "kda_conv": (7, 5, 3, 3 * 64)}
    assert c["kda_state"].dtype == jnp.float32
    assert kv_cache.page_geometry(c, cfg.model.n_paged_layers) == (8, 64)
    assert kv_cache.host_page_bytes(c, 1) == 8 * 128 * 4
    ones = jax.tree.map(jnp.ones_like, c)
    scrubbed = kv_cache.scrub_pages(
        ones, jnp.asarray([3]), n_layers=1, num_pages=64)
    assert float(scrubbed["latent"][3].sum()) == 0.0
    assert float(scrubbed["latent"][4].min()) == 1.0
    assert all(bool((scrubbed[k] == 1).all())
               for k in ("kda_state", "kda_conv"))


def test_the_counters_are_host_arithmetic_on_lengths(tiny):
    eng = _engine(tiny[1])
    eng.generate([list(range(1, 12))], max_new_tokens=9)
    t = eng.reset_timing()
    assert t["prefill_kda_token_layers"] == 7 * 11
    assert t["prefill_attn_pairs"] == 1 * 66          # the one latent layer
    assert t["windows"] == 2
    assert t["decode_kda_slot_layers"] == 7 * 8
    assert t["decode_latent_token_layers"] == sum(range(11, 19))
    row = 7 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert t["kda_live_state_bytes"] == 2 * row
    assert t["latent_live_page_bytes"] % (8 * 128 * 4) == 0
    assert t["latent_live_tokens"] == 11 + 15
    assert t["decode_kv_tokens"] == t["decode_state_slot_layers"] == 0
    # no layer of this model keeps K and V in pages: decode_kv_* count none
    assert t["decode_kv_token_layers"] == t["decode_kv_pages_read"] == 0
    assert not eng._layers_by_window and eng._window_layers == 0
    eng.close()


# -- the router -------------------------------------------------------------------


def test_the_router_is_the_references_and_a_bias_flips_a_group(tiny):
    """The program's top-4 under groups against the reference's on the same
    scores; a bias on one group's experts brings that group in where it was
    out, and the gates still read the scores without it."""
    from orion_tpu.models import moe

    cfg, params = tiny
    p = jax.tree.map(lambda a: a[0], {
        k: v for k, v in params["blocks"]["period"]["1"]["moe"].items()
        if k in ("router", "router_bias")})
    h = jnp.asarray(np.random.default_rng(7).normal(size=(1, 40, 64)),
                    jnp.float32)
    for flip in (False, True):
        bias = p["router_bias"]
        if flip:
            bias = bias.at[12:16].add(1.0)          # group 3
        _, gate, idx = moe._router_topk(h, p["router"], cfg, bias)
        gates, _ = _reference()._router(
            h[0], {"router": p["router"], "router_bias": bias}, HF)
        got = np.zeros((40, 16), np.float32)
        np.put_along_axis(got, np.asarray(idx[0]), np.asarray(gate[0]), 1)
        assert np.allclose(got, np.asarray(gates), atol=1e-6)
        groups = np.asarray(idx[0]) // 4
        assert all(len(set(row)) <= 2 for row in groups)   # 2 of 4 kept
        share = (groups == 3).any(1).mean()
        assert (share == 1.0) if flip else (share < 1.0)
    # without groups another set is chosen at some position
    import dataclasses

    flat = dataclasses.replace(cfg, n_group=1, topk_group=1)
    _, _, idx_flat = moe._router_topk(h, p["router"], flat, p["router_bias"])
    _, _, idx = moe._router_topk(h, p["router"], cfg, p["router_bias"])
    assert not np.array_equal(np.sort(np.asarray(idx_flat), -1),
                              np.sort(np.asarray(idx), -1))


def test_groups_are_refused_by_the_all_to_all_dispatch(tiny):
    from orion_tpu.models import moe

    cfg, params = tiny
    p = jax.tree.map(lambda a: a[0], params["blocks"]["period"]["1"]["moe"])
    p.pop("router_bias")
    if len(jax.devices()) < 2:
        pytest.skip("one device: no ep axis to refuse on")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with pytest.raises(ValueError, match="n_group"):
        moe.moe_mlp_sorted_a2a(jnp.zeros((1, 8, 64)), p, cfg, mesh)


def test_the_shares_parts_of_a_sparse_layer_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: four chips each hold 4 of the 16 experts
    (one group) under the 16-wide router; the four parts, the shared expert
    counted once, add up to the uncut layer's output, in the program and in
    the reference."""
    import dataclasses

    from orion_tpu.models.transformer import mlp_or_moe

    cfg, params = tiny
    # layer 5, the one sparse layer that is a run of one: leaves [1, ...]
    bp = jax.tree.map(lambda a: a[0], params["blocks"]["period"]["1"])
    h = jnp.asarray(np.random.default_rng(8).normal(size=(1, 24, 64)),
                    jnp.float32)
    whole, _ = mlp_or_moe(h, bp, cfg)
    shared_only = whole * 0
    parts = []
    for c in range(4):
        held = dataclasses.replace(cfg, n_experts=4, expert_offset=4 * c)
        moe = {k: (v[4 * c:4 * c + 4] if k in ("w_in", "w_gate", "w_out")
                   else v) for k, v in bp["moe"].items()}
        parts.append(mlp_or_moe(h, {**bp, "moe": moe}, held)[0])
    from orion_tpu.models.moe import _shared_expert

    shared_only = _shared_expert(h, bp["moe"]["shared"], cfg)
    total = sum(parts) - 3 * shared_only
    assert _rel(total, whole) < 1e-5
    # the reference's share, the same way
    ref = _reference()
    stack = {k: params["blocks"]["period"]["1"]["moe"][k]
             for k in ("w_in", "w_gate", "w_out")}
    p = {**{k: v for k, v in bp["moe"].items()
            if k not in ("w_in", "w_gate", "w_out")}}
    full, _ = ref._moe(h[0], {**p, "experts": (stack, (0,))}, HF, None)
    assert _rel(full, whole[0]) < 1e-5
    got = 0
    for c in range(4):
        hf = dict(HF, num_experts=4,
                  deployment={"experts_held": [4 * c, 4 * c + 4]})
        part = {k: v[:, 4 * c:4 * c + 4] for k, v in stack.items()}
        got = got + ref._moe(h[0], {**p, "experts": (part, (0,))}, hf, None)[0]
    assert _rel(got - 3 * ref._swiglu(h[0], p["shared"], None), full) < 1e-5


# -- what is refused --------------------------------------------------------------


@pytest.mark.parametrize("override, named", [
    ("inference.prefix_cache=true", "inference.prefix_cache"),
    ("inference.speculative=true", "inference.speculative"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("inference.kv_quant=int8", "inference.kv_quant"),
    ("inference.constrained=true", "inference.constrained"),
    ("model.weight_quant=int8", "model.weight_quant"),
    ("inference.host_tier_bytes=1048576", "inference.host_tier_bytes"),
    ("inference.long_context=true", "inference.long_context"),
])
def test_what_a_state_without_pages_is_not_served_with_is_refused_by_name(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=named) as e:
        InferenceEngine(get_config("tiny-ling", [override]), tiny[1])
    assert "no page in its KDA layers" in str(e.value)


def test_a_tp_mesh_is_refused_by_name(tiny):
    """Weights on a mesh with a live ``tp`` axis under compiled or
    interpreted kernels: the decode kernels are not run per shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from orion_tpu.infer import InferenceEngine

    if len(jax.devices()) < 2:
        pytest.skip("one device: no tp axis to refuse on")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    params = jax.device_put(tiny[1], NamedSharding(mesh, P()))
    with pytest.raises(ValueError, match="served on one device"):
        InferenceEngine(get_config(
            "tiny-ling", ["model.kernels=pallas_interpret"]), params)


def test_migration_is_refused_by_name(tiny):
    eng = _engine(tiny[1])
    req = eng.submit_request([1, 2, 3, 4, 5], 8)
    eng.step()
    with pytest.raises(ValueError, match="model.attention=kda"):
        eng.export_migration_state(req.rid)
    eng.close()


def test_a_cached_prefix_is_refused_by_the_prefill_program(tiny):
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    cfg, params = tiny
    icfg = get_config("tiny-ling").inference
    with pytest.raises(ValueError, match="whole prompts"):
        runner.prefill_step(
            params, init_cache(cfg, icfg), jnp.zeros((1, 16), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            cfg=cfg)


@pytest.mark.parametrize("converter", [
    "from_hf_llama", "from_hf_mixtral", "to_hf_llama"])
def test_no_converter_has_this_key_set_and_says_so(tiny, converter):
    from orion_tpu.models import convert

    arg = tiny[1] if converter.startswith("to_") else {}
    with pytest.raises(ValueError, match="bailing_hybrid"):
        getattr(convert, converter)(arg, tiny[0])


# -- the preset -------------------------------------------------------------------


def test_the_preset_is_the_published_configuration():
    m, pub = get_config("ling-3.0-flash").model, PUBLISHED
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.vocab_size, m.resolved_head_dim) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_hidden_layers"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["vocab_size"], pub["head_dim"])
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.latent_head_dim) == (
        pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["qk_head_dim"])
    assert pub["rotary_dim"] == m.qk_rope_head_dim == int(
        pub["partial_rotary_factor"] * pub["head_dim"])
    assert (m.n_experts, m.resolved_router_width, m.n_experts_per_token,
            m.moe_d_ff, m.shared_expert_d_ff, m.n_shared_experts,
            m.n_dense_layers, m.router_scale, m.n_group, m.topk_group) == (
        pub["num_experts"], pub["num_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["num_shared_experts"],
        pub["first_k_dense_replace"], pub["routed_scaling_factor"],
        pub["n_group"], pub["topk_group"])
    assert (m.layer_group_size, m.kda_conv_size, m.kda_lower_bound) == (
        pub["layer_group_size"], pub["short_conv_kernel_size"],
        pub["kda_lower_bound"])
    assert (m.rope_theta, m.norm_eps, m.tie_embeddings, m.attn_bias,
            m.mlp_bias, m.qk_norm, m.router_bias) == (
        pub["rope_theta"], pub["rms_norm_eps"], pub["tie_word_embeddings"],
        pub["use_qkv_bias"], pub["use_bias"], pub["use_qk_norm"],
        pub["moe_router_enable_expert_bias"])
    assert pub["score_function"] == pub["scoring_func"] == m.router_score
    assert pub["topk_method"] == "noaux_tc" and pub["norm_topk_prob"]
    assert pub["hidden_act"] == "silu" and m.activation == "swiglu"
    assert pub["gated_attention_proj_granularity_type"] == "head_wise"
    assert m.attn_gate == "per-head" and m.attention == "kda"
    assert pub["kda_safe_gate"] and pub["no_kda_lora"] and pub["linear_silu"]
    assert m.max_seq_len == pub["max_position_embeddings"]
    assert m.capacity_factor == pub["num_experts"] / pub["num_experts_per_tok"]
    # the per-layer lists: no clamp in any of the layers the cell runs
    assert pub["expert_swiglu_limit_list"][:8] == [0] * 8
    assert pub["share_expert_swiglu_limit_list"][:8] == [0] * 8
    assert len(pub["expert_swiglu_limit_list"]) == 42
    assert pub["num_nextn_predict_layers"] == 1 and not pub["mtp_use_kda"]
