"""Block-sparse layers that select their pages through compressed keys beside
lightning layers, prompts that enter in chunks which resume (MiniCPM-SALA):
``tiny-sala`` through the ENGINE (chunked prefill, then decode windows)
against the benchmark's float32 reference at every compared position, by the
benchmark's own comparison; chunks of two sizes and a whole prompt leave the
same pages, compressed keys, state rows and logits; the lightning chunked
form against the recurrence; the program's selection against the
reference's, id for id; a short context is plain causal attention; the decode
kernel and the prefill kernel (interpret mode) against the gather form, a
first chunk, resumed chunks and a tail bucket; under peaked weights, a
selection without the forced window, without the pooling, a head's in a
group's place, and a lightning layer without its decay each fail the logit
comparison; what the engine refuses; the preset against the published file.
CPU, float32.

Tolerance: 2e-5 relative L2 on logits: the program's chunked and paged forms
and the reference's scan and mask differ by rounding alone (measured 2e-7 to
4e-6)."""

import dataclasses
import importlib.util
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
                        "minicpm-sala-serve-1chip.json").read_text())
HF = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    intermediate_size=128, qk_norm=True, lightning_use_rope=True,
    attn_use_rope=False, rope_theta=10000, rms_norm_eps=1e-6,
    use_output_norm=True, use_output_gate=True, attn_use_output_gate=True,
    scale_emb=12, scale_depth=1.4, dim_model_base=16,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    published={"num_hidden_layers": 4},
    assumed={"sparse": dict(kernel=4, stride=2, block=8, init_blocks=1,
                            local_blocks=3, topk=6)})
TOL = 2e-5


def _reference():
    from benchmarks.reference import sala

    return sala


@pytest.fixture(scope="module")
def tiny():
    """(model config, weights drawn as the benchmark draws them)."""
    from benchmarks.reference import weights

    cfg = get_config("tiny-sala").model
    params = weights.make_params(
        _reference().param_spec(HF), cfg.n_layers, "float32", 5)
    return cfg, params


@pytest.fixture(scope="module")
def peaked(tiny):
    """The same weights with every sparse layer's q and k norm scales times
    3 (scores times 9: a softmax over a few keys, so WHICH blocks a query
    reads moves its logits)."""
    cfg, params = tiny
    blocks = dict(params["blocks"]["lead"])
    for e in ("0", "2"):
        attn = dict(blocks[e]["attn"])
        attn["q_norm"], attn["k_norm"] = (3.0 * attn["q_norm"],
                                          3.0 * attn["k_norm"])
        blocks[e] = {**blocks[e], "attn": attn}
    return cfg, {**params, "blocks": {**params["blocks"], "lead": blocks}}


def _engine(params, overrides=()):
    from orion_tpu.infer import InferenceEngine

    return InferenceEngine(get_config("tiny-sala", list(overrides)), params,
                           seed=0)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _probe(eng, mix, **kw):
    from benchmarks.kinds import serve_chunks

    return serve_chunks.probe_numbers(eng, _reference(), HF, mix, seed=3,
                                      **kw)


# -- the model ------------------------------------------------------------------


def test_a_layers_kind_is_the_published_lists_and_the_plan_takes_it_whole():
    m = get_config("minicpm-sala").model
    kinds = [k.attention for k in m.layer_kinds]
    assert kinds == ["sparse" if t == "minicpm4" else "lightning"
                     for t in PUBLISHED["mixer_types"]]
    assert (m.n_layers_of("sparse"), m.n_layers_of("lightning")) == (8, 24)
    assert m.n_paged_layers == 8 and m.resumes_prefill
    # an aperiodic list: nine runs, each a lead element, and no period
    assert m.layer_plan == (9, 1, 0, 0, (1, 8, 1, 6, 2, 4, 1, 6, 3))
    for kind in m.layer_kinds:
        if kind.attention == "sparse":      # no rotary embedding, 2 K/V heads
            assert kind.rope is None and kind.n_kv_heads is None
        else:                               # rotary q/k, a K/V head a head
            assert kind.rope.theta == PUBLISHED["rope_theta"]
            assert kind.n_kv_heads == PUBLISHED["lightning_nkv"] == 32
    # a layer's row among the layers of its kind
    plan, seen = m.layer_plan, {"sparse": 0, "lightning": 0}
    for e in range(plan.lead):
        j = plan.start(e)
        for l in range(j, j + plan.width(e)):
            att = m.layer_kinds[l].attention
            assert m.cache_layer(l, j) == seen[att]
            seen[att] += 1
    cut = get_config("tiny-sala").model
    assert cut.layer_plan == (3, 1, 0, 0, (1, 2, 1))


def test_the_parameter_tree_is_the_references(tiny):
    from orion_tpu.models.transformer import init_params

    cfg, params = tiny
    mine = jax.tree.map(lambda a: a.shape,
                        init_params(cfg, jax.random.PRNGKey(0)))
    assert mine == jax.tree.map(lambda a: a.shape, params)


def test_the_lightning_chunked_form_is_the_recurrence_and_resumes():
    from orion_tpu.ops.lightning import (
        lightning_chunked,
        lightning_recurrent,
        lightning_step,
    )

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 70, 4, 16))
               for i in range(3))
    lightning_recurrent = jax.jit(lightning_recurrent)
    lightning_chunked = jax.jit(lightning_chunked, static_argnames="chunk")
    o, M = lightning_recurrent(q, k, v)
    o2, M2 = lightning_chunked(q, k, v, chunk=16)
    assert _rel(o2, o) < 1e-5 and _rel(M2, M) < 1e-5
    # a chunk resumes from the state the last left; positions past a row's
    # length neither write nor decay
    _, Ma = lightning_chunked(q[:, :32], k[:, :32], v[:, :32], chunk=16)
    ob, Mb = lightning_chunked(q[:, 32:], k[:, 32:], v[:, 32:], Ma,
                               jnp.asarray([38, 20]), chunk=16)
    assert _rel(ob[0], o[0, 32:]) < 1e-5 and _rel(Mb[0], M[0]) < 1e-5
    assert _rel(ob[1, :20], o[1, 32:52]) < 1e-5
    _, M52 = lightning_recurrent(q[1:, :52], k[1:, :52], v[1:, :52])
    assert _rel(Mb[1], M52[0]) < 1e-5
    # one decode step is one position of the recurrence
    o1, M1 = lightning_step(M52, q[1:, 52], k[1:, 52], v[1:, 52])
    _, M53 = lightning_recurrent(q[1:, :53], k[1:, :53], v[1:, :53])
    assert _rel(M1, M53) < 1e-5 and _rel(o1, o[1:, 52]) < 1e-5


def test_the_selection_is_the_references_id_for_id_and_short_is_dense():
    """The program's ``select`` against the reference's on the same q and
    compressed keys, float32: the same block ids at every position, from
    where a query chooses (49 positions on) as before it, where every causal
    block is taken and the layer is plain causal attention."""
    from orion_tpu.ops import sparse

    ref, sp = _reference(), get_config("tiny-sala").model.sparse
    S, N, K, H = 104, 4, 2, 16
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, S, n, H))
            for i, n in ((0, N), (1, K)))
    pos = jnp.arange(S)[None]

    @jax.jit
    def both(q, k):             # (one program: the eager forms are slow)
        c = sparse.compress(jnp.zeros_like(k[:, :sp.block]), k, sp)
        ck = jnp.concatenate([c[:, 1:], jnp.zeros_like(c[:, :1])], 1)
        score = ref.block_scores(q[0], ck[0], pos[0], HF)     # [S, K, nb]
        return (ck, *sparse.select(q, ck, pos, sp),           # [1, K, S, T]
                sparse.block_scores(q, ck, pos, sp), score,
                ref.select(score, pos[0], HF)[0])

    ck, ids, n, mine_score, score, chosen = both(q, k)
    assert _rel(mine_score[0].transpose(1, 0, 2)[jnp.isfinite(score)],
                score[jnp.isfinite(score)]) < 1e-6
    nb = score.shape[-1]
    mine = (ids[0][..., None] == jnp.arange(nb)).any(-2)      # [K, S, nb]
    assert bool((mine.transpose(1, 0, 2) == chosen).all())
    blocks = np.asarray(pos[0]) // sp.block + 1
    assert (np.asarray(n[0, 0]) == np.minimum(blocks, sp.topk)).all()
    # the kernel's mean, written out
    assert _rel(ck[0, 5], k[0, 10:14].mean(0)) < 1e-6
    # 48 positions or fewer: every block, so the mask is the causal mask
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 48, K, H))
    from orion_tpu.ops.attention import attention_xla

    dense = attention_xla(q[:, :48], k[:, :48], v, causal=True)
    assert _rel(jax.jit(lambda q, k, v: sparse.whole_sequence(q, k, v, sp))(
        q[:, :48], k[:, :48], v), dense) < 1e-5


def _selected(sp, q, pos, key, NP, P=20):
    """(page table [B, P] drawn from a pool of NP pages, and what ``select``
    makes of q at ``pos`` over random compressed keys: ids, n, pages)."""
    from orion_tpu.ops import sparse

    B = q.shape[0]
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, NP))[:B * P].reshape(B, P))
    # compressed keys that are never complete score nothing: give some
    ck = jax.random.normal(key, (B, P * 4, q.shape[2] // 2, q.shape[3]))
    @jax.jit
    def lists(q, ck, pos):      # (one program: the eager forms are slow)
        ids, n = sparse.select(q, ck, pos, sp)
        used = jnp.arange(ids.shape[-1])[None, None, None] < n[..., None]
        return ids, n, jnp.where(used, jnp.take_along_axis(
            jnp.broadcast_to(table[:, None, None], (*ids.shape[:3], P)),
            jnp.minimum(ids, P - 1), -1), 0)

    return (table, *lists(q, ck, pos))


def test_the_decode_kernel_is_its_gather_form():
    """``attend_pallas`` (interpret mode: the paged decode kernel over
    virtual slots, the new token's write fused in) against ``attend_xla`` on
    the pool the write was made in beforehand: two slots at unlike
    positions, one of them on a page's first column."""
    from orion_tpu.ops import sparse

    sp = get_config("tiny-sala").model.sparse
    B, N, K, H, psz, NP = 2, 4, 2, 16, sp.block, 64
    key = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    pools = [jax.random.normal(next(key), (2 * NP * K, 1, psz, H))
             for _ in range(2)]
    q = jax.random.normal(next(key), (B, 1, N, H))
    k, v = (jax.random.normal(next(key), (B, 1, K, H)) for _ in range(2))
    pos = jnp.asarray([[150], [64]])
    table, ids, n, pages = _selected(sp, q, pos, next(key), NP)
    base = NP       # the second layer's rows
    out, kp, vp = sparse.attend_pallas(
        q, *pools, pages, n, pos, layer_base=base, k_new=k, v_new=v,
        interpret=True)
    rows = ((base + jnp.take_along_axis(table, pos // psz, 1))[..., None] * K
            + jnp.arange(K))
    at = (rows, 0, (pos % psz)[..., None])
    written = [pools[0].at[at].set(k), pools[1].at[at].set(v)]
    assert bool((kp == written[0]).all()) and bool((vp == written[1]).all())
    want = sparse.attend_xla(q, *written, base + pages, ids, n, pos)
    assert _rel(out, want) < 1e-5


@pytest.mark.parametrize("starts, lengths, steps, shared, private", [
    # a prompt's first chunk: every block's pages are shared, none private
    ([0], [32], None, [[1, 2, 3, 4]], [[0, 0, 0, 0]]),
    # resumed chunks across and past ``topk`` causal blocks: both walks,
    # lists of unlike length, two shared and two private steps a list
    ([32, 64], [32, 32], (2, 1), [[5, 6, 4, 4], [4, 4, 4, 4]],
     [[0, 0, 2, 2], [2, 2, 2, 2]]),
    # a tail bucket: 13 real positions, two blocks that hold none
    ([64], [13], None, [[4, 4, 0, 0]], [[2, 2, 0, 0]]),
], ids=["first", "resumed", "ragged"])
def test_the_prefill_kernel_is_its_gather_form(monkeypatch, starts, lengths,
                                               steps, shared, private):
    """``attend_blocks`` (interpret mode: a block's shared pages walked once
    for its 8 queries, its queries' private pages a query at a time, one
    softmax) against ``attend_xla`` at every real position of a chunk of 4
    blocks, and how ``split_blocks`` split each block's lists."""
    from orion_tpu.ops import sparse
    from orion_tpu.ops.pallas import sparse_prefill

    if steps:
        monkeypatch.setattr(sparse_prefill, "SHARED_PAGES", steps[0])
        monkeypatch.setattr(sparse_prefill, "PRIVATE_PAGES", steps[1])
    sp = get_config("tiny-sala").model.sparse
    B, Q, N, K, H, psz, NP = len(starts), 32, 4, 2, 16, sp.block, 64
    key = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    pools = [jax.random.normal(next(key), (2 * NP * K, 1, psz, H))
             for _ in range(2)]
    q = jax.random.normal(next(key), (B, Q, N, H))
    pos = jnp.asarray(starts)[:, None] + jnp.arange(Q)[None]
    _, ids, n, pages = _selected(sp, q, pos, next(key), NP)
    real = np.arange(Q)[None] < np.asarray(lengths)[:, None]
    live = jnp.asarray(real[:, ::psz])
    _, n_shared, _, n_private = sparse.split_blocks(pages, pos, sp, live)
    for got, want in ((n_shared, shared), (n_private, private)):
        assert (np.asarray(got) == np.asarray(want)[:, None]).all()
    base = NP       # the second layer's rows
    out = sparse.attend_blocks(q, *pools, pages, pos, sp, layer_base=base,
                               live=live, interpret=True)
    want = jax.jit(sparse.attend_xla)(q, *pools, base + pages, ids, n, pos)
    assert _rel(np.asarray(out)[real], np.asarray(want)[real]) < 1e-5
    assert not np.asarray(out)[~np.repeat(real[:, ::psz], psz, 1)].any()


# -- the engine, through the benchmark's own comparison ---------------------------


def test_the_engine_is_the_reference_at_every_position(tiny):
    """A probe of 70 tokens through the engine under the KERNELS (interpret
    mode; the XLA forms' engine is held to the reference, 150 tokens, by
    ``test_a_fault_in_the_selection_or_the_decay_fails_the_logits[none]``):
    prefill in chunks of 32 positions (three dispatches, each resuming from
    pages, compressed keys and state rows) and a decode window of 4: every
    compared position against the reference, the program's selection at each
    of them no worse than the reference's by the reference's scores, the
    window link (K, V, compressed keys and state rows against the one-step
    body's) bitwise. Then the link's control on the same engine: the
    one-step body fed another token."""
    eng = _engine(tiny[1], ["model.kernels=pallas_interpret"])
    numbers = _probe(eng, {"probe_prompts": [70], "probe_windows": 1})
    assert len(numbers["err"]) == 5
    assert max(numbers["err"]) < TOL, max(numbers["err"])
    assert max(numbers["regret"]) == 0.0
    assert max(numbers["window_kv_rel_err"]) == 0.0
    assert max(numbers["window_token_gap"]) == 0.0
    assert eng.reset_timing()["prefill_dispatches"] == 3
    broken = _probe(eng, {"probe_prompts": [40], "probe_windows": 1},
                    break_link=True)
    assert max(broken["window_kv_rel_err"]) > 0.1
    eng.close()


def test_chunks_of_two_sizes_and_a_whole_prompt_leave_the_same_cache(tiny):
    """One prompt of 120 tokens through ``prefill_step`` whole, in chunks of
    32 and in chunks of 48: the same logits and, at the prompt's positions,
    the same pages, compressed keys and state row."""
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache

    cfg, params = tiny
    icfg = get_config("tiny-sala").inference
    n, psz = 120, icfg.page_size
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, n), 1, 256)
    row = jnp.zeros((1, 32), jnp.int32).at[0, :16].set(jnp.arange(1, 17))
    step = jax.jit(lambda c, t, ln, pg, s0: runner.prefill_step(
        params, c, t, ln, pg, s0, row, jnp.asarray([1]), cfg=cfg))
    got = []
    for chunk in (128, 32, 48):
        cache, s0 = init_cache(cfg, icfg), 0
        while s0 < n:
            m = min(chunk, n - s0)
            pad = -(-m // 16) * 16
            t = jnp.zeros((1, pad), jnp.int32).at[:, :m].set(
                tokens[:, s0:s0 + m])
            logits, cache = step(
                cache, t, jnp.asarray([m]),
                row[:, s0 // psz:(s0 + pad) // psz], jnp.asarray([s0]))
            s0 += m
        live = np.asarray(row[0, :n // psz])
        heads = (live[:, None] * 2 + np.arange(2)).ravel()
        got.append((logits, cache["lightning_state"][:, 1], *(
            cache[name][np.concatenate(
                [base * 2 + heads if name != "ck" else base + live
                 for base in (0, icfg.num_pages * (2 if name != "ck" else 1))
                 ])] for name in ("k", "v", "ck"))))
    whole, *chunked = got
    for other in chunked:
        for a, b in zip(whole, other):
            assert _rel(b, a) < 2e-6


def test_slots_of_unlike_length_and_a_slot_reused_decode_as_each_alone(tiny):
    """Greedy tokens of four requests over TWO slots, prompts of one to
    seven chunks, the later ones taking the slots the earlier ones released
    (a prompt's first chunk starts its state rows from zeros: what the last
    tenant left is not read), are those of each alone (one at a time, in the
    same engine beforehand: its slots' rows are then dirty)."""
    _, params = tiny
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, 256, n)))
               for n in (120, 37, 200, 64)]
    news = [6, 20, 9, 12]
    eng = _engine(params, ["inference.max_batch_size=2"])
    alone = [list(eng.generate([p], max_new_tokens=n)[0])
             for p, n in zip(prompts, news)]
    reqs = [eng.submit_request(p, n) for p, n in zip(prompts, news)]
    while eng.has_work():
        eng.step()
    assert [list(r.generated) for r in reqs] == alone
    eng.assert_page_accounting()
    eng.close()


def test_the_counters_are_host_arithmetic_on_lengths(tiny):
    eng = _engine(tiny[1])
    n, new = 100, 9                 # 13 blocks: past the 6 a query attends
    eng.generate([list(range(1, n + 1))], max_new_tokens=new)
    t = eng.reset_timing()
    sp, L = eng.mcfg.sparse, 2
    seen = lambda p: min(p + 1, (sp.topk - 1) * sp.block + p % sp.block + 1)
    assert t["prefill_sparse_visible_pairs"] == L * 4 * sum(
        seen(p) for p in range(n))
    # pages a (position, layer, K/V head) lists; every one is shared by
    # its block while nothing is chosen, then the forced four of six
    blocks = [p // sp.block + 1 for p in range(n)]
    assert t["prefill_sparse_selected_pages"] == L * 2 * sum(
        min(b, sp.topk) for b in blocks)
    assert t["prefill_sparse_shared_pages"] == L * 2 * sum(
        b if b <= sp.topk else sp.init_blocks + sp.local_blocks
        for b in blocks)
    assert t["prefill_lightning_token_layers"] == 2 * n
    steps = range(n, n + new - 1)           # the first token is prefill's
    windows = -(-len(steps) // 4)
    assert t["decode_lightning_slot_layers"] == 2 * 4 * windows
    pos = range(n, n + 4 * windows)
    assert t["decode_sparse_visible_keys"] == L * 2 * sum(map(seen, pos))
    assert t["decode_sparse_context_keys"] == L * 2 * sum(
        p + 1 for p in pos)
    assert t["prefill_dispatches"] == 4 and t["prefill_tokens"] == n
    eng.close()
    # a model with no sparse layer lists nothing
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models.transformer import init_params

    cfg = get_config("tiny-llama")
    plain = InferenceEngine(
        cfg, init_params(cfg.model, jax.random.PRNGKey(0)), seed=0)
    plain.generate([[7, 8, 9]], max_new_tokens=2)
    t = plain.reset_timing()
    assert t["prefill_tokens"] == 3 and not (
        t["prefill_sparse_selected_pages"] or t["prefill_sparse_shared_pages"])
    plain.close()


# -- faults the logit comparison has to see -------------------------------------


def _tool():
    spec = importlib.util.spec_from_file_location(
        "sala_fault_probe", REPO / "tools/sala_fault_probe.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.fixture(scope="module")
def ran(peaked):
    """The sound program's logits on one sequence of 150 tokens under peaked
    weights (the engine's chunked prefill and 8 decode steps), and the
    sequence."""
    from benchmarks.kinds import serve_chunks

    eng = _engine(peaked[1])
    with serve_chunks.ChunkTap(eng) as tap:
        rng = np.random.default_rng(9)
        prompt = list(map(int, rng.integers(1, 256, 150)))
        req = eng.submit_request(prompt, 9)
        while eng.has_work():
            eng.step()
    got = np.concatenate(
        [tap.prefill[0][:1]] + [logits for logits, _, _ in tap.decode])
    eng.close()
    return jnp.asarray(prompt + list(req.generated[:8])), got


@pytest.mark.parametrize("fault", [
    "none", "no_window", "unpooled", "per_head", "no_decay"])
def test_a_fault_in_the_selection_or_the_decay_fails_the_logits(
        peaked, ran, fault):
    """The reference with ONE thing otherwise (no forced window, a block's
    first kernel alone, a group's first head alone, no decay) against the
    sound program under peaked weights: each is 100 times the tolerance
    away and more; the reference as it stands is inside it."""
    tokens, got = ran
    at = jnp.arange(149, 158)
    want, _ = jax.jit(lambda p, t, a: _reference().logits_at(
        p, t, a, HF, None, () if fault == "none" else (fault,)))(
            peaked[1], tokens, at)
    errs = [_rel(g, w) for g, w in zip(got, want)]
    if fault == "none":
        assert max(errs) < TOL
    else:
        assert float(np.median(errs)) > 100 * TOL, errs


def test_a_lost_state_planted_in_the_program_is_seen(tiny):
    """``tools/sala_fault_probe.py``'s ``no_carry`` on the tiny model,
    through the benchmark's own comparison: a lightning state lost at every
    chunk boundary moves the logits (only the program can have this fault:
    the reference has no chunk)."""
    from benchmarks.kinds import serve_chunks

    with _tool().planted("no_carry"):
        eng = _engine(tiny[1])
        numbers = _probe(eng, {"probe_prompts": [100], "probe_windows": 1})
    judged = serve_chunks.judged(numbers, 0.0)
    assert judged["logit_rel_err_worst_probe_median_clear"] > 100 * TOL
    assert judged["window_kv_rel_err_max"] < 1e-6
    eng.close()


def test_the_regret_scores_a_selection_by_the_references_scores():
    """``reference/sala.regret`` on hand-made scores (one layer, one query at
    position 95 of 12 blocks of 8, one K/V head): the reference takes the
    forced blocks 0, 9, 10, 11 and the two best others (5 and 2); the same
    set reads 0; a worse free choice reads the gap over the cutoff; an equal
    score reads 0 (ties are free); a list without a forced block, with a
    block twice or with a block of the future reads inf. And the window
    planted out of the PROGRAM's selection (``no_window``) drops forced
    blocks, which is how the chip's check sees it."""
    ref = _reference()
    score = jnp.asarray([[[[0.1, 0.2, 0.6, 0.3, 0.3, 0.9, 0.05, 0.6, 0.1,
                            0.5, 0.5, 0.5]]]])
    at = jnp.asarray([95])
    read = lambda ids: float(ref.regret(
        score, jnp.asarray([[[ids]]], jnp.int32), at, HF)[0, 0, 0])
    assert read([0, 2, 5, 9, 10, 11]) == 0.0
    assert read([0, 5, 7, 9, 10, 11]) == 0.0          # 7 ties with 2
    assert read([0, 3, 5, 9, 10, 11]) == pytest.approx((0.6 - 0.3) / 0.6)
    assert read([0, 2, 5, 10, 11, 12]) == float("inf")    # 9 is forced
    assert read([0, 2, 2, 9, 10, 11]) == float("inf")
    from orion_tpu.ops import sparse

    with _tool().planted("no_window"):
        sp = get_config("tiny-sala").model.sparse
        forced = sparse.forced_blocks(at, 12, sp)[0]
    assert [int(b) for b in np.flatnonzero(np.asarray(forced))] == [0, 11]


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
def test_what_a_selection_and_a_state_row_are_not_served_with_is_refused(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=named) as e:
        InferenceEngine(get_config("tiny-sala", [override]), tiny[1])
    assert "selects the pages its sparse layers read" in str(e.value)


def test_migration_and_converters_are_refused_by_name(tiny):
    from orion_tpu.models import convert

    eng = _engine(tiny[1])
    req = eng.submit_request([1, 2, 3, 4, 5], 8)
    eng.step()
    with pytest.raises(ValueError, match="model.mixer_types"):
        eng.export_migration_state(req.rid)
    eng.close()
    with pytest.raises(ValueError, match="minicpm_sala"):
        convert.from_hf_llama({}, tiny[0])


def test_a_page_is_not_the_selected_block_is_refused():
    from orion_tpu.infer.kv_cache import init_cache

    cfg = get_config("tiny-sala", ["inference.page_size=16"])
    with pytest.raises(ValueError, match="a selected block is a page"):
        init_cache(cfg.model, cfg.inference)


# -- the preset -------------------------------------------------------------------


def test_the_preset_is_the_published_configuration():
    m = get_config("minicpm-sala").model
    p = PUBLISHED
    assert (m.d_model, m.n_layers, m.n_heads, m.n_kv_heads, m.head_dim,
            m.d_ff, m.vocab_size, m.max_seq_len) == (
        p["hidden_size"], p["num_hidden_layers"], p["num_attention_heads"],
        p["num_key_value_heads"], p["head_dim"], p["intermediate_size"],
        p["vocab_size"], p["max_position_embeddings"])
    assert list(m.mixer_types) == p["mixer_types"]
    assert (m.embed_scale, m.qk_norm, m.norm_eps, m.rope_theta,
            m.tie_embeddings, m.attn_bias) == (
        p["scale_emb"], p["qk_norm"], p["rms_norm_eps"], p["rope_theta"],
        p["tie_word_embeddings"], p["attention_bias"])
    assert m.residual_scale == pytest.approx(
        p["scale_depth"] / p["num_hidden_layers"] ** 0.5)
    assert m.logit_scale == p["dim_model_base"] / p["hidden_size"]
    assert m.attn_gate == "elementwise" and m.activation == "swiglu"
    assert dataclasses.astuple(m.sparse) == (32, 16, 64, 1, 32, 64)
    assert dataclasses.replace(m, n_layers=8).n_layers_of("sparse") == 1
