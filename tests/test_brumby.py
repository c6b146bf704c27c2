"""Power retention (Brumby-14B-Base): the operator in its three forms against
a quadratic float32 form written here, the kernels under the interpreter, the
engine across folds against the benchmark's quadratic reference, and what the
engine refuses. CPU, ``tiny-brumby``: head 16 (a state of 9 slabs of 16), 3
layers, a fold chunk of 16 (an eighth of its longest sequence) over pages of
4. A model drawn as the benchmark draws it forgets within a few positions
(its gates are about a half), so ``_long_memory`` makes one whose gates are
near 1, and three tests PLANT a fault in the state (a fold that writes
nothing, a state read as zeros) and see the comparison fail."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import get_config
from orion_tpu.ops import retention as ret

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

PUBLISHED = json.loads((REPO / "tests/benchmark/data/published/"
                        "brumby-14b-serve-1chip.json").read_text())
HF = dict(hidden_size=64, vocab_size=256, num_hidden_layers=3,
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          intermediate_size=128, rms_norm_eps=1e-6, rope_theta=1e6,
          tie_word_embeddings=False)


def _reference():
    from benchmarks.reference import brumby

    return brumby


@pytest.fixture(scope="module")
def tiny():
    """(model config, weights drawn as the benchmark draws them)."""
    from benchmarks.reference import weights

    cfg = get_config("tiny-brumby").model
    params = weights.make_params(
        _reference().param_spec(HF), cfg.n_layers, "float32", 5)
    return cfg, params


def _engine(params, overrides=(), inj=None):
    from orion_tpu.infer import InferenceEngine

    cfg = get_config("tiny-brumby", ["inference.decode_window=4",
                                     *overrides])
    return InferenceEngine(cfg, params, seed=0, fault_injector=inj)


# -- the operator ---------------------------------------------------------------


def _inputs(B, S, N, K, H, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, N, H))
    k = jax.random.normal(ks[1], (B, S, K, H))
    v = jax.random.normal(ks[2], (B, S, K, H))
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, K)) + 2.0)
    return q, k, v, g


def _quadratic(q, k, v, g):
    """Every pair's weight written out: A_ij = exp(sum_{j<l<=i} g_l)
    (q_i . k_j / sqrt(H))^2, y_i = sum_j A_ij v_j / sum_j A_ij."""
    B, S, N, H = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, N // K, H)
    s = jnp.einsum("bikgh,bjkh->bkgij", qg, k) / H ** 0.5
    since = jnp.swapaxes(jnp.cumsum(g, axis=1), 1, 2)          # [B, K, S]
    seen = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(
        seen, since[:, :, :, None] - since[:, :, None, :], -jnp.inf))
    a = decay[:, :, None] * s * s
    y = jnp.einsum("bkgij,bjkh->bikgh", a, v) / jnp.moveaxis(
        a.sum(-1), 3, 1)[..., None]
    return y.reshape(B, S, N, H)


def _recurrence(q, k, v, g, state=False):
    """One token at a time: S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T
    (``state``: the last (S, z) instead of the outputs)."""
    B, S, N, H = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, N // K, H)

    def step(carry, x):
        S_, z_ = carry
        qt, kt, vt, gt = x
        e = jnp.exp(gt)
        pk = ret.phi(kt)                                       # [B, K, R, H]
        S_ = e[..., None, None, None] * S_ + jnp.einsum(
            "bkra,bkh->bkrah", pk, vt)
        z_ = e[..., None, None] * z_ + pk
        pq = ret.phi(qt)                                       # [B,K,G,R,H]
        num = jnp.einsum("bkgra,bkrah->bkgh", pq, S_)
        den = jnp.einsum("bkgra,bkra->bkg", pq, z_)
        return (S_, z_), num / den[..., None]

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (qg, k, v, g))
    last, ys = jax.lax.scan(step, ret.empty_state(B, K, H), xs)
    return last if state else jnp.moveaxis(ys, 0, 1).reshape(B, S, N, H)


def test_phi_is_the_symmetric_square():
    a, b = jax.random.normal(jax.random.key(3), (2, 7, 16))
    got = (ret.phi(a) * ret.phi(b)).sum((-1, -2))
    np.testing.assert_allclose(got, (a * b).sum(-1) ** 2, rtol=1e-4, atol=1e-5)
    assert ret.n_slabs(128) * 128 == 8320            # 64 more than 8256


@pytest.mark.parametrize("N, K", [(2, 2), (10, 2)])       # groups of 1 and 5
@pytest.mark.parametrize("S", [32, 27, 9])    # chunks: whole, ragged, under one
@pytest.mark.parametrize("form", ["xla", "recurrence", "pallas_interpret"])
def test_every_form_is_the_quadratic_form(form, S, N, K):
    q, k, v, g = _inputs(2, S, N, K, 16, seed=S + N)
    want = _quadratic(q, k, v, g)
    if form == "recurrence":
        got = _recurrence(q, k, v, g)
    else:
        got, _ = ret.power_retention(q, k, v, g, chunk=8, impl=form)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_state_handed_out_is_that_of_a_rows_complete_chunks(impl):
    """Rows of unlike lengths: padding adds nothing to a state, an
    incomplete chunk stays out of it (it is the caller's tail), and what is
    in it is the recurrence's state after as many positions."""
    q, k, v, g = _inputs(2, 24, 4, 2, 16, seed=11)
    want = _quadratic(q, k, v, g)
    lens = jnp.asarray([19, 24], jnp.int32)
    y, (S, z) = ret.power_retention(q, k, v, g, lengths=lens, chunk=8,
                                    impl=impl)
    np.testing.assert_allclose(y[0, :19], want[0, :19], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(y[1], want[1], rtol=2e-4, atol=2e-5)
    assert not np.asarray(y[0, 19:]).any()           # padding rows are zeros
    for row, n in ((0, 16), (1, 24)):
        Sw, zw = _recurrence(q[row:row + 1, :n], k[row:row + 1, :n],
                             v[row:row + 1, :n], g[row:row + 1, :n],
                             state=True)
        np.testing.assert_allclose(S[row], Sw[0], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(z[row], zw[0], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("N", [4, 10])                    # groups of 2 and 5
def test_the_decode_and_fold_kernels_against_their_xla_forms(N):
    """The serving pair under the interpreter: one new token a slot over a
    state row and a paged tail (tail only; state and a tail that crosses a
    chunk's end; state and an empty tail), the page it wrote bitwise, then
    a chunk folded into a row."""
    from orion_tpu.ops.pallas import retention as pret

    K, H, C, psz, B, L, NP = 2, 16, 16, 4, 3, 2, 32
    R, P = ret.n_slabs(H), 12
    ks = iter(jax.random.split(jax.random.key(N), 16))
    nrm = lambda *sh: jax.random.normal(next(ks), sh)          # noqa: E731
    F = jnp.asarray([0, 16, 32], jnp.int32)
    pos = jnp.asarray([9, 33, 32], jnp.int32)
    table = np.zeros((B, P), np.int32)
    nxt = 1
    for s in range(B):
        for pg in range(int(F[s]) // psz, int(pos[s]) // psz + 1):
            table[s, pg], nxt = nxt, nxt + 1
    table = jnp.asarray(table)
    kp, vp = nrm(L * NP, K, psz, H), nrm(L * NP, K, psz, H)
    state = 0.1 * nrm(L * (B + 1), K, R, H, H)
    state_z = jnp.abs(nrm(L * (B + 1), R, K, H))
    T = ret.tail_pages(C, psz) * psz
    jpos = F[:, None] + jnp.arange(T)
    c_tail = jnp.where((jpos <= pos[:, None])[:, None],
                       -0.07 * (jnp.arange(T) + 1.0) * jnp.ones((B, K, 1)),
                       ret.BIG)
    c_q = -0.07 * (pos - F + 1.0)[:, None] * jnp.ones((1, K))
    args = (nrm(B, N, H), nrm(B, K, H), nrm(B, K, H), c_q, c_tail,
            kp, vp, state, state_z, table, F, pos)
    kw = dict(layer_base=NP, state_base=B + 1)
    want = ret.retention_decode_xla(*args, **kw)
    got = pret.retention_decode(*args, interpret=True, **kw)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        assert (np.asarray(a) == np.asarray(b)).all()
    kc, vc = nrm(K, C, H), nrm(K, C, H)
    b = jnp.cumsum(jax.nn.log_sigmoid(nrm(K, C) + 2.0), axis=1)
    row = jnp.int32(B + 3)
    Sw, zw = ret.retention_fold_xla(state, state_z, kc, vc, b, row)
    Sg, zg = pret.retention_fold(state, state_z, kc, vc, b, row,
                                 interpret=True)
    np.testing.assert_allclose(Sg, Sw, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(zg, zw, rtol=2e-4, atol=2e-5)
    rest = np.arange(state.shape[0]) != int(row)
    assert (np.asarray(Sg)[rest] == np.asarray(state)[rest]).all()


def test_a_prompts_least_cost_by_hand():
    """``query_units`` (the engine's ``prefill_retention_units`` a layer) at
    the published head of 128, D = 8256: position t counts min(2 (t + 1),
    D), the cheaper of attending its t + 1 predecessors and of reading a
    state."""
    # 700 positions: under one chunk AND under D / 2, all quadratic
    assert ret.query_units(700, 128) == sum(
        2 * (t + 1) for t in range(700)) == 700 * 701
    assert ret.query_units(4128, 128) == 4128 * 4129
    # past D / 2 = 4128 positions a query is cheaper from a state
    assert ret.query_units(8192, 128) == 4128 * 4129 + (8192 - 4128) * 8256
    assert ret.query_units(9, 16) == 9 * 10              # D / 2 = 68


def test_the_chunk_is_the_programs_and_no_option():
    """One number (``ops/retention.CHUNK``), an eighth of the longest
    sequence where that is shorter: the published model folds every 512
    positions, the tests' every 16."""
    assert ret.fold_chunk(get_config("brumby-14b").model.max_seq_len) == 512
    assert ret.fold_chunk(get_config("tiny-brumby").model.max_seq_len) == 16
    with pytest.raises((ValueError, KeyError, AttributeError, TypeError)):
        get_config("tiny-brumby", ["model.retention_chunk=8"])


# -- the model and the engine -----------------------------------------------------


def test_forward_and_three_gradients_against_the_reference(tiny):
    from orion_tpu.models.transformer import forward, loss_fn

    cfg, params = tiny
    ref = _reference()
    tokens = jax.random.randint(jax.random.key(1), (2, 41), 1, 256)
    logits = forward(params, tokens[:, :-1], cfg)
    logits = logits[0] if isinstance(logits, tuple) else logits
    for b in range(2):
        want, margin = ref.logits_at(params, tokens[b, :-1],
                                     jnp.arange(40), HF)
        np.testing.assert_allclose(logits[b], want, rtol=2e-3, atol=2e-4)
        assert np.isinf(np.asarray(margin)).all()

    def ref_loss(p):
        def one(row):
            lg, _ = ref.logits_at(p, row[:-1], jnp.arange(40), HF)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return (lse - jnp.take_along_axis(
                lg, row[1:, None], axis=-1)[:, 0]).sum()
        return (one(tokens[0]) + one(tokens[1])) / 80

    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    got = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    want = jax.grad(ref_loss)(params)
    for path in (("attn", "wr"), ("attn", "wk"), ("mlp", "w_gate")):
        a, b = got["blocks"], want["blocks"]
        for name in path:
            a, b = a[name], b[name]
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 2e-3, (path, err)


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_the_engine_across_folds_against_the_reference(tiny, kernels):
    """The benchmark's own output check (``kinds/serve.probe_numbers``) on
    the engine: each probe through prefill and two decode windows with every
    window step run again by ``_decode_core``; 15 is under a chunk (tail
    only), 28 completes its second chunk inside the first window and folds
    at the start of the second, 32 is two whole chunks (an empty tail), 41
    holds state and tail."""
    from benchmarks.kinds import serve

    _, params = tiny
    eng = _engine(params, [f"model.kernels={kernels}"])
    mix = {"probe_prompts": [15, 28, 32, 41], "probe_windows": 2}
    numbers = serve.probe_numbers(eng, _reference(), HF, mix, seed=3)
    assert len(numbers["err"]) == 4 * 9
    assert max(numbers["err"]) < 5e-4
    assert max(numbers["window_kv_rel_err"]) == 0.0
    assert max(numbers["window_token_gap"]) == 0.0
    t = eng.reset_timing()
    assert t["folds"] == 1 + 0 + 0 + 1      # 28 -> 32, and 41 -> 48
    eng.close()


def _long_memory(params):
    """The same tree with gates near 1, which no random draw gives (the gate
    has no bias): coordinate 0 of every embedding is a constant 8 (so the
    normed input reads about 8 there), the gate's matrix reads it with 0.6
    (a gate of sigmoid(4.8) = 0.992: a chunk of 16 keeps 88 %), and the
    final norm drops it so that the logits are the rest's."""
    p = jax.tree.map(lambda a: a, params)
    p["embed"] = {"tokens": params["embed"]["tokens"].at[:, 0].set(8.0)}
    blocks = dict(p["blocks"])
    blocks["attn"] = dict(blocks["attn"])
    blocks["attn"]["wr"] = blocks["attn"]["wr"].at[:, 0, :].set(0.6)
    blocks["attn_norm"] = {"scale": blocks["attn_norm"]["scale"].at[
        :, 0].set(1.0)}
    p["blocks"] = blocks
    p["final_norm"] = {"scale": params["final_norm"]["scale"].at[0].set(0.0)}
    return p


def _without_state(eng, fold_too=False):
    """PLANT a fault, as ``tools/state_fault_probe.py`` plants it on the
    chip: every decode dispatch reads state rows of zeros (a decode kernel
    that ignores the state) or, ``fold_too``, every fold leaves the state
    as it was (a fold that writes nothing)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "state_fault_probe", REPO / "tools/state_fault_probe.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tool.plant(eng, "fold" if fold_too else "zeros")


@pytest.mark.parametrize("fault", [None, "state read as zeros",
                                   "a fold that writes nothing"])
def test_a_long_memory_is_held_and_a_lost_state_is_seen(tiny, fault):
    """Gates near 1, through the benchmark's own comparison: the engine
    agrees with the quadratic reference at every position; with the state
    lost the worst probe's MEDIAN, the number ``decide`` judges, is 7 times
    the tolerance and more (with the benchmark's own weights the lost fold
    does not move it: the next test, and PERF.md section 7). A fold at the start of a probe's second window
    spoils the later half of its positions at most, which no median sees
    however long the memory: that fault takes three windows a probe."""
    from benchmarks.kinds import serve

    eng = _engine(_long_memory(tiny[1]))
    lost_fold = fault is not None and fault.startswith("a fold")
    if fault:
        _without_state(eng, fold_too=lost_fold)
    mix = {"probe_prompts": [15, 28, 32, 41],
           "probe_windows": 3 if lost_fold else 2}
    numbers = serve.probe_numbers(eng, _reference(), HF, mix, seed=3)
    judged = serve.judged(numbers, 0.0)[
        "logit_rel_err_worst_probe_median_clear"]
    if fault is None:
        assert max(numbers["err"]) < 5e-4
    else:
        assert judged > 7 * 5e-4, judged
    eng.close()


def test_a_fold_that_writes_nothing_is_seen_with_the_benchmarks_weights(tiny):
    """Weights as the benchmark draws them (gates of about a half): only the
    few positions right after a fold read the state above the tolerance, so
    the worst POSITION sees the fault and a probe's median does not."""
    from benchmarks.kinds import serve

    eng = _engine(tiny[1])
    _without_state(eng, fold_too=True)
    mix = {"probe_prompts": [28], "probe_windows": 2}
    numbers = serve.probe_numbers(eng, _reference(), HF, mix, seed=3)
    assert max(numbers["err"]) > 10 * 5e-4
    assert float(np.median(numbers["err"])) < 5e-4
    eng.close()


def test_a_quarantined_request_leaves_no_nan_behind(tiny):
    """NaN quarantine on a model with per-slot leaves: the victim errors,
    its pages of ``k`` and ``v`` are scrubbed, the slot leaves (``g`` among
    them: no page, the slot's next prefill writes its row whole) are left
    alone, and the neighbour's tokens are those of a fault-free run. The
    victim's NaN did reach its row of ``g`` (or this would hold nothing);
    the slot's next tenant reads none of it and leaves the leaf finite."""
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    prompts = [list(range(1, 22)), list(range(30, 40))]
    later = list(range(50, 57))         # 7 positions: a tail and no state
    guard = ["inference.nan_guard=true"]
    eng = _engine(tiny[1], guard)
    want = eng.generate(prompts, max_new_tokens=16)
    want_later = eng.generate([later], max_new_tokens=30)[0]
    eng.close()
    eng = _engine(tiny[1], guard, FaultInjector([FaultSpec("nan", step=1)]))
    reqs = [eng.submit_request(p, 16) for p in prompts]
    victim = None
    while eng.has_work():
        eng.step()
        if reqs[0].done and victim is None:
            victim = np.asarray(eng.cache["g"])
            # the neighbour still decodes beside the row the victim left
            assert not reqs[1].done
    assert [r.outcome for r in reqs] == ["error:nan", "completed"]
    assert list(reqs[1].generated) == list(want[1])
    assert eng.reset_timing()["quarantined_requests"] == 1
    for name in ("k", "v"):
        assert np.isfinite(np.asarray(eng.cache[name])).all(), name
    assert not np.isfinite(victim[:, 1]).all()       # slot 0 owns row 1
    assert np.isfinite(np.delete(victim, 1, axis=1)).all()
    req = eng.submit_request(later, 30)              # folds at 16 and at 32
    eng.step()
    assert req.slot == 0
    while eng.has_work():
        eng.step()
    assert list(req.generated) == list(want_later)
    assert eng.reset_timing()["folds"] == 2
    assert np.isfinite(np.asarray(eng.cache["g"])).all()
    eng.assert_page_accounting()
    eng.close()


def test_the_one_token_body_runs_again_and_never_writes_the_state(tiny):
    from orion_tpu.infer import runner

    cfg, params = tiny
    eng = _engine(params)
    req = eng.submit_request(list(range(1, 38)), 20)
    while not req.generated:
        eng.step()
    cache = {k: jnp.array(v) for k, v in eng.cache.items()}
    tok = jnp.asarray(eng.last_token)
    pos, table = jnp.asarray(eng.seq_lens), jnp.asarray(eng.page_table)
    assert int(cache["state_len"][1 + req.slot]) == 32
    one, c1 = runner._decode_core(params, cache, tok, pos, table, cfg, None)
    two, c2 = runner._decode_core(params, c1, tok, pos, table, cfg, None)
    assert (np.asarray(one) == np.asarray(two)).all()
    for name in ("state", "state_z", "state_len"):
        assert (np.asarray(c2[name]) == np.asarray(cache[name])).all()
    for name in ("k", "v", "g"):
        assert (np.asarray(c2[name]) == np.asarray(c1[name])).all()
    eng.close()


def _reference_log_gates(params, tokens):
    """[layers, K, S] float32: every layer's log-gates of one sequence, by
    the reference's own layer (``benchmarks/reference/brumby.hidden_states``
    with the gates handed out beside the residual stream)."""
    ref = _reference()
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731
    N, K, H = (HF["num_attention_heads"], HF["num_key_value_heads"],
               HF["head_dim"])
    eps, theta = HF["rms_norm_eps"], HF["rope_theta"]
    tokens = jnp.asarray(tokens)
    S, positions = tokens.shape[0], jnp.arange(tokens.shape[0])

    def layer(x, bp):
        a = bp["attn"]
        h = ref._rmsnorm(x, f32(bp["attn_norm"]["scale"]), eps)
        q, k, v = (ref._matmul(h, f32(a[w]), None).reshape(S, n, H)
                   for w, n in (("wq", N), ("wk", K), ("wv", K)))
        q = ref._rope(ref._head_norm(q, f32(a["q_norm"]), eps), positions,
                      theta)
        k = ref._rope(ref._head_norm(k, f32(a["k_norm"]), eps), positions,
                      theta)
        log_g = ref._log_gate(h, f32(a["wr"]), None)
        y = ref._retention(q, k, v, log_g).reshape(S, N * H)
        x = x + ref._matmul(y, f32(a["wo"]), None)
        h = ref._rmsnorm(x, f32(bp["mlp_norm"]["scale"]), eps)
        return x + ref._mlp(h, bp["mlp"], None), log_g.T

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(
            layer, f32(params["embed"]["tokens"][tokens]),
            params["blocks"])[1]


def _gate_rows(eng, reqs):
    """Run the engine dry; after every step, of every request that holds a
    slot: (positions in the cache, ``state_len``, its row of ``g``, the
    step's number)."""
    seen, step = {r.rid: [] for r in reqs}, 0
    while eng.has_work():
        eng.step()
        step += 1
        g, folded = np.asarray(eng.cache["g"]), np.asarray(
            eng.cache["state_len"])
        for r in reqs:
            if r.slot is not None:
                assert folded[1 + r.slot] == eng.fold_lens[r.slot]
                seen[r.rid].append((int(eng.seq_lens[r.slot]),
                                    int(folded[1 + r.slot]),
                                    g[:, 1 + r.slot], step))
    return seen


def _assert_rows_are_the_references(params, req, seen, C=16):
    """Column c of a slot's row is position ``state_len + c``: its gate
    summed within its own chunk, as ``chunk_cumsum`` of the reference's
    log-gates has it, at every position since ``state_len``."""
    tokens = list(req.prompt) + list(req.generated)
    want = ret.chunk_cumsum(
        jnp.moveaxis(_reference_log_gates(params, tokens), 2, 1), C)
    want = np.moveaxis(np.asarray(want), 1, 2)                 # [L, K, S]
    assert len(seen) > 2
    for n, folded, row, _ in seen:
        assert 0 < n - folded <= C + 4                 # a chunk and a window
        np.testing.assert_allclose(
            row[:, :, :n - folded], want[:, :, folded:n],
            rtol=2e-4, atol=2e-5, err_msg=f"{n} positions, {folded} folded")
    return {folded for _, folded, _, _ in seen}


def test_a_slots_row_of_gates_is_the_references_column_for_column(tiny):
    """A prompt that ends inside a chunk (29 = 16 + 13), a window of 4, the
    fold at 32, four windows more and the fold at 48: after every engine
    step the slot's row holds the reference's gates of the positions since
    its ``state_len``, column for column."""
    _, params = tiny
    eng = _engine(params)
    req = eng.submit_request(list(range(1, 30)), 30)
    seen = _gate_rows(eng, [req])[req.rid]
    assert _assert_rows_are_the_references(params, req, seen) == {16, 32, 48}
    assert eng.reset_timing()["folds"] == 2
    assert seen[0][:2] == (33, 16)       # a whole chunk waits for its fold
    eng.close()


def test_no_column_behind_the_newest_position_is_read(tiny):
    """Every column of ``g`` that holds no live position poisoned with NaN,
    empty slots' rows and the scratch row whole: a decode step's logits, a
    fold's state and the step after that fold are bitwise those of the
    clean cache, and the logits are finite in every slot."""
    from orion_tpu.infer import runner

    cfg, params = tiny
    eng = _engine(params)
    req = eng.submit_request(list(range(1, 30)), 24)
    while not req.generated or int(eng.seq_lens[req.slot]) < 32:
        eng.step()
    slot, n = req.slot, int(eng.seq_lens[req.slot])
    assert (n, int(eng.fold_lens[slot])) == (33, 16)   # a fold is due
    clean = {k: jnp.array(v) for k, v in eng.cache.items()}
    live = jnp.zeros(clean["g"].shape, bool).at[:, 1 + slot, :, :n - 16].set(
        True)
    bad = {**clean, "g": jnp.where(live, clean["g"], jnp.nan)}
    tok, pos = jnp.asarray(eng.last_token), jnp.asarray(eng.seq_lens)
    table = jnp.asarray(eng.page_table)

    def step(cache):
        return runner._decode_core(params, cache, tok, pos, table, cfg, None)

    def fold(cache):
        return runner.fold_step(cache, jnp.int32(slot), table[slot], cfg=cfg)

    def same_step(a, b, end):
        """One step on the clean cache ``a`` and on the poisoned ``b``:
        the same finite logits, the new column written and the poison
        (up to column ``end``) left where it was."""
        (la, ca), (lb, cb) = step(a), step(b)
        assert np.isfinite(np.asarray(lb)).all()
        assert (np.asarray(la) == np.asarray(lb)).all()
        keep = n + 1 - int(ca["state_len"][1 + slot])
        ga, gb = (np.asarray(c["g"])[:, 1 + slot] for c in (ca, cb))
        assert (ga[:, :, :keep] == gb[:, :, :keep]).all()
        assert np.isnan(gb[:, :, keep:end]).all()

    same_step(clean, bad, None)
    a, b = fold(clean), fold(bad)
    for name in ("state", "state_z", "state_len"):
        assert (np.asarray(a[name]) == np.asarray(b[name])).all()
    assert int(a["state_len"][1 + slot]) == 32
    same_step(a, b, -16)    # a fold moves a row down, zeros behind it
    eng.close()


def test_two_slots_fold_in_windows_of_their_own_and_keep_their_rows(tiny):
    """Prompts of 29 and 21: the first slot folds after its first window,
    the second after its third. Each row follows its own sequence's
    reference at every step, and a fold of one slot leaves every other row
    of ``g`` (the scratch row too) bitwise as it was."""
    from orion_tpu.infer import runner

    cfg, params = tiny
    eng = _engine(params)
    reqs = [eng.submit_request(list(range(1, 30)), 30),
            eng.submit_request(list(range(40, 61)), 30)]
    eng.step()
    while int(eng.seq_lens[reqs[0].slot]) < 32:
        eng.step()
    a, b = reqs[0].slot, reqs[1].slot
    assert int(eng.seq_lens[b]) - int(eng.fold_lens[b]) < 16   # none due
    before = {k: jnp.array(v) for k, v in eng.cache.items()}
    after = runner.fold_step(before, jnp.int32(a),
                             jnp.asarray(eng.page_table[a]), cfg=cfg)
    g0, g1 = np.asarray(before["g"]), np.asarray(after["g"])
    others = np.arange(g0.shape[1]) != 1 + a
    assert (g0[:, others] == g1[:, others]).all()
    assert (g1[:, 1 + a, :, :-16] == g0[:, 1 + a, :, 16:]).all()
    assert not g1[:, 1 + a, :, -16:].any()
    assert [int(x) for x in after["state_len"]] == [
        int(x) + 16 * (i == 1 + a) for i, x in enumerate(before["state_len"])]
    seen = _gate_rows(eng, reqs)
    folds = [_assert_rows_are_the_references(params, r, seen[r.rid])
             for r in reqs]
    assert folds == [{32, 48}, {16, 32}]     # seen from here on
    when = [[step for _, folded, _, step in seen[r.rid] if folded == 32][0]
            for r in reqs]
    assert when[0] != when[1]
    eng.close()


@pytest.mark.parametrize("num_pages", [40, 160])
def test_the_gates_leaf_is_the_slots_and_not_the_pools(num_pages):
    """[layers, slots + 1, K, T], T a chunk and a window in whole lane
    rows, whatever ``inference.num_pages`` is; the other slot leaves
    alike, and only ``k`` / ``v`` grow with the pool."""
    from orion_tpu.infer.kv_cache import SLOT_LEAVES, init_cache

    cfg = get_config("tiny-brumby", [f"inference.num_pages={num_pages}"])
    cache = init_cache(cfg.model, cfg.inference)
    assert set(cache) == {"k", "v", *SLOT_LEAVES} and "g" in SLOT_LEAVES
    T = ret.tail_pages(16, 4) * 4
    assert T == 128 and cache["g"].shape == (3, 4 + 1, 2, T)
    assert cache["g"].dtype == jnp.float32
    assert cache["state_len"].shape == (5,)
    assert cache["state"].shape[0] == cache["state_z"].shape[0] == 3 * 5
    assert cache["k"].shape[0] == cache["v"].shape[0] == 3 * num_pages
    big = get_config("brumby-14b", [
        "inference.page_size=64", "inference.max_batch_size=32",
        f"inference.num_pages={num_pages}"])
    shapes = jax.eval_shape(lambda: init_cache(big.model, big.inference))
    assert shapes["g"].shape == (40, 33, 8, 640)     # 512 + 8 -> 5 x 128


def test_no_float32_array_of_the_decode_window_is_as_long_as_the_pool(tiny):
    """The lowered decode window of a pool of 53 pages (a number no other
    size of the tiny model is): no float32 array has a dimension of 53,
    as the paged gates [layers, pages, K, page] and a layer's slice of
    them had; the K/V pools are [layers x pages, ...] and the slots' gates
    [layers, slots + 1, K, T]."""
    import functools
    import re

    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    cfg = get_config("tiny-brumby", ["inference.num_pages=53"])
    m, i = cfg.model, cfg.inference
    B, W = i.max_batch_size, i.decode_window
    cache = init_cache(m, i)
    text = jax.jit(functools.partial(
        runner.decode_window, cfg=m, max_seq_len=i.max_seq_len)).lower(
        tiny[1], cache, jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.int32),
        jnp.zeros((B, pages_per_seq(i)), jnp.int32), jnp.ones(B, bool),
        jax.random.split(jax.random.key(0), W), jnp.zeros(B), jnp.zeros(
            B, jnp.int32), jnp.ones(B)).as_text()
    shapes = {tuple(int(d) for d in dims.split("x") if d)
              for dims in re.findall(r"tensor<((?:\d+x)+)f32>", text)}
    assert (3, 5, 2, 128) in shapes and (3 * 53, 2, 4, 16) in shapes
    assert not [s for s in shapes if 53 in s], sorted(
        s for s in shapes if 53 in s)


def test_a_preempted_request_re_prefills_to_the_same_tokens(tiny):
    _, params = tiny
    prompt = [int(x) for x in np.random.default_rng(1).integers(1, 256, 21)]
    eng = _engine(params)
    want = eng.generate([prompt], max_new_tokens=24)[0]
    eng.close()
    eng = _engine(params)
    req = eng.submit_request(prompt, 24)
    while len(req.generated) < 17:      # past the fold at 32 positions
        eng.step()
    assert eng.fold_lens[req.slot] == 32
    eng._preempt(req)
    assert req.slot is None and eng.alloc.free_pages == eng.icfg.num_pages - 1
    while eng.has_work():
        eng.step()
    assert list(req.generated) == list(want)
    assert eng.preemptions == 1
    eng.assert_page_accounting()
    eng.close()


def test_the_pages_behind_a_fold_go_back_to_the_pool(tiny):
    _, params = tiny
    eng = _engine(params)
    req = eng.submit_request(list(range(1, 10)), 50)
    held = []
    while eng.has_work():
        eng.step()
        if req.slot is not None:
            held.append(sum(p is not None for p in req.pages))
    # never more than a chunk, a window and the prefill bucket's slack
    assert max(held) <= (16 + 4) // 4 + 4
    t = eng.reset_timing()
    assert t["folds"] == 3 and t["decode_state_slot_layers"] > 0
    assert 0 < t["decode_state_empty_slot_layers"] < t[
        "decode_state_slot_layers"]
    assert t["prefill_retention_units"] == 3 * 9 * 10    # 9 < D / 2 = 68
    assert t["decode_kv_tokens"] == 0
    eng.assert_page_accounting()
    eng.close()


@pytest.mark.parametrize("override, named", [
    ("inference.prefix_cache=true", "inference.prefix_cache"),
    ("inference.speculative=true", "inference.speculative"),
    ("inference.chunked_prefill=true", "inference.chunked_prefill"),
    ("inference.kv_quant=int8", "inference.kv_quant"),
    ("inference.constrained=true", "inference.constrained"),
    ("model.weight_quant=int8", "model.weight_quant"),
    ("inference.host_tier_bytes=1048576", "inference.host_tier_bytes"),
])
def test_what_cannot_snapshot_a_state_is_refused_by_name(
        tiny, override, named):
    from orion_tpu.infer import InferenceEngine

    with pytest.raises(ValueError, match=named):
        InferenceEngine(get_config("tiny-brumby", [override]), tiny[1])


def test_migration_is_refused_by_name(tiny):
    eng = _engine(tiny[1])
    req = eng.submit_request([1, 2, 3, 4, 5], 8)
    eng.step()
    with pytest.raises(ValueError, match="power_retention"):
        eng.export_migration_state(req.rid)
    eng.close()


def test_a_kv_model_counts_nothing_of_this(tiny):
    from orion_tpu.infer import InferenceEngine
    from orion_tpu.models.transformer import init_params

    cfg = get_config("tiny-llama")
    eng = InferenceEngine(cfg, init_params(cfg.model, jax.random.key(0)))
    eng.generate([[1, 2, 3, 4, 5, 6]], max_new_tokens=6)
    t = eng.reset_timing()
    for key in ("fold_s", "folds", "decode_state_slot_layers",
                "decode_state_empty_slot_layers", "decode_tail_token_layers",
                "prefill_retention_units"):
        assert t[key] == 0, key
    assert set(eng.cache) == {"k", "v"}
    eng.close()


def test_the_preset_is_the_published_configuration():
    m, pub = get_config("brumby-14b").model, PUBLISHED
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.resolved_head_dim, m.vocab_size) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_hidden_layers"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["vocab_size"])
    assert (m.rope_theta, m.norm_eps, m.tie_embeddings, m.attn_bias) == (
        pub["rope_theta"], pub["rms_norm_eps"], pub["tie_word_embeddings"],
        pub["attention_bias"])
    assert pub["hidden_act"] == "silu" and m.activation == "swiglu"
    assert pub["sliding_window"] is None and m.sliding_window is None
    assert not pub["use_sliding_window"] and pub["rope_scaling"] is None
    assert pub["model_type"] == "brumby" and m.is_retention and m.qk_norm
    assert m.max_seq_len == pub["max_position_embeddings"]
