"""The flash forward under a narrow window as a band (PR 53): query chunks
folded into the batch, each with its own keys behind the window before them
(``flash_attention._band_chunk`` / ``_fold_bands``). The folded call has to
equal the plain walk of the same kernel and ``attention_xla``; the rule has
to leave every other call's statics as they were."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops.attention import attention_xla

fa = importlib.import_module("orion_tpu.ops.pallas.flash_attention")

C = fa.BAND_CHUNK


def _inputs(B, S, N, K, H, Hv, seed=0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    sink = jnp.asarray(rng.uniform(0, 4, size=(N,)), jnp.float32)
    return draw(B, S, N, H), draw(B, S, K, H), draw(B, S, K, Hv), sink


def _segments(S, real):
    return jnp.asarray(
        (np.arange(S)[None, :] < np.asarray(real)[:, None]).astype(np.int32))


# real: each row's prompt length (None: no segment ids at all)
CASES = {
    # MiMo's layer: a sink, keys 192 / values 128; a row shorter than one
    # window whose other chunks are all padding; S no multiple of the chunk
    "sink-k192-v128-ragged": dict(
        N=4, K=2, H=192, Hv=128, sink=True, S=2 * C + 276,
        real=[2 * C + 276, 97, C + 1]),
    "sink-k128-v128": dict(
        N=4, K=2, H=128, Hv=128, sink=True, S=2 * C, real=[2 * C, C - 3]),
    "nosink-k192-v128": dict(
        N=4, K=2, H=192, Hv=128, sink=False, S=2 * C, real=[2 * C, 700]),
    # the differentiable path: no sink, one width, as many K/V heads as heads
    "nosink-k128-v128-heads4-4": dict(
        N=4, K=4, H=128, Hv=128, sink=False, S=2 * C + 8, real=[2 * C + 8, 130]),
    "sink-heads64-8": dict(
        N=64, K=8, H=192, Hv=128, sink=True, S=C + 88, real=[C + 88]),
    "no-segments": dict(
        N=4, K=2, H=128, Hv=128, sink=False, S=2 * C + 76, real=None),
    "window-100": dict(
        N=4, K=2, H=192, Hv=128, sink=True, S=2 * C, real=[2 * C, 99],
        window=100),
    "window-256": dict(
        N=4, K=2, H=128, Hv=128, sink=True, S=3 * C - 5,
        real=[3 * C - 5, 2 * C], window=fa.BAND_MAX_WINDOW),
}


@pytest.mark.parametrize("case", CASES)
def test_band_equals_plain_walk_and_xla(case, monkeypatch):
    c = CASES[case]
    W = c.get("window", 128)
    real = c["real"]
    B = 1 if real is None else len(real)
    q, k, v, sink = _inputs(B, c["S"], c["N"], c["K"], c["H"], c["Hv"])
    seg = None if real is None else _segments(c["S"], real)
    kw = dict(causal=True, window=W, q_segment_ids=seg, kv_segment_ids=seg,
              sink=sink if c["sink"] else None)
    folds = []
    fold = fa._fold_bands
    monkeypatch.setattr(
        fa, "_fold_bands", lambda *a: folds.append(a[-1]) or fold(*a))

    with jax.default_matmul_precision("highest"):
        band = fa.flash_attention(
            q, k, v, seg_pad_zero=seg is not None, interpret=True, **kw)
        assert folds == [C]
        plain = fa.flash_attention(
            q, k, v, seg_pad_zero=seg is not None, interpret=True,
            block_q=1024, block_kv=1024, **kw)
        assert folds == [C]                  # given blocks are kept
        ref = attention_xla(q, k, v, **kw)

    keep = 1.0 if seg is None else np.asarray(seg)[:, :, None, None] > 0
    assert band.shape == ref.shape
    np.testing.assert_allclose(
        np.where(keep, band, 0), np.where(keep, plain, 0), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        np.where(keep, band, 0), np.where(keep, ref, 0), rtol=1e-5, atol=1e-5)


def test_band_gradients_equal_plain_walk():
    """The fold is plain array code around the differentiable core, so a call
    without a sink still has its backward: through the overlapping bands a
    key's gradient adds up over the two chunks that see it."""
    S, W = 2 * C + 40, 128
    q, k, v, _ = _inputs(2, S, 4, 2, 128, 128, seed=1)
    seg = _segments(S, [S, C + 9])
    keep = np.asarray(seg)[:, :, None, None] > 0

    def loss(**blocks):
        def f(q, k, v):
            o = fa.flash_attention(
                q, k, v, window=W, q_segment_ids=seg, kv_segment_ids=seg,
                seg_pad_zero=True, interpret=True, **blocks)
            return (jnp.where(keep, o, 0) ** 2).sum()
        return f

    with jax.default_matmul_precision("highest"):
        band = jax.grad(loss(), (0, 1, 2))(q, k, v)
        plain = jax.grad(loss(block_q=1024, block_kv=1024), (0, 1, 2))(q, k, v)
    for a, b in zip(band, plain):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _statics_of(monkeypatch, q, k, v, **kw):
    """The statics ``flash_attention`` hands its kernel for a call."""
    seen = []

    def record(st, q, k, v, *rest):
        seen.append(st)
        return jnp.zeros((*q.shape[:3], v.shape[3]), q.dtype)

    monkeypatch.setattr(fa, "_flash", record)
    monkeypatch.setattr(fa, "_flash_forward_only", record)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True, **kw), q, k, v)
    assert len(seen) == 1
    return seen[0]


def _plain(S, Skv=None, **over):
    """What a call of length S traced before there was a band."""
    Skv = Skv or S
    return fa._Statics(**{**dict(
        causal=True, logit_softcap=None, q_offset=0, seq_q=S, seq_kv=Skv,
        block_q=min(1024, S), block_kv=min(1024, Skv), interpret=True), **over})


S0 = 4 * C
EDGES = {
    # Laguna's and Mistral's windows; the train cells' call is the second
    "window-512": (dict(window=512), _plain(S0, window=512)),
    "window-4096": (dict(window=4096), _plain(S0, window=4096)),
    "window-257": (dict(window=257), _plain(S0, window=257)),
    "no-window": (dict(), _plain(S0)),
    "positions": (
        dict(window=128, q_positions=jnp.arange(S0),
             kv_positions=jnp.arange(S0)),
        _plain(S0, window=128, has_pos=True)),
    "q-offset": (dict(window=128, q_offset=64),
                 _plain(S0, window=128, q_offset=64)),
    "kv-longer": (dict(window=128, q_offset=C, Skv=S0 + C),
                  _plain(S0, S0 + C, window=128, q_offset=C)),
    "one-chunk": (dict(window=128, S=C), _plain(C, window=128)),
    "blocks-given": (dict(window=128, block_q=256, block_kv=256),
                     _plain(S0, window=128, block_q=256, block_kv=256)),
    # 0 may be a real segment id here, so no row of the band may carry it
    "segments-zero-real": (dict(window=128, seg=True),
                           _plain(S0, window=128)),
}


@pytest.mark.parametrize("edge", EDGES)
def test_rule_leaves_other_calls_as_they_were(edge, monkeypatch):
    kw, want = EDGES[edge]
    kw = dict(kw)
    S = kw.pop("S", S0)
    Skv = kw.pop("Skv", S)
    if kw.pop("seg", False):
        kw.update(q_segment_ids=jnp.ones((1, S), jnp.int32),
                  kv_segment_ids=jnp.ones((1, Skv), jnp.int32))
    q = jnp.zeros((1, S, 2, 128), jnp.float32)
    k = v = jnp.zeros((1, Skv, 2, 128), jnp.float32)
    assert _statics_of(monkeypatch, q, k, v, **kw) == want


def test_band_is_one_step_a_chunk(monkeypatch):
    """At W = 128 and C = 512 a chunk is one grid step, and a head visits
    S x (C + W) pairs where two 1024-wide blocks a query block visit
    S x 2048."""
    S, W = 4 * C, 128
    q = jnp.zeros((2, S, 2, 192), jnp.float32)
    k = jnp.zeros((2, S, 2, 192), jnp.float32)
    v = jnp.zeros((2, S, 2, 128), jnp.float32)
    seg = jnp.ones((2, S), jnp.int32)
    st = _statics_of(
        monkeypatch, q, k, v, window=W, q_segment_ids=seg,
        kv_segment_ids=seg, seg_pad_zero=True, sink=jnp.zeros((2,)))
    assert (st.block_q, st.block_kv, st.seq_q, st.seq_kv, st.q_offset) == (
        C, C + W, C, C + W, W)
    counts = fa.block_counts(st, 1, 1, has_seg=True)
    assert counts["steps"] == counts["visited"] == counts["full"] == 1
    chunks = S // C
    assert chunks * counts["visited"] * st.block_q * st.block_kv == S * (C + W)

    plain = fa.block_counts(_plain(S, window=W), S // 1024, S // 1024)
    assert plain["steps"] == 2 * (S // 1024)
    assert plain["visited"] * 1024 * 1024 == S * 2048 - 1024 * 1024
