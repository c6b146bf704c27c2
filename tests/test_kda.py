"""Kimi delta attention (``ops/kda.py``, ``ops/pallas/kda.py``): the chunked
form and the decode kernel (under the interpreter) against the plain
recurrence, at decays near 1 (a state that remembers) and at the gate's bound
of -5 a step (where a chunk's cumulative log reaches -320 and a ratio of
exponentials would overflow), ragged lengths, a prompt shorter than the
convolution; the chunk's own pieces against their definitions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import kda

TOL = 2e-5      # float32 on the CPU


def _draw(seed, B, S, N, dk, dv, gscale):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (B, S, N, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (B, S, N, dk)))
    v = jax.random.normal(ks[2], (B, S, N, dv))
    g = gscale * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (B, S, N, dk)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, N)))
    return q, k, v, g, b


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("gscale", [-0.01, -1.0, -5.0])
@pytest.mark.parametrize("S, lens", [(200, [200, 77]), (64, [3, 64]),
                                     (130, [130, 1])])
def test_the_chunked_form_is_the_recurrence(gscale, S, lens):
    """Outputs at every real position and the state behind each row's last
    real position; lengths that end inside a chunk, on its end, at one
    position; three chunks and a part of one."""
    q, k, v, g, b = _draw(1, 2, S, 3, 16, 8, gscale)
    lens = jnp.asarray(lens)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, b, lengths=lens)
    got_o, got_s = kda.kda_chunked(q, k, v, g, b, lengths=lens)
    live = (jnp.arange(S)[None, :] < lens[:, None])[..., None, None]
    assert _rel(jnp.where(live, got_o, 0), jnp.where(live, want_o, 0)) < TOL
    assert _rel(got_s, want_s) < TOL


def test_the_bound_is_what_keeps_a_chunk_finite():
    """At -5 a step a chunk's cumulative log reaches -320: exp(+320)
    overflows float32, which is why no ratio of exponentials is formed; the
    chunked form stays finite and right there."""
    q, k, v, g, b = _draw(2, 1, 128, 2, 16, 16, -5.0)
    g = jnp.full_like(g, -5.0)
    assert not np.isfinite(np.exp(np.float32(320.0)))
    got_o, got_s = kda.kda_chunked(q, k, v, g, b)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, b)
    assert bool(jnp.isfinite(got_o).all()) and _rel(got_o, want_o) < TOL
    assert _rel(got_s, want_s) < TOL


def test_the_chunked_form_goes_on_from_a_state_and_over_segments():
    """A block in two halves, the second from the state the first handed
    out, is the block whole; a segment of one chunk at a time is a segment
    of all of them."""
    q, k, v, g, b = _draw(3, 2, 256, 2, 16, 16, -0.3)
    whole_o, whole_s = kda.kda_chunked(q, k, v, g, b)
    half = lambda x, i: x[:, i * 128:(i + 1) * 128]
    o1, s1 = kda.kda_chunked(*(half(x, 0) for x in (q, k, v, g, b)))
    o2, s2 = kda.kda_chunked(*(half(x, 1) for x in (q, k, v, g, b)), state=s1)
    assert _rel(jnp.concatenate([o1, o2], 1), whole_o) < TOL
    assert _rel(s2, whole_s) < TOL
    o3, s3 = kda.kda_chunked(q, k, v, g, b, segment=64)
    assert _rel(o3, whole_o) < TOL and _rel(s3, whole_s) < TOL


def test_the_delta_rule_erases_before_it_writes():
    """One key written twice with b = 1 and no decay holds the SECOND value
    alone (a gated sum would hold both)."""
    k = jnp.zeros((1, 2, 1, 4)).at[..., 0].set(1.0)
    v = jnp.asarray([[[[1.0, 2.0]]], [[[5.0, 7.0]]]]).reshape(1, 2, 1, 2)
    o, s = kda.kda_recurrent(k, k, v, jnp.zeros_like(k), jnp.ones((1, 2, 1)))
    assert np.allclose(np.asarray(s[0, 0, 0]), [5.0, 7.0])
    assert np.allclose(np.asarray(o[0, 1, 0]), [5.0, 7.0])


@pytest.mark.parametrize("sub", [4, 16])
def test_pair_scores_and_the_inverse_against_their_definitions(sub):
    rng = np.random.default_rng(0)
    C, dk = 64, 8
    rows, keys = (jnp.asarray(rng.normal(size=(C, dk)), jnp.float32)
                  for _ in range(2))
    G = jnp.cumsum(-jnp.asarray(rng.uniform(0, 5, (C, dk)), jnp.float32), 0)
    got = kda._pair_scores(rows, keys, G, sub)
    d = np.asarray(G, np.float64)[:, None, :] - np.asarray(G, np.float64)[None]
    want = np.tril((np.asarray(rows, np.float64)[:, None, :]
                    * np.asarray(keys, np.float64)[None, :, :]
                    * np.exp(np.minimum(d, 0))).sum(-1))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    n = 4 * sub                      # the inverse at 16 and at 64 positions
    A = jnp.tril(jnp.asarray(rng.normal(size=(n, n)) * 0.3, jnp.float32), -1)
    T = kda._unit_lower_inverse(A)
    assert np.abs(np.asarray(T @ (jnp.eye(n) + A)) - np.eye(n)).max() < 1e-4


@pytest.mark.parametrize("gscale", [-0.01, -5.0])
@pytest.mark.parametrize("B, S, lens", [
    (1, 100, [61]), (2, 130, [130, 65]), (8, 70, [70, 1, 64, 65, 3, 33, 69, 2])])
def test_the_chunked_form_from_a_state_by_rows(gscale, B, S, lens):
    """Rows 1, 2 and 8 (a segment of 256 positions holds 4, 2 and 1 chunks
    a row), lengths short of the block, from a carried state: the
    outputs at real positions and each row's state behind its last one."""
    q, k, v, g, b = _draw(11, B, S, 2, 16, 8, gscale)
    state = jax.random.normal(jax.random.key(12), (B, 2, 16, 8))
    lens = jnp.asarray(lens)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, b, state, lens)
    got_o, got_s = jax.jit(kda.kda_chunked)(q, k, v, g, b, state, lens)
    live = (jnp.arange(S)[None, :] < lens[:, None])[..., None, None]
    assert _rel(jnp.where(live, got_o, 0), jnp.where(live, want_o, 0)) < TOL
    assert _rel(got_s, want_s) < TOL


def test_the_inverse_issues_no_product_with_a_narrow_side():
    """The regrouping held by COUNT: at one row of 2048 positions, 32 heads
    of 128 x 128, the lowered program holds 10 ``dot_general``s, every one
    float32 at ``highest``, and 2 of them have a matrix side of 16 or less
    (a row block's pair scores, for ``A`` and for ``B``); before PR 48 seven
    of thirteen had one: four more in the inverse's two loops, and a 4 x 4
    product at default precision that picked the diagonal blocks. Shapes
    from the lowered text; nothing is compiled or timed."""
    import re

    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    x = f(1, 2048, 32, 128)
    text = jax.jit(kda.kda_chunked).lower(x, x, x, x, f(1, 2048, 32)).as_text()
    sides = []
    for line in text.splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        batch = re.search(r"batching_dims = \[([\d, ]*)\] x \[([\d, ]*)\]", line)
        shapes = re.findall(r"tensor<([\dx]+)xf32>", line.split(" : ")[-1])
        assert "precision = [HIGHEST, HIGHEST]" in line
        for dims, skip in zip(shapes[:2], batch.groups()):
            skip = {int(i) for i in skip.split(",") if i.strip()}
            # a block of one row leaves ``jnp.matmul`` a dimension of 1: no
            # side of a matrix
            sides.append([int(n) for i, n in enumerate(dims.split("x"))
                          if i not in skip and int(n) > 1])
    pairs = list(zip(sides[::2], sides[1::2]))
    assert len(pairs) == 10
    assert sum(min(l + r) <= 16 for l, r in pairs) == 2


@pytest.mark.parametrize("lens", [[1, 2], [3, 4], [9, 12]])
def test_the_convolution_and_its_tail(lens):
    """The whole-sequence convolution is the step form fed one row at a
    time from a zero tail; the tail handed out is the last K - 1 input rows
    of each sequence, zeros where a prompt is shorter than the
    convolution."""
    rng = np.random.default_rng(1)
    S, C, K = 12, 6, 4
    x = jnp.asarray(rng.normal(size=(2, S, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, C)), jnp.float32)
    lens = jnp.asarray(lens)
    y, tail = kda.short_conv(x, w, lens)
    for b, n in enumerate(np.asarray(lens)):
        t = jnp.zeros((1, K - 1, C))
        for i in range(n):
            yi, t = kda.short_conv_step(x[b:b + 1, i], t, w)
            assert np.allclose(np.asarray(yi[0]), np.asarray(y[b, i]),
                               atol=1e-5)
        assert np.allclose(np.asarray(t[0]), np.asarray(tail[b]), atol=1e-6)
        want = np.zeros((K - 1, C), np.float32)
        m = min(n, K - 1)
        want[K - 1 - m:] = np.asarray(x[b, n - m:n])
        assert np.allclose(np.asarray(tail[b]), want)
    assert np.allclose(np.asarray(kda.short_conv(x, w)[1]),
                       np.asarray(x[:, S - K + 1:]))


def test_the_bounded_gate_stays_inside_its_bound():
    z = jnp.asarray(np.random.default_rng(2).normal(size=(5, 3, 8)) * 30)
    g = kda.safe_log_decay(z, jnp.ones((3,)), jnp.zeros((3, 8)), -5.0)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01


LIVE_DEAD_LIVE = (True, False, True)


@pytest.mark.parametrize("gscale, N, H, active", [
    (-0.01, 8, 128, LIVE_DEAD_LIVE), (-5.0, 8, 128, LIVE_DEAD_LIVE),
    (-0.01, 4, 128, LIVE_DEAD_LIVE), (-5.0, 4, 128, LIVE_DEAD_LIVE),
    # a slot's 32 heads in one grid step, four turns of the loop of eight
    (-0.01, 32, 16, LIVE_DEAD_LIVE), (-5.0, 32, 16, LIVE_DEAD_LIVE),
    # heads that are no multiple of a tile of eight: one unrolled group
    (-0.01, 12, 16, LIVE_DEAD_LIVE),
    # more heads than the buffers hold at 128 x 128: two blocks of 20
    (-5.0, 40, 128, (True, False)),
    # a run of dead slots between live ones, and dead ones at both ends
    (-0.01, 16, 16, (False, True, False, False, False, True, True, False)),
])
def test_the_decode_kernel_is_a_step_of_the_recurrence(gscale, N, H, active):
    """``kda_decode`` under the interpreter: live slots advance as
    ``kda_step`` (and so as the recurrence) says, at a traced layer; the
    dead slots' rows and the other layer are bitwise what they were.
    ``head_block`` takes all the heads of a slot that fit its buffers."""
    from orion_tpu.ops.pallas.kda import head_block, kda_decode

    assert head_block(N, H, H) == {40: 20}.get(N, N)
    B = len(active)
    q, k, v, g, b = (x[:, 0] for x in _draw(4, B, 1, N, H, H, gscale))
    state = jax.random.normal(jax.random.key(9), (2, B + 1, N, H, H))
    active = jnp.asarray(active)
    want_o, want_s = kda.kda_step(state[1, 1:], q, k, v, g, b, active)
    got_o, got_s = jax.jit(lambda st, l: kda_decode(
        st, q, k, v, g, b, layer=l, active=active, interpret=True))(
            state, jnp.int32(1))
    assert _rel(got_o[active], want_o[active]) < TOL
    assert _rel(got_s[1, 1:], want_s) < TOL
    assert bool((got_s[0] == state[0]).all())
    assert bool((got_s[1, 1:][~active] == state[1, 1:][~active]).all())
    # and the step is the recurrence's (the rows are value-major)
    s, o = kda._step(jnp.swapaxes(state[1, 1:], -1, -2), q, k, v, g, b)
    assert _rel(want_o, o) < TOL
    live = int(jnp.argmax(active))
    assert _rel(want_s[live], jnp.swapaxes(s, -1, -2)[live]) < TOL


def test_prefill_then_steps_is_the_recurrence_over_the_whole():
    q, k, v, g, b = _draw(5, 2, 90, 2, 16, 16, -0.05)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, b)
    o, s = kda.kda_chunked(*(x[:, :70] for x in (q, k, v, g, b)))
    rows = jnp.swapaxes(s, -1, -2)
    for t in range(70, 90):
        ot, rows = kda.kda_step(rows, q[:, t], k[:, t], v[:, t], g[:, t],
                                b[:, t])
        assert _rel(ot, want_o[:, t]) < TOL
    assert _rel(jnp.swapaxes(rows, -1, -2), want_s) < TOL


@pytest.mark.parametrize("gscale, seen", [(-0.01, True), (-5.0, False)])
def test_a_lost_carry_shows_only_where_a_state_outlives_a_chunk(gscale, seen):
    """The fault ``tools/kda_fault_probe.py`` plants in the chunked prefill
    (every chunk of 64 starts from a zero state): a single chunk is the
    chunked form itself; over three chunks, with decays near 1 the outputs
    behind the first boundary and the final state are far from the
    recurrence's; where a position leaves e^-2.5 (the geometric mean: half
    of the bounded gate's -5) nothing two positions behind a boundary can
    tell (three positions are e^-7.5), which is why the benchmark's output check cannot hold the carry
    under weights whose gates sit there (PERF.md section 7)."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "tools/kda_fault_probe.py"
    spec = importlib.util.spec_from_file_location("kda_fault_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    q, k, v, g, b = _draw(7, 2, 180, 3, 16, 8, gscale)
    if not seen:
        g = jnp.full_like(g, -2.5)
    lens = jnp.asarray([180, 50])
    want_o, want_s = kda.kda_recurrent(q, k, v, g, b, lengths=lens)
    with probe.planted("carry"):
        got_o, got_s = kda.kda_chunked(q, k, v, g, b, lengths=lens)
    assert kda.kda_chunked.__name__ == "kda_chunked"        # put back
    # the row that ends inside its first chunk lost nothing
    assert _rel(got_o[1, :50], want_o[1, :50]) < TOL
    assert _rel(got_s[1], want_s[1]) < TOL
    assert _rel(got_o[0, :64], want_o[0, :64]) < TOL
    far = np.flatnonzero((np.arange(180) >= 64) & (np.arange(180) % 64 >= 3))
    behind = _rel(got_o[0, far], want_o[0, far])
    if seen:
        assert behind > 0.1 and _rel(got_s[0], want_s[0]) > 0.1
    else:
        assert behind < 1e-2 and _rel(got_s[0], want_s[0]) < 1e-3
