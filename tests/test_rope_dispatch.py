"""``ops.apply_rope`` picks its implementation by the sequence length it is
handed (ISSUE 34): under ``ops.rope.KERNEL_MIN_SEQ`` the Pallas kernel would
pad the rows to its tile, so ``impl="pallas"`` runs the XLA form there, and
the kernel from that length up. These hold the two to each other around the
threshold; every number here is a difference between results, not a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import RopeConfig, get_config
from orion_tpu.ops.pallas.common import pad_axis, round_up
from orion_tpu.ops.pallas.rope import rope_pallas
from orion_tpu.ops.rope import (
    KERNEL_MIN_SEQ, _rope_xla, _rope_xla_table, apply_rope, rope_table,
)
from tests.test_phases import pallas_names

H, THETA, LAST = 128, 1e6, 12287
TABLES = {
    "plain": None,
    "partial": RopeConfig(theta=1e4, rotary_fraction=0.5),
    "yarn": get_config("laguna-s-2.1").model.rope_full,
}
LENGTHS = sorted({1, 2, 7, KERNEL_MIN_SEQ - 1, KERNEL_MIN_SEQ})


def inputs(S, dtype):
    """Three rows: one ending at the longest position a serving cell holds,
    one mid-sequence, one at the start."""
    x = jax.random.normal(jax.random.key(S), (3, S, 4, H), dtype)
    first = jnp.asarray([[LAST + 1 - S], [5000], [0]], jnp.int32)
    return x, first + jnp.arange(S)[None, :]


def kernel_padded_by_hand(x, pos, rope):
    """The kernel on the rows padded to its tile of 8, as decode steps ran
    it before the threshold."""
    Sp = round_up(x.shape[1], 8)
    table = None if rope is None else rope_table(H, rope)
    return rope_pallas(
        pad_axis(x, 1, Sp), pad_axis(pos, 1, Sp), theta=THETA, table=table,
        interpret=True)[:, : x.shape[1]]


def bf16_ulp(a):
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 2.0 ** -126))) - 7)


@pytest.mark.parametrize("S", LENGTHS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_small_seq_equals_kernel(table, dtype, S):
    rope = TABLES[table]
    x, pos = inputs(S, jnp.dtype(dtype))
    got = np.asarray(apply_rope(
        x, pos, theta=THETA, rope=rope, impl="pallas_interpret"), np.float32)
    want = np.asarray(kernel_padded_by_hand(x, pos, rope), np.float32)
    # A table hands both forms the same float32 frequencies. The plain
    # kernel computes its own as exp(-log(theta) i / half) where the XLA
    # form divides by theta ** (i / half): a few ulp apart, times the
    # position, is the angle's difference (0 from the threshold up, where
    # both sides are the kernel).
    slack = 1e-5
    if rope is None:
        slack = max(slack, 4e-7 * LAST * float(jnp.abs(x).max()))
    if S >= KERNEL_MIN_SEQ:
        np.testing.assert_array_equal(got, want)
    elif dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=0, atol=slack)
    else:
        # bit for bit, but for the few float32 results that sit on a
        # bfloat16 rounding edge: those land one ulp apart
        room = np.maximum(
            bf16_ulp(np.maximum(np.abs(got), np.abs(want))), slack)
        assert (np.abs(got - want) <= room).all()
        assert (got != want).mean() < (0.05 if rope is None else 0.001)


@pytest.mark.parametrize("table", sorted(TABLES))
def test_small_seq_gradient_is_the_xla_forms(table):
    rope = TABLES[table]
    x, pos = inputs(KERNEL_MIN_SEQ - 1, jnp.float32)

    def xla(x):
        if rope is None:
            return _rope_xla(x, pos, THETA)
        return _rope_xla_table(x, pos, *rope_table(H, rope))

    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    got = jax.grad(lambda x: jnp.sum(w * apply_rope(
        x, pos, theta=THETA, rope=rope, impl="pallas_interpret")))(x)
    want = jax.grad(lambda x: jnp.sum(w * xla(x)))(x)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("S,kernels", [
    (1, 0), (KERNEL_MIN_SEQ - 1, 0), (KERNEL_MIN_SEQ, 1), (512, 1)])
@pytest.mark.parametrize("table", ["plain", "yarn"])
def test_kernel_is_traced_from_the_threshold_up(table, S, kernels):
    rope = TABLES[table]
    x = jax.ShapeDtypeStruct((2, S, 4, H), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((2, S), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda x, p: apply_rope(
        x, p, theta=THETA, rope=rope, impl="pallas_interpret"))(x, pos).jaxpr
    assert pallas_names(jaxpr) == ({"rope"} if kernels else set())
    assert str(jaxpr).count("pallas_call") == kernels
