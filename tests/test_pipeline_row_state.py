"""Pipeline row-state validation — fast, execution-free
checks that stay in tier-1 while the pipeline-execution tests (slow tier)
carry the schedule equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import make_mesh


def test_pipeline_row_state_broadcast_lifted():
    """A [1, S] broadcast row-state leaf (explicitly supported by the
    non-pp block_fn) is lifted to [B, S] before microbatch slicing instead
    of dying in an opaque reshape."""
    from orion_tpu.parallel.pipeline import validate_row_state

    rs = validate_row_state(
        {"positions": jnp.arange(8, dtype=jnp.int32)[None],   # [1, 8]
         "segment_ids": jnp.ones((4, 8), jnp.int32)},
        batch=4, num_microbatches=2,
    )
    assert rs["positions"].shape == (4, 8)
    assert rs["segment_ids"].shape == (4, 8)
    np.testing.assert_array_equal(
        np.asarray(rs["positions"]), np.tile(np.arange(8), (4, 1))
    )
    assert validate_row_state(None, batch=4, num_microbatches=2) is None


def test_pipeline_row_state_bad_leading_dim_raises(cpu_devices):
    """A row-state leaf whose leading dim is neither B nor 1 must raise a
    descriptive ValueError up front, from the real pipeline entry point
    (it previously surfaced as an opaque reshape error)."""
    from orion_tpu.parallel.pipeline import pipeline_forward

    mesh = make_mesh(cpu_devices, pp=2, dp=4)
    x = jnp.zeros((4, 8, 16))
    blocks = {"w": jnp.zeros((4, 1, 1))}

    def fn(c, bp, rs):
        return c + bp["w"], jnp.zeros(())

    with pytest.raises(ValueError, match="row_state"):
        pipeline_forward(
            x, blocks, fn, mesh, num_microbatches=2,
            row_state={"positions": jnp.zeros((3, 8), jnp.int32)},
        )
