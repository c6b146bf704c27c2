"""The serve cells' programs compiled at their real sizes for a v5e that is
described and not attached: the chip's compiler refuses here, at no chip
time, what it would refuse there (a kernel, or a program that does not fit
beside the weights and the pool). The only file of the benchmark's tests that
describes the topology; everything built from it is built in fixtures."""

import importlib
import pkgutil
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


USABLE = 15.75 * 2 ** 30       # what a v5e chip reports of its 16 GiB


def _serve_cells() -> list:
    """Every cell of BENCHMARK.json whose traffic mix is of kind ``serve``
    (small files read at collection; nothing here touches a device)."""
    from benchmarks.harness.cell import BENCH, load_benchmark
    from benchmarks.traffic.generator import load_mix

    return [w["name"] for w in load_benchmark()["workloads"]
            if load_mix(w["traffic"], BENCH / "traffic")["kind"] == "serve"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:     # no compiler for the chip on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The default backend is the CPU here, where the program refuses
    compiled kernels; the test compiles for the described chip."""
    import orion_tpu.ops.pallas as pallas_pkg

    for m in pkgutil.iter_modules(pallas_pkg.__path__):
        mod = importlib.import_module(f"orion_tpu.ops.pallas.{m.name}")
        if hasattr(mod, "resolve_interpret"):
            monkeypatch.setattr(mod, "resolve_interpret", bool)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("cell_name", _serve_cells())
def test_decode_and_widest_prefill_fit_the_chip(
        one_chip, compiled_kernels, cell_name):
    from benchmarks.harness.cell import Cell
    from benchmarks.kinds import serve
    from benchmarks.reference import weights
    from orion_tpu.infer import runner
    from orion_tpu.infer.kv_cache import init_cache, pages_per_seq

    cell = Cell.find(cell_name)
    cfg = cell.program_config()
    mcfg, icfg = cfg.model, cfg.inference
    spec = cell.reference().param_spec(cell.config)
    params = _abstract(jax.eval_shape(lambda: weights._draw(
        spec, mcfg.n_layers, jnp.dtype(mcfg.param_dtype),
        jax.random.key(0))), one_chip)
    cache = _abstract(jax.eval_shape(lambda: init_cache(mcfg, icfg)), one_chip)
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=one_chip)

    def total(compiled):
        m = compiled.memory_analysis()
        return (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)

    B, W = icfg.max_batch_size, icfg.decode_window
    decode = jax.jit(partial(
        runner.decode_window, cfg=mcfg, max_seq_len=icfg.max_seq_len,
        mesh=None, nan_guard=False, temperature=icfg.temperature,
        top_k=icfg.top_k, top_p=icfg.top_p), donate_argnums=(1,))
    keys = jax.ShapeDtypeStruct((W,), jax.random.key(0).dtype,
                                sharding=one_chip)
    compiled = decode.lower(
        params, cache, i32(B), i32(B), i32(B, pages_per_seq(icfg)),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip), keys,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the paged kernel
    assert total(compiled) < USABLE

    todo = serve.cell_prefill_shapes(cell, icfg)
    nb, s_pad = max(todo, key=lambda s: (s[0] * s[1], s[0]))
    prefill = jax.jit(partial(
        runner.prefill_step, cfg=mcfg, mesh=None,
        paged_prefill=icfg.paged_prefill), donate_argnums=(1,))
    compiled = prefill.lower(
        params, cache, i32(nb, s_pad), i32(nb),
        i32(nb, s_pad // icfg.page_size), i32(nb), i32(nb, 0)).compile()
    # beside the weights and the pool, with 1 GiB left for the output check
    assert total(compiled) < USABLE - 2 ** 30
