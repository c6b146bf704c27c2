"""The enumerated set of prefill shapes covers every shape an instrumented
tiny engine is asked for, dense and sparse, and the warm-up compiles them
all so the window compiles nothing."""

import pytest

from benchmarks.harness.cell import Cell
from benchmarks.harness.compiles import CompileCounter
from benchmarks.traffic import generator


@pytest.mark.parametrize("workload", ["tiny.dense-batch", "tiny.batch"])
def test_enumerator_covers_what_the_engine_requests(tiny_root, workload):
    from benchmarks.kinds import serve

    cell = Cell.find(workload, root=tiny_root)
    cfg, engine = serve.build_engine(cell, seed=5)
    icfg = cfg.inference
    budget = cell.config["frontend"]["prefill_token_budget"]
    allowed = set(serve.cell_prefill_shapes(cell, icfg))
    warmed = serve.warm_shapes(engine, cell, cfg)
    assert set(warmed) == allowed

    seen, orig = set(), engine._executor.run

    def spy(path, name, *args, **kwargs):
        if path == "prefill":
            seen.add(tuple(args[2].shape))      # the tokens: (rows, padded)
        return orig(path, name, *args, **kwargs)

    engine._executor.run = spy
    serve.probe_numbers(engine, cell.reference(), cell.config, cell.mix,
                        seed=5)
    stream = generator.request_stream(cell.mix, 5, cfg.model.vocab_size)
    drv = serve.Driver(engine, cell.mix, icfg, budget, stream)
    counter = CompileCounter()
    drv.run_until(lambda: sum(r.t_done is not None for r in drv.records) >= 48)
    assert seen and seen <= allowed
    assert any(rows > 1 for rows, _ in seen)        # bursts did occur
    assert counter.take()[0] == 0               # nothing compiled while driving
    assert drv.steps > 0 and all(
        r.req.outcome == "completed" and len(r.req.generated) == r.max_new
        for r in drv.records if r.t_done is not None)
    engine.close()


@pytest.mark.parametrize("workload", ["tiny.dense-batch", "tiny.batch"])
def test_the_window_program_is_tied_to_the_logits_and_a_broken_tie_shows(
        tiny_root, workload):
    """The decode-window program's own KV and tokens against the one-step
    body whose logits go to the reference: equal when sound, far apart when
    the one-step body is fed another token (the control of the two)."""
    from benchmarks.kinds import serve

    cell = Cell.find(workload, root=tiny_root)
    _, engine = serve.build_engine(cell, seed=9)
    sound = serve.probe_numbers(engine, cell.reference(), cell.config,
                                cell.mix, 9)
    n_pos = 1 + cell.mix["probe_windows"] * engine.decode_window
    assert len(sound["err"]) == n_pos * len(cell.mix["probe_prompts"])
    assert len(sound["window_kv_rel_err"]) == (
        cell.mix["probe_windows"] * len(cell.mix["probe_prompts"]))
    ok, checks = serve.decide(sound, cell.config["correct"])
    assert ok and len(checks) == 3
    broken = serve.probe_numbers(engine, cell.reference(), cell.config,
                                 cell.mix, 9,
                                 break_link=True)
    ok, _ = serve.decide(broken, cell.config["correct"])
    assert not ok
    got = serve.judged(broken, 0.0)
    assert got["window_kv_rel_err_max"] > 0.5
    assert got["window_token_gap_max"] > 0.5
    engine.close()


def test_the_cache_compare_reads_every_paged_leaf_the_cache_has():
    """A K/V pool reads what it read when the compare knew two names; a
    cache of another make (one latent leaf of its own width) is read too,
    and a leaf of another layout (a scale pool) is left out."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.kinds.serve import _kv_at, _rel_err

    L, P, K, page, H = 2, 3, 2, 4, 8
    rng = np.random.default_rng(0)
    pool = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    cache = {"k": pool(L * P, K, page, H), "v": pool(L * P, K, page, H),
             "k_scale": pool(L * P, K, 128)}
    page_table = jnp.asarray([[0, 0, 0], [2, 1, 0]], jnp.int32)
    at = jnp.asarray([3, 4, 5])          # slot 1: page 2 (offset 3), page 1
    got = np.asarray(_kv_at(cache, page_table, 1, at, L, P, page))
    rows = np.asarray([[2, 1, 1], [P + 2, P + 1, P + 1]])
    offs = np.asarray([3, 0, 1])
    by_name = lambda c: np.stack([
        np.stack([[np.asarray(c[n])[rows[l, w], :, offs[w], :]
                   for w in range(3)] for l in range(L)]) for n in ("k", "v")])
    want = by_name(cache)                # [2, layers, W, kv, head], as it was
    np.testing.assert_array_equal(got, want.ravel())
    other = dict(cache, v=cache["v"] + 0.01)
    again = np.asarray(_kv_at(other, page_table, 1, at, L, P, page))
    assert _rel_err(got, again) == _rel_err(want, by_name(other))

    latent = {"latent": pool(L * P, 1, page, 24), "scale": pool(L * P, 1, 128)}
    got = np.asarray(_kv_at(latent, page_table, 1, at, L, P, page))
    assert got.shape == (L * 3 * 24,)
    np.testing.assert_array_equal(
        got[:24], np.asarray(latent["latent"])[2, 0, 3])
    with pytest.raises(ValueError, match="paged layout"):
        _kv_at({"state": pool(L, 4, 16)}, page_table, 1, at, L, P, page)
