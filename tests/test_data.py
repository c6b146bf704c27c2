"""Data pipeline tests: loader determinism, the native C++ reader vs the
numpy fallback, and end-to-end memmap training."""

import numpy as np
import pytest

from orion_tpu.config import DataConfig
from orion_tpu.data.loader import (
    MemmapLoader,
    SyntheticLoader,
    _NumpyReader,
)


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("data") / "tokens.u16"
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50000, size=20_000, dtype=np.uint16)
    tokens.tofile(path)
    return str(path), tokens


def test_synthetic_deterministic_and_shifted():
    cfg = DataConfig(batch_size=4, seq_len=32)
    ldr = SyntheticLoader(cfg, 0, 1, vocab_size=256)
    b1, b2 = ldr.batch_at(7), ldr.batch_at(7)
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
    # targets are inputs shifted by one
    b3 = ldr.batch_at(8)
    assert not np.array_equal(b1["inputs"], b3["inputs"])
    np.testing.assert_array_equal(b1["inputs"][:, 1:], b1["targets"][:, :-1])


@pytest.mark.parametrize("packed", [False, True])
def test_loaders_invariant_across_process_counts(token_file, packed):
    """Elastic-resume contract: the global batch at a step is identical
    whether served by 1 process or sliced across 2 — the data stream must
    not depend on process count (SURVEY.md §6 elastic recovery)."""
    from orion_tpu.data.loader import MemmapLoader, SyntheticLoader

    path, _ = token_file
    cfgs = [
        (SyntheticLoader,
         DataConfig(batch_size=4, seq_len=32, packed=packed),
         {"vocab_size": 256}),
        (MemmapLoader,
         DataConfig(source="memmap", path=path, batch_size=4, seq_len=32,
                    packed=packed, eos_token_id=0, use_native_loader=False),
         {"vocab_size": 256}),
    ]
    for cls, cfg, kw in cfgs:
        whole = cls(cfg, 0, 1, **kw).batch_at(5)
        lo = cls(cfg, 0, 2, **kw).batch_at(5)
        hi = cls(cfg, 1, 2, **kw).batch_at(5)
        for key in whole:
            np.testing.assert_array_equal(
                whole[key],
                np.concatenate([lo[key], hi[key]]),
                err_msg=f"{cls.__name__}.{key}",
            )


def test_native_reader_matches_numpy(token_file):
    path, tokens = token_file
    native = pytest.importorskip("orion_tpu.data.native")
    rdr = native.NativeReader(path, np.uint16)
    ref = _NumpyReader(path, np.dtype(np.uint16))
    assert len(rdr) == len(ref) == len(tokens)
    offs = np.asarray([0, 17, 5000, len(tokens) - 129])
    np.testing.assert_array_equal(rdr.gather(offs, 129), ref.gather(offs, 129))
    rdr.prefetch(offs, 129)  # smoke: readahead must not crash
    rdr.close()


def test_native_reader_bounds_check(token_file):
    path, tokens = token_file
    native = pytest.importorskip("orion_tpu.data.native")
    rdr = native.NativeReader(path, np.uint16)
    with pytest.raises(IndexError):
        rdr.gather(np.asarray([len(tokens) - 10]), 129)
    rdr.close()


@pytest.mark.parametrize("use_native", [True, False])
def test_memmap_loader_native_and_fallback_agree(token_file, use_native):
    path, _ = token_file
    cfg = DataConfig(source="memmap", path=path, batch_size=4, seq_len=64,
                     use_native_loader=use_native)
    ldr = MemmapLoader(cfg, 0, 1, vocab_size=50000)
    batch = ldr.batch_at(3)
    assert batch["inputs"].shape == (4, 64)
    np.testing.assert_array_equal(batch["inputs"][:, 1:],
                                  batch["targets"][:, :-1])
    # Same (seed, step) -> same windows regardless of reader backend.
    cfg2 = DataConfig(source="memmap", path=path, batch_size=4, seq_len=64,
                      use_native_loader=not use_native)
    ldr2 = MemmapLoader(cfg2, 0, 1, vocab_size=50000)
    np.testing.assert_array_equal(batch["inputs"],
                                  ldr2.batch_at(3)["inputs"])


def test_memmap_training_smoke(token_file):
    """train.py path over a real token file (memmap + native reader)."""
    import jax

    from orion_tpu.config import get_config
    from orion_tpu.train import Trainer

    path, _ = token_file
    cfg = get_config("tiny", [
        "runtime.platform=cpu",
        "data.source=memmap", f"data.path={path}", "data.batch_size=4",
        "data.seq_len=32", "model.vocab_size=50304",
        "train.num_steps=3", "train.log_interval=100",
        "optimizer.warmup_steps=1",
    ])
    t = Trainer(cfg)
    state, _ = t.restore_or_init()
    state, m = t.train_step(state, t.global_batch(0))
    assert np.isfinite(float(jax.device_get(m["loss"])))


# -- sequence packing ---------------------------------------------------------


def test_pack_rows_invariants():
    from orion_tpu.data.loader import pack_rows

    docs = [[np.arange(1, 6), np.arange(10, 14)],   # lens 5, 4 -> 4+3 pairs
            [np.arange(20, 40)]]                     # one long doc
    b = pack_rows(docs, seq_len=10)
    assert set(b) == {"inputs", "targets", "segment_ids", "positions",
                      "loss_mask"}
    # Row 0: doc 1 occupies 4 slots (seg 1), doc 2 occupies 3 (seg 2).
    np.testing.assert_array_equal(
        b["segment_ids"][0], [1, 1, 1, 1, 2, 2, 2, 0, 0, 0]
    )
    np.testing.assert_array_equal(
        b["positions"][0], [0, 1, 2, 3, 0, 1, 2, 0, 0, 0]
    )
    np.testing.assert_array_equal(
        b["loss_mask"][0], [1, 1, 1, 1, 1, 1, 1, 0, 0, 0]
    )
    # Targets are next-token within each document.
    np.testing.assert_array_equal(b["inputs"][0][:4], [1, 2, 3, 4])
    np.testing.assert_array_equal(b["targets"][0][:4], [2, 3, 4, 5])
    np.testing.assert_array_equal(b["inputs"][0][4:7], [10, 11, 12])
    np.testing.assert_array_equal(b["targets"][0][4:7], [11, 12, 13])
    # Long doc truncates to the row.
    assert b["loss_mask"][1].sum() == 10


def test_pack_rows_carries_truncated_doc_tail():
    """A doc crossing the row boundary resumes in the next row — the tail
    pairs are trained, not dropped (only the final row's overhang is lost)."""
    from orion_tpu.data.loader import pack_rows

    long = np.arange(100, 116)                      # 16 tokens, 15 pairs
    b = pack_rows([[long], []], seq_len=10)
    # Row 0: first 10 pairs of the doc.
    np.testing.assert_array_equal(b["inputs"][0], long[:10])
    np.testing.assert_array_equal(b["targets"][0], long[1:11])
    # Row 1: the carried tail resumes at token 10 — pair (110 -> 111) first,
    # so no pair is dropped or duplicated across the split.
    np.testing.assert_array_equal(b["inputs"][1][:5], long[10:15])
    np.testing.assert_array_equal(b["targets"][1][:5], long[11:16])
    assert b["loss_mask"][1].sum() == 5
    # The tail is its own segment with restarted positions.
    np.testing.assert_array_equal(b["segment_ids"][1][:5], [1] * 5)
    np.testing.assert_array_equal(b["positions"][1][:5], np.arange(5))


def test_pack_rows_masks_empty_rows():
    """A row with no packable document (all spans < 2 tokens) trains
    nothing: fully masked, segment 0 everywhere."""
    from orion_tpu.data.loader import pack_rows

    b = pack_rows([[np.array([7])], [np.array([1, 2, 3])]], seq_len=4)
    assert b["loss_mask"][0].sum() == 0
    assert (b["segment_ids"][0] == 0).all()
    assert b["loss_mask"][1].sum() == 2


def test_synthetic_packed_loader():
    from orion_tpu.config import DataConfig
    from orion_tpu.data import make_loader

    cfg = DataConfig(batch_size=4, seq_len=64, packed=True)
    loader = make_loader(cfg, vocab_size=251)
    b1, b2 = loader.batch_at(3), loader.batch_at(3)
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])  # deterministic
    assert b1["segment_ids"].max() >= 2       # actually multi-document
    assert (b1["loss_mask"].sum(1) > 48).all()  # rows mostly filled
    # Positions restart at every segment boundary.
    seg, pos = b1["segment_ids"][0], b1["positions"][0]
    starts = np.flatnonzero(np.diff(seg, prepend=seg[0] - 1) != 0)
    valid = seg > 0
    assert (pos[starts[valid[starts]]] == 0).all()


def test_memmap_packed_splits_at_eos(tmp_path):
    from orion_tpu.config import DataConfig
    from orion_tpu.data import make_loader

    rng = np.random.default_rng(0)
    toks = rng.integers(1, 250, size=50_000).astype(np.uint16)
    toks[::17] = 0    # sprinkle eos
    path = str(tmp_path / "t.u16")
    toks.tofile(path)
    cfg = DataConfig(source="memmap", path=path, batch_size=4, seq_len=32,
                     packed=True, eos_token_id=0, use_native_loader=False)
    loader = make_loader(cfg, vocab_size=251)
    b = loader.batch_at(5)
    assert b["segment_ids"].max() >= 2
    # No target may be a cross-document prediction: inside one segment the
    # (input, target) pairs chain (targets[i] == inputs[i+1]).
    seg, inp, tgt = b["segment_ids"][0], b["inputs"][0], b["targets"][0]
    for i in range(len(seg) - 1):
        if seg[i] != 0 and seg[i] == seg[i + 1]:
            assert tgt[i] == inp[i + 1]


def test_packed_training_runs_and_learns():
    """End-to-end: packed batches through the jit train step on a dp mesh;
    the synthetic structure is learnable, so loss must fall."""
    from orion_tpu.config import get_config
    from orion_tpu.train import Trainer

    cfg = get_config(
        "tiny-llama",
        ["runtime.platform=cpu", "data.packed=true", "data.batch_size=8",
         "parallel.dp=2", "train.num_steps=30", "train.log_interval=1000",
         "optimizer.warmup_steps=3"],
    )
    hist = Trainer(cfg).fit()
    assert hist[-1].loss < hist[0].loss - 0.3, (hist[0].loss, hist[-1].loss)


def test_packed_training_composes_with_pipeline():
    """Packed rows x pp (r4 restriction lifted): pipelined packed training
    matches the single-layout packed trajectory — segment masks and
    per-doc positions slice per microbatch and are looked up per stage."""
    import jax as _jax
    import numpy as _np

    from orion_tpu.config import get_config
    from orion_tpu.train import Trainer

    def run(axes):
        overrides = [
            "runtime.platform=cpu", "data.packed=true", "data.batch_size=4",
            "data.seq_len=32", "train.num_steps=3", "train.log_interval=100",
            "optimizer.warmup_steps=1",
        ] + [f"parallel.{k}={v}" for k, v in axes.items()]
        t = Trainer(get_config("tiny-llama", overrides))
        state, _ = t.restore_or_init()
        losses = []
        for step in range(3):
            state, m = t.train_step(state, t.global_batch(step))
            losses.append(float(_jax.device_get(m["loss"])))
        return losses

    base = run({})
    pp = run({"pp": 2, "pp_microbatches": 2})
    _np.testing.assert_allclose(pp, base, rtol=2e-4)


def test_pack_rows_skips_degenerate_docs():
    """A <2-token document must be skipped, not end the row's packing."""
    from orion_tpu.data.loader import pack_rows

    b = pack_rows([[np.array([7]), np.array([1, 2, 3, 4])]], seq_len=8)
    assert b["loss_mask"][0].sum() == 3          # the 4-token doc packed
    np.testing.assert_array_equal(b["inputs"][0][:3], [1, 2, 3])


import pytest as _pytest


@_pytest.mark.parametrize("method", ["ring", "ring_striped", "ulysses"])
def test_packed_composes_with_sequence_parallelism(method):
    """Packed batches under sp=2 (segment ids + custom positions sharded —
    and, for ring_striped, permuted — over the sequence) match the sp=1
    loss trajectory for every sequence method."""
    from orion_tpu.config import get_config
    from orion_tpu.train import Trainer

    def run(axes):
        cfg = get_config(
            "tiny-llama",
            ["runtime.platform=cpu", "data.packed=true", "data.batch_size=8",
             "data.seq_len=64", "train.num_steps=3",
             "train.log_interval=1000", "optimizer.warmup_steps=1",
             f"parallel.sequence_method={method}"] + axes,
        )
        return Trainer(cfg).fit()

    base = run(["parallel.dp=4"])
    sp = run(["parallel.dp=2", "parallel.sp=2"])
    for a, b in zip(base, sp):
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-3, atol=2e-3)


def test_pack_rows_drop_counter_observable():
    """The bounded token loss at carry-group resets is tallied
    in loader.pack_stats so it can be monitored at scale."""
    import numpy as np

    from orion_tpu.data import loader as L

    L.pack_stats["dropped_tokens"] = 0
    long = np.arange(25, dtype=np.int32)       # 24 pairs >> seq_len
    # Row 0 packs 10 pairs, tail (14 pairs) carries; carry_group=1 resets
    # the carry at row 1 -> the whole tail is dropped and tallied.
    L.pack_rows([[long], []], seq_len=10, carry_group=1)
    assert L.pack_stats["dropped_tokens"] == 14
    # No reset boundary crossed with the carry non-empty: nothing tallied.
    L.pack_stats["dropped_tokens"] = 0
    L.pack_rows([[long], []], seq_len=10, carry_group=2)
    assert L.pack_stats["dropped_tokens"] == 0
