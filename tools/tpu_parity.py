#!/usr/bin/env python
"""On-TPU compiled parity check for every Pallas kernel in the repo.

Runs the fused kernels *compiled* on the real chip (interpret=False) and
compares fwd + grads against the xla reference ops:

  - flash attention: plain GQA causal; sliding window (full dq/dk/dv);
    segment-packed; explicit-position (striped-ring layout); the benchmark
    cells' own shapes (train 8192 under window 4096 with all three
    gradients; Laguna's and Mixtral's prefill layers over a padded burst's
    segments)
  - flash_attention_with_lse: out + lse parity and grads THROUGH the lse
    (a two-block ring-style merge, exactly how parallel/sequence.py uses it)
  - paged decode attention: gather parity, fused in-kernel KV write,
    sliding window, ragged tail lengths, int8 pools
  - multi-query ragged paged attention (speculative verification):
    W in {2, 5} x {float, int8 kv_quant} x {full, sliding window}, fused
    multi-token write with BITWISE pool/scale checks vs the host-side
    quantize
  - token-TREE verification masks on the same kernel: {branchy,
    chain-degenerate} x {float, int8} x {full, window}
  - blockwise paged-flash prefill (chunk queries over the paged history,
    chunk pages written in-kernel): {float, int8} x {full, window}
  - fused RMSNorm, fused RoPE; and the rotation a decode program runs
    (``--only rope`` runs these alone): one new token a slot is under
    ``ops.rope.KERNEL_MIN_SEQ`` and so the XLA form, held here to the kernel
    on the same rows at ``[32, 1, 40 | 72 | 8, 128]``, positions to 12287,
    the plain, the half-rotated and the YaRN table
  - latent attention (``--only latent`` runs these alone): the latent
    paged decode kernel at the GLM cell's shapes against the XLA absorbed
    form, against the expanded form, and a control without the rotary term.
  - a sink and values narrower than keys (``--only sink`` runs these
    alone; PR 50): the flash forward and the paged decode kernel over packed
    key rows at the MiMo cell's shapes, against the XLA form;
  - Kimi delta attention (``--only kda`` runs these alone): the chunked
    form and the decode kernel ``kda_decode`` at the Ling cell's shapes, with
    decays near 1 and at the -5 bound, against the plain recurrence in
    float32; a dead slot passed by; a control without the erase term.
  - power retention (``--only retention`` runs these alone): the chunked
    prefill kernel (outputs and the state it hands out, ragged lengths),
    the decode kernel over a state row and a paged tail (tail only, state
    and tail across a chunk's end, an empty tail; the page it wrote
    bitwise) and the fold, each against the XLA form; and, with gates near
    1, the chain prefill -> fold -> decode against the benchmark's
    QUADRATIC float32 reference (which shares nothing with the program),
    with a control that reads the state as zeros and has to fail; at the
    Brumby cell's shapes (40 query / 8 kv heads x 128, the program's chunk,
    64-token pages) on the chip and at a head of 16 under the interpreter

The paged / ragged / tree / prefill groups run at two geometries: a small
one (8 query / 4 kv heads, 4 pages a row, window 100) and the serving leg's
of ``chip_smoke.py`` — Mistral-7B's 32 query / 8 kv heads x 128, 64-token
pages, 80 pages a row, window 4096 — so the kernels are compiled at the
block shapes the engine actually dispatches. The paged and ragged groups
also run at the Laguna cell's decode shapes (query groups of 6 and 9, 76
pages a row, window 512).

The pytest suite runs these kernels only through the Pallas interpreter on
the fake-CPU mesh (tests/conftest.py); this script is the complementary
real-hardware check (Mosaic compile != interpreter semantics):

    python tools/tpu_parity.py

Each group runs under a guard: a kernel Mosaic refuses counts as one failed
check, names the error, and the remaining groups still run. The last line
is ``ALL-OK <green> of <run> checks`` (exit 0) or ``SOME-FAIL ...`` (exit
1). Without a TPU backend the script exits non-zero.

``--interpret`` runs the identical checks through the Pallas interpreter on
the CPU (a self-test of this script's own logic; it does NOT validate
Mosaic compilation).
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import dataclasses
import sys
import traceback

INTERP = False  # set by --interpret; default is compiled-on-TPU

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.ops.attention import attention_xla
from orion_tpu.ops.norms import _rmsnorm_xla
from orion_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
)
from orion_tpu.ops.pallas.norms import rmsnorm_pallas
from orion_tpu.ops.pallas.rope import rope_pallas
from orion_tpu.ops.rope import _rope_xla

RESULTS: list[tuple[str, bool]] = []   # every check run, in order


def record(name: str, ok: bool, detail: str = "") -> bool:
    RESULTS.append((name, ok))
    print(f"{'OK' if ok else 'FAIL'} {name}{detail}", flush=True)
    return ok


def check(name, got, want, tol):
    got32 = got.astype(jnp.float32)
    want32 = want.astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(got32 - want32))) / (
        float(jnp.max(jnp.abs(want32))) + 1e-6
    )
    # `not rel < tol` so a NaN fails.
    return record(name, rel < tol, f": rel={rel:.3e}")


def bitwise(name, pairs) -> bool:
    return record(name, all(
        bool((np.asarray(a) == np.asarray(b)).all()) for a, b in pairs
    ))


def guarded(name, fn, *args) -> None:
    """Run one group of checks; an exception (a Mosaic compile refusal, a
    device fault) is ONE failed check naming the error, and the script
    carries on to the next group."""
    try:
        fn(*args)
    except Exception as e:  # the whole point: report it and keep going
        traceback.print_exc()
        head = str(e).strip().splitlines()[0][:300] if str(e) else ""
        record(f"{name} (raised {type(e).__name__}: {head})", False)


@dataclasses.dataclass(frozen=True)
class Geom:
    """Kernel geometry of one paged/ragged/tree/prefill check group."""

    tag: str
    N: int            # query heads
    K: int            # kv heads
    P: int            # pages per row
    num_pages: int    # pool pages
    window: int       # sliding window of the windowed variants
    straddle: int     # a position on a page's last slot, past the window
    P_pre: int        # prefill check: prefix pages walked
    NC: int           # prefill check: chunk pages
    H: int = 128
    psz: int = 64
    B: int = 4

    def page_table(self) -> jax.Array:
        # Distinct non-scratch pages for every (row, logical page).
        perm = np.random.default_rng(3).permutation(self.num_pages - 1) + 1
        return jnp.asarray(
            perm[: self.B * self.P].reshape(self.B, self.P), jnp.int32
        )


SMALL = Geom("", N=8, K=4, P=4, num_pages=64, window=100, straddle=127,
             P_pre=3, NC=2)
# chip_smoke.py's serving leg: Mistral-7B heads, rows past the 4096 window.
SERVE = Geom(" @serve", N=32, K=8, P=80, num_pages=384, window=4096,
             straddle=4223, P_pre=70, NC=4)
# The laguna-s-2.1 cell's decode shapes: query groups of 6 (full layers) and
# 9 (window layers), a 76-page table walked in blocks, window 512.
LAGUNA = [
    Geom(f" @laguna-G{n // 8}", N=n, K=8, P=76, num_pages=384, window=512,
         straddle=1023, P_pre=70, NC=4)
    for n in (48, 72)
]


def quantized_pools(k_pool, v_pool, psz):
    """(kq, vq, k_sc, v_sc, kd, vd): int8 pools + lanes-padded scale pools
    from float pools via the shared host-side quantize, and their
    dequantized f32 twins for the reference."""
    from orion_tpu.infer.kv_cache import SCALE_LANES, quantize_kv

    out = []
    for pool in (k_pool, v_pool):
        q, s = quantize_kv(pool.transpose(0, 2, 1, 3))
        q = q.transpose(0, 2, 1, 3)
        sc = jnp.zeros(
            (pool.shape[0], pool.shape[1], SCALE_LANES), jnp.float32
        ).at[:, :, :psz].set(s.transpose(0, 2, 1))
        out.append((q, sc, q.astype(jnp.float32) * sc[:, :, :psz][..., None]))
    (kq, k_sc, kd), (vq, v_sc, vd) = out
    return kq, vq, k_sc, v_sc, kd, vd


def paged_checks(g: Geom) -> None:
    """Compiled paged decode attention vs the gather reference: plain,
    fused in-kernel KV write, sliding window, traced layer base, ragged
    tail lengths, int8 pools."""
    from orion_tpu.ops.pallas.paged_attention import paged_attention

    t = g.tag
    N, K, B, H, psz, P, num_pages = g.N, g.K, g.B, g.H, g.psz, g.P, g.num_pages
    keys = jax.random.split(jax.random.key(7), 6)
    q = jax.random.normal(keys[0], (B, N, H), jnp.bfloat16)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (B, K, H), jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (B, K, H), jnp.bfloat16)
    page_table = g.page_table()
    # Ragged: 1 token, mid-page, a page's last slot, full context.
    last_pos = jnp.asarray([0, 93, g.straddle, P * psz - 1], jnp.int32)

    def reference(q, kp, vp, window=None):
        k_ctx = kp[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        v_ctx = vp[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        kv_pos = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
        mask = kv_pos <= last_pos[:, None, None]
        if window is not None:
            mask &= last_pos[:, None, None] - kv_pos < window
        return attention_xla(q[:, None], k_ctx, v_ctx, causal=False,
                             mask=mask)[:, 0]

    # Plain ragged decode.
    out = jax.jit(
        lambda q, kp, vp: paged_attention(
            q, kp, vp, page_table, last_pos, interpret=INTERP)
    )(q, k_pool, v_pool)
    check(f"paged{t} fwd ragged", out, reference(q, k_pool, v_pool), 2e-2)

    # Fused in-kernel KV write (input/output aliasing on the real chip).
    rows = page_table[jnp.arange(B), last_pos // psz]
    kp_ref = k_pool.at[rows, :, last_pos % psz].set(k_new)
    vp_ref = v_pool.at[rows, :, last_pos % psz].set(v_new)
    out_w, kp_w, vp_w = jax.jit(
        lambda q, kp, vp, kn, vn: paged_attention(
            q, kp, vp, page_table, last_pos, k_new=kn, v_new=vn,
            interpret=INTERP)
    )(q, k_pool, v_pool, k_new, v_new)
    check(f"paged{t} fused-write fwd", out_w,
          reference(q, kp_ref, vp_ref), 2e-2)
    check(f"paged{t} fused-write k_pool", kp_w, kp_ref, 1e-6)
    check(f"paged{t} fused-write v_pool", vp_w, vp_ref, 1e-6)

    # Sliding window (page-skip + DMA elision path), incl. fused write.
    W = g.window
    out_win = jax.jit(
        lambda q, kp, vp, kn, vn: paged_attention(
            q, kp, vp, page_table, last_pos, k_new=kn, v_new=vn, window=W,
            interpret=INTERP)[0]
    )(q, k_pool, v_pool, k_new, v_new)
    check(f"paged{t} window fwd", out_win,
          reference(q, kp_ref, vp_ref, window=W), 2e-2)

    # Traced layer_base over a flat 2-layer pool (the layer-scan calling
    # convention of the serving path).
    kp2 = jnp.concatenate([k_pool, k_pool * 0.5], axis=0)
    vp2 = jnp.concatenate([v_pool, v_pool * 0.5], axis=0)
    out_l1 = jax.jit(
        lambda q, kp, vp: paged_attention(
            q, kp, vp, page_table, last_pos,
            layer_base=jnp.int32(num_pages), interpret=INTERP)
    )(q, kp2, vp2)
    check(f"paged{t} layer_base fwd", out_l1,
          reference(q, k_pool * 0.5, v_pool * 0.5), 2e-2)

    # int8 KV pools (inference.kv_quant): in-kernel dequantization + the
    # fused quantized write, vs attention over the dequantized pools —
    # full and windowed.
    from orion_tpu.infer.kv_cache import quantize_kv

    kq, vq, k_sc, v_sc, kd, vd = quantized_pools(k_pool, v_pool, psz)
    knq, kns = quantize_kv(k_new)
    vnq, vns = quantize_kv(v_new)
    kd = kd.at[rows, :, last_pos % psz].set(
        knq.astype(jnp.float32) * kns[..., None])
    vd = vd.at[rows, :, last_pos % psz].set(
        vnq.astype(jnp.float32) * vns[..., None])
    for wname, win in (("", None), (" window", W)):
        out_q = jax.jit(
            lambda q, kp, vp, ksc, vsc, kn, vn, w=win: paged_attention(
                q, kp, vp, page_table, last_pos, k_new=kn, v_new=vn,
                k_scale=ksc, v_scale=vsc, window=w, interpret=INTERP)[0]
        )(q, kq, vq, k_sc, v_sc, k_new, v_new)
        check(f"paged{t} int8{wname} fwd", out_q,
              reference(q, kd.astype(jnp.bfloat16),
                        vd.astype(jnp.bfloat16), window=win), 2e-2)


def ragged_paged_checks(g: Geom) -> None:
    """Compiled multi-query ragged paged attention (the speculative-
    verification kernel) vs the scatter + masked-gather reference:
    W in {2, 5} queries per slot x {float, int8} pools x {full, sliding
    window}, page-boundary straddles, ragged per-slot lengths, in-kernel
    fused multi-token writes (pool bytes bitwise; int8 scales bitwise vs
    the shared host-side quantize)."""
    from orion_tpu.infer.kv_cache import quantize_kv
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    t = g.tag
    N, K, B, H, psz, P, num_pages = g.N, g.K, g.B, g.H, g.psz, g.P, g.num_pages
    keys = jax.random.split(jax.random.key(13), 6)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.bfloat16)
    page_table = g.page_table()

    def reference(q, kp, vp, start, lens, k_new, v_new, window=None):
        # Scatter every real token (padding tokens park on a dummy extra
        # row), gather, mask per query incl. same-dispatch causality.
        W = q.shape[1]
        steps = jnp.arange(W, dtype=jnp.int32)[None, :]
        q_pos = start[:, None] + steps
        valid = steps < lens[:, None]
        kp = jnp.concatenate(
            [kp, jnp.zeros((1,) + kp.shape[1:], kp.dtype)])
        vp = jnp.concatenate(
            [vp, jnp.zeros((1,) + vp.shape[1:], vp.dtype)])
        rows = jnp.where(
            valid, page_table[jnp.arange(B)[:, None], q_pos // psz],
            num_pages,
        )
        off = q_pos % psz
        kp = kp.at[rows, :, off].set(k_new.astype(kp.dtype))[:num_pages]
        vp = vp.at[rows, :, off].set(v_new.astype(vp.dtype))[:num_pages]
        k_ctx = kp[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        v_ctx = vp[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        kv = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
        mask = kv <= q_pos[:, :, None]
        if window is not None:
            mask &= kv >= (q_pos - window + 1)[:, :, None]
        out = attention_xla(q, k_ctx, v_ctx, causal=False, mask=mask)
        return jnp.where(valid[:, :, None, None], out, 0.0), kp, vp

    for W in (2, 5):
        q = jax.random.normal(keys[0], (B, W, N, H), jnp.bfloat16)
        k_new = jax.random.normal(keys[3], (B, W, K, H), jnp.bfloat16)
        v_new = jax.random.normal(keys[4], (B, W, K, H), jnp.bfloat16)
        # Ragged: from zero, 1 real token, page straddle, table end.
        start = jnp.asarray([0, 93, g.straddle, P * psz - W], jnp.int32)
        lens = jnp.asarray([W, 1, min(W, 3), W], jnp.int32)
        steps = jnp.arange(W, dtype=jnp.int32)[None, :]
        vmask = (steps < lens[:, None])[:, :, None, None]

        def masked(o):
            return jnp.where(vmask, o.astype(jnp.float32), 0.0)

        # Float pools: fwd + bitwise written pools.
        ref_o, kp_r, vp_r = reference(
            q, k_pool, v_pool, start, lens, k_new, v_new)
        out, kp_w, vp_w = jax.jit(
            lambda q, kp, vp, kn, vn, st, ln: ragged_paged_attention(
                q, kp, vp, page_table, st, ln, k_new=kn, v_new=vn,
                interpret=INTERP)
        )(q, k_pool, v_pool, k_new, v_new, start, lens)
        check(f"ragged{t} W={W} fwd", masked(out), ref_o, 2e-2)
        check(f"ragged{t} W={W} k_pool", kp_w, kp_r, 1e-6)
        check(f"ragged{t} W={W} v_pool", vp_w, vp_r, 1e-6)

        # Sliding window (behind-window page clamp + per-query mask).
        ref_w, _, _ = reference(
            q, k_pool, v_pool, start, lens, k_new, v_new, window=g.window)
        out_w = jax.jit(
            lambda q, kp, vp, kn, vn, st, ln: ragged_paged_attention(
                q, kp, vp, page_table, st, ln, k_new=kn, v_new=vn,
                window=g.window, interpret=INTERP)[0]
        )(q, k_pool, v_pool, k_new, v_new, start, lens)
        check(f"ragged{t} W={W} window fwd", masked(out_w), ref_w, 2e-2)

        # int8 pools (inference.kv_quant): in-kernel quantized write of
        # all W drafts — scales and bytes bitwise vs the host quantize —
        # and dequantizing attention, with and without the window.
        kq, vq, k_sc, v_sc, kd, vd = quantized_pools(k_pool, v_pool, psz)
        knq, kns = quantize_kv(k_new)
        vnq, vns = quantize_kv(v_new)
        for wname, win in (("", None), (" window", g.window)):
            ref_q, _, _ = reference(
                q, kd.astype(jnp.bfloat16), vd.astype(jnp.bfloat16),
                start, lens,
                knq.astype(jnp.float32) * kns[..., None],
                vnq.astype(jnp.float32) * vns[..., None], window=win)
            out_q, kp_q, vp_q, ks_q, vs_q = jax.jit(
                lambda q, kp, vp, ksc, vsc, kn, vn, st, ln, w=win:
                ragged_paged_attention(
                    q, kp, vp, page_table, st, ln, k_new=kn, v_new=vn,
                    k_scale=ksc, v_scale=vsc, window=w, interpret=INTERP)
            )(q, kq, vq, k_sc, v_sc, k_new, v_new, start, lens)
            check(f"ragged{t} W={W} int8{wname} fwd", masked(out_q),
                  ref_q, 3e-2)
            if win is None:
                # Written bytes/scales: bitwise vs the host-side
                # quantization at every real (slot, draft) position.
                pairs = []
                for b in range(B):
                    for j in range(int(lens[b])):
                        pos = int(start[b]) + j
                        r, o = int(page_table[b, pos // psz]), pos % psz
                        pairs += [
                            (kp_q[r, :, o], knq[b, j]),
                            (ks_q[r, :, o], kns[b, j]),
                            (vp_q[r, :, o], vnq[b, j]),
                            (vs_q[r, :, o], vns[b, j]),
                        ]
                bitwise(f"ragged{t} W={W} int8 write bitwise", pairs)


def ragged_tree_checks(g: Geom) -> None:
    """Compiled token-TREE verification on the ragged kernel: the packed
    ancestor mask + depth scalar-prefetch path, {branchy,
    chain-degenerate} x {float, int8} x {full, sliding window}.

    Chain-degenerate trees must be BITWISE the plain kernel (outputs and
    written pools — the tree machinery adds ops, not numerics); branchy
    trees check against the ancestor-masked scatter+gather reference
    (pools bitwise either way: writes are slot-sequential and
    tree-agnostic)."""
    from orion_tpu.infer.kv_cache import quantize_kv
    from orion_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    t = g.tag
    N, K, B, H, psz, P, num_pages = g.N, g.K, g.B, g.H, g.psz, g.P, g.num_pages
    W = 5
    keys = jax.random.split(jax.random.key(17), 6)
    q = jax.random.normal(keys[0], (B, W, N, H), jnp.bfloat16)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (B, W, K, H), jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (B, W, K, H), jnp.bfloat16)
    page_table = g.page_table()
    start = jnp.asarray([0, 93, g.straddle, P * psz - W], jnp.int32)
    lens = jnp.asarray([W, 1, 3, W], jnp.int32)
    steps = np.arange(W, dtype=np.int64)
    chain_dep = jnp.asarray(np.tile(steps, (B, 1)), jnp.int32)
    chain_words = jnp.asarray(
        np.tile((np.int64(1) << (steps + 1)) - 1, (B, 1)), jnp.int32
    )
    # Branchy shape shared by all rows: 1<-0, 2<-1 (primary), 3<-0
    # (sibling), 4<-3 (nested) — DraftTree's flattened layout.
    parents = [0, 1, 0, 3]
    dep_row, word_row = [0], [1]
    for j, par in enumerate(parents):
        dep_row.append(dep_row[par] + 1)
        word_row.append(word_row[par] | (1 << (j + 1)))
    tree_dep = jnp.asarray(np.tile(dep_row, (B, 1)), jnp.int32)
    tree_words = jnp.asarray(np.tile(word_row, (B, 1)), jnp.int32)

    def tree_reference(q, kp, vp, kn, vn, depths, words, window=None):
        steps_j = jnp.arange(W, dtype=jnp.int32)[None, :]
        wpos = start[:, None] + steps_j
        valid = steps_j < lens[:, None]
        kpx = jnp.concatenate(
            [kp, jnp.zeros((1,) + kp.shape[1:], kp.dtype)])
        vpx = jnp.concatenate(
            [vp, jnp.zeros((1,) + vp.shape[1:], vp.dtype)])
        rows = jnp.where(
            valid, page_table[jnp.arange(B)[:, None], wpos // psz],
            num_pages,
        )
        off = wpos % psz
        kpx = kpx.at[rows, :, off].set(kn.astype(kpx.dtype))[:num_pages]
        vpx = vpx.at[rows, :, off].set(vn.astype(vpx.dtype))[:num_pages]
        k_ctx = kpx[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        v_ctx = vpx[page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, H)
        kv = jnp.arange(P * psz, dtype=jnp.int32)[None, None, :]
        slot = kv - start[:, None, None]
        in_new = (slot >= 0) & (slot < W)
        slot_c = jnp.clip(slot, 0, W - 1)
        anc = ((words[:, :, None] >> steps_j[None, :, :]) & 1).astype(bool)
        anc = anc | jnp.eye(W, dtype=bool)[None]
        vis = jnp.take_along_axis(
            anc, jnp.broadcast_to(slot_c, (B, W, P * psz)), axis=2)
        mask = jnp.where(in_new, vis, kv < start[:, None, None])
        if window is not None:
            sdep = jnp.take_along_axis(
                jnp.broadcast_to(depths[:, None, :], (B, 1, W)),
                slot_c, axis=2)
            mask &= jnp.where(
                in_new, sdep >= depths[:, :, None] - window + 1,
                kv >= start[:, None, None] + depths[:, :, None]
                - window + 1,
            )
        out = attention_xla(q, k_ctx, v_ctx, causal=False, mask=mask)
        vmask = (steps_j < lens[:, None])[:, :, None, None]
        return jnp.where(vmask, out.astype(jnp.float32), 0.0), kpx, vpx

    def masked(o):
        steps_j = jnp.arange(W, dtype=jnp.int32)[None, :]
        vmask = (steps_j < lens[:, None])[:, :, None, None]
        return jnp.where(vmask, o.astype(jnp.float32), 0.0)

    # Float pools: chain-degenerate bitwise vs the plain kernel, then the
    # branchy mask vs the reference — with and without a window.
    for wname, win in (("", None), (" window", g.window)):
        plain = jax.jit(
            lambda q, kp, vp, kn, vn, w=win: ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                window=w, interpret=INTERP)
        )(q, k_pool, v_pool, k_new, v_new)
        chain = jax.jit(
            lambda q, kp, vp, kn, vn, w=win: ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                window=w, tree_mask=chain_words, depths=chain_dep,
                interpret=INTERP)
        )(q, k_pool, v_pool, k_new, v_new)
        bitwise(f"tree{t} chain-degenerate{wname} bitwise",
                zip(plain, chain))

        ref_o, kpr, vpr = tree_reference(
            q, k_pool, v_pool, k_new, v_new, tree_dep, tree_words,
            window=win)
        out_t, kp_t, vp_t = jax.jit(
            lambda q, kp, vp, kn, vn, w=win: ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                window=w, tree_mask=tree_words, depths=tree_dep,
                interpret=INTERP)
        )(q, k_pool, v_pool, k_new, v_new)
        check(f"tree{t} branchy{wname} fwd", masked(out_t), ref_o, 2e-2)
        if win is None:
            check(f"tree{t} branchy k_pool", kp_t, kpr, 1e-6)
            check(f"tree{t} branchy v_pool", vp_t, vpr, 1e-6)

    # int8 pools: branchy tree attention vs the dequantized reference +
    # chain-degenerate bitwise vs the plain int8 kernel (pools ride the
    # slot-sequential write, already pinned bitwise in
    # ragged_paged_checks).
    kq, vq, k_sc, v_sc, kd, vd = quantized_pools(k_pool, v_pool, psz)
    knq, kns = quantize_kv(k_new)
    vnq, vns = quantize_kv(v_new)
    for wname, win in (("", None), (" window", g.window)):
        plain_q = jax.jit(
            lambda q, kp, vp, ksc, vsc, kn, vn, w=win:
            ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                k_scale=ksc, v_scale=vsc, window=w, interpret=INTERP)
        )(q, kq, vq, k_sc, v_sc, k_new, v_new)
        chain_q = jax.jit(
            lambda q, kp, vp, ksc, vsc, kn, vn, w=win:
            ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                k_scale=ksc, v_scale=vsc, window=w,
                tree_mask=chain_words, depths=chain_dep,
                interpret=INTERP)
        )(q, kq, vq, k_sc, v_sc, k_new, v_new)
        bitwise(f"tree{t} int8 chain-degenerate{wname} bitwise",
                zip(plain_q, chain_q))

        ref_q, _, _ = tree_reference(
            q, kd.astype(jnp.bfloat16), vd.astype(jnp.bfloat16),
            knq.astype(jnp.float32) * kns[..., None],
            vnq.astype(jnp.float32) * vns[..., None],
            tree_dep, tree_words, window=win)
        out_q = jax.jit(
            lambda q, kp, vp, ksc, vsc, kn, vn, w=win:
            ragged_paged_attention(
                q, kp, vp, page_table, start, lens, k_new=kn, v_new=vn,
                k_scale=ksc, v_scale=vsc, window=w,
                tree_mask=tree_words, depths=tree_dep,
                interpret=INTERP)[0]
        )(q, kq, vq, k_sc, v_sc, k_new, v_new)
        check(f"tree{t} int8 branchy{wname} fwd", masked(out_q),
              ref_q, 3e-2)


def paged_prefill_checks(g: Geom) -> None:
    """Compiled blockwise paged-flash prefill (a chunk of NC pages of new
    queries over a P_pre-page paged history, the chunk's pages written
    in-kernel) vs the XLA body it replaces — dense prefix gather +
    masked attention + page scatter: {float, int8} x {full, window}.
    Row 0 carries a full prefix and a full chunk; row 1 a shorter prefix
    and a ragged 7-token chunk. Written chunk pages are bitwise (int8:
    bytes and scales vs the shared host-side quantize)."""
    from orion_tpu.infer.kv_cache import quantize_kv
    from orion_tpu.ops.pallas.paged_flash_prefill import paged_flash_prefill

    t = g.tag
    N, K, H, psz, num_pages = g.N, g.K, g.H, g.psz, g.num_pages
    B, P_pre, NC = 2, g.P_pre, g.NC
    S = NC * psz
    keys = jax.random.split(jax.random.key(19), 6)
    q = jax.random.normal(keys[0], (B, S, N, H), jnp.bfloat16)
    k_pool = jax.random.normal(keys[1], (num_pages, K, psz, H), jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], (num_pages, K, psz, H), jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (B, S, K, H), jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (B, S, K, H), jnp.bfloat16)
    perm = np.random.default_rng(5).permutation(num_pages - 1) + 1
    walk = jnp.asarray(
        perm[: B * (P_pre + NC)].reshape(B, P_pre + NC), jnp.int32)
    start = jnp.asarray([P_pre * psz, (P_pre - 1) * psz], jnp.int32)
    lens = jnp.asarray([S, 7], jnp.int32)
    real = (jnp.arange(S)[None, :] < lens[:, None])[:, :, None, None]

    def reference(kp, vp, kn, vn, window=None):
        pre = walk[:, :P_pre]
        k_pre = kp[pre].transpose(0, 1, 3, 2, 4).reshape(
            B, P_pre * psz, K, H)
        v_pre = vp[pre].transpose(0, 1, 3, 2, 4).reshape(
            B, P_pre * psz, K, H)
        kk = jnp.concatenate([k_pre, kn.astype(k_pre.dtype)], axis=1)
        vv = jnp.concatenate([v_pre, vn.astype(v_pre.dtype)], axis=1)
        pre_idx = jnp.arange(P_pre * psz, dtype=jnp.int32)
        loc = jnp.arange(S, dtype=jnp.int32)
        q_pos = start[:, None] + loc[None, :]                  # [B, S]
        kv_pos = jnp.concatenate(
            [jnp.broadcast_to(pre_idx[None], (B, P_pre * psz)), q_pos], 1)
        kv_real = jnp.concatenate(
            [pre_idx[None] < start[:, None], loc[None] < lens[:, None]], 1)
        mask = kv_real[:, None, :] & (
            kv_pos[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            mask &= kv_pos[:, None, :] >= (q_pos - window + 1)[:, :, None]
        out = attention_xla(q, kk, vv, causal=False, mask=mask)
        return jnp.where(real, out.astype(jnp.float32), 0.0)

    def masked(o):
        return jnp.where(real, o.astype(jnp.float32), 0.0)

    def chunk_pages(pool):
        # [B, NC, K, psz, ...] view of the rows the chunk's pages own.
        return pool[walk[:, P_pre:]]

    def paged(a):
        # [B, S, K, ...] -> page layout [B, NC, K, psz, ...].
        a = a.reshape(B, NC, psz, *a.shape[2:])
        return jnp.swapaxes(a, 2, 3)

    kq, vq, k_sc, v_sc, kd, vd = quantized_pools(k_pool, v_pool, psz)
    knq, kns = quantize_kv(k_new)             # [B,S,K,H] i8, [B,S,K]
    vnq, vns = quantize_kv(v_new)
    for wname, win in (("", None), (" window", g.window)):
        out, kp_w, vp_w = jax.jit(
            lambda q, kp, vp, kn, vn, w=win: paged_flash_prefill(
                q, kp, vp, walk, start, lens, kn, vn,
                n_prefix_pages=P_pre, window=w, interpret=INTERP)
        )(q, k_pool, v_pool, k_new, v_new)
        check(f"prefill{t}{wname} fwd", masked(out),
              reference(k_pool, v_pool, k_new, v_new, window=win), 2e-2)
        if win is None:
            bitwise(f"prefill{t} chunk pages bitwise", [
                (chunk_pages(kp_w), paged(k_new)),
                (chunk_pages(vp_w), paged(v_new)),
            ])

        # int8 history dequantized in-kernel; the chunk itself attends
        # RAW (like the XLA body's concat) and lands quantized.
        res = jax.jit(
            lambda q, kp, vp, ksc, vsc, kn, vn, w=win: paged_flash_prefill(
                q, kp, vp, walk, start, lens, kn, vn,
                n_prefix_pages=P_pre, window=w, k_scale=ksc, v_scale=vsc,
                interpret=INTERP)
        )(q, kq, vq, k_sc, v_sc, k_new, v_new)
        check(f"prefill{t} int8{wname} fwd", masked(res[0]),
              reference(kd.astype(jnp.bfloat16), vd.astype(jnp.bfloat16),
                        k_new, v_new, window=win), 3e-2)
        if win is None:
            bitwise(f"prefill{t} int8 chunk pages + scales bitwise", [
                (chunk_pages(res[1]), paged(knq)),
                (chunk_pages(res[2]), paged(vnq)),
                (chunk_pages(res[3])[..., :psz],
                 jnp.swapaxes(kns.reshape(B, NC, psz, K), 2, 3)),
                (chunk_pages(res[4])[..., :psz],
                 jnp.swapaxes(vns.reshape(B, NC, psz, K), 2, 3)),
            ])


def flash_checks() -> None:
    """Flash attention, GQA bf16, fwd + all three grads: causal, sliding
    window, segment-packed, explicit positions, and the lse merge."""
    B, S, N, K, H = 2, 512, 8, 4, 128
    q = jax.random.normal(jax.random.key(0), (B, S, N, H), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, S, K, H), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, S, K, H), jnp.bfloat16)

    def sq(o):
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def pair(name, f_pallas, f_xla, args=(q, k, v)):
        """fwd + grads of a (pallas, xla) function pair."""
        check(f"flash {name} fwd", jax.jit(f_pallas)(*args),
              jax.jit(f_xla)(*args), 2e-2)
        g_p = jax.jit(jax.grad(
            lambda *a: sq(f_pallas(*a)), argnums=(0, 1, 2)))(*args)
        g_x = jax.jit(jax.grad(
            lambda *a: sq(f_xla(*a)), argnums=(0, 1, 2)))(*args)
        for n, gp, gx in zip("qkv", g_p, g_x):
            check(f"flash {name} d{n}", gp, gx, 4e-2)

    pair("causal",
         lambda q, k, v: flash_attention(q, k, v, causal=True,
                                         interpret=INTERP),
         lambda q, k, v: attention_xla(q, k, v, causal=True))

    # Sliding-window flash (Mistral-family).
    pair("window",
         lambda q, k, v: flash_attention(q, k, v, window=128,
                                         interpret=INTERP),
         lambda q, k, v: attention_xla(q, k, v, causal=True, window=128))

    # Segment-packed flash (packed training batches).
    seg = (jnp.arange(S)[None, :] >= S // 3).astype(jnp.int32) + 1
    seg = jnp.broadcast_to(seg, (B, S))
    pair("segments",
         lambda q, k, v: flash_attention(
             q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg,
             interpret=INTERP),
         lambda q, k, v: attention_xla(
             q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg))

    # Explicit-position flash (the striped-ring layout): a striped
    # permutation of the sequence must reproduce the contiguous result.
    stripes = 4
    perm = jnp.arange(S).reshape(stripes, S // stripes).T.reshape(-1)
    pos = perm.astype(jnp.int32)  # slot i holds the token at global perm[i]
    striped = (q[:, perm], k[:, perm], v[:, perm])
    pair("positions",
         lambda a, b, c: flash_attention(
             a, b, c, causal=True, q_positions=pos, kv_positions=pos,
             interpret=INTERP),
         lambda a, b, c: attention_xla(
             a, b, c, causal=True, q_positions=pos, kv_positions=pos),
         striped)
    o_x = jax.jit(lambda q, k, v: attention_xla(q, k, v, causal=True))(
        q, k, v)
    check("flash positions vs contiguous",
          jax.jit(lambda a, b, c: flash_attention(
              a, b, c, causal=True, q_positions=pos, kv_positions=pos,
              interpret=INTERP))(*striped),
          o_x[:, perm], 2e-2)

    # flash_attention_with_lse: ring attention's blockwise unit. Out + lse
    # parity and grads THROUGH the lse via a two-block ring-style merge
    # (exactly parallel/sequence.py's accumulation).
    from orion_tpu.parallel.sequence import _merge_blocks

    half = S // 2
    iota = jnp.arange(S, dtype=jnp.int32)

    def merged(q_, k_, v_):
        o1, l1 = flash_attention_with_lse(
            q_, k_[:, :half], v_[:, :half], causal=True, q_positions=iota,
            kv_positions=iota[:half], interpret=INTERP)
        o2, l2 = flash_attention_with_lse(
            q_, k_[:, half:], v_[:, half:], causal=True, q_positions=iota,
            kv_positions=iota[half:], interpret=INTERP)
        o, _ = _merge_blocks(
            o1.astype(jnp.float32), l1, o2.astype(jnp.float32), l2)
        return o

    pair("lse merge", merged,
         lambda q, k, v: attention_xla(
             q, k, v, causal=True).astype(jnp.float32))


def flash_cell_checks() -> None:
    """Flash attention at the benchmark cells' own shapes (PR 32): the train
    cells' 8192 under window 4096 with all three gradients (4 query heads
    over one kv head: the XLA reference holds [heads, 8192, 8192] in f32),
    and the serving cells' prefill layers over a padded burst's segment ids
    (id 0 = padding; real rows compared): Laguna's 72 heads under window 512
    and 48 under none, Mixtral's 32 heads at 8 rows x 512."""
    def sq(o):
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def qkv(B, S, N, K=8):
        ks = jax.random.split(jax.random.key(S + N), 3)
        return (jax.random.normal(ks[0], (B, S, N, 128), jnp.bfloat16),
                jax.random.normal(ks[1], (B, S, K, 128), jnp.bfloat16),
                jax.random.normal(ks[2], (B, S, K, 128), jnp.bfloat16))

    q, k, v = qkv(1, 8192, 4, K=1)
    fns = (lambda *a: flash_attention(*a, window=4096, interpret=INTERP),
           lambda *a: attention_xla(*a, causal=True, window=4096))
    check("flash @train 8192 w4096 fwd", jax.jit(fns[0])(q, k, v),
          jax.jit(fns[1])(q, k, v), 2e-2)
    g_p, g_x = (jax.jit(jax.grad(lambda *a, f=f: sq(f(*a)),
                                 argnums=(0, 1, 2)))(q, k, v) for f in fns)
    for n, gp, gx in zip("qkv", g_p, g_x):
        check(f"flash @train 8192 w4096 d{n}", gp, gx, 4e-2)

    rng = np.random.default_rng(32)
    for tag, B, S, N, window in (("laguna 72 w512", 2, 2048, 72, 512),
                                 ("laguna 48 full", 2, 2048, 48, None),
                                 ("mixtral 32 full", 8, 512, 32, None)):
        real = rng.integers(S - 511, S + 1, size=B)
        real[0] = S
        seg = jnp.asarray(np.arange(S)[None, :] < real[:, None], jnp.int32)
        q, k, v = qkv(B, S, N)
        kw = dict(causal=True, window=window, q_segment_ids=seg,
                  kv_segment_ids=seg)
        rows = seg[:, :, None, None].astype(jnp.bfloat16)
        check(f"flash @{tag} {B}x{S} burst fwd",
              jax.jit(lambda q, k, v: flash_attention(
                  q, k, v, seg_pad_zero=True, interpret=INTERP, **kw))(
                      q, k, v) * rows,
              jax.jit(lambda q, k, v: attention_xla(q, k, v, **kw))(
                  q, k, v) * rows, 2e-2)


def norm_rope_checks() -> None:
    """Fused RMSNorm and RoPE, fwd + grads, at the bench width and at
    Mistral-7B's (the row/sequence blocks scale with the width)."""
    def sq(o):
        return jnp.sum(o.astype(jnp.float32) ** 2)

    for D in (2048, 4096):
        x = jax.random.normal(jax.random.key(0), (2, 512, D), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(3), (D,), jnp.float32) * 0.1 + 1.0
        check(
            f"rmsnorm D={D} fwd",
            jax.jit(lambda x, w: rmsnorm_pallas(x, w, interpret=INTERP))(x, w),
            jax.jit(lambda x, w: _rmsnorm_xla(x, w, 1e-5))(x, w),
            2e-2,
        )
        gp = jax.jit(jax.grad(
            lambda x, w: sq(rmsnorm_pallas(x, w, interpret=INTERP)),
            argnums=(0, 1)))(x, w)
        gx = jax.jit(jax.grad(
            lambda x, w: sq(_rmsnorm_xla(x, w, 1e-5)), argnums=(0, 1)))(x, w)
        check(f"rmsnorm D={D} dx", gp[0], gx[0], 4e-2)
        check(f"rmsnorm D={D} dw", gp[1], gx[1], 4e-2)

    pos = jnp.arange(512)[None, :].repeat(2, 0)
    for n_heads in (8, 32):
        xr = jax.random.normal(
            jax.random.key(0), (2, 512, n_heads, 128), jnp.bfloat16)
        check(
            f"rope N={n_heads} fwd",
            jax.jit(lambda x: rope_pallas(
                x, pos, theta=5e5, interpret=INTERP))(xr),
            jax.jit(lambda x: _rope_xla(x, pos, 5e5))(xr),
            2e-2,
        )
        gp = jax.jit(jax.grad(lambda x: sq(rope_pallas(
            x, pos, theta=5e5, interpret=INTERP))))(xr)
        gx = jax.jit(jax.grad(lambda x: sq(_rope_xla(x, pos, 5e5))))(xr)
        check(f"rope N={n_heads} dx", gp, gx, 4e-2)


def rope_decode_checks() -> None:
    """The rotation a decode program runs (one new token a slot, under
    ``ops.rope.KERNEL_MIN_SEQ``: the XLA form, compiled) against the kernel
    on the same rows, at the serving cells' head counts and tables, with
    positions up to the longest sequence a cell holds."""
    from orion_tpu.config import RopeConfig, get_config
    from orion_tpu.ops.rope import KERNEL_MIN_SEQ, apply_rope, rope_table

    impl = "pallas_interpret" if INTERP else "pallas"
    B, H, last = 32, 128, 12287
    pos = jnp.concatenate([
        jnp.asarray([last, last - 1, 0, 1], jnp.int32),
        jax.random.randint(jax.random.key(5), (B - 4,), 0, last + 1)])
    tables = {
        "plain 1e6": (1e6, None),
        "half-rotated": (1e4, RopeConfig(theta=1e4, rotary_fraction=0.5)),
        "yarn": (5e5, get_config("laguna-s-2.1").model.rope_full),
    }
    for S in sorted({1, KERNEL_MIN_SEQ - 1}):
        p2 = jnp.minimum(pos[:, None] + jnp.arange(S)[None, :], last)
        for N in (40, 72, 8):
            x = jax.random.normal(
                jax.random.key(N), (B, S, N, H), jnp.bfloat16)
            for tag, (theta, rope) in tables.items():
                table = None if rope is None else rope_table(H, rope)
                check(
                    f"rope decode [{B},{S},{N},{H}] {tag}: xla form vs kernel",
                    jax.jit(lambda x: apply_rope(
                        x, p2, theta=theta, rope=rope, impl=impl))(x),
                    jax.jit(lambda x: rope_pallas(
                        x, p2, theta=theta, table=table,
                        interpret=INTERP))(x),
                    1e-2,
                )


def retention_checks() -> None:
    from benchmarks.reference.brumby import _retention as quadratic
    from orion_tpu.ops import retention as ret
    from orion_tpu.ops.pallas import retention as pret

    if INTERP:
        N, K, H, C, psz, dt, tol = 4, 2, 16, 16, 4, jnp.float32, 2e-5
        lens, S, fade = (40, 16, 9), 48, 0.06
    else:
        N, K, H, C, psz, dt, tol = 40, 8, 128, ret.CHUNK, 64, jnp.bfloat16, 3e-2
        lens, S, fade = (2304, 1024, 700), 3072, 0.001
    R = ret.n_slabs(H)
    ks = iter(jax.random.split(jax.random.key(7), 48))
    nrm = lambda *sh: jax.random.normal(next(ks), sh, jnp.float32)  # noqa: E731
    gate = lambda *sh: jax.nn.log_sigmoid(nrm(*sh) + 2.0)           # noqa: E731
    # Gates NEAR 1 (a chunk keeps 22-37 % of what came before it, the whole
    # row 1-5 %), where a state read wrongly is a wrong output.
    slow = lambda *sh: -fade * (1.0 + jax.nn.sigmoid(nrm(*sh)))     # noqa: E731

    # prefill: ragged rows against the XLA form
    B = len(lens)
    q, k, v = (nrm(B, S, n, H).astype(dt) for n in (N, K, K))
    g, ln = gate(B, S, K), jnp.asarray(lens, jnp.int32)
    b = ret.chunk_cumsum(g, C)
    want, (Sw, zw) = ret.power_retention(q, k, v, g, lengths=ln, chunk=C)
    got, (Sg, zg) = pret.retention_prefill(
        q, k, v, b, lengths=ln, chunk=C, interpret=INTERP)
    check("retention prefill out", got, want, tol)
    check("retention prefill state", Sg, Sw, tol)
    check("retention prefill state_z", zg, zw, tol)

    # The chain prefill -> fold -> decode with gates near 1 against the
    # QUADRATIC float32 form of benchmarks/reference/brumby.py (every
    # pair's weight written out: no state, no chunk, nothing of the
    # program's): a row of 3 chunks less a quarter, then one more token.
    n = 3 * C - C // 4
    q1, k1, v1 = (nrm(1, n + 1, h, H).astype(dt) for h in (N, K, K))
    g1 = slow(1, n + 1, K)
    f32 = lambda x: x[0].astype(jnp.float32)                        # noqa: E731
    with jax.default_matmul_precision("highest"):
        quad = jax.jit(quadratic)(f32(q1), f32(k1), f32(v1), g1[0])
    b1 = ret.chunk_cumsum(g1, C)                           # [1, n + 1, K]
    y1, (S1, z1) = pret.retention_prefill(
        q1[:, :n], k1[:, :n], v1[:, :n], b1[:, :n], chunk=C,
        interpret=INTERP)
    check("retention prefill out, gates near 1, vs the quadratic form",
          y1[0], quad[:n], tol)
    # The state of ONE chunk from a prefill, the second folded onto it,
    # against the state of two chunks that the prefill handed out above.
    _, (Sa, za) = pret.retention_prefill(
        q1[:, :C], k1[:, :C], v1[:, :C], b1[:, :C], chunk=C,
        interpret=INTERP)
    Bd, L, NP, P = 4, 2, 64, 3 * C // psz
    slot, lay = 1, 1
    row = lay * (Bd + 1) + slot + 1
    state = jnp.zeros((L * (Bd + 1), K, R, H, H), dt).at[row].set(
        Sa[0].astype(dt))
    state_z = jnp.zeros((L * (Bd + 1), R, K, H), jnp.float32).at[row].set(
        jnp.swapaxes(za[0], 0, 1))
    heads_first = lambda x: jnp.swapaxes(x[0, C:2 * C], 0, 1)       # noqa: E731
    state, state_z = pret.retention_fold(
        state, state_z, heads_first(k1), heads_first(v1),
        b1[0, C:2 * C].T, jnp.int32(row), interpret=INTERP)
    check("retention fold onto a prefill's state vs the prefill of both",
          state[row], S1[0], tol)
    check("retention fold onto a prefill's state_z vs the prefill of both",
          jnp.swapaxes(state_z[row], 0, 1), z1[0], tol)
    # One more token: the folded row, the tail (positions 2C .. n) in pages.
    nT = ret.tail_pages(C, psz)
    T = nT * psz
    table = np.zeros((Bd, P), np.int32)
    pages = np.arange(2 * C // psz, n // psz + 1)
    table[slot, pages] = 1 + np.arange(len(pages))
    assert len(pages) < NP
    kp, vp = (jnp.zeros((L * NP, K, psz, H), dt) for _ in range(2))
    tail = lambda x: jnp.pad(                                       # noqa: E731
        x[0, 2 * C:n], ((0, len(pages) * psz - (n - 2 * C)), (0, 0), (0, 0))
    ).reshape(-1, psz, K, H).transpose(0, 2, 1, 3)
    rows = lay * NP + 1 + np.arange(len(pages))
    kp, vp = kp.at[rows].set(tail(k1)), vp.at[rows].set(tail(v1))
    F = jnp.zeros((Bd,), jnp.int32).at[slot].set(2 * C)
    pos = jnp.zeros((Bd,), jnp.int32).at[slot].set(n)
    held = jnp.arange(T) <= n - 2 * C
    ct = jnp.where(held[None, :], jnp.pad(
        b1[0, 2 * C:n + 1].T, ((0, 0), (0, T - (n + 1 - 2 * C)))), ret.BIG)
    c_tail = jnp.full((Bd, K, T), ret.BIG).at[slot].set(ct)
    c_q = jnp.zeros((Bd, K)).at[slot].set(b1[0, n])
    one = lambda x: jnp.zeros((Bd,) + x.shape[2:], dt).at[slot].set(  # noqa: E731
        x[0, n])
    yd, _, _ = pret.retention_decode(
        one(q1), one(k1), one(v1), c_q, c_tail, kp, vp, state, state_z,
        jnp.asarray(table), F, pos, layer_base=lay * NP,
        state_base=lay * (Bd + 1), interpret=INTERP)
    check("retention decode after prefill and fold, gates near 1, vs the "
          "quadratic form", yd[slot], quad[n], tol)
    blind, _, _ = pret.retention_decode(
        one(q1), one(k1), one(v1), c_q, c_tail, kp, vp,
        jnp.zeros_like(state), jnp.zeros_like(state_z), jnp.asarray(table),
        F, pos, layer_base=lay * NP, state_base=lay * (Bd + 1),
        interpret=INTERP)
    seen = float(jnp.max(jnp.abs(blind[slot].astype(jnp.float32) - quad[n]))
                 / jnp.max(jnp.abs(quad[n])))
    record("the same check with the state read as zeros FAILS (it holds "
           "the state)", not seen < 3 * tol, f": rel={seen:.3e}")

    # decode against the XLA form: slot 0 tail only, 1 state + a tail past a
    # chunk's end, 2 state + empty tail, 3 inactive (page table of zeros)
    F = jnp.asarray([0, C, 2 * C, 0], jnp.int32)
    pos = jnp.asarray([C // 2 + 3, 2 * C + 2, 2 * C, 0], jnp.int32)
    table = np.zeros((Bd, P), np.int32)
    nxt = 1
    for s in range(3):
        for pg in range(int(F[s]) // psz, int(pos[s]) // psz + 1):
            table[s, pg] = nxt
            nxt += 1
    assert nxt <= NP
    table = jnp.asarray(table)
    kp, vp = (0.5 * nrm(L * NP, K, psz, H).astype(dt) for _ in range(2))
    state = (0.1 * nrm(L * (Bd + 1), K, R, H, H)).astype(dt)
    state_z = jnp.abs(nrm(L * (Bd + 1), R, K, H))
    jpos = F[:, None] + jnp.arange(T)
    c_tail = -0.05 * (jnp.arange(T) + 1.0)[None, None] * jnp.ones((Bd, K, 1))
    c_tail = jnp.where((jpos <= pos[:, None])[:, None], c_tail, ret.BIG)
    c_q = -0.05 * (pos - F + 1.0)[:, None] * jnp.ones((1, K))
    qd, kn, vn = (nrm(Bd, h, H).astype(dt) for h in (N, K, K))
    args = (qd, kn, vn, c_q, c_tail, kp, vp, state, state_z, table, F, pos)
    kw = dict(layer_base=NP, state_base=Bd + 1)
    yw, kw_, vw_ = ret.retention_decode_xla(*args, **kw)
    yg, kg_, vg_ = pret.retention_decode(*args, interpret=INTERP, **kw)
    check("retention decode out", yg[:3], yw[:3], tol)
    # every page but the scratch page (the inactive slot's sink)
    bitwise("retention decode pools",
            [(kg_[:NP], kw_[:NP]), (kg_[NP + 1:], kw_[NP + 1:]),
             (vg_[NP + 1:], vw_[NP + 1:])])

    # fold: one slot's chunk into its row, the other rows untouched
    kc, vc = (nrm(K, C, H).astype(dt) for _ in range(2))
    bc = jnp.cumsum(gate(K, C), axis=1)
    row = jnp.int32(Bd + 2)
    # Jitted: run op by op, the XLA form's row update aborts the v5e
    # compiler (fusion_emitter: IsFusibleUnalignedDUS; PR 33, call 1).
    Sw, zw = jax.jit(ret.retention_fold_xla)(state, state_z, kc, vc, bc, row)
    Sg, zg = pret.retention_fold(state, state_z, kc, vc, bc, row,
                                 interpret=INTERP)
    check("retention fold state", Sg, Sw, tol)
    check("retention fold state_z", zg, zw, tol)
    others = np.arange(state.shape[0]) != int(row)
    bitwise("retention fold leaves other rows",
            [(Sg[others], state[others]), (zg[others], state_z[others])])


def latent_checks() -> None:
    """The latent paged decode kernel (``latent_paged_decode``) at the GLM
    cell's shapes: 20 query heads over rows of 512 + 64 padded to 640,
    pages of 64, contexts from one row to the whole 21504, at a traced
    layer base. Against the XLA absorbed form on the same pool (and the
    pool it hands back bitwise), against the EXPANDED form on the same rows
    (equation 4 against equation 3, peaked scores), and a control that
    drops ``q_rope . k_pe`` and has to fail."""
    from orion_tpu.config import get_config
    from orion_tpu.models.transformer import (
        latent_absorb, latent_expand, latent_unabsorb)
    from orion_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention,
    )

    cfg = get_config("glm-4.7-flash").model
    N, R, rope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    psz, Wd = 64, 640
    P, NP = (16, 80) if INTERP else (336, 700)
    lens = [1, 700, 1000, 1024] if INTERP else [1, 3000, 11501, 21504]
    B, scale, dt = len(lens), cfg.resolved_head_dim ** -0.5, jnp.bfloat16
    ks = jax.random.split(jax.random.key(7), 5)
    rows = jax.random.normal(ks[0], (2 * NP, 1, psz, R + rope), jnp.float32)
    pool = jnp.pad(rows, ((0, 0),) * 3 + ((0, Wd - R - rope),)).astype(dt)
    table = jnp.asarray(np.stack([
        np.random.default_rng(b).permutation(np.arange(1, NP))[:P]
        for b in range(B)]), jnp.int32)
    pos = jnp.asarray(lens, jnp.int32) - 1
    # Peaked: a query of a few units against rows of unit numbers.
    wkv_b = (jax.random.normal(ks[1], (R, N * (cfg.qk_nope_head_dim
                                               + cfg.v_head_dim)))
             * R ** -0.5).astype(dt)
    q = (3.0 * jax.random.normal(ks[2], (B, 1, N, cfg.resolved_head_dim))
         ).astype(dt)
    new = jnp.pad(jax.random.normal(ks[3], (B, R + rope)),
                  ((0, 0), (0, Wd - R - rope))).astype(dt)
    q_lat = jnp.pad(latent_absorb(q, wkv_b, cfg)[:, 0],
                    ((0, 0), (0, 0), (0, Wd - R - rope)))

    def context(pool_, base):
        written = pool_.at[base + table[jnp.arange(B), pos // psz], 0,
                           pos % psz].set(new)
        return written, written[base + table][:, :, 0].reshape(B, -1, Wd)

    def absorbed(ql, pool_, base):
        written, ctx = context(pool_, base)
        z = jnp.einsum("bnw,btw->bnt", ql.astype(jnp.float32),
                       ctx.astype(jnp.float32)) * scale
        live = jnp.arange(ctx.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(live[:, None], z, -jnp.inf), axis=-1)
        return jnp.einsum("bnt,btr->bnr", p,
                          ctx[..., :R].astype(jnp.float32)), written

    def expanded(pool_, base):
        _, ctx = context(pool_, base)
        k, v = latent_expand(ctx[..., :R + rope].astype(jnp.float32),
                             wkv_b.astype(jnp.float32), cfg)
        live = jnp.arange(ctx.shape[1])[None, None, :] <= pos[:, None, None]
        return attention_xla(q.astype(jnp.float32), k, v, causal=False,
                             mask=live)[:, 0]

    for layer in (0, 1):
        base = jnp.asarray(layer * NP, jnp.int32)
        got, pool_got = jax.jit(
            lambda ql, pl_, b: latent_paged_attention(
                ql, pl_, table, pos, new, layer_base=b, value_width=R,
                scale=scale, interpret=INTERP))(q_lat, pool, base)
        want, pool_want = jax.jit(absorbed)(q_lat, pool, base)
        check(f"latent decode out, layer {layer}", got, want, 2e-2)
        bitwise(f"latent decode pool, layer {layer}", [(pool_got, pool_want)])
    base = jnp.asarray(NP, jnp.int32)
    out = latent_unabsorb(got[:, None], wkv_b, cfg)[:, 0]
    full = jax.jit(expanded)(pool, base)
    check("latent decode (absorbed) vs the expanded form", out, full, 3e-2)
    blind, _ = latent_paged_attention(
        q_lat.at[..., R:].set(0), pool, table, pos, new, layer_base=base,
        value_width=R, scale=scale, interpret=INTERP)
    blind = latent_unabsorb(blind[:, None], wkv_b, cfg)[:, 0].astype(
        jnp.float32)
    rel = float(jnp.max(jnp.abs(blind - full))) / float(
        jnp.max(jnp.abs(full)))
    record("latent decode CONTROL without q_rope . k_pe differs from the "
           "expanded form", rel > 0.1, f": rel={rel:.3e}")


def kda_checks() -> None:
    """Kimi delta attention at the Ling cell's shapes (32 heads of 128 keys
    and values) with decays NEAR 1 (a state that remembers: a step's
    log-decay in (-0.02, 0)) and at the gate's bound of -5, each against the
    plain recurrence in float32 (``ops.kda.kda_recurrent``): the chunked
    form a prefill runs (outputs and the state it hands out, ragged
    lengths), the decode kernel ``kda_decode`` (a step on a state the
    recurrence made, at a traced layer, a dead slot passed by and its row
    bitwise untouched), the chain chunked prefill -> eight decode steps
    against the recurrence over the whole sequence, and a control that
    drops the erase term and has to fail."""
    from orion_tpu.ops import kda
    from orion_tpu.ops.pallas.kda import kda_decode

    N, H = (4, 128) if INTERP else (32, 128)
    B, S = (2, 200) if INTERP else (4, 1500)
    lens = jnp.asarray([S, S // 3] if INTERP else [S, 1111, 64, 3], jnp.int32)
    W = 8

    def draw(seed, gscale, n):
        ks = jax.random.split(jax.random.key(seed), 5)
        q = kda.l2norm(jax.random.normal(ks[0], (B, n, N, H))) * H ** -0.5
        k = kda.l2norm(jax.random.normal(ks[1], (B, n, N, H)))
        v = jax.random.normal(ks[2], (B, n, N, H))
        g = gscale * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (B, n, N, H)))
        b = jax.nn.sigmoid(jax.random.normal(ks[4], (B, n, N)))
        return q, k, v, g, b

    for tag, gscale in (("decays near 1", -0.02), ("the -5 bound", -5.0)):
        q, k, v, g, b = draw(11, gscale, S + W)
        head = lambda x: x[:, :S]
        want_o, want_s = jax.jit(
            lambda *a: kda.kda_recurrent(*a, lengths=lens))(
                *(head(x) for x in (q, k, v, g, b)))
        got_o, got_s = jax.jit(
            lambda *a: kda.kda_chunked(*a, lengths=lens))(
                *(head(x) for x in (q, k, v, g, b)))
        live = (jnp.arange(S)[None, :] < lens[:, None])[..., None, None]
        check(f"kda chunked out, {tag}", jnp.where(live, got_o, 0),
              jnp.where(live, want_o, 0), 2e-3)
        check(f"kda chunked state, {tag}", got_s, want_s, 2e-3)
        # The decode kernel: W steps from the state the prefill handed out,
        # rows at (layer 1, slot + 1); slot 1 dead.
        state = jnp.zeros((2, B + 1, N, H, H), jnp.float32).at[1, 1:].set(
            jnp.swapaxes(got_s, -1, -2))
        active = jnp.arange(B) != 1
        step = jax.jit(lambda st, t: kda_decode(
            st, q[:, t], k[:, t], v[:, t], g[:, t], b[:, t],
            layer=jnp.int32(1), active=active, interpret=INTERP))
        ref_s, outs = want_s, []
        for t in range(S, S + W):
            o, state = step(state, t)
            ref_s, ref_o = kda._step(ref_s, q[:, t], k[:, t], v[:, t],
                                     g[:, t], b[:, t])
            outs.append((o, ref_o))
        # Row 0 of the lengths is whole: its chain is the recurrence's.
        check(f"kda decode out after {W} steps, {tag}", outs[-1][0][0],
              outs[-1][1][0], 2e-3)
        check(f"kda decode state after {W} steps, {tag}", state[1, 1],
              jnp.swapaxes(ref_s, -1, -2)[0], 2e-3)
        bitwise(f"kda decode passes a dead slot and the other layer by, "
                f"{tag}", [(state[1, 2], jnp.swapaxes(got_s, -1, -2)[1]),
                           (state[0], jnp.zeros_like(state[0]))])
    # CONTROL: a gated sum (no erase term) is not the delta rule.
    q, k, v, g, b = (x[:, :128, :2] for x in draw(11, -0.02, S))
    blind = jnp.einsum("bsnkv,bsnk->bsnv", jnp.cumsum(jnp.einsum(
        "bsnk,bsnv->bsnkv", k * b[..., None], v), axis=1), q)
    want = kda.kda_recurrent(q, k, v, 0 * g, b)[0]
    rel = float(jnp.max(jnp.abs(blind - want))) / float(
        jnp.max(jnp.abs(want)))
    record("kda CONTROL without the erase term differs from the "
           "recurrence", rel > 0.1, f": rel={rel:.3e}")


def sink_checks() -> None:
    """The two softmax kernels at the MiMo cell's shapes (PR 50): keys 192
    wide, values 128, 64 query heads over 8 K/V heads under a window of 128
    with a learned sink, and over 4 with neither. The flash forward against
    the XLA form (one prompt a row, ragged lengths by segment ids); the
    paged decode kernel over packed key rows, on a ring of 3 pages that has
    gone round and on a pool at contexts up to 16384, the new token's write
    fused in (the pools it hands back bitwise against a scatter); and a
    control without the sink that has to fail."""
    from orion_tpu.infer import kv_cache
    from orion_tpu.ops.pallas.paged_attention import attend as paged

    N, H, Hv, psz, dt = 64, 192, 128, 64, jnp.bfloat16
    ks = jax.random.split(jax.random.key(11), 8)
    sink = 4.0 * jax.random.uniform(ks[0], (N,))
    # flash forward: rows of 2048 (lengths 2048 and 300) and one of 4096
    for K, window, b, S, lens in ((8, 128, sink, 256 if INTERP else 2048,
                                   (1.0, 0.15)),
                                  (4, None, None, 512 if INTERP else 4096,
                                   (1.0,))):
        q = jax.random.normal(ks[1], (len(lens), S, N, H), jnp.float32)
        k = jax.random.normal(ks[2], (len(lens), S, K, H), jnp.float32)
        v = jax.random.normal(ks[3], (len(lens), S, K, Hv), jnp.float32)
        seg = (jnp.arange(S)[None, :] < jnp.asarray(
            [int(f * S) for f in lens])[:, None]).astype(jnp.int32)
        kw = dict(q_segment_ids=seg, kv_segment_ids=seg, window=window)
        args = [a.astype(dt) for a in (q * 0.5, k, v)]
        want = attention_xla(*args, sink=b, **kw)
        got = flash_attention(*args, sink=b, seg_pad_zero=True,
                              interpret=INTERP, **kw)
        live = seg[:, :, None, None] > 0
        tag = f"flash fwd K={K} window={window} sink={b is not None}"
        check(tag, jnp.where(live, got, 0), jnp.where(live, want, 0), 2e-2)
        if b is not None:
            bare = flash_attention(*args, seg_pad_zero=True,
                                   interpret=INTERP, **kw)
            rel = float(jnp.max(jnp.abs(jnp.where(live, bare - want, 0))))
            record(tag + " control (no sink) differs", rel > 0.05,
                   f": max abs {rel:.3e}")
    # paged decode over packed keys: a ring of 3 pages (window layers) and a
    # pool (full layers), B slots
    # (64 slots on their rings; 8 on the pool, whose pages for 64 slots of
    # 288 would not fit beside the scatter that checks them)
    for K, window, b, B, P, hi in (
            (8, 128, sink, 4 if INTERP else 64, 3, 190),
            (4, None, None, 4 if INTERP else 8, 8 if INTERP else 288,
             500 if INTERP else 16384)):
        NP = B * P + 1
        pool_k = jax.random.normal(
            ks[4], (2 * NP, K + K // 2, psz, Hv), jnp.float32).astype(dt)
        pool_v = jax.random.normal(
            ks[5], (2 * NP, K, psz, Hv), jnp.float32).astype(dt)
        table = 1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
        start = jnp.asarray(np.random.default_rng(3).integers(
            1, hi, B), jnp.int32).at[0].set(hi - 1)
        q = (0.5 * jax.random.normal(ks[6], (B, 1, N, H))).astype(dt)
        kn = jax.random.normal(ks[7], (B, 1, K, H)).astype(dt)
        vn = jax.random.normal(ks[1], (B, 1, K, Hv)).astype(dt)
        rows = kv_cache.pack_keys(kn, Hv)
        out, kp, vp = paged(
            kv_cache.pack_queries(q, K, Hv), pool_k, pool_v, table, start,
            jnp.ones_like(start), layer_base=NP, k_new=rows, v_new=vn,
            logit_softcap=None, window=window, interpret=INTERP,
            k_scale=None, v_scale=None, sink=b, scale=H ** -0.5)
        at = (NP + table[jnp.arange(B), start // psz], slice(None),
              start % psz)
        want_k = pool_k.at[at].set(rows[:, 0])
        want_v = pool_v.at[at].set(vn[:, 0])
        ctx_k = kv_cache.unpack_keys(
            want_k[NP + table].transpose(0, 1, 3, 2, 4).reshape(
                B, P * psz, K + K // 2, Hv), K)
        ctx_v = want_v[NP + table].transpose(0, 1, 3, 2, 4).reshape(
            B, P * psz, K, Hv)
        pos = jnp.arange(P * psz)[None, None, :]
        mask = pos <= start[:, None, None]
        if window is not None:
            mask &= pos > (start[:, None, None] - window)
        want = attention_xla(q, ctx_k, ctx_v, causal=False, mask=mask,
                             sink=b)
        tag = f"paged packed K={K} window={window} sink={b is not None}"
        check(tag, out, want, 2e-2)
        bitwise(tag + " pools", [(kp, want_k), (vp, want_v)])


def main() -> int:
    global INTERP
    INTERP = "--interpret" in sys.argv[1:]
    if INTERP:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); this is the real-hardware "
              f"check — --interpret runs its logic on the CPU")
        return 1
    from orion_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} interpret={INTERP}", flush=True)

    for name, group in (("retention", retention_checks),
                        ("rope", rope_decode_checks),
                        ("latent", latent_checks),
                        ("kda", kda_checks),
                        ("sink", sink_checks)):
        if name in sys.argv[1:]:        # --only <name>
            guarded(name, group)
            green = sum(ok for _, ok in RESULTS)
            print(f"{'ALL-OK' if green == len(RESULTS) else 'SOME-FAIL'} "
                  f"{green} of {len(RESULTS)} checks [{name} only]")
            return 0 if green == len(RESULTS) else 1
    guarded("retention", retention_checks)
    guarded("latent", latent_checks)
    guarded("kda", kda_checks)
    guarded("sink", sink_checks)
    guarded("flash", flash_checks)
    if not INTERP:      # the cells' sizes: minutes under the interpreter
        guarded("flash @cells", flash_cell_checks)
    for g in (SMALL, SERVE):
        guarded(f"paged{g.tag}", paged_checks, g)
        guarded(f"ragged{g.tag}", ragged_paged_checks, g)
        guarded(f"tree{g.tag}", ragged_tree_checks, g)
        guarded(f"prefill{g.tag}", paged_prefill_checks, g)
    for g in LAGUNA:
        guarded(f"paged{g.tag}", paged_checks, g)
        guarded(f"ragged{g.tag}", ragged_paged_checks, g)
    guarded("norm/rope", norm_rope_checks)
    guarded("rope decode", rope_decode_checks)

    green = sum(ok for _, ok in RESULTS)
    verdict = "ALL-OK" if green == len(RESULTS) else "SOME-FAIL"
    print(f"{verdict} {green} of {len(RESULTS)} checks "
          f"[platform={dev.platform} device_kind={dev.device_kind!r}]")
    return 0 if green == len(RESULTS) else 1


if __name__ == "__main__":
    sys.exit(main())
