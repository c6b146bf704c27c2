#!/usr/bin/env python
"""Single-chip long-sequence attention benchmark (VERDICT r3 item 4).

Measures, at bench-model dims (d_model=2048, 16 heads, GQA 8 kv) over
S in {8k, 16k, 32k}:

  - flash kernel fwd+bwd time vs the xla attention reference (the causal
    block-skip's value grows with S — attention is O(S^2), everything
    else O(S)),
  - the attention share of a full transformer block fwd+bwd, i.e. the
    fraction of step time the ring distributes at long context,
  - the windowed-flash time at window=4096 (the O(S*W) sliding-window
    regime the Mistral-family long-context path rides).

    python tools/longcontext_bench.py          # on-chip numbers
    python tools/longcontext_bench.py --cpu    # tiny-shape logic check

Serving mode (ISSUE 19): end-to-end long-context SERVING numbers on the
real engine — TTFT and mean ITL per context length for the paged-flash
prefill body vs the XLA reference body, the over-pool admit-and-complete
run (inference.long_context lazy provisioning vs the reject baseline),
and the per-chunk prefix copy-volume audit (paged-flash clamped-index
DMA elision pays O(real, window-clamped context) bytes per chunk where
the dense-gather reference pays the pow2-padded prefix). Ends with one
``verdict`` JSON line: admit-and-complete must strictly beat reject, and
the paged copy volume must stay O(real context).

    python tools/longcontext_bench.py --serve           # on-chip
    python tools/longcontext_bench.py --serve --smoke   # CPU, tier-1

Output: one JSON line per (S, measurement).
"""
import sys as _sys, pathlib as _pathlib
_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parent.parent))
import json
import sys
import time

import jax
import jax.numpy as jnp


def bench(fn, args, iters=10, warmup=2):
    out = jax.jit(fn)
    for _ in range(warmup):
        jax.block_until_ready(out(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = out(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def chunk_copy_volume(ctx: int, chunk: int, psz: int, window):
    """Prefix copy volume (in TOKENS of KV) each prefill body pays over
    one long prompt's chunk schedule — the arithmetic the paged kernel's
    parity-tested clamped-index DMA elision implies:

    - dense-gather reference: each chunk gathers its WHOLE prefix,
      padded to the burst's pow2 page count, into a contiguous buffer
      before attending — sum over chunks of pow2(ceil(cursor/psz))*psz.
    - paged-flash: the kernel walks pages in place and elides the DMA
      for every block past the row's real length or behind its sliding
      window — at most ceil((min(cursor, window) + chunk)/psz)+1 pages
      actually move per chunk.

    Returns (paged_tokens, dense_tokens, real_tokens): real is the
    window-clamped prefix each chunk genuinely attends — the O(real
    context) yardstick the verdict pins paged against."""
    paged = dense = real = 0
    cursor = 0
    while cursor < ctx:
        k = min(chunk, ctx - cursor)
        npre = -(-cursor // psz)
        if npre:
            p_pre = 1 << (npre - 1).bit_length()
            dense += p_pre * psz
        span = cursor if window is None else min(cursor, window)
        real += span + k
        paged += (-(-(span + k) // psz) + 1) * psz
        cursor += k
    return paged, dense, real


def _serve_once(cfg, params, prompt, max_new):
    """One cold engine, one request: (ttft_s, itl_s, n_tokens, outcome)."""
    from orion_tpu.infer import InferenceEngine

    eng = InferenceEngine(cfg, params)
    t0 = time.perf_counter()
    r = eng.submit_request(list(prompt), max_new)
    ttft = t_last = None
    while eng.has_work():
        eng.step()
        now = time.perf_counter()
        if r.generated and ttft is None:
            ttft = now - t0
        if r.generated:
            t_last = now
    n = len(r.generated)
    itl = ((t_last - t0 - ttft) / (n - 1)) if ttft and n > 1 else None
    t = eng.reset_timing()
    return {
        "ttft_s": round(ttft, 4) if ttft is not None else None,
        "itl_s": round(itl, 5) if itl is not None else None,
        "tokens": n,
        "outcome": r.outcome,
        "paged_out": t.get("request_paged_out", 0),
        "paged_in": t.get("request_paged_in", 0),
    }


def serve_main(smoke: bool) -> int:
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --serve --smoke for the CPU check")
        return 1
    from orion_tpu.config import get_config
    from orion_tpu.models import init_params

    if smoke:
        # Both contexts must sit ABOVE the lazy working-set pool below,
        # so every run is a genuine over-pool admission.
        contexts, psz, chunk, window, max_new = [320, 512], 16, 32, 32, 6
        preset, kernels = "tiny-llama", "xla"
    else:
        contexts = [8192, 16384, 32768]
        psz, chunk, window, max_new = 64, 512, 4096, 32
        preset, kernels = "tiny-llama", "pallas"
    seq_cap = -(-(max(contexts) + 2 * max_new) // psz) * psz

    def mk(ctx, *, long, paged, pool):
        ov = [
            f"inference.max_seq_len={seq_cap}",
            f"inference.page_size={psz}",
            "inference.max_batch_size=2",
            f"inference.prefill_chunk={psz}",
            f"inference.max_new_tokens={max_new}",
            "inference.chunked_prefill=true",
            f"inference.prefill_chunk_tokens={chunk}",
            f"inference.num_pages={pool}",
            f"inference.paged_prefill={'true' if paged else 'false'}",
            f"model.sliding_window={window}",
            f"model.kernels={kernels}",
        ]
        if long:
            ov += [
                "inference.long_context=true",
                "inference.host_tier_bytes=8388608",
                "inference.host_tier_min_tokens=0",
            ]
        return get_config(preset, ov)

    cfg0 = mk(contexts[0], long=True, paged=True,
              pool=2 * (window + chunk) // psz + 8)
    params = init_params(cfg0.model, jax.random.key(0))
    ok = True
    for ctx in contexts:
        prompt = [(i * 11) % 250 + 1 for i in range(ctx)]
        # Pool sized for the lazy working set, NOT the eager footprint:
        # every row below is an over-pool admission.
        pool = 2 * (window + chunk) // psz + 8
        eager_need = ctx // psz + 2
        row = {"S": ctx, "pool_pages": pool, "eager_need": eager_need}
        new = _serve_once(
            mk(ctx, long=True, paged=True, pool=pool), params, prompt,
            max_new,
        )
        row["paged_flash"] = new
        if not smoke:
            # The XLA reference prefill body at identical scheduling —
            # the old-vs-paged-flash TTFT/ITL column (CPU smoke runs XLA
            # both ways, so the compare is on-chip only).
            row["xla_body"] = _serve_once(
                mk(ctx, long=True, paged=False, pool=pool), params,
                prompt, max_new,
            )
        # Reject baseline: the same over-pool request WITHOUT
        # long_context is refused at submit — zero tokens served.
        try:
            mk_cfg = mk(ctx, long=False, paged=True, pool=pool)
            from orion_tpu.infer import InferenceEngine
            InferenceEngine(mk_cfg, params).submit(prompt, max_new)
            rejected = False
        except ValueError:
            rejected = True
        row["reject_baseline_refuses"] = rejected
        paged_t, dense_t, real_t = chunk_copy_volume(
            ctx, chunk, psz, window
        )
        row["copy_volume_tokens"] = {
            "paged_flash": paged_t, "dense_gather": dense_t,
            "real_attended": real_t,
            "dense_over_paged": round(dense_t / max(paged_t, 1), 2),
        }
        # The two pins: admit-and-complete strictly beats reject (the
        # request completes with every token; reject serves none), and
        # the paged copy volume is O(real context) — bounded by a
        # page-rounding constant of the window-clamped real prefix,
        # while the dense gather's pow2-padded volume runs away with S.
        ok &= new["outcome"] == "completed" and new["tokens"] == max_new
        ok &= rejected
        ok &= paged_t <= 1.5 * real_t + 2 * psz * (ctx // chunk + 1)
        print(json.dumps(row))
    print(json.dumps({
        "verdict": "PASS" if ok else "FAIL",
        "pins": [
            "over-pool admit-and-complete beats reject",
            "paged-flash per-chunk copy bytes O(real context)",
        ],
    }))
    return 0 if ok else 1


def main() -> int:
    if "--serve" in sys.argv[1:]:
        return serve_main("--smoke" in sys.argv[1:])
    cpu = "--cpu" in sys.argv[1:]
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        print(f"FAIL: no TPU backend (default backend is "
              f"{jax.default_backend()!r}); use --cpu for the logic check")
        return 1

    from orion_tpu.ops.attention import attention_xla
    from orion_tpu.ops.pallas.flash_attention import flash_attention

    if cpu:
        seqs, N, K, H, D, F = [256, 512], 4, 2, 64, 256, 512
        interpret = True
    else:
        # Bench-model dims (llama-1b-bench): the 16 GB v5e bounds B*S.
        seqs, N, K, H, D, F = [8192, 16384, 32768], 16, 8, 128, 2048, 8192
        interpret = False
    dev = jax.devices("cpu" if cpu else None)[0]

    with jax.default_device(dev):
        for S in seqs:
            ks = jax.random.split(jax.random.key(0), 4)
            q = jax.random.normal(ks[0], (1, S, N, H), jnp.bfloat16)
            k = jax.random.normal(ks[1], (1, S, K, H), jnp.bfloat16)
            v = jax.random.normal(ks[2], (1, S, K, H), jnp.bfloat16)

            def loss_flash(q, k, v, window=None):
                o = flash_attention(q, k, v, causal=True, window=window,
                                    interpret=interpret)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def loss_xla(q, k, v):
                o = attention_xla(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            grad_f = jax.grad(loss_flash, argnums=(0, 1, 2))
            t_flash = bench(grad_f, (q, k, v))
            t_win = bench(
                jax.grad(
                    lambda q, k, v: loss_flash(q, k, v, window=4096
                                               if not cpu else 128),
                    argnums=(0, 1, 2)),
                (q, k, v))
            try:
                t_xla = bench(jax.grad(loss_xla, argnums=(0, 1, 2)),
                              (q, k, v))
            except Exception:           # [S,S] logits OOM at long S
                t_xla = None

            # Attention share of a full block: attention + the block's
            # matmul FLOPs (qkv/out proj + swiglu MLP) timed as real ops.
            x = jax.random.normal(ks[3], (1, S, D), jnp.bfloat16)
            wq = jax.random.normal(ks[0], (D, N * H), jnp.bfloat16) * 0.02
            wkv = jax.random.normal(ks[1], (D, 2 * K * H), jnp.bfloat16) * 0.02
            wo = jax.random.normal(ks[2], (N * H, D), jnp.bfloat16) * 0.02
            w1 = jax.random.normal(ks[0], (D, 2 * F), jnp.bfloat16) * 0.02
            w2 = jax.random.normal(ks[1], (F, D), jnp.bfloat16) * 0.02

            def block_matmuls(x):
                a = x @ wq
                b = x @ wkv          # [1, S, 2*K*H]; 2*K*H == D here
                y = a @ wo + b[..., :D]   # consume b: keep the KV-proj
                h = x @ w1                # matmul out of DCE's reach
                hh = jax.nn.silu(h[..., :F]) * h[..., F:]
                return jnp.sum((y + hh @ w2).astype(jnp.float32) ** 2)

            t_mm = bench(jax.grad(block_matmuls), (x,))
            share = t_flash / (t_flash + t_mm)
            print(json.dumps({
                "S": S,
                "flash_fwdbwd_ms": round(t_flash * 1e3, 2),
                "window_fwdbwd_ms": round(t_win * 1e3, 2),
                "xla_fwdbwd_ms": (round(t_xla * 1e3, 2)
                                  if t_xla is not None else None),
                "attention_share_of_block": round(share, 4),
                "speedup_vs_xla": (round(t_xla / t_flash, 2)
                                   if t_xla is not None else None),
                "window_speedup": round(t_flash / t_win, 2),
            }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
