"""Multi-query ragged paged attention: W queries per slot, KV write fused.

Speculative verification (``runner.verify_step`` / ``mixed_verify_step``)
scores each slot's pending token + drafts — W = speculate_tokens + 1 query
positions per slot — in one pass over the weights. The XLA reference body
scatters all W tokens' K/V into the pool and re-materializes every slot's
full padded context via a pool gather, exactly the copy tax the paged
decode kernel exists to avoid. This is that kernel at W ragged queries per
slot: ONE body (``paged_attention._kernel``) with a static W, so the block
walk, the operand dtypes, the online softmax and the fused write are the
decode kernel's by construction, and W = 1 here is decode bitwise.

What W adds is shape, all of it in the shared body:

  - ``round_up(W * G, 8)`` query rows per kv head instead of ``G8``.
  - Causal masking among the W new positions rides the same kv-position
    mask as raggedness: query w at position ``start + w`` attends
    kv_pos <= start + w, which includes the earlier drafts of the same
    dispatch (their K/V is already merged into the block being read).
    Token trees replace it by packed ancestor words and depths.
  - Up to W merged rows, on at most two pages for W <= psz + 1; rows
    shorter than W (``lens``) exclude their padding tokens from the
    merge, so padding never touches the pool (the XLA path parks it on
    scratch instead — both are unobservable). Padding QUERIES still
    compute, masked like a real query at ``start + j``, and return
    garbage rows the caller discards — do NOT "fix" them to fully masked,
    the XLA reference's discard semantics are the contract.
  - Under ``kv_quant=int8`` each new token quantizes in-kernel with the
    SAME ``common.quantize_kv`` the jnp paths use, so acceptance numerics
    stay bit-identical to sequential decode.

Per-query numerics match the W=1 call's op-for-op: the extra blocks a
non-final query visits (between its own position and the row's last, or
behind its own window) are exact no-ops in the online softmax (fully
masked blocks contribute p=0, alpha=1), so greedy acceptance on this path
reproduces the sequential pallas decode stream.

Verification is inference-only; no VJP is defined.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

from orion_tpu.ops.pallas.paged_attention import (
    VMEM_BUDGET_BYTES,
    attend,
    vmem_bytes as verify_vmem_bytes,
)


def check_verify_fit(
    W: int,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_quant: Optional[str],
    dtype_itemsize: int = 2,
) -> None:
    """Reject a speculative verify width the kernel cannot hold in VMEM.

    Called by the engine at init when ``inference.speculative`` rides the
    pallas kernel path, so the failure is a config error naming the knob,
    not a Mosaic allocation failure mid-serving."""
    quant = kv_quant == "int8"
    need = verify_vmem_bytes(
        W, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        page_size=page_size, kv_itemsize=1 if quant else dtype_itemsize,
        quant=quant,
    )
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"speculative verify width W={W} "
            f"(inference.speculate_tokens={W - 1}) needs ~"
            f"{need / 2**20:.1f} MiB of VMEM per kernel step, over the "
            f"~{VMEM_BUDGET_BYTES / 2**20:.0f} MiB the ragged "
            f"paged-attention kernel budgets; lower "
            f"inference.speculate_tokens or serve with model.kernels='xla'"
        )


def ragged_paged_attention(
    q: jax.Array,            # [B, W, N, H] the W new positions' queries
    k_pool: jax.Array,       # [L*num_pages, K, psz, H] flat pool
    v_pool: jax.Array,       # [L*num_pages, K, psz, H]
    page_table: jax.Array,   # [B, P] int32 per-layer-relative page ids
    start: jax.Array,        # [B] int32: first new position (the cursor)
    lens: jax.Array,         # [B] int32: real queries this row (1..W)
    *,
    layer_base: Union[jax.Array, int] = 0,  # flat-pool row offset (layer*NP)
    k_new: Optional[jax.Array] = None,      # [B, W, K, H]: K/V of the W
    v_new: Optional[jax.Array] = None,      #   tokens, written in-kernel
    logit_softcap: Optional[float] = None,
    window: Optional[int] = None,           # sliding window per query:
    #                                         attend iff q_pos - kv_pos < w
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,    # [rows, K, SCALE_LANES] f32:
    v_scale: Optional[jax.Array] = None,    #   int8-pool per-token scales
    tree_mask: Optional[jax.Array] = None,  # [B, W] i32 packed ancestor
    #                                         words (bit i of word j: query
    #                                         j may attend new slot i)
    depths: Optional[jax.Array] = None,     # [B, W] i32 tree depth per
    #                                         column (logical position =
    #                                         start + depth)
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: str = "tp",
):
    """W-query ragged decode attention over the paged KV pool.

    Row b holds ``lens[b]`` real queries at positions ``start[b] + j``;
    query j attends every pool position <= its own (earlier same-dispatch
    tokens included) under the optional sliding window. Returns
    [B, W, N, H] when ``k_new``/``v_new`` are None, else ``(out, pools...)``
    with all ``lens[b]`` tokens' K/V written in place (aliased); padding
    queries (j >= lens[b]) write nothing and return garbage rows the
    caller discards. Rows whose page-table entries are 0 (inactive /
    mid-prefill slots) read and write only the reserved scratch page.

    Semantics match the XLA branch of ``runner._paged_layer``: scatter all
    W tokens, gather the padded context, mask per query. With
    ``k_scale``/``v_scale`` the pools are int8 (inference.kv_quant) and
    the fused write quantizes in-kernel (kv_cache.quantize_kv semantics),
    returning ``(out, k_pool', v_pool', k_scale', v_scale')``.

    Token trees (``tree_mask``/``depths``): the intra-dispatch causal
    mask generalizes to an arbitrary ancestor mask — query j attends the
    committed context plus exactly the new slots whose bits are set in
    its packed word (ancestors/root/self), at logical position
    ``start + depths[b, j]``; KV WRITES stay slot-sequential
    (``start + j``), so the page walk, fused write and provisioning are
    unchanged. Chain-shaped words/depths reproduce the positional mask
    bit-for-bit (the degenerate case IS the plain W-query verify). Mask
    words are int32, so tree verification caps W at 31 columns.
    """
    out = attend(
        q, k_pool, v_pool, page_table, start, lens, layer_base=layer_base,
        k_new=k_new, v_new=v_new, logit_softcap=logit_softcap,
        window=window, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale, tree_mask=tree_mask, depths=depths, mesh=mesh,
        tp_axis=tp_axis, name="ragged_paged",
    )
    return out[0] if k_new is None else out
