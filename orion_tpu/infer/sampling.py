"""Token sampling: greedy / temperature / top-k / nucleus (top-p).

Static-shape TPU formulation: top-k and top-p are masks over the full vocab
(sort + cumulative sum), never a dynamic-length candidate list.

Constrained decoding (orion_tpu.constrain) composes a per-row legal-token
bitmask into the SAME filtered distribution every consumer shares: greedy,
sampled, and both speculative verify paths mask before any filtering, so a
constrained draft is accepted by exactly the rejection-sampling math the
unconstrained path runs — no new acceptance rule. ``legal_mask=None``
keeps every trace byte-identical to the unconstrained build (the jit
specializes on the None pytree).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


class AllMaskedRows(ValueError):
    """Typed per-slot error: legal-mask rows that admit NO token. The
    filtered distribution for such a row is undefined (softmax of all
    NEG_INF is uniform garbage), so the engine must fail the offending
    slots — and only those slots — before dispatch. ``slots`` lists the
    guilty row indices; neighbors are unaffected."""

    def __init__(self, slots):
        self.slots = list(slots)
        super().__init__(
            f"legal_mask rows {self.slots} admit no token (constraint "
            f"dead end); quarantine those slots"
        )


def check_legal_mask(legal_mask) -> None:
    """Host-side pre-dispatch validation: raise :class:`AllMaskedRows`
    naming every all-masked row. Rows are the leading axis (flatten
    [B, W, V] masks to row-major [B*W, V] semantics upstream if per-slot
    attribution over positions is needed; the engine checks per-slot
    rows before building verify masks)."""
    m = np.asarray(legal_mask, bool)
    rows = m.reshape(-1, m.shape[-1])
    bad = np.flatnonzero(~rows.any(axis=-1))
    if bad.size:
        raise AllMaskedRows(bad.tolist())


def _apply_mask(logits: jax.Array, legal_mask) -> jax.Array:
    """Illegal tokens drop to NEG_INF BEFORE temperature/top-k/top-p so
    every downstream filter sees the constrained distribution."""
    if legal_mask is None:
        return logits
    return jnp.where(legal_mask, logits.astype(jnp.float32), NEG_INF)


@jax.named_scope("sample")
def sample(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
    legal_mask=None,
) -> jax.Array:
    """logits: [B, V] -> sampled token ids [B] int32.

    Each parameter is a python scalar (whole batch) or a [B] array
    (per-request sampling params, vLLM-style). temperature <= 0 means
    greedy argmax for that row (the deterministic mode the
    batching-equivalence tests rely on). top_k=0 / top_p=1.0 disable the
    respective filters.

    ``legal_mask`` ([B, V] bool or None) constrains rows to their legal
    tokens: illegal logits drop to NEG_INF before any filter, and a row
    whose mask admits exactly ONE token short-circuits to that token —
    deterministically, on BOTH the greedy and sampled paths (a forced
    continuation must not depend on the sampling mode). All-masked rows
    are a caller bug; validate with ``check_legal_mask`` pre-dispatch.

    The all-scalar greedy case short-circuits to a bare argmax — the bench
    path compiles no sampling machinery.
    """
    logits = _apply_mask(logits, legal_mask)
    if legal_mask is not None:
        forced = jnp.argmax(legal_mask, axis=-1).astype(jnp.int32)
        single = jnp.sum(legal_mask, axis=-1) == 1

        def finish(toks):
            return jnp.where(single, forced, toks)
    else:
        def finish(toks):
            return toks

    # Trace-time constants (python scalars, e.g. bound via functools.partial
    # before jit) let disabled filters compile to nothing: the greedy bench
    # decode is a bare argmax, plain-temperature sampling skips the [B, V]
    # sort/softmax/cumsum entirely.
    no_topk = isinstance(top_k, int) and top_k == 0
    no_topp = isinstance(top_p, (int, float)) and top_p >= 1.0
    if isinstance(temperature, (int, float)):
        if temperature <= 0.0:
            return finish(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        if no_topk and no_topp:
            scaled = logits.astype(jnp.float32) / temperature
            return finish(
                jax.random.categorical(key, scaled, axis=-1).astype(
                    jnp.int32
                )
            )

    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits, temp, top_k, top_p,
                           no_topk=no_topk, no_topp=no_topp)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return finish(jnp.where(temp > 0, sampled, greedy))


def filter_logits(
    logits: jax.Array,     # [B, V] float32
    temp: jax.Array,       # [B] f32 (rows <= 0 pass through at scale 1)
    top_k: jax.Array,      # [B] i32
    top_p: jax.Array,      # [B] f32
    *,
    no_topk: bool = False,
    no_topp: bool = False,
    legal_mask=None,
) -> jax.Array:
    """Temperature-scaled, top-k/top-p-masked logits [B, V].

    The single definition of the target distribution: ``sample`` draws a
    categorical from it, and speculative verification (spec_verify_sample)
    measures draft-acceptance probabilities against softmax of the SAME
    array — rejection sampling preserves the output distribution only if
    both sides agree on it exactly. ``legal_mask`` applies FIRST, so
    top-k/top-p renormalize over the constrained support (top-k acts as
    min(k, legal count): the k-th largest of a masked row is NEG_INF
    once k exceeds the legal count, which keeps every legal token).
    """
    B, V = logits.shape
    logits = _apply_mask(logits, legal_mask)
    scaled = logits / jnp.where(temp > 0, temp, 1.0)[:, None]

    if not (no_topk and no_topp):
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]

    if not no_topk:
        # top-k: threshold at the k-th largest logit per row (0 disables).
        kth_idx = jnp.clip(top_k - 1, 0, V - 1)[:, None]
        kth = jnp.take_along_axis(sorted_desc, kth_idx, axis=-1)
        scaled = jnp.where(
            (top_k[:, None] > 0) & (scaled < kth), NEG_INF, scaled
        )

    if not no_topp:
        # top-p: keep the smallest prefix with cumulative mass >= top_p
        # (always keep the row argmax). 1.0 disables. Mass is measured on
        # the top-k-filtered distribution (descending positions >= k are
        # the filtered-out tail), matching filters applied in sequence.
        idx = jnp.arange(V)[None, :]
        sorted_masked = jnp.where(
            (top_k[:, None] > 0) & (idx >= top_k[:, None]),
            NEG_INF,
            sorted_desc,
        )
        probs = jax.nn.softmax(sorted_masked, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = jnp.concatenate(
            [jnp.ones((B, 1), bool), cum[:, :-1] < top_p[:, None]], axis=-1
        )
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_masked, jnp.inf), axis=-1,
            keepdims=True,
        )
        scaled = jnp.where(
            (top_p[:, None] < 1.0) & (scaled < thresh), NEG_INF, scaled
        )
    return scaled


@jax.named_scope("sample")
def spec_verify_sample(
    logits: jax.Array,       # [B, W, V] verify logits, position-major
    draft_next: jax.Array,   # [B, W] i32: the draft token each position is
    #                          checking (tokens[:, j+1]); -1 at bonus /
    #                          padding positions (no draft to check)
    key: jax.Array,
    *,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
    legal_mask=None,
) -> tuple[jax.Array, jax.Array]:
    """Per-position draft acceptance for speculative decoding.

    Returns ``(accept [B, W] bool, alt [B, W] int32)``. The host walks each
    row's positions left to right: while ``accept[j]`` holds, draft j+1 is
    emitted; at the first rejection (or at the row's bonus position)
    ``alt[j]`` is emitted instead, and the rest of the row is discarded.

    Greedy rows (temperature <= 0): accept is exact argmax match and alt
    is the argmax — the emitted stream is byte-identical to non-speculative
    greedy decoding. Sampled rows use standard rejection sampling against
    the deterministic n-gram proposal q = delta(draft): accept with
    probability p(draft) under the filtered target distribution p
    (filter_logits — the same array ``sample`` draws from); on rejection,
    alt is drawn from the residual max(0, p - q) normalized, i.e. p
    conditioned on != draft; at the bonus position (draft_next < 0) alt is
    a plain sample from p. The marginal law of every emitted token is
    exactly p, so the served distribution is provably unchanged.

    The all-scalar greedy case (python temperature <= 0) compiles to a bare
    argmax + compare — no sort, no categorical (mirrors ``sample``'s
    specialization contract).

    ``legal_mask`` ([B, W, V] bool or None): position j's mask is the
    constraint state AFTER consuming the row's draft prefix up to j —
    masking before filtering makes p the constrained target, so a forced
    draft (single legal token) has p(draft) exactly 1.0 in f32 (every
    competitor underflows through exp(NEG_INF)) and u ~ U[0,1) < 1.0
    accepts it ALWAYS, greedy or sampled: forced runs are free drafts
    under the unmodified acceptance rule.
    """
    B, W, V = logits.shape
    logits = _apply_mask(logits, legal_mask)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, W]
    if isinstance(temperature, (int, float)) and temperature <= 0.0:
        return greedy == draft_next, greedy

    flat = logits.reshape(B * W, V).astype(jnp.float32)
    # Per-request params broadcast over the row's W positions.
    rep = lambda a, dt: jnp.broadcast_to(  # noqa: E731
        jnp.asarray(a, dt).reshape(-1, 1) if jnp.ndim(a) else
        jnp.asarray(a, dt), (B, W)
    ).reshape(B * W)
    temp = rep(temperature, jnp.float32)
    no_topk = isinstance(top_k, int) and top_k == 0
    no_topp = isinstance(top_p, (int, float)) and top_p >= 1.0
    filtered = filter_logits(
        flat, temp, rep(top_k, jnp.int32), rep(top_p, jnp.float32),
        no_topk=no_topk, no_topp=no_topp,
    )
    dn = draft_next.reshape(B * W)
    probs = jax.nn.softmax(filtered, axis=-1)
    p_draft = jnp.take_along_axis(
        probs, jnp.clip(dn, 0, V - 1)[:, None], axis=-1
    )[:, 0]
    k_u, k_alt = jax.random.split(key)
    u = jax.random.uniform(k_u, (B * W,))
    # Residual on rejection: p excluding the rejected draft; the bonus
    # position (dn < 0) excludes nothing (plain sample from p).
    excl = (jnp.arange(V)[None, :] == dn[:, None]) & (dn >= 0)[:, None]
    alt_s = jax.random.categorical(
        k_alt, jnp.where(excl, NEG_INF, filtered), axis=-1
    ).astype(jnp.int32)
    g = greedy.reshape(B * W)
    accept = jnp.where(temp > 0, u < p_draft, g == dn) & (dn >= 0)
    alt = jnp.where(temp > 0, alt_s, g)
    return accept.reshape(B, W), alt.reshape(B, W)


@jax.named_scope("sample")
def spec_verify_sample_tree(
    logits: jax.Array,       # [B, W, V] verify logits, column-major
    tokens: jax.Array,       # [B, W] i32: col 0 the pending token, cols
    #                          1..lens-1 the tree nodes' tokens
    parents: jax.Array,      # [B, W] i32: parent COLUMN per column (col 0
    #                          ignored); chain rows carry j - 1
    lens: jax.Array,         # [B] i32: real columns (1..W)
    key: jax.Array,
    *,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
    legal_mask=None,
) -> tuple[jax.Array, jax.Array]:
    """Token-tree draft acceptance (``spec_verify_sample`` generalized
    from a chain to an ancestor tree; SpecInfer-style multi-branch
    rejection sampling).

    Returns ``(accept [B, W] bool, alt [B, W] int32)``, CHILD-indexed:
    ``accept[c]`` says whether node column c is accepted by its PARENT's
    logits, and ``alt[j]`` is column j's fallback token — drawn from j's
    filtered target distribution with j's own children's tokens excluded
    (the residual after every child was rejected; a leaf excludes
    nothing, which is the chain bonus sample). The host walks the tree
    root-down: at each node it descends into the first accepted child in
    sibling (insertion-priority) order, else emits ``alt`` and stops.

    Greedy (temperature <= 0): ``accept[c]`` is an exact argmax match
    against the parent — at most one sibling can match (sibling tokens
    are distinct by tree construction), so the walk reproduces
    sequential greedy decoding byte-for-byte, and a chain-shaped tree
    reproduces ``spec_verify_sample``'s emissions exactly.

    Sampled rows: sibling c's acceptance probability is
    ``p(x_c) / (1 - sum of ELDER siblings' p)`` — the sequential
    rejection-sampling scheme against the shared filtered target
    (filter_logits): try the first sibling against p, on rejection
    renormalize p without it and try the next, finally sample the
    residual excluding all siblings. The marginal law of every emitted
    token is exactly p, so the served distribution is unchanged; with a
    single child per node this is rejection sampling against the same
    target as ``spec_verify_sample`` (the draws ride child-indexed keys,
    so the chain STREAM differs while the law does not).

    ``legal_mask`` ([B, W, V] bool or None): column j's mask is the
    constraint state after consuming j's ANCESTOR path (the distribution
    j's logits feed) — siblings at an FSM branch point are each legal
    under their shared parent's mask, so multi-branch rejection sampling
    covers the branch with the standard elder-sibling renormalization.
    """
    B, W, V = logits.shape
    logits = _apply_mask(logits, legal_mask)
    steps = jnp.arange(W, dtype=jnp.int32)[None, :]
    valid = (steps >= 1) & (steps < lens[:, None])             # [B, W]
    par = jnp.clip(parents.astype(jnp.int32), 0, W - 1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [B, W]
    g_par = jnp.take_along_axis(greedy, par, axis=1)           # [B, W]
    g_accept = valid & (g_par == tokens)
    if isinstance(temperature, (int, float)) and temperature <= 0.0:
        return g_accept, greedy

    flat = logits.reshape(B * W, V).astype(jnp.float32)
    rep = lambda a, dt: jnp.broadcast_to(  # noqa: E731
        jnp.asarray(a, dt).reshape(-1, 1) if jnp.ndim(a) else
        jnp.asarray(a, dt), (B, W)
    ).reshape(B * W)
    temp = rep(temperature, jnp.float32)
    no_topk = isinstance(top_k, int) and top_k == 0
    no_topp = isinstance(top_p, (int, float)) and top_p >= 1.0
    filtered = filter_logits(
        flat, temp, rep(top_k, jnp.int32), rep(top_p, jnp.float32),
        no_topk=no_topk, no_topp=no_topp,
    ).reshape(B, W, V)
    probs = jax.nn.softmax(filtered, axis=-1)                  # [B, W, V]
    # p(x_c) under the PARENT's target distribution, per child column.
    parent_probs = probs[jnp.arange(B)[:, None], par]          # [B, W, V]
    p_vals = jnp.take_along_axis(
        parent_probs, jnp.clip(tokens, 0, V - 1)[:, :, None], axis=2
    )[:, :, 0]
    p_vals = jnp.where(valid, p_vals, 0.0)                     # [B, W]
    # Elder-sibling mass: same parent, earlier column — the probability
    # already consumed by the siblings tried (and rejected) before c.
    same_par = par[:, :, None] == par[:, None, :]              # [B, W, W]
    elder = (
        same_par & (steps[:, None, :] < steps[:, :, None])
        & valid[:, :, None] & valid[:, None, :]
    )
    mass = jnp.einsum("bcs,bs->bc", elder.astype(jnp.float32), p_vals)
    k_u, k_alt = jax.random.split(key)
    u = jax.random.uniform(k_u, (B, W))
    s_accept = valid & (
        u * jnp.maximum(1.0 - mass, 1e-9) < p_vals
    )
    # Residual fallback per NODE: its target with its children's tokens
    # excluded (scatter child tokens onto their parents' rows; invalid
    # columns drop out of range).
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, W))
    excl = jnp.zeros((B, W, V), bool).at[
        bidx,
        jnp.where(valid, par, W),
        jnp.clip(tokens, 0, V - 1),
    ].set(True, mode="drop")
    alt_s = jax.random.categorical(
        k_alt, jnp.where(excl, NEG_INF, filtered).reshape(B * W, V),
        axis=-1,
    ).astype(jnp.int32).reshape(B, W)
    tmat = temp.reshape(B, W)
    accept = jnp.where(tmat > 0, s_accept, g_accept)
    alt = jnp.where(tmat > 0, alt_s, greedy)
    return accept, alt
