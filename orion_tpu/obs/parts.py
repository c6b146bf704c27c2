"""The parts of the model that a device operation is booked to, and the names
the serve programs' modules carry: the one place either is spelled.

The layer body (``models/transformer.block``), its backends
(``infer/runner``), the expert dispatch (``models/moe``) and the sampler
enter each part as nested ``jax.named_scope``s (``attention`` then ``qkv``),
so that a compiled instruction's ``op_name`` reads
``jit(orion_decode_window)/while/body/attention/qkv/dot_general``. A scope is
metadata on an instruction: no instruction, operand, shape, layout or
schedule changes with it (``tools/program_diff.py``). The reader is the
benchmark's ``benchmarks/trace/scopes.py``; XProf's framework-op view shows
the same paths to an operator.
"""

from __future__ import annotations

# A part is "<parent>/<child>", or a parent that has no children.
PARTS = (
    "embed",                # the embedding lookup
    "attention/norm",       # the input norm (and the post-norm)
    "attention/qkv",        # q/k/v projections (latent/down), per-head
                            # q/k norm, rotary, a retention layer's log-gate
    "attention/kernel",     # the attention, latent or retention kernel (or
                            # its XLA form) and what it is handed:
                            # latent/absorb, latent/expand, gate row, c_tail
    "attention/cache",      # what writes or gathers the cache outside the
                            # kernel: page scatter, the new column's select
                            # and update, gathers through page ids
    "attention/out",        # the output projection (and the residual add)
    "mlp_moe/norm",         # the norm (and the post-norm)
    "mlp_moe/router",       # scores, top-k, bias, renormalisation
    "mlp_moe/dispatch",     # sort, gathers, scatter into buckets, combine
    "mlp_moe/experts",      # the routed experts' matmuls
    "mlp_moe/shared",       # the shared expert
    "mlp_moe/dense",        # a dense layer's MLP
    "unembed",              # final norm and the head's matmul
    "sample",               # the sampler or the greedy pick
)
# Under no part: a window program's top (weight stacks re-laid once a
# window), key handling, loop bookkeeping.
UNSCOPED = "unscoped"
# The train step's own scopes (``train/trainer.py``), around the model's.
STEP_SCOPES = ("fwd_bwd", "fwd_bwd_zero1", "optimizer", "anomaly_guard")

# Dispatch stem (``DispatchExecutor.PROGRAM_FNS``) -> the name its jitted
# program carries: the compiled module is ``jit_<name>``. Only the decode
# program may carry ``decode_window`` (the benchmark adds up every module of
# that name).
PROGRAM_NAMES = {
    "prefill": "orion_prefill",
    "decode": "orion_decode_window",
    "mixed": "orion_mixed",
    "verify": "orion_verify",
    "mixed_verify": "orion_mixed_verify",
    "fold": "orion_fold",
    "denoise": "orion_denoise_block",
}
