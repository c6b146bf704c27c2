"""Device time of the decode-window program (``jit_orion_decode_window``) in
the feed-forward: norm, router, dispatch, routed experts, shared expert or
dense MLP (every ``mlp_moe/*``), per token step (the denominator of
``decode_step_ms.batch``), from the instructions' scope paths in the trace
(``benchmarks/trace/scopes.py``). A program without named programs and parts
reads nothing."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.decode_ms_per_step(obs, scopes.DECODE_FFN)
