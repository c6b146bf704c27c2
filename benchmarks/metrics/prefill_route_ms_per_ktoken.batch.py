"""Device time of the prefill programs (``jit_orion_prefill``) in the feed-
forward's norm, the router and the dispatch: sort, gathers, the combine
(``mlp_moe/norm``, ``mlp_moe/router``, ``mlp_moe/dispatch``), per 1000 real
prompt positions (``prefill_tokens``) of the traced segment (the denominator
of ``prefill_device_ms_per_ktoken.batch``), from the instructions' scope
paths in the trace (``benchmarks/trace/scopes.py``). Read over the six
seconds after the window, like the metric it splits, so it moves with which
prompts fall there. A program without named programs and parts reads
nothing."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.prefill_ms_per_ktoken(obs, scopes.PREFILL_ROUTE)
