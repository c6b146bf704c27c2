"""Device time of the prefill programs (``jit_orion_prefill``) that the three
``prefill_*_ms_per_ktoken.batch`` of a part do not read: the embedding
lookup, the head on each row's last position, operations under no part, and
the time inside a program during which no operation ran (the first token's
sampler is a program of its own and is in none of the four), per 1000 real
prompt positions (``prefill_tokens``) of the traced segment (the denominator
of ``prefill_device_ms_per_ktoken.batch``), from the instructions' scope
paths in the trace (``benchmarks/trace/scopes.py``). Read over the six
seconds after the window, like the metric it splits, so it moves with which
prompts fall there. A program without named programs and parts reads
nothing."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.prefill_ms_per_ktoken(obs, None)
