"""Device time of the decode-window program (``jit_orion_decode_window``) that
the four ``decode_*_ms_per_step.batch`` of a part do not read, per token step:
operations under no part of the model (the window's top, where weight stacks
are laid out again once a window; key handling; loop bookkeeping), under a
parent with no child, and the time inside the program during which no
operation ran. With the other four it adds up to ``decode_step_ms.batch``.
A program without named programs and parts reads nothing."""
from benchmarks.trace import scopes


def read(obs):
    return scopes.decode_ms_per_step(obs, None)
