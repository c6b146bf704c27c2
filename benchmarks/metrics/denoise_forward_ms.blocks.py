"""Device time of the block program (``jit_orion_denoise_block``: the
denoising forwards of one block for every live slot, the choice of positions
after each, and the commit forward) per forward: its modules' seconds over
runs x (``denoising_steps`` + 1), from the traced segment. What
``decode_step_ms.batch`` is to a decode window. A program without the block
program, or another kind of configuration, reads nothing."""
from benchmarks.metrics import sdar


def read(obs):
    found = sdar.block_program(obs)
    if found is None:
        return None
    got, runs = found
    return 1e3 * got["module_s"][sdar.PROGRAM] / (
        runs * sdar.forwards_a_block(obs["config"]))
