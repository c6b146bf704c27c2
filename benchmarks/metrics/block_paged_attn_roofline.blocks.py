"""The W-query paged attention kernel of the block program (a block's 4
queries a slot, all 4 new rows visible to each) against the MEMORY roofline:
the least time is the K and V a forward has to read (the engine's
``block_kv_positions_read``: per live slot and forward its cached positions
and the block's own, each 6 layers x 2 x 4 heads x 128 x 2 B:
``sdar.position_bytes``) over the published bandwidth; the time is the block
program's under the part ``attention/kernel``, by SCOPE
(``benchmarks/trace/scopes.py``). A program without the block program or the
counter, or another kind of configuration, reads nothing."""
from benchmarks.metrics import sdar
from benchmarks.trace import scopes


def read(obs):
    found = sdar.block_program(obs)
    if found is None or not obs.get("peaks"):
        return None
    positions = obs["trace"]["timing"].get("block_kv_positions_read")
    seconds = scopes.seconds(found[0], sdar.PROGRAM, ("attention/kernel",))
    if not positions or not seconds:
        return None
    least = (positions * sdar.position_bytes(obs["config"])
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
