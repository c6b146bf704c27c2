"""Expert-matmul rows a MoE layer computed in the prefill dispatches, per
real prompt position (``prefill_expert_rows`` / ``prefill_tokens``, both engine
counters). The model needs its top-k (Mixtral: 2.0); capacity buckets at the
dropless factor compute all E experts over every dispatched position, padding
included. Counts routed assignments, not the grouped matmul's tile rounding.
Exact counts, so a CPU run prints it too; a program without the counter
(before PR 26) reads nothing."""


def read(obs):
    t = obs["timing"]
    rows, tokens = t.get("prefill_expert_rows"), t.get("prefill_tokens")
    return rows / tokens if rows and tokens else None
