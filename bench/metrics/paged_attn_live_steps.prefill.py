"""Share of the prefill calls' paged-attention grid steps that touch a live
KV block: the engine's counters over the window, live over launched grid
steps (``serve/prefill_attn_live_steps``, ``serve/prefill_attn_grid_steps``,
counted once a call by ``kernels/paged_attention/ops.grid_steps``)."""
UNIT = "%"


def read(data):
    grid = data.counters.get("prefill_attn_grid_steps")
    if not grid:
        return None
    return 100.0 * data.counters.get("prefill_attn_live_steps", 0.0) / grid
