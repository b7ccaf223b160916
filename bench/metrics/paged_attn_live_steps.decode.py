"""Share of the decode calls' paged-attention grid steps that touch a live
KV block: the engine's counters over the window, live over launched grid
steps (``serve/decode_attn_live_steps``, ``serve/decode_attn_grid_steps``,
counted once a call by ``kernels/paged_attention/ops.grid_steps``)."""
UNIT = "%"


def read(data):
    grid = data.counters.get("decode_attn_grid_steps")
    if not grid:
        return None
    return 100.0 * data.counters.get("decode_attn_live_steps", 0.0) / grid
