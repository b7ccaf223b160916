"""The per-layer metrics that read the engine's dispatch counters: each is a
ratio (or a count) of the window's counter deltas, and each is silent where
its counters are absent (a program without them) or its denominator is 0."""
import pytest

import harness

READERS = ("paged_attn_live_steps.decode", "paged_attn_live_steps.prefill",
           "prefill_token_share", "compiles_in_window")


def _data(counters: dict) -> harness.RunData:
    return harness.RunData(widths={}, peak={}, block_size=16,
                           window=(0.0, 1.0), reqs=[], setup_s=0.0,
                           counters=counters, phases={},
                           calls={"decode": [], "prefill": []})


def test_readers_compute_from_counters():
    got = harness.read_metrics(list(READERS), _data({
        "decode_attn_live_steps": 1024.0, "decode_attn_grid_steps": 8192.0,
        "prefill_attn_live_steps": 30.0, "prefill_attn_grid_steps": 2000.0,
        "prefill_chunk_tokens": 512.0, "prefill_token_slots": 4096.0,
        "compiles": 0.0}))
    assert got == {
        "paged_attn_live_steps.decode": {"value": 12.5, "unit": "%"},
        "paged_attn_live_steps.prefill": {"value": 1.5, "unit": "%"},
        "prefill_token_share": {"value": 12.5, "unit": "%"},
        "compiles_in_window": {"value": 0.0, "unit": "count"}}


def test_compiles_counted():
    got = harness.read_metrics(["compiles_in_window"],
                               _data({"compiles": 3.0}))
    assert got["compiles_in_window"]["value"] == 3.0


@pytest.mark.parametrize("counters", [
    {},                                           # a program without them
    {"decode_attn_live_steps": 0.0, "decode_attn_grid_steps": 0.0,
     "prefill_attn_live_steps": 0.0, "prefill_attn_grid_steps": 0.0,
     "prefill_chunk_tokens": 0.0, "prefill_token_slots": 0.0},
], ids=["absent", "no_calls"])
def test_readers_silent_without_counters(counters):
    assert harness.read_metrics(list(READERS), _data(counters)) == {}
