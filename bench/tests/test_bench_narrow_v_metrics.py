"""The pruned cell's per-layer metrics: the V pool's lane fill from the
engine's counter pair, and the paged-attention kernel's roofline shares
at the cell's narrow value width.  Each is silent where it finds nothing
to read (a program without the counters, a run without a trace)."""
import math
import os

import numpy as np
import pytest

import counts
import harness
import trace_reduce as tr
from repro.kernels.paged_attention.ops import pool_lanes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOFLINES = ("paged_attn_roofline.narrow_v.decode",
             "paged_attn_roofline.narrow_v.prefill")
# the pruned configuration's pool: 28 layers, 8,193 blocks of 16 tokens,
# 4 KV heads of 64 values
L, P, BS, KH, DV = 28, 8193, 16, 4, 64


def _data(counters=None, trace=None, calls=None, widths=None, peak=None):
    return harness.RunData(
        widths=widths or {}, peak=peak or {}, block_size=BS,
        window=(0.0, 1.0), reqs=[], setup_s=0.0, counters=counters or {},
        phases={}, calls=calls or {"decode": [], "prefill": []},
        trace=trace)


def _lanes(shape, steps=3):
    value, stored = pool_lanes(shape)
    return {"v_pool_lanes_value": float(steps * value),
            "v_pool_lanes_stored": float(steps * stored)}


@pytest.mark.parametrize("shape,fill", [
    ((L, P, KH, BS, DV), 50.0),         # head-major: 64 values, 128 stored
    ((L, P, BS, KH * DV), 100.0),       # heads side by side on the lanes
    ((L, P, BS, 8 * 128), 100.0),       # the dense model's 8 heads of 128
], ids=["head_major_64", "packed_256", "dense_1024"])
def test_lane_fill_from_counter_pair(shape, fill):
    got = harness.read_metrics(["v_pool_lane_fill"], _data(_lanes(shape)))
    assert got == {"v_pool_lane_fill": {"value": fill, "unit": "%"}}


@pytest.mark.parametrize("counters", [
    {},                                           # a program without them
    {"v_pool_lanes_value": 0.0, "v_pool_lanes_stored": 0.0},
], ids=["absent", "no_calls"])
def test_lane_fill_silent_without_counters(counters):
    assert harness.read_metrics(["v_pool_lane_fill"],
                                _data(counters)) == {}


def test_pool_lanes_pads_to_128():
    assert pool_lanes((2, 3, 64)) == (6 * 64, 6 * 128)
    assert pool_lanes((2, 3, 256)) == (6 * 256, 6 * 256)
    assert pool_lanes((5, 200)) == (5 * 200, 5 * 256)


def _pruned():
    conf = harness.load_json(BENCH, "configs", "qwen3-1.7b-spa50.json")
    ref = harness.load_module(os.path.join(BENCH, "reference", "qwen3.py"),
                              "ref_narrow_v")
    return ref.dims(conf)


def _trace():
    chip = "/device:TPU:0"
    return tr.summary({
        "modules": [["jit__step_impl", 0, 40_000_000, chip],
                    ["jit__prefill_impl", 50_000_000, 200_000_000, chip]],
        "ops": [["paged_attention.11", 0, 30_000_000, "jit__step_impl",
                 False, chip],
                ["paged_attention.9", 50_000_000, 20_000_000,
                 "jit__prefill_impl", False, chip]],
        "host": []})


def test_rooflines_at_the_narrow_value_width():
    w = _pruned()
    assert w["vhd"] == 64 and w["hd"] == 128
    peak = counts.peaks("TPU v5 lite")
    kv = np.array([1300, 2100, 700])
    starts, valids = np.array([1024, 0]), np.array([256, 100])
    calls = {"decode": [(kv,)], "prefill": [(starts, valids)]}
    got = harness.read_metrics(list(ROOFLINES), _data(
        trace=_trace(), calls=calls, widths=w, peak=peak))
    # the kernel's least time over its traced time (30 ms and 20 ms)
    f, b = counts.decode_attn(w, kv)
    want_d = 100 * counts.least_time(f * L, b * L, peak)[0] / 30e-3
    f, b = counts.prefill_attn(w, starts, valids)
    want_p = 100 * counts.least_time(f * L, b * L, peak)[0] / 20e-3
    assert got[ROOFLINES[0]]["value"] == pytest.approx(want_d)
    assert got[ROOFLINES[1]]["value"] == pytest.approx(want_p)
    assert all(v["unit"] == "%" for v in got.values())
    # prefill reads each live token's 4 x (128 + 64) bf16 K and V values
    # once, and its 8 heads' query and output for each real token
    assert b == pytest.approx(KH * (128 + DV) * 2 * (starts + valids).sum()
                              + valids.sum() * 8 * (128 + DV) * 2)
    assert 0 < got[ROOFLINES[0]]["value"] < 100
    assert math.isfinite(got[ROOFLINES[1]]["value"])


@pytest.mark.parametrize("what", ["no_trace", "no_calls"])
def test_rooflines_silent_without_trace_or_calls(what):
    calls = {"decode": [(np.array([100]),)],
             "prefill": [(np.array([0]), np.array([16]))]}
    data = _data(trace=None if what == "no_trace" else _trace(),
                 calls=calls if what == "no_trace" else None,
                 widths=_pruned(), peak=counts.peaks("TPU v5 lite"))
    assert harness.read_metrics(list(ROOFLINES), data) == {}
