"""FLOP and byte counts from shapes, against hand counts at both
configurations' widths, and the peaks table."""
import json
import os

import pytest

import counts
import harness

CONF = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _widths(name):
    ref = harness.load_module(
        os.path.join(os.path.dirname(CONF), "reference", "qwen3.py"), "r")
    with open(os.path.join(CONF, name + ".json")) as f:
        return ref.dims(json.load(f))


DENSE, PRUNED = _widths("qwen3-1.7b"), _widths("qwen3-1.7b-spa50")


def test_widths_as_published_and_pruned():
    assert (DENSE["L"], DENSE["d"], DENSE["H"], DENSE["KH"], DENSE["hd"],
            DENSE["vhd"], DENSE["F"], DENSE["V"]) == \
        (28, 2048, 16, 8, 128, 128, 6144, 151_936)
    assert (PRUNED["H"], PRUNED["KH"], PRUNED["hd"], PRUNED["vhd"],
            PRUNED["F"]) == (8, 4, 128, 64, 3072)


@pytest.mark.parametrize("w,params,pair,kv_token", [
    # q 2048*16*128 + k,v 2*2048*8*128 + o 16*128*2048 + mlp 3*2048*6144
    (DENSE, 4_194_304 + 4_194_304 + 4_194_304 + 37_748_736, 2 * 16 * 256,
     8 * 256 * 2),
    # q 2048*8*128 + k 2048*4*128 + v 2048*4*64 + o 8*64*2048
    # + mlp 3*2048*3072
    (PRUNED, 2_097_152 + 1_048_576 + 524_288 + 1_048_576 + 18_874_368,
     2 * 8 * 192, 4 * 192 * 2),
])
def test_per_layer_counts(w, params, pair, kv_token):
    assert counts.layer_matmul_params(w) == params
    assert counts.attn_pair_flops(w) == pair
    assert counts.kv_bytes_per_token(w) == kv_token
    # the whole model's KV per token: 114,688 B dense, 43,008 B pruned
    assert counts.kv_bytes_per_token(w) * w["L"] == \
        {8: 114_688, 4: 43_008}[w["KH"]]


def test_decode_attention_and_step():
    f, b = counts.decode_attn(DENSE, [100, 300])
    assert f == 8192 * 400
    assert b == 4096 * 400 + 2 * 16 * 256 * 2
    per_token = 2 * (28 * 50_331_648 + 2048 * 151_936)
    assert counts.decode_step_flops(DENSE, [100, 300]) == \
        2 * per_token + 28 * 8192 * 400
    f, b = counts.decode_attn(PRUNED, [64])
    assert (f, b) == (3072 * 64, 1536 * 64 + 8 * 192 * 2)


def test_prefill_attention_and_step():
    # row 0: 256 queries from 0 (256*257/2 pairs); row 1: 10 queries
    # after 1,024 cached keys (10*1024 + 10*11/2)
    pairs = 32_896 + 10_240 + 55
    assert counts.causal_pairs([0, 1024], [256, 10]) == pairs
    f, b = counts.prefill_attn(DENSE, [0, 1024], [256, 10])
    assert f == 8192 * pairs
    assert b == 4096 * (256 + 1034) + 266 * 16 * 256 * 2
    assert counts.prefill_step_flops(DENSE, [0, 1024], [256, 10]) == \
        2 * 28 * 50_331_648 * 266 + 2 * 2048 * 151_936 * 2 + 28 * 8192 * pairs


def test_least_time_picks_the_binding_roof():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert counts.least_time(197e12, 1.0, peak) == (1.0, "compute")
    assert counts.least_time(1.0, 819e9 * 2, peak) == (2.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(counts.UnknownDevice):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(counts.UnknownDevice):
        counts.peaks("cpu")
