"""``correct`` against the plain reference, at a size a CPU test holds.

A run of a tiny Qwen3-shaped cell through the whole harness (the look for
a chip skipped) comes out correct; the same run with the timed path broken
underneath comes out not correct: a served token altered where it is
sampled, and a decode step that leaves the KV pool as it found it.  The
int8 control, the reference one precision step below bf16, reads above the
limit where the program reads below it, and comes out not correct.
"""
import json
import os
import time

import numpy as np
import pytest

import correctness
import harness
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 5e-4          # tiny model: mean gap of a sound run 1.6e-4, int8 1.2e-3


def tiny_cell() -> harness.Cell:
    with open(os.path.join(BENCH, "configs", "qwen3-1.7b-spa50.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny", hidden_size=256, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                v_head_dim=32, intermediate_size=512, vocab_size=4096)
    conf["serve"] = {"flags": ["--max-seqs", "4", "--max-len", "512",
                               "--block-size", "16", "--chunk-size", "32"]}
    mix = traffic.load_mix("chat-prefix")
    mix["prompt"]["shared"]["length"] = 64
    mix["prompt"]["own"].update(median=32, min=8, max=128)
    mix["output"].update(median=8, min=2, max=32)
    mix.update(lead_in_s=0.5, tail_s=2.0)
    return harness.Cell(
        name="tiny", conf=conf, mix=mix,
        spec={"rate_per_s": 10.0, "limits": {"mean_logit_gap": LIMIT}},
        end_to_end=["output_tokens_per_s"], per_layer=[], chips=1)


@pytest.fixture(autouse=True)
def _few_tokens(monkeypatch):
    monkeypatch.setattr(correctness, "SAMPLE_TOKENS", 60)


def _run(hooks=None, control=False):
    return harness.run_cell(tiny_cell(), 2**33 + 3, 1.5, False,
                            t_start=time.perf_counter(), hooks=hooks,
                            control=control)


def test_sound_run_is_correct_and_the_control_is_not():
    out = _run(control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["mean_logit_gap"]["value"] < LIMIT
    assert out["load"]["tokens_compared"] >= 60
    assert not out["control"]["correct"]
    assert out["control"]["checks"]["mean_logit_gap"]["value"] > LIMIT


def _alter_sampled_token(eng):
    sample = eng._sample

    def altered(logits, temps, key):
        return (sample(logits, temps, key) + 1) % logits.shape[-1]
    eng._sample = altered


def _decode_leaves_pool(eng):
    import jax
    impl = eng._step_impl

    def stale(params, cache, *args):
        tokens, _ = impl(params, cache, *args)
        return tokens, cache
    eng._step_fn = jax.jit(stale)


@pytest.mark.parametrize("fault", [_alter_sampled_token, _decode_leaves_pool])
def test_broken_timed_path_is_not_correct(fault):
    out = _run(hooks=fault)
    assert not out["correct"]
    assert out["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_sample_holds_the_longest_and_enough_tokens():
    recs = [{"prompt": [0] * n, "tokens": [1] * k}
            for n, k in ((5, 3), (50, 2), (7, 9), (3, 4))]
    got = correctness.sample(recs, 7, 10)
    assert got[0] is recs[1]
    assert sum(len(r["tokens"]) for r in got) >= 10
    assert correctness.sample(recs, 7, 10) == got            # seeded
    ok, checks = correctness.judge({"a": 0.1, "b": 2}, {"a": 0.2, "b": 1})
    assert not ok and checks["a"] == {"value": 0.1, "limit": 0.2}
    assert np.isinf(correctness.judge({"a": float("inf")}, {"a": 1})[1]
                    ["a"]["value"])
