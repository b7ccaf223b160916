"""A value head narrower than the query/key head and than the TPU's 128
lanes, as structural pruning leaves it (SPA-L1 at 0.5 of Qwen3-1.7B: q/k
head 128, v head 64).  The V pool is token-major, heads side by side on
the lanes (``v_rows``); these tests hold the paged-attention kernel to
its reference at that width, and the serving engine, through chunked
prefill, a prefix-cache hit and decode, to the benchmark's plain float32
reference (``bench/reference/qwen3.py``) on logits."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention import (
    paged_attention, paged_attention_reference, paged_prefill_attention,
    paged_prefill_attention_reference, pool_dtype, quantize, v_rows)
from repro.kernels.paged_attention.ops import pool_lanes
from repro.models import build
from repro.models.api import Model
from repro.serve import Engine, ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "qwen3.py")
    spec = importlib.util.spec_from_file_location("bench_ref_qwen3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the kernel (interpret mode) against ref.py at q/k head 128
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("H,KH,DV", [(8, 4, 64), (4, 2, 128)],
                         ids=["dv64", "dv128"])
def test_kernel_matches_reference_at_value_width(H, KH, DV, mode,
                                                 cache_dtype):
    L, B, C, D, bs, NB = 2, 3, 5, 128, 16, 4
    P = B * NB + 1
    rng = np.random.default_rng(DV)
    k = jnp.asarray(rng.normal(size=(L, P, KH, bs, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, KH, bs, DV)), jnp.float32)
    if cache_dtype == "int8":
        (k, ks), (v, vs) = (quantize(x, pool_dtype("int8")) for x in (k, v))
        # same stored bytes on both sides: only the order of the f32
        # sums differs (online softmax against one softmax)
        q_dtype, tol = jnp.float32, 1e-5
    else:
        k, v, ks, vs = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), \
            None, None
        # bf16 queries and pools: the kernel rounds its output to bf16
        # (2^-8 relative) on outputs of magnitude up to about 3
        q_dtype, tol = jnp.bfloat16, 3e-2
    v = v_rows(v)
    assert v.shape == (L, P, bs, KH * DV)
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    lens = jnp.asarray(rng.integers(C, NB * bs + 1, size=(B,)), jnp.int32)
    scales = dict(k_scale=ks, v_scale=vs)
    if mode == "decode":
        q = jnp.asarray(rng.normal(size=(B, H, D)), q_dtype)
        out = paged_attention(q, k, v, tables, lens, layer=1,
                              use_kernel=True, **scales)
        ref = paged_attention_reference(q, k, v, tables, lens, layer=1,
                                        **scales)
    else:
        q = jnp.asarray(rng.normal(size=(B, C, H, D)), q_dtype)
        out = paged_prefill_attention(q, k, v, tables, lens - C, lens,
                                      layer=1, use_kernel=True, **scales)
        ref = paged_prefill_attention_reference(q, k, v, tables, lens - C,
                                                lens, layer=1, **scales)
    assert out.shape[-1] == DV
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_v_rows_puts_heads_side_by_side():
    v = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)  # KH bs DV
    rows = np.asarray(v_rows(v))
    assert rows.shape == (3, 8)
    for h in range(2):
        np.testing.assert_array_equal(rows[:, 4 * h:4 * (h + 1)],
                                      np.asarray(v[h]))


# ---------------------------------------------------------------------------
# the engine against the plain float32 reference, on logits
# ---------------------------------------------------------------------------

# a small pruned width: q/k head 128, v head 64
SMALL = {"name": "qwen3-small-narrow-v", "num_hidden_layers": 2,
         "hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 128, "v_head_dim": 64,
         "intermediate_size": 384, "vocab_size": 512, "rms_norm_eps": 1e-6,
         "rope_theta": 1000000.0, "tie_word_embeddings": True}
CHUNK, BLOCK = 32, 16
# logits of these weights are about 0.3 in magnitude.  float32: the same
# arithmetic in both programs up to the order of sums (paged online
# softmax against one softmax, fused projections) -- observed 2.2e-6 to
# 2.4e-6.  bfloat16: the program rounds activations and the pools to 8
# significant bits at every layer while the reference computes in
# float32 from the same bf16 weights -- observed 0.014 to 0.016; the
# benchmark's limit at full width and depth is 0.13.
LOGIT_TOL = {"float32": 1e-4, "bfloat16": 5e-2}


class _Recorder(Model):
    """The model with every decode and prefill step's logits handed to the
    host in order, with the position each row's logits predict from."""

    def paged_decode_step(self, params, cache, tokens, positions,
                          block_tables, active=None):
        logits, cache = super().paged_decode_step(
            params, cache, tokens, positions, block_tables, active)
        jax.debug.callback(_record, positions, active, logits, ordered=True)
        return logits, cache

    def paged_prefill_step(self, params, cache, tokens, positions, slots,
                           block_tables, valid):
        logits, cache = super().paged_prefill_step(
            params, cache, tokens, positions, slots, block_tables, valid)
        last = positions[:, 0] + valid - 1
        jax.debug.callback(_record, last, valid > 0, logits, ordered=True)
        return logits, cache


_LOG: list[tuple[int, np.ndarray]] = []


def _record(rows, live, logits):
    for b in np.flatnonzero(np.asarray(live)):
        _LOG.append((int(rows[b]), np.asarray(logits[b], np.float32)))


@pytest.mark.parametrize("path,dtype", [
    ("reference", "float32"), ("kernel", "float32"), ("kernel", "bfloat16"),
])
def test_engine_logits_match_reference(path, dtype, monkeypatch):
    """Request A prefills 80 tokens in three 32-token chunks and decodes;
    request B opens with A's first three blocks (a prefix-cache hit of 48
    tokens), prefills its own 40 tokens in two chunks and decodes.  Every
    logit row the engine computes matches the reference's full forward
    pass over the same tokens."""
    from repro.kernels.paged_attention import ops
    rng = np.random.default_rng(7)
    monkeypatch.setattr(ops, "_kernel_path",
                        lambda window: path == "kernel"
                        and isinstance(window, int))
    ref = _reference()
    conf = dict(SMALL, torch_dtype=dtype)
    params = ref.make_params(conf, 2**33 + 5)
    model = _Recorder(ArchConfig(**ref.program_arch(conf)))
    eng = Engine(model, params, ServeConfig(
        max_seqs=2, block_size=BLOCK, max_len=128, chunk_size=CHUNK))
    a = rng.integers(0, conf["vocab_size"], 80).tolist()
    b = a[:3 * BLOCK] + rng.integers(0, conf["vocab_size"], 40).tolist()
    worst = 0.0
    # (prompt, first uncached position, the rows whose logits the engine
    # computes: each chunk's last row, then one row per decode step)
    for prompt, start in ((a, 0), (b, 3 * BLOCK)):
        n = len(prompt)
        want_rows = list(range(start + CHUNK - 1, n - 1, CHUNK)) + list(
            range(n - 1, n + 5))
        _LOG.clear()
        rid = eng.add_request(prompt, max_new_tokens=6)
        out, _ = eng.run()
        jax.effects_barrier()
        assert [r for r, _ in _LOG] == want_rows
        got = np.stack([x for _, x in _LOG])
        want = ref.logits_at(params, conf, prompt + out[rid].tokens,
                             want_rows)
        worst = max(worst, float(np.abs(got - want).max()))
        # greedy tokens are the argmax of the program's own logits
        assert out[rid].tokens == got[-6:].argmax(-1).tolist()
    assert eng.cache_host.prefix_hits >= 3      # B's three shared blocks
    assert worst < LOGIT_TOL[dtype], worst


def test_engine_counts_v_pool_lanes():
    """The engine's counter pair: the V pool's lanes that hold values and
    the lanes stored for them, summed over the pool once per step
    program that carries it -- 100% with the heads side by side."""
    ref = _reference()
    conf = dict(SMALL, torch_dtype="float32")
    model = build(ArchConfig(**ref.program_arch(conf)))
    eng = Engine(model, ref.make_params(conf, 3), ServeConfig(
        max_seqs=2, block_size=BLOCK, max_len=64, chunk_size=CHUNK))
    eng.add_request(list(range(7, 47)), max_new_tokens=3)
    eng.run()
    c = eng.obs.registry.counter_values("serve/")
    value, stored = pool_lanes(eng.cache["v"].shape)
    assert stored == value == eng.cache["v"].size
    calls = c["serve/v_pool_lanes_value"] // value
    assert calls >= 2 + 2                 # two prefill chunks, decodes
    assert c["serve/v_pool_lanes_stored"] == calls * stored
