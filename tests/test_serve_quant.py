"""Quantized paged KV pools (ServeConfig.cache_dtype; DESIGN.md §11).

Three layers of coverage:

  1. kernel vs reference: the Pallas fused-dequant epilogue must match
     the jnp oracle *bit-for-bit in what the stored bytes mean* — both
     sides dequantize the same pool, so parity is tight (the attention
     math, not the quantizer, is under test) — and stay within a
     per-dtype tolerance of the unquantized oracle across GQA, sliding
     window and prefill shapes (the quantizer's error budget);
  2. the quantizer itself: symmetric per-(token, kv-head) scales, bounded
     round-trip error, zero-vector safety (null-block writes);
  3. the engine: scale pools allocated and COW'd in lockstep with their
     KV blocks, greedy outputs matching the fp32 engine's top-1 tokens on
     a briefly-*trained* model (random-init argmax is noise — quantization
     cannot preserve a decision the model makes at chance), and the
     sliding-window DMA skip asserted through the visit counters that
     share the kernel's index-map liveness predicate.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import (
    dequantize, is_quantized, paged_attention, paged_attention_reference,
    paged_prefill_attention, paged_prefill_attention_reference, pool_dtype,
    quantize, v_rows)
from repro.launch.serve import generate
from repro.models import build
from repro.serve import Engine, ServeConfig

rng = np.random.default_rng(11)

# attention-output tolerance vs the full-precision oracle: int8 holds
# ~2.4 significant digits per element, fp8-e4m3 ~1 (3-bit mantissa)
QTOL = {"int8": 2e-2, "fp8_e4m3": 1e-1}


def _quantized_pools(P, bs, KH, D, DV, dtype_name):
    k = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, KH, bs, DV)), jnp.float32)
    dt = pool_dtype(dtype_name)
    qk, sk = quantize(k, dt)
    qv, sv = quantize(v, dt)
    return k, v_rows(v), qk, sk, v_rows(qv), sv


# ---------------------------------------------------------------------------
# 1. kernel vs reference, quantized pools
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype_name", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("B,H,KH,D,DV,bs,NB,window", [
    (2, 4, 2, 16, 16, 8, 4, 0),       # GQA
    (3, 4, 1, 32, 16, 4, 8, 0),       # MQA, DV != D
    (1, 8, 8, 16, 16, 16, 2, 0),      # MHA
    (2, 4, 2, 16, 16, 8, 4, 5),       # GQA + sliding window
])
def test_quantized_decode_kernel_parity(dtype_name, B, H, KH, D, DV, bs,
                                        NB, window):
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k, v, qk, sk, qv, sv = _quantized_pools(P, bs, KH, D, DV, dtype_name)
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    lens = jnp.asarray(rng.integers(1, NB * bs + 1, size=(B,)), jnp.int32)

    out = paged_attention(q, qk, qv, tables, lens, window=window,
                          use_kernel=True, k_scale=sk, v_scale=sv)
    ref = paged_attention_reference(q, qk, qv, tables, lens, window=window,
                                    k_scale=sk, v_scale=sv)
    # fused dequant == gather-then-dequant: same bytes, same values
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and the quantizer's error stays inside the per-dtype budget
    full = paged_attention_reference(q, k, v, tables, lens, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=QTOL[dtype_name])


@pytest.mark.parametrize("dtype_name", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("B,C,H,KH,D,bs,NB", [
    (2, 4, 4, 2, 16, 8, 4),
    (3, 7, 4, 1, 32, 4, 8),
])
def test_quantized_prefill_kernel_parity(dtype_name, B, C, H, KH, D, bs,
                                         NB):
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    k, v, qk, sk, qv, sv = _quantized_pools(P, bs, KH, D, D, dtype_name)
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    starts = jnp.asarray(rng.integers(0, NB * bs - C + 1, size=(B,)),
                         jnp.int32)
    valid = rng.integers(1, C + 1, size=(B,))
    lens = starts + jnp.asarray(valid, jnp.int32)

    out = paged_prefill_attention(q, qk, qv, tables, starts, lens,
                                  use_kernel=True, k_scale=sk, v_scale=sv)
    ref = paged_prefill_attention_reference(q, qk, qv, tables, starts, lens,
                                            k_scale=sk, v_scale=sv)
    full = paged_prefill_attention_reference(q, k, v, tables, starts, lens)
    for b in range(B):                 # rows past valid are don't-care
        np.testing.assert_allclose(np.asarray(out)[b, :valid[b]],
                                   np.asarray(ref)[b, :valid[b]],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out)[b, :valid[b]],
                                   np.asarray(full)[b, :valid[b]],
                                   atol=QTOL[dtype_name])


def test_quantized_window_skip_visit_counters():
    """The kernel loops over a row's window-live pages only, fetching and
    computing those and no others: the visit counters must equal the
    analytic count of window-live blocks exactly."""
    B, H, KH, D, bs, NB = 2, 2, 2, 16, 4, 8
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k, v, qk, sk, qv, sv = _quantized_pools(P, bs, KH, D, D, "int8")
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    for window, lens in ((6, [32, 13]), (3, [9, 27]), (12, [32, 5])):
        lens_a = jnp.asarray(lens, jnp.int32)
        out, visits = paged_attention(q, qk, qv, tables, lens_a,
                                      window=window, use_kernel=True, return_visits=True,
                                      k_scale=sk, v_scale=sv)
        ref = paged_attention_reference(q, qk, qv, tables, lens_a,
                                        window=window, k_scale=sk,
                                        v_scale=sv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # a page is live when it holds a key at or before the query (at
        # L - 1) and right of its window's left edge (L - 1 - window)
        expect = [sum(j * bs < L and j * bs + bs - 1 > L - 1 - window
                      for j in range(NB)) for L in lens]
        np.testing.assert_array_equal(
            np.asarray(visits), np.tile(np.asarray(expect)[:, None], KH))
        assert int(np.asarray(visits).sum()) < B * NB * KH


# ---------------------------------------------------------------------------
# 2. the quantizer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype_name", ["int8", "fp8_e4m3"])
def test_quantize_roundtrip_and_zero_safety(dtype_name):
    dt = pool_dtype(dtype_name)
    x = jnp.asarray(rng.normal(size=(5, 3, 8)) * 10, jnp.float32)
    q, s = quantize(x, dt)
    assert q.dtype == dt and s.shape == x.shape[:-1]
    back = dequantize(q, s)
    # symmetric per-vector scaling: int8's error is uniform (half a step
    # of the vector's absmax); fp8-e4m3's is mantissa-relative (3 bits ->
    # up to 1/16 of the value, largest at the absmax element)
    absmax = np.asarray(jnp.max(jnp.abs(x), axis=-1))
    bound = absmax * {"int8": 0.5 / 127.0, "fp8_e4m3": 1.0 / 16.0}[dtype_name]
    err = np.max(np.abs(np.asarray(back - x)), axis=-1)
    assert (err <= bound + 1e-6).all()
    # the null-block write case: all-zero vectors quantize to exactly 0
    qz, sz = quantize(jnp.zeros((4, 8)), dt)
    assert not np.asarray(dequantize(qz, sz)).any()
    assert not np.asarray(sz).any()


def test_is_quantized_names():
    assert is_quantized("int8") and is_quantized("fp8_e4m3")
    assert not is_quantized("") and not is_quantized("bfloat16")
    assert not is_quantized(None) and not is_quantized("float32")


# ---------------------------------------------------------------------------
# 3. the engine
# ---------------------------------------------------------------------------

def _train_briefly(model, params, steps=80, seed=3):
    """A few steps of next-token training on an affine-cycle task: enough
    logit structure that top-1 agreement is a real claim (random-init
    argmax flips under any perturbation, quantization included)."""
    from repro.train.optim import OptConfig, init_opt_state, make_train_step
    V = model.cfg.vocab_size
    mult, add = 37, 11
    chain = np.empty(2 * V, np.int64)
    chain[0] = 0
    for i in range(len(chain) - 1):
        chain[i + 1] = (chain[i] * mult + add) % V
    step = jax.jit(make_train_step(model, OptConfig(
        lr=3e-3, warmup_steps=10, total_steps=steps)))
    opt = init_opt_state(params)
    r = np.random.default_rng(seed)
    for _ in range(steps):
        rows = [chain[int(r.integers(0, V)):][:32] for _ in range(8)]
        params, opt, _ = step(params, opt,
                              {"tokens": np.stack(rows).astype(np.int32)})
    return params, chain


@pytest.fixture(scope="module")
def trained():
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params, chain = _train_briefly(m, m.init(jax.random.PRNGKey(0)))
    return m, params, chain


def test_engine_scale_pools_allocated(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    eng = Engine(m, m.init(key), ServeConfig(
        max_seqs=2, block_size=4, max_len=16, cache_dtype="int8"))
    assert eng.cache["k"].dtype == jnp.int8
    assert eng.cache["v"].dtype == jnp.int8
    for name in ("k_scale", "v_scale"):
        assert eng.cache[name].dtype == jnp.float32
        # scales mirror the K pool's (L, P, KH, bs) block layout
        assert eng.cache[name].shape == eng.cache["k"].shape[:-1]


def test_engine_rejects_unknown_cache_dtype(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    with pytest.raises(ValueError, match="cache_dtype"):
        Engine(m, m.init(key), ServeConfig(cache_dtype="int4"))


@pytest.mark.parametrize("dtype_name", ["int8", "fp8_e4m3"])
def test_engine_top1_matches_fp32_on_trained_model(trained, dtype_name):
    """Greedy int8/fp8 engine == greedy fp32 engine == sequential oracle,
    token for token, on the briefly-trained model — the accuracy half of
    the bandwidth-for-accuracy trade (DESIGN.md §11)."""
    m, params, chain = trained
    V = m.cfg.vocab_size
    r = np.random.default_rng(5)
    prompts = [[int(t) for t in chain[int(r.integers(0, V)):][:9 - (i % 3)]]
               for i in range(4)]
    GEN = 8

    def serve(dt):
        eng = Engine(m, params, ServeConfig(
            max_seqs=4, block_size=4, max_len=32, chunk_size=4,
            cache_dtype=dt))
        rids = [eng.add_request(p, max_new_tokens=GEN) for p in prompts]
        out, stats = eng.run()
        return [out[r].tokens for r in rids], stats

    ref, ref_stats = serve("")
    for i, p in enumerate(prompts):     # fp32 engine == sequential oracle
        oracle = np.asarray(generate(
            m, params, jnp.asarray(p, jnp.int32)[None], GEN))
        assert ref[i] == list(oracle[0, len(p):])
    qout, qstats = serve(dtype_name)
    assert qout == ref, dtype_name
    # quantization must not change scheduler behavior: same step count
    assert qstats["steps"] == ref_stats["steps"]
    assert qstats["prefill_chunks"] == ref_stats["prefill_chunks"]


def test_engine_cow_copies_scale_blocks(trained):
    """Prefix-cached int8 serving with COW must match the same engine
    with prefix caching off: an aliased block's scales travel with it,
    and a COW copy moves k/v *and* k_scale/v_scale (a dropped scale copy
    would dequantize the copied bytes under the wrong scale)."""
    m, params, chain = trained
    shared = [int(t) for t in chain[:8]]
    prompts = [shared + [int(t) for t in chain[8 + i:10 + i]]
               for i in range(3)] + [shared]     # full-cover hit -> COW

    def serve(prefix):
        eng = Engine(m, params, ServeConfig(
            max_seqs=2, block_size=4, max_len=32, chunk_size=4,
            cache_dtype="int8", prefix_caching=prefix))
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out, _ = eng.run()
        return [out[r].tokens for r in rids], eng

    plain, plain_eng = serve(False)
    cached, eng = serve(True)
    assert eng._cow_copies > 0                   # COW actually fired
    assert eng.cache_host.allocator.total_allocated < \
        plain_eng.cache_host.allocator.total_allocated  # sharing paid
    assert cached == plain


def test_spec_draft_pool_int8_lossless_greedy(key):
    """An int8-quantized *draft* pool may change which drafts are
    proposed, but greedy verify keeps the emitted tokens byte-identical
    to the dense-only engine (same contract as bfloat16 narrowing)."""
    from repro.core.pruner import prune_model
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    pr = prune_model(m, params, 0.5, criterion="l1")
    dm, dp = build(pr.cfg), pr.params
    B, P, GEN = 3, 11, 10
    prompt = jax.random.randint(jax.random.PRNGKey(91), (B, P), 0,
                                cfg.vocab_size)
    prompts = [[int(t) for t in prompt[b]] for b in range(B)]
    ref = np.asarray(generate(m, params, prompt, GEN))

    eng = Engine(m, params, ServeConfig(
        max_seqs=3, block_size=4, max_len=32, chunk_size=4, spec_k=3,
        draft_cache_dtype="int8"), draft_model=dm, draft_params=dp)
    assert eng.draft_cache["k"].dtype == jnp.int8
    assert "k_scale" in eng.draft_cache
    assert eng.cache["k"].dtype == jnp.float32    # target pool untouched
    rids = [eng.add_request(p, max_new_tokens=GEN) for p in prompts]
    out, stats = eng.run()
    assert stats["spec_cycles"] > 0
    for b, r in enumerate(rids):
        assert out[r].tokens == list(ref[b, P:]), b


def test_quantized_pool_bytes_shrink(key):
    """The capacity claim at its root: an int8 pool (elements + scales)
    is < 0.4x the bytes of the fp32 pool for the same block count."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)

    def pool_bytes(dt):
        eng = Engine(m, params, ServeConfig(
            max_seqs=2, block_size=4, max_len=16, cache_dtype=dt))
        return sum(int(np.prod(eng.cache[n].shape))
                   * eng.cache[n].dtype.itemsize
                   for n in ("k", "v", "k_scale", "v_scale")
                   if n in eng.cache)

    assert pool_bytes("int8") < 0.4 * pool_bytes("")
