"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.obspa_update import obspa_sweep, sweep_oracle
from repro.kernels.obspa_update.ref import sweep_reference
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref

rng = np.random.default_rng(42)


@pytest.mark.parametrize("B,S,H,KH,D,DV,causal,window,dtype", [
    (2, 128, 4, 2, 32, 32, True, 0, jnp.float32),
    (1, 200, 4, 1, 64, 48, True, 0, jnp.float32),
    (2, 128, 8, 8, 32, 32, False, 0, jnp.float32),
    (1, 256, 4, 2, 32, 32, True, 64, jnp.float32),
    (1, 128, 2, 2, 64, 64, True, 0, jnp.bfloat16),
    (1, 96, 4, 4, 16, 16, True, 32, jnp.bfloat16),
])
def test_flash_attention(B, S, H, KH, D, DV, causal, window, dtype):
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KH, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KH, DV)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("R,K,frac", [
    (64, 96, 0.3), (100, 256, 0.5), (17, 130, 0.7), (256, 128, 0.25),
])
def test_obspa_sweep(R, K, frac):
    W = rng.normal(size=(R, K)).astype(np.float32)
    X = rng.normal(size=(K, 4 * K)).astype(np.float32)
    H = (X @ X.T / (4 * K) + 0.01 * np.eye(K)).astype(np.float32)
    Hinv = np.linalg.inv(H).astype(np.float32)
    mask = rng.random(K) < frac
    gold = sweep_oracle(W, Hinv, mask)
    kern = np.asarray(obspa_sweep(W, Hinv, mask))
    refj = np.asarray(sweep_reference(
        jnp.asarray(W), jnp.asarray(Hinv), jnp.asarray(mask)))
    scale = np.abs(gold).max() + 1e-9
    assert np.abs(kern - gold).max() / scale < 1e-4
    assert np.abs(refj - gold).max() / scale < 1e-4


def test_obspa_sweep_zeroes_pruned_columns():
    R, K = 32, 64
    W = rng.normal(size=(R, K)).astype(np.float32)
    Hinv = np.eye(K, dtype=np.float32)
    mask = np.zeros(K, bool)
    mask[[3, 10, 50]] = True
    out = np.asarray(obspa_sweep(W, Hinv, mask))
    assert np.abs(out[:, mask]).max() < 1e-6
    # identity Hessian -> no compensation of kept columns
    np.testing.assert_allclose(out[:, ~mask], W[:, ~mask], atol=1e-6)


@pytest.mark.parametrize("b,l,h,p,n,Q,dtype", [
    (2, 64, 4, 16, 16, 16, jnp.float32),
    (1, 256, 2, 32, 64, 64, jnp.float32),
    (2, 128, 8, 64, 128, 32, jnp.float32),
    (1, 64, 2, 16, 32, 32, jnp.bfloat16),
])
def test_ssd_scan(b, l, h, p, n, Q, dtype):
    x = jnp.asarray(rng.normal(size=(b, l, h, p)), dtype)
    dt = jnp.asarray(rng.random((b, l, h)) * 0.5 + 0.05, jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(h,))) - 0.1, jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, l, n)), dtype)
    C = jnp.asarray(rng.normal(size=(b, l, n)), dtype)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(dtype)
    out = np.asarray(ssd_scan(xdt, dt, A, B, C, Q), np.float32)
    ref = np.asarray(ssd_scan_ref(xdt, dt, A, B, C, Q), np.float32)
    scale = np.abs(ref).max() + 1e-9
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    assert np.abs(out - ref).max() / scale < tol


def test_model_pallas_parity(key):
    """Model forward with use_pallas must match the XLA path."""
    from repro.configs import get_config, reduced
    from repro.models import build
    for name in ["tinyllama-1.1b", "mamba2-1.3b"]:
        cfg = reduced(get_config(name))
        m0, m1 = build(cfg), build(cfg.replace(use_pallas=True))
        p = m0.init(key)
        b = m0.dummy_batch(key, 2, 32)
        l0, l1 = float(m0.loss(p, b)[0]), float(m1.loss(p, b)[0])
        assert abs(l0 - l1) < 1e-3, name


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "fp8_e4m3"])
@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_paged_attention_reads_stacked_pool_at_layer(mode, cache_dtype,
                                                     layer):
    """The kernel on every layer's pool stacked ((L, P, KH, bs, D) and
    (L, P, bs, KH*DV), two 8-wide value heads sharing a lane group),
    handed a traced layer index, equals the reference on that layer's
    pool alone at the edges of its chunks (32 pages of 4 tokens, 128
    keys): kv lengths 1, one block, a chunk less one, a chunk, a chunk
    and one, the whole table.  A row with kv length 0 runs no iteration
    and writes zeros.  The kernel visits exactly the live pages
    ``grid_steps`` counts."""
    from repro.kernels.paged_attention import (
        paged_attention, paged_attention_reference, paged_prefill_attention,
        paged_prefill_attention_reference, pool_dtype, quantize, v_rows)
    from repro.kernels.paged_attention.ops import chunk_pages, grid_steps

    L, C, H, KH, D, DV, bs, NB = 3, 4, 4, 2, 16, 8, 4, 40
    keys = 128                         # a chunk: 32 pages of 4 tokens
    lens = np.array([1, bs, keys - 1, keys, keys + 1, NB * bs, 0], np.int32)
    B = len(lens)
    P = B * NB + 1
    at = 0 if layer == "first" else L - 1
    k = jnp.asarray(rng.normal(size=(L, P, KH, bs, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, KH, bs, DV)), jnp.float32)
    if cache_dtype != "bfloat16":
        (k, ks), (v, vs) = (quantize(x, pool_dtype(cache_dtype))
                            for x in (k, v))
        q_dtype, tol = jnp.float32, 1e-5
    else:
        k, v, ks, vs = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), \
            None, None
        q_dtype, tol = jnp.bfloat16, 3e-2
    v = v_rows(v)
    assert chunk_pages(k, v, NB) * bs == keys
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    one = dict(k_scale=None if ks is None else ks[at],
               v_scale=None if vs is None else vs[at])
    if mode == "decode":
        q = jnp.asarray(rng.normal(size=(B, H, D)), q_dtype)
        starts = lens - 1
        valid = np.minimum(lens, 1)
        out, visits = jax.jit(lambda q, layer: paged_attention(
            q, k, v, tables, jnp.asarray(lens), layer=layer, k_scale=ks,
            v_scale=vs, use_kernel=True, return_visits=True))(q, at)
        ref = paged_attention_reference(q, k[at], v[at], tables,
                                        jnp.asarray(lens), **one)
        out, ref = out[:, None], ref[:, None]
    else:
        q = jnp.asarray(rng.normal(size=(B, C, H, D)), q_dtype)
        starts = np.maximum(lens - C, 0)
        valid = lens - starts
        out, visits = jax.jit(lambda q, layer: paged_prefill_attention(
            q, k, v, tables, jnp.asarray(starts), jnp.asarray(lens),
            layer=layer, k_scale=ks, v_scale=vs, use_kernel=True,
            return_visits=True))(q, at)
        ref = paged_prefill_attention_reference(
            q, k[at], v[at], tables, jnp.asarray(starts), jnp.asarray(lens),
            **one)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    for b in range(B):                 # rows past valid are don't-care
        np.testing.assert_allclose(out[b, :valid[b]], ref[b, :valid[b]],
                                   rtol=tol, atol=tol)
    assert not out[-1].any()           # the empty row: zeros, no NaN
    live, _ = grid_steps(lens, starts, chunk_pages(k, v, NB), bs)
    assert int(np.asarray(visits)[-1, 0]) == 0
    assert int(np.asarray(visits)[:, 0].sum()) == live
