"""Pure-jnp oracle for paged decode attention.

One query token per sequence attends over a KV history stored in
non-contiguous fixed-size blocks of a shared pool, addressed through a
per-sequence block table (vLLM-style paging).

Shapes:
  q            (B, H, D)           one decode token per sequence,
                                   H = KH * G
  k_pool       (L, P, KH, bs, D)   every layer's block pool stacked (P
                                   blocks of bs tokens, head-major within
                                   a block), read at ``layer``; with no
                                   layer, one layer's (P, KH, bs, D)
  v_pool       (L, P, bs, KH*DV)   the same blocks token-major, the
                                   heads side by side on the lanes
                                   (``v_rows``); with no layer, one
                                   layer's (P, bs, KH*DV)
  block_tables (B, NB) int32       pool index of each logical block
  kv_lens      (B,)    int32       valid tokens per sequence (incl.
                                   current)
  layer        int32 scalar | None the layer whose pool is read
  window       int | (B,) array    0 = full causal; >0 = sliding window
  k/v_scale    (L, P, KH, bs) f32  per-write dequant scales when the
                                   pools are quantized (int8 / fp8-e4m3)

Output (B, H, DV).  The reference gathers ``pool[layer, block_tables]``,
materializing that layer's history (B, NB*bs, KH, D/DV) and nothing
more; the Pallas kernel never materializes it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.quant import dequantize

NEG_INF = -1e30


def v_rows(v):
    """Head-major values (..., KH, bs, DV) in the V pool's token-major
    layout (..., bs, KH*DV): row t holds token t of every head, head h
    on lanes [h*DV, (h+1)*DV)."""
    *lead, KH, bs, DV = v.shape
    return jnp.swapaxes(v, -3, -2).reshape(*lead, bs, KH * DV)


def _gather_kv(pool, block_tables, scale, layer, heads=None):
    """Gather (B, S, KH, d) history from ``layer``'s pool, dequantizing
    with the identically-gathered scales when given — the same
    bytes->values rule as the kernel's fused dequant, applied after
    materialization.  ``heads`` marks a token-major V pool of that many
    heads; without it the pool is head-major (K)."""
    B, NB = block_tables.shape
    at = block_tables if layer is None else (layer, block_tables)
    out = pool[at]              # (B, NB, KH, bs, d) or (B, NB, bs, KH*d)
    if heads is None:
        out = out.transpose(0, 1, 3, 2, 4)
    KH = out.shape[3] if heads is None else heads
    out = out.reshape(B, NB * out.shape[2], KH, -1)
    if scale is not None:
        out = dequantize(out, scale[at].transpose(0, 1, 3, 2)
                         .reshape(B, out.shape[1], KH))
    return out


def paged_prefill_attention_reference(q, k_pool, v_pool, block_tables,
                                      q_starts, kv_lens, *, layer=None,
                                      window=0,
                                      scale: float | None = None,
                                      k_scale=None, v_scale=None
                                      ) -> jax.Array:
    """Chunked-prefill oracle: C query tokens per sequence at absolute
    positions ``q_starts + arange(C)`` attend causally over the paged
    history.  q (B, C, H, D); ``kv_lens = q_starts + valid``; rows past a
    sequence's valid count produce garbage the caller discards.  Output
    (B, C, H, DV)."""
    B, C, H, D = q.shape
    KH, bs = k_pool.shape[-3], k_pool.shape[-2]
    NB = block_tables.shape[1]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5

    k = _gather_kv(k_pool, block_tables, k_scale, layer)  # (B, S, KH, D)
    v = _gather_kv(v_pool, block_tables, v_scale, layer, heads=KH)

    qg = q.reshape(B, C, KH, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    idx = jnp.arange(NB * bs, dtype=jnp.int32)[None, None, :]    # (1, 1, S)
    qpos = (q_starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
            )[..., None]                                         # (B, C, 1)
    valid = (idx <= qpos) & (idx < kv_lens[:, None, None])
    win = jnp.asarray(window, jnp.int32)
    if win.ndim == 0:
        win = jnp.broadcast_to(win, (B,))
    winb = win[:, None, None]
    valid &= (winb <= 0) | (idx > qpos - winb)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)    # (B,KH,G,C,S)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, C, H, v.shape[-1]).astype(q.dtype)


def paged_attention_reference(q, k_pool, v_pool, block_tables, kv_lens, *,
                              layer=None, window=0,
                              scale: float | None = None,
                              k_scale=None, v_scale=None) -> jax.Array:
    B, H, D = q.shape
    KH, bs = k_pool.shape[-3], k_pool.shape[-2]
    NB = block_tables.shape[1]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5

    k = _gather_kv(k_pool, block_tables, k_scale, layer)  # (B, S, KH, D)
    v = _gather_kv(v_pool, block_tables, v_scale, layer, heads=KH)

    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    idx = jnp.arange(NB * bs, dtype=jnp.int32)[None, :]     # (1, S)
    lens = kv_lens[:, None]
    valid = idx < lens
    win = jnp.asarray(window, jnp.int32)
    if win.ndim == 0:
        win = jnp.broadcast_to(win, (B,))
    valid &= (win[:, None] <= 0) | (idx > lens - 1 - win[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, v.shape[-1]).astype(q.dtype)
