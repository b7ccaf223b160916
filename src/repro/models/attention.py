"""Grouped-query attention with RoPE, qk-norm, masking modes, KV-cache decode.

Parameters are kept 3-D ``(d_model, heads, head_dim)`` so (a) tensor
parallelism shards the *head* axis, and (b) the SPA pruning graph sees heads
as a first-class channel axis (head pruning = slicing axis 1).

Mask modes:
  "causal"  — standard decoder
  "sliding" — causal + window (Hymba SWA layers)
  "bidir"   — encoder (HuBERT)
  "prefix"  — PaliGemma: bidirectional over the first ``prefix_len`` tokens
              (image+prompt), causal after.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


def attn_init(key, cfg) -> dict:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    vhd = cfg.v_head_dim_
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, (d, H, hd), dt),
        "wk": dense_init(kk, (d, KH, hd), dt),
        "wv": dense_init(kv, (d, KH, vhd), dt),
        "wo": dense_init(ko, (H, vhd, d), dt, fan_in=H * vhd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


ATTN_AXES = {
    "wq": ("fsdp", "heads", "head_dim"),
    "wk": ("fsdp", "kv_heads", "head_dim"),
    "wv": ("fsdp", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "fsdp"),
    "q_norm": ("head_dim",),
    "k_norm": ("head_dim",),
}


def _build_mask(mode: str, q_pos: jax.Array, kv_pos: jax.Array,
                window: int, prefix_len: int) -> jax.Array:
    """Boolean (…, Sq, Skv) mask; True = attend."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    causal = k <= q
    if mode == "bidir":
        return jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if mode == "causal":
        return causal
    if mode == "sliding":
        return causal & (k > q - window)
    if mode == "prefix":
        return causal | (k < prefix_len)
    raise ValueError(mode)


def _qkv(params, cfg, x, positions):
    """Project + rope + qk-norm.  Returns q (B,S,KH,G,hd), k, v (B,S,KH,hd)."""
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KH
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(q.shape[:2] + (KH, G, hd))
    return q, k, v


def _sdpa(q, k, v, mask):
    """q (B,Sq,KH,G,hd); k,v (B,Skv,KH,hd); mask (B?,Sq,Skv) -> (B,Sq,KH,G,hd)."""
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", q, k).astype(jnp.float32) * scale
    # constraining the *logits* (not just q) is what forces GSPMD to shard
    # the attention matmuls: an operand-only constraint gets re-gathered
    # (§Perf iteration A1 — hypothesis refuted, fixed here)
    logits = constrain(logits, "batch", "kv_heads", None, "seq_q", "kv_seq")
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqs,bshk->bqhgk", probs, v)
    return constrain(o, "batch", "seq_q", "kv_heads", None, None)


def attention_block(params: dict, cfg, x: jax.Array, positions: jax.Array,
                    mask_mode: str, window: int = 0, prefix_len: int = 0,
                    ) -> jax.Array:
    """Full-sequence attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions)
    if cfg.use_pallas and mask_mode in ("causal", "bidir", "sliding"):
        # Pallas flash attention (TPU target; interpret mode on CPU)
        from repro.kernels.flash_attention import flash_attention
        qf = q.reshape(B, S, q.shape[2] * q.shape[3], q.shape[4])
        o = flash_attention(qf, k, v, causal=mask_mode != "bidir",
                            window=window if mask_mode == "sliding" else 0)
    else:
        mask = _build_mask(mask_mode, positions, positions, window, prefix_len)
        if mask.ndim == 2:
            mask = jnp.broadcast_to(mask[None], (B,) + mask.shape)
        # "seq_q" -> model enables context-parallel attention: per-device
        # work becomes S/tp x S regardless of head divisibility
        q = constrain(q, "batch", "seq_q", "kv_heads", None, None)
        o = _sdpa(q, k, v, mask)
        o = o.reshape(B, S, o.shape[2] * o.shape[3], o.shape[4])
    o = constrain(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


class KVCache(NamedTuple):
    k: jax.Array    # (B, S_max, KH, hd)
    v: jax.Array    # (B, S_max, KH, hd)


def init_layer_cache(cfg, batch: int, max_len: int, dtype) -> KVCache:
    KH, hd, vhd = cfg.n_kv_heads, cfg.head_dim_, cfg.v_head_dim_
    return KVCache(jnp.zeros((batch, max_len, KH, hd), dtype),
                   jnp.zeros((batch, max_len, KH, vhd), dtype))


def attention_decode(params: dict, cfg, x: jax.Array, pos: jax.Array,
                     cache: KVCache, mask_mode: str, window: int = 0,
                     prefix_len: int = 0) -> tuple[jax.Array, KVCache]:
    """One-token decode.  x (B,1,d); pos scalar int32 (current index)."""
    B = x.shape[0]
    KH, hd = cfg.n_kv_heads, cfg.head_dim_
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    ck = jax.lax.dynamic_update_slice(cache.k, k_new, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v_new, (0, pos, 0, 0))
    S = ck.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    valid = kv_pos <= pos
    if mask_mode == "sliding":
        valid &= kv_pos > pos - window
    # bidir/prefix reduce to "attend to all valid" during decode
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, S))
    o = _sdpa(q, ck, cv, mask)
    o = o.reshape(B, 1, o.shape[2] * o.shape[3], o.shape[4])
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, KVCache(ck, cv)


def _scatter_kv(kv: dict, layer, k_new, v_new, block_tables, positions,
                inchunk=None) -> dict:
    """Scatter one layer's per-token K/V (B, C, KH, hd) into the stacked
    pool at ``layer`` and the blocks their absolute ``positions`` (B, C)
    map to through ``block_tables`` (B, NB).

    ``kv`` is the stacked pool that rides the layer scan's carry:
    ``{"k"}`` (L, P, KH, bs, hd) and ``{"v"}`` (L, P, bs, KH*vhd) plus,
    when the pool is quantized, ``{"k_scale", "v_scale"}`` (L, P, KH, bs)
    f32.  Each token takes one scatter per leaf — at (layer, block, head,
    offset) into K and the scales, and one lane-dense row at (layer,
    block, offset) into V — which XLA updates in place in the loop
    carry: no layer's pool is sliced out or stacked back.  ``inchunk``
    (B, C) bool masks padding: masked tokens (and positions pointing past
    the table) are redirected to the layer's reserved null block 0, where
    writes are harmless by construction.  Shared by the paged decode,
    chunked-prefill and speculative draft/verify paths, so the "where
    does a token's KV land — and what bytes does it land as" rule exists
    exactly once.  Plain
    narrow pools cast on write (a draft pool may be allocated narrower
    than the compute dtype — ``ServeConfig.draft_cache_dtype``);
    quantized pools quantize symmetrically on write, storing the
    per-(token, kv-head) scale at the same (layer, block, offset)
    coordinates (DESIGN.md §11)."""
    k_pool, v_pool = kv["k"], kv["v"]
    KH, bs, NB = k_pool.shape[2], k_pool.shape[3], block_tables.shape[1]
    blk_idx = jnp.clip(positions // bs, 0, NB - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    off = positions % bs
    if inchunk is not None:
        blk = jnp.where(inchunk, blk, 0)
        off = jnp.where(inchunk, off, 0)
    # K is head-major within a block: (layer, block, head, offset); V is
    # token-major, one row of every head's values: (layer, block, offset)
    at = (layer, blk[..., None], jnp.arange(KH), off[..., None])
    at_v = (layer, blk, off)
    B, C = positions.shape

    def rows(v):
        return v.reshape(B, C, v_pool.shape[-1])

    if "k_scale" in kv:
        from repro.kernels.paged_attention import quantize
        qk, sk = quantize(k_new, k_pool.dtype)
        qv, sv = quantize(v_new, v_pool.dtype)
        return {"k": k_pool.at[at].set(qk),
                "v": v_pool.at[at_v].set(rows(qv)),
                "k_scale": kv["k_scale"].at[at].set(sk),
                "v_scale": kv["v_scale"].at[at].set(sv)}
    return {"k": k_pool.at[at].set(k_new.astype(k_pool.dtype)),
            "v": v_pool.at[at_v].set(rows(v_new).astype(v_pool.dtype))}


def attention_paged_decode(params: dict, cfg, x: jax.Array,
                           positions: jax.Array, kv: dict, layer,
                           block_tables: jax.Array,
                           window=0) -> tuple[jax.Array, dict]:
    """One-token decode over a paged KV pool (continuous batching).

    x (B,1,d); positions (B,) int32 — per-sequence write index (sequences in
    a serving batch are at *different* depths, unlike ``attention_decode``'s
    single scalar pos).  ``kv`` is the stacked pool ``{"k", "v"}``
    ((L, P, KH, bs, hd) and (L, P, bs, KH*vhd)), plus ``{"k_scale",
    "v_scale"}`` when quantized, carried whole through the layer scan;
    ``layer`` (int32 scalar, traced or not) is this layer's index into
    it: the new token is scattered there and the attention reads it
    there.  block_tables (B, NB) maps logical to pool blocks.  window:
    python int for static masking (the kernel on TPU) or a (B,) array for
    per-sequence dynamic windows (hybrid layers; reference path).

    Returns (out (B,1,d), new stacked kv dict).
    """
    from repro.kernels.paged_attention import paged_attention

    B = x.shape[0]
    q, k_new, v_new = _qkv(params, cfg, x, positions[:, None])
    kv = _scatter_kv(kv, layer, k_new, v_new, block_tables,
                     positions[:, None])
    qf = q.reshape(B, q.shape[2] * q.shape[3], q.shape[4])
    o = paged_attention(qf, kv["k"], kv["v"], block_tables, positions + 1,
                        layer=layer, window=window,
                        k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
    o = o[:, None]                                       # (B, 1, H, vhd)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, kv


def attention_paged_prefill(params: dict, cfg, x: jax.Array,
                            positions: jax.Array, kv: dict, layer,
                            block_tables: jax.Array,
                            valid: jax.Array, window=0
                            ) -> tuple[jax.Array, dict]:
    """Chunked-prefill attention over the paged KV pool.

    x (B, C, d) — a fixed-size chunk of tokens per sequence, right-padded;
    positions (B, C) absolute write indices (``chunk_start + arange(C)``);
    valid (B,) real-token counts.  K/V of the valid tokens are scattered
    into ``layer``'s pool blocks their positions map to (padding scatters
    into the reserved null block 0), then the chunk's queries attend
    causally over that layer's *pool* history — which includes any prefix
    blocks aliased in by prefix caching.  The per-row absolute-position
    masking makes the same path serve speculative verify chunks
    (``[sampled token, K drafts]``): each drafted query sees exactly the
    history a one-token decode at its position would see.  ``kv``,
    ``layer`` and window as in ``attention_paged_decode``.  Returns
    (out (B, C, d), new stacked kv dict).
    """
    from repro.kernels.paged_attention import paged_prefill_attention

    B, C, _ = x.shape
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    inchunk = jnp.arange(C)[None, :] < valid[:, None]
    kv = _scatter_kv(kv, layer, k_new, v_new, block_tables, positions,
                     inchunk)
    qf = q.reshape(B, C, q.shape[2] * q.shape[3], q.shape[4])
    o = paged_prefill_attention(
        qf, kv["k"], kv["v"], block_tables, positions[:, 0],
        positions[:, 0] + valid, layer=layer, window=window,
        k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, kv


def attention_flops(cfg, batch: int, seq: int, causal: bool = True) -> int:
    """Analytic attention matmul FLOPs (for MODEL_FLOPS accounting)."""
    H, hd = cfg.n_heads, cfg.head_dim_
    pairs = seq * seq * (0.5 if causal else 1.0)
    return int(2 * 2 * batch * H * pairs * hd)
