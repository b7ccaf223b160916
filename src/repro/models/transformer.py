"""Unified transformer backbone for all six assigned LM families.

One per-layer function covers:
  dense  — GQA attention + SwiGLU
  moe    — GQA attention + routed experts (+ shared experts)
  vlm    — dense backbone, vision-stub patch embeddings prepended, prefix mask
  hybrid — Hymba: parallel attention + SSD heads in the same layer, + SwiGLU
  ssm    — Mamba-2: SSD block only (no attention, no separate FFN)
  audio  — encoder-only (bidirectional) over stub frame embeddings

Layers are scanned (``jax.lax.scan`` over stacked params) for production /
dry-run tracing, or unrolled (python loop over per-layer pytrees) for SPA
graph analysis — both built from the same ``layer_forward`` so they cannot
diverge.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    cross_entropy, dense_init, dtype_of, embed_init, rms_norm, swiglu,
    swiglu_init, SWIGLU_AXES)

from repro.configs.base import AUDIO_FRAME_DIM  # noqa: F401  (stub width)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ArchConfig) -> dict:
    dt = dtype_of(cfg.dtype)
    keys = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dt)}
    if cfg.family != "ssm":
        p["attn"] = attn.attn_init(keys[0], cfg)
    if cfg.family == "ssm" or cfg.hybrid:
        p["ssm"] = ssm_mod.ssm_init(keys[1], cfg)
    if cfg.n_experts:
        p["ln2"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = moe_mod.moe_init(keys[2], cfg)
    elif cfg.d_ff:
        p["ln2"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = swiglu_init(keys[3], cfg.d_model, cfg.d_ff, dt)
    return p


def layer_axes(cfg: ArchConfig) -> dict:
    p: dict[str, Any] = {"ln1": (None,)}
    if cfg.family != "ssm":
        a = dict(attn.ATTN_AXES)
        if not cfg.qk_norm:
            a.pop("q_norm"), a.pop("k_norm")
        p["attn"] = a
    if cfg.family == "ssm" or cfg.hybrid:
        p["ssm"] = dict(ssm_mod.SSM_AXES)
    if cfg.n_experts:
        p["ln2"] = (None,)
        m = dict(moe_mod.MOE_AXES)
        if not cfg.n_shared_experts:
            m.pop("shared")
        p["moe"] = m
    elif cfg.d_ff:
        p["ln2"] = (None,)
        p["mlp"] = dict(SWIGLU_AXES)
    return p


def init_params(cfg: ArchConfig, key) -> dict:
    dt = dtype_of(cfg.dtype)
    k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    if cfg.family == "audio":
        params["frame_proj"] = dense_init(k_emb, (AUDIO_FRAME_DIM, cfg.d_model), dt)
    else:
        params["tok_embed"] = embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt)
    if cfg.family == "vlm":
        params["vision_proj"] = dense_init(
            k_extra, (cfg.vision_embed_dim, cfg.d_model), dt)

    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    per_layer = [layer_init(k, cfg) for k in layer_keys]
    params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)

    params["final_norm"] = jnp.ones((cfg.d_model,), dt)
    if cfg.is_encoder:
        params["head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)
    elif not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)
    return params


def param_axes(cfg: ArchConfig) -> dict:
    axes: dict[str, Any] = {}
    if cfg.family == "audio":
        axes["frame_proj"] = (None, "fsdp")
    else:
        axes["tok_embed"] = ("vocab", "fsdp")
    if cfg.family == "vlm":
        axes["vision_proj"] = (None, "fsdp")
    la = layer_axes(cfg)
    axes["layers"] = jax.tree.map(
        lambda t: ("layers",) + tuple(t), la,
        is_leaf=lambda t: isinstance(t, tuple))
    axes["final_norm"] = (None,)
    if cfg.is_encoder or not cfg.tie_embeddings:
        axes["head"] = ("fsdp", "vocab")
    return axes


# ---------------------------------------------------------------------------
# Per-layer forward (shared by scan + unrolled paths)
# ---------------------------------------------------------------------------

def _mask_mode(cfg: ArchConfig) -> str:
    if cfg.is_encoder:
        return "bidir"
    if cfg.family == "vlm":
        return "prefix"
    return "causal"


def layer_forward(lp: dict, cfg: ArchConfig, x: jax.Array,
                  positions: jax.Array, is_global: jax.Array | None,
                  ) -> tuple[jax.Array, jax.Array]:
    """One layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    mode = _mask_mode(cfg)

    if cfg.family == "ssm":
        x = x + ssm_mod.ssm_block(lp["ssm"], cfg, h)
        return x, aux

    if cfg.hybrid:
        # Hymba: SWA layers window, global layers attend fully.  With scanned
        # layers the mode is data, not code: widen the window to the sequence
        # length when is_global.
        win = jnp.where(is_global, jnp.int32(2**30), jnp.int32(cfg.sliding_window))
        a_out = _hybrid_attention(lp["attn"], cfg, h, positions, win)
        s_out = ssm_mod.ssm_block(lp["ssm"], cfg, h)
        x = x + a_out + s_out
    else:
        x = x + attn.attention_block(
            lp["attn"], cfg, h, positions, mode,
            window=cfg.sliding_window, prefix_len=cfg.vision_tokens)

    if cfg.n_experts:
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        m_out, aux = moe_mod.moe_block(lp["moe"], cfg, h2)
        x = x + m_out
    elif cfg.d_ff:
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + swiglu(lp["mlp"], h2)
    # "seq_sp" -> model: sequence-parallel residual stream — the scan carry
    # (= the remat stash, L x (B,S,d)) shards over the tensor axis
    x = constrain(x, "batch", "seq_sp", None)
    return x, aux


def _hybrid_attention(ap, cfg, h, positions, win):
    """Sliding-window attention with a *dynamic* window (scalar array)."""
    B, S, _ = h.shape
    q, k, v = attn._qkv(ap, cfg, h, positions)
    qp = positions[..., :, None]
    kp = positions[..., None, :]
    mask = (kp <= qp) & (kp > qp - win)
    if mask.ndim == 2:
        mask = jnp.broadcast_to(mask[None], (B,) + mask.shape)
    o = attn._sdpa(q, k, v, mask)
    o = o.reshape(B, S, o.shape[2] * o.shape[3], o.shape[4])
    return jnp.einsum("bshk,hkd->bsd", o, ap["wo"])


# ---------------------------------------------------------------------------
# Full forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch) -> jax.Array:
    if cfg.family == "audio":
        h = batch["frames"].astype(dtype_of(cfg.dtype)) @ params["frame_proj"]
    else:
        h = jnp.take(params["tok_embed"], batch["tokens"], axis=0)
    if cfg.family == "vlm":
        vis = batch["patches"].astype(h.dtype) @ params["vision_proj"]
        h = jnp.concatenate([vis, h], axis=1)
    return constrain(h, "batch", "seq", None)


def _is_global_flags(cfg) -> jax.Array:
    flags = jnp.zeros((cfg.num_layers,), bool)
    if cfg.global_layers:
        flags = flags.at[jnp.asarray(cfg.global_layers)].set(True)
    return flags


def forward(params: dict, cfg: ArchConfig, batch: dict,
            unroll: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (hidden_states (B,S,d), total_aux_loss)."""
    h = _embed_inputs(params, cfg, batch)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    flags = _is_global_flags(cfg)

    if unroll or not cfg.use_scan:
        aux = jnp.zeros((), jnp.float32)
        layers = params["layers"]
        if isinstance(layers, list):            # analysis mode: list of pytrees
            per_layer = layers
        else:
            per_layer = [jax.tree.map(lambda a, i=i: a[i], layers)
                         for i in range(cfg.num_layers)]
        body = layer_forward
        if cfg.remat and not unroll:
            body = jax.checkpoint(
                layer_forward, policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=False, static_argnums=(1,))
        for i, lp in enumerate(per_layer):
            h, a = body(lp, cfg, h, positions, flags[i])
            aux = aux + a
    else:
        body = functools.partial(_scan_body, cfg=cfg, positions=positions)
        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=False)
        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), (params["layers"], flags))

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, aux


def _scan_body(carry, xs, *, cfg, positions):
    h, aux = carry
    lp, flag = xs
    h, a = layer_forward(lp, cfg, h, positions, flag)
    return (h, aux + a), None


def logits_from_hidden(params, cfg, h) -> jax.Array:
    if cfg.is_encoder or not cfg.tie_embeddings:
        logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, params["tok_embed"])
    return constrain(logits, "batch", "seq", "vocab")


def loss_fn(params: dict, cfg: ArchConfig, batch: dict,
            unroll: bool = False) -> tuple[jax.Array, dict]:
    h, aux = forward(params, cfg, batch, unroll=unroll)
    if cfg.is_encoder:
        if cfg.vocab_size <= 16:                 # sequence classification
            pooled = jnp.mean(h, axis=1)
            logits = pooled @ params["head"]
            ce = cross_entropy(logits, batch["targets"])
        else:                                    # per-frame prediction (HuBERT)
            logits = logits_from_hidden(params, cfg, h)
            ce = cross_entropy(logits, batch["targets"])
    else:
        logits = logits_from_hidden(params, cfg, h)
        if cfg.family == "vlm":                  # loss on text positions only
            logits = logits[:, cfg.vision_tokens:]
        ce = cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    dt = dtype_of(cfg.dtype)
    L = cfg.num_layers
    cache: dict[str, Any] = {}
    if cfg.family != "ssm":
        kv = attn.init_layer_cache(cfg, batch, max_len, dt)
        cache["k"] = jnp.broadcast_to(kv.k[None], (L,) + kv.k.shape)
        cache["v"] = jnp.broadcast_to(kv.v[None], (L,) + kv.v.shape)
    if cfg.family == "ssm" or cfg.hybrid:
        sc = ssm_mod.init_ssm_cache(cfg, batch, dt)
        cache["conv"] = jnp.broadcast_to(sc.conv[None], (L,) + sc.conv.shape)
        cache["state"] = jnp.broadcast_to(sc.state[None], (L,) + sc.state.shape)
    return jax.tree.map(jnp.array, cache)        # materialize broadcasts


def cache_axes(cfg: ArchConfig, long_context: bool = False) -> dict:
    """Logical axes of the cache pytree.  "kv_seq" defaults to replicated;
    rules override it for long-context (data) or kv-replicated (model)."""
    del long_context
    axes: dict[str, Any] = {}
    seq_ax = "kv_seq"
    if cfg.family != "ssm":
        axes["k"] = ("layers", "batch", seq_ax, "kv_heads", None)
        axes["v"] = ("layers", "batch", seq_ax, "kv_heads", None)
    if cfg.family == "ssm" or cfg.hybrid:
        axes["conv"] = ("layers", "batch", None, None)
        axes["state"] = ("layers", "batch", "ssm_heads", None, None)
    return axes


def paged_cache_axes(cfg: ArchConfig, quantized: bool = False) -> dict:
    """Logical axes of the paged-pool cache pytree (dry-run sharding and
    the serving engine's sharded jit).  The block-address axes
    (``serve_blocks``, block offset) stay replicated — any slot's blocks
    must be readable from every data shard, and a block is a unit of
    *addressing*, not of parallelism; KV shards over kv_heads (tensor
    parallel) and the per-slot SSM state over the slot (``serve_batch``,
    data parallel) axis.  ``quantized`` adds the scale-pool leaves, which
    shard *exactly* like the K pool minus the head_dim axis: a
    tensor shard holding a kv-head's bytes holds its scales, and pure-DP
    per-device replicas carry scales alongside (DESIGN.md §10/§11)."""
    axes: dict[str, Any] = {}
    if cfg.family != "ssm":
        axes["k"] = ("layers", "serve_blocks", "kv_heads", None, None)
        # V's lanes hold the heads side by side: sharding them over
        # kv_heads gives each shard its own heads' contiguous lanes
        axes["v"] = ("layers", "serve_blocks", None, "kv_heads")
        if quantized:
            axes["k_scale"] = ("layers", "serve_blocks", "kv_heads", None)
            axes["v_scale"] = ("layers", "serve_blocks", "kv_heads", None)
    if cfg.family == "ssm" or cfg.hybrid:
        axes["conv"] = ("layers", "serve_batch", None, None)
        axes["state"] = ("layers", "serve_batch", "ssm_heads", None, None)
    return axes


# the KV leaves, stacked over layers, that ride the layer loop's carry
# (scale pools exist only when the pool is quantized —
# ServeConfig.cache_dtype, DESIGN.md §11)
_KV_POOL_KEYS = ("k", "v", "k_scale", "v_scale")


def _decode_layer(lp: dict, lc: dict, kv: dict, layer, flag, h: jax.Array,
                  cfg: ArchConfig, attn_fn, ssm_fn, moe_mask=None
                  ) -> tuple[jax.Array, dict, dict]:
    """One incremental layer, shared by the contiguous decode, paged decode
    and chunked paged-prefill paths.

    ``attn_fn(attn_params, hn, kv, layer, flag) -> (a_out, kv)`` reads and
    writes the stacked KV leaves ``kv`` at index ``layer``, and
    ``ssm_fn(ssm_params, hn, lc) -> (delta, SSMCache)`` advances this
    layer's recurrent state ``lc``; the two encapsulate everything the
    cache layouts / step widths disagree on, and the residual/FFN
    scaffolding stays single-source.  ``moe_mask`` (B, S) marks real
    tokens for expert dispatch — fixed-shape serving batches carry
    padding that must not consume expert capacity (moe.moe_block).
    Returns (h, kv, this layer's new recurrent state).
    """
    out_cache: dict[str, Any] = {}
    hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        delta, new_sc = ssm_fn(lp["ssm"], hn, lc)
        h = h + delta
        out_cache["conv"], out_cache["state"] = new_sc.conv, new_sc.state
        return h, kv, out_cache
    a_out, kv = attn_fn(lp["attn"], hn, kv, layer, flag)
    if cfg.hybrid:
        s_out, new_sc = ssm_fn(lp["ssm"], hn, lc)
        h = h + a_out + s_out
        out_cache["conv"], out_cache["state"] = new_sc.conv, new_sc.state
    else:
        h = h + a_out
    if cfg.n_experts:
        h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        m_out, _ = moe_mod.moe_block(lp["moe"], cfg, h2,
                                     token_mask=moe_mask)
        h = h + m_out
    elif cfg.d_ff:
        h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + swiglu(lp["mlp"], h2)
    return h, kv, out_cache


def _run_decode_layers(params: dict, cfg: ArchConfig, cache: dict,
                       x: jax.Array, attn_fn, ssm_fn, moe_mask=None
                       ) -> tuple[jax.Array, dict]:
    """Scan/unrolled layer loop + final norm shared by the incremental
    paths.  Returns (hidden (B, S, d), new cache); callers project the
    position(s) they need to logits.

    The KV leaves (``_KV_POOL_KEYS``) ride the loop carry whole, stacked
    (L, ...), beside the hidden state, and each layer reads and writes
    them at its own index (``jnp.arange(L)`` in the scan's xs, a python
    int unrolled): as scan xs/ys, XLA would copy every layer's slice out
    and back, and the whole pool once more, every step (DESIGN.md §8).
    The small per-slot recurrent state (``conv``, ``state``) rides
    xs/ys."""
    flags = _is_global_flags(cfg)
    kv = {n: cache[n] for n in _KV_POOL_KEYS if n in cache}
    per_layer = {n: a for n, a in cache.items() if n not in kv}

    def body(carry, xs):
        h, kv = carry
        lp, lc, flag, layer = xs
        h, kv, oc = _decode_layer(lp, lc, kv, layer, flag, h, cfg, attn_fn,
                                  ssm_fn, moe_mask=moe_mask)
        return (h, kv), oc

    if cfg.use_scan:
        (h, kv), new_cache = jax.lax.scan(
            body, (x, kv), (params["layers"], per_layer, flags,
                            jnp.arange(cfg.num_layers)))
    else:
        h = x
        per_layer_caches = []
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            lc = jax.tree.map(lambda a, i=i: a[i], per_layer)
            (h, kv), oc = body((h, kv), (lp, lc, flags[i], i))
            per_layer_caches.append(oc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer_caches)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, {**new_cache, **kv}


def decode_step(params: dict, cfg: ArchConfig, cache: dict,
                tokens: jax.Array, pos: jax.Array) -> tuple[jax.Array, dict]:
    """One decode step.  tokens (B,) int32, pos scalar int32.

    Returns (logits (B, V), updated cache).
    """
    x = jnp.take(params["tok_embed"], tokens[:, None], axis=0)  # (B,1,d)

    def attn_fn(ap, hn, kv, layer, flag):
        kvc = attn.KVCache(kv["k"][layer], kv["v"][layer])
        if cfg.hybrid:
            win = jnp.where(flag, jnp.int32(2**30),
                            jnp.int32(cfg.sliding_window))
            a_out, new_kv = attn.attention_decode(
                ap, cfg, hn, pos, kvc, "sliding", window=win)
        else:
            a_out, new_kv = attn.attention_decode(
                ap, cfg, hn, pos, kvc, "causal")
        return a_out, {"k": kv["k"].at[layer].set(new_kv.k),
                       "v": kv["v"].at[layer].set(new_kv.v)}

    def ssm_fn(sp, hn, lc):
        return ssm_mod.ssm_decode(sp, cfg, hn,
                                  ssm_mod.SSMCache(lc["conv"], lc["state"]))

    h, new_cache = _run_decode_layers(params, cfg, cache, x, attn_fn, ssm_fn)
    return logits_from_hidden(params, cfg, h)[:, 0], new_cache


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching serving; see DESIGN.md §8)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int,
                     max_seqs: int, dtype: str | None = None) -> dict:
    """Block-pool KV cache + per-slot SSM state.

    KV lives in a shared pool of ``num_blocks`` blocks of ``block_size``
    tokens (block 0 is the reserved null block that idle slots write into);
    SSM/conv state is O(1) per sequence, so it is a plain per-slot tensor —
    paging it would buy nothing.  ``dtype`` overrides the KV pool element
    type: a plain narrow dtype ("bfloat16") casts on write (speculative
    draft pools tolerate lower precision — a draft rejection costs speed,
    never correctness, DESIGN.md §9); a quantized dtype ("int8",
    "fp8_e4m3") additionally allocates per-(block, token, kv-head) f32
    scale pools mirroring the KV pools' block layout, written by
    ``_scatter_kv`` and consumed by the kernel's fused dequant
    (DESIGN.md §11).
    """
    from repro.kernels.paged_attention import is_quantized, pool_dtype
    quant = is_quantized(dtype)
    dt = pool_dtype(dtype) if quant else dtype_of(dtype or cfg.dtype)
    L = cfg.num_layers
    cache: dict[str, Any] = {}
    if cfg.family != "ssm":
        KH, hd, vhd = cfg.n_kv_heads, cfg.head_dim_, cfg.v_head_dim_
        # K head-major within a block: one block of one head is a
        # contiguous (block_size, hd) tile for the paged-attention kernel;
        # V token-major, the heads side by side on the lanes, so a value
        # width under the TPU's 128 lanes (a pruned head) still fills them
        # and the pool keeps the kernel's row-major tiles (DESIGN.md §8)
        cache["k"] = jnp.zeros((L, num_blocks, KH, block_size, hd), dt)
        cache["v"] = jnp.zeros((L, num_blocks, block_size, KH * vhd), dt)
        if quant:
            for name in ("k_scale", "v_scale"):
                cache[name] = jnp.zeros((L, num_blocks, KH, block_size),
                                        jnp.float32)
    if cfg.family == "ssm" or cfg.hybrid:
        # recurrent state keeps the compute dtype: it is carried, not
        # re-derived, so narrowing it would compound per step
        sc = ssm_mod.init_ssm_cache(cfg, max_seqs, dtype_of(cfg.dtype))
        cache["conv"] = jnp.array(
            jnp.broadcast_to(sc.conv[None], (L,) + sc.conv.shape))
        cache["state"] = jnp.array(
            jnp.broadcast_to(sc.state[None], (L,) + sc.state.shape))
    return cache


def paged_decode_step(params: dict, cfg: ArchConfig, cache: dict,
                      tokens: jax.Array, positions: jax.Array,
                      block_tables: jax.Array,
                      active: jax.Array | None = None
                      ) -> tuple[jax.Array, dict]:
    """One continuous-batching decode step.

    tokens (B,) int32; positions (B,) int32 per-slot write index (slots may
    be at different depths — this is what ``decode_step``'s scalar pos can't
    express); block_tables (B, NB) int32; active (B,) bool marks the slots
    actually fed this step (None = all).  Inactive slots — idle, or mid
    chunked-prefill and advancing through ``paged_prefill_step`` instead —
    must keep their recurrent SSM/conv state untouched; their K/V writes
    are already harmless because the engine hands them a zeroed table row
    (everything lands in the null block).  Returns (logits (B, V), cache).
    """
    x = jnp.take(params["tok_embed"], tokens[:, None], axis=0)  # (B,1,d)
    B = tokens.shape[0]
    # slots at position 0 start a (re-)prefill: their recurrent SSM/conv
    # state is from a previous occupant (or idle-step garbage) and must be
    # zeroed before use — KV needs no such reset, reads are length-masked
    fresh = positions == 0

    def attn_fn(ap, hn, kv, layer, flag):
        if cfg.hybrid:
            win = jnp.where(flag, jnp.int32(2**30),
                            jnp.int32(cfg.sliding_window))
            win = jnp.broadcast_to(win, (B,))    # dynamic -> reference path
        else:
            win = 0
        return attn.attention_paged_decode(
            ap, cfg, hn, positions, kv, layer, block_tables, window=win)

    def ssm_fn(sp, hn, lc):
        sc = ssm_mod.SSMCache(
            jnp.where(fresh[:, None, None], 0, lc["conv"]),
            jnp.where(fresh[:, None, None, None], 0, lc["state"]))
        return ssm_mod.ssm_decode(sp, cfg, hn, sc)

    h, new_cache = _run_decode_layers(
        params, cfg, cache, x, attn_fn, ssm_fn,
        moe_mask=None if active is None else active[:, None])
    if active is not None:
        for name, nd in (("conv", 2), ("state", 3)):
            if name in new_cache:
                act = active.reshape((1, B) + (1,) * nd)
                new_cache[name] = jnp.where(act, new_cache[name], cache[name])
    return logits_from_hidden(params, cfg, h)[:, 0], new_cache


def _paged_chunk_forward(params: dict, cfg: ArchConfig, cache: dict,
                         tokens: jax.Array, positions: jax.Array,
                         slots: jax.Array, block_tables: jax.Array,
                         valid: jax.Array) -> tuple[jax.Array, dict]:
    """Shared core of chunked prefill and speculative verify: push a
    fixed-width chunk of tokens per sequence through the layer stack,
    scattering K/V of the valid tokens into the paged pool (padding lands
    in the null block) and advancing the recurrent SSM state through the
    valid prefix.  Returns (hidden (B, C, d), new cache)."""
    x = jnp.take(params["tok_embed"], tokens, axis=0)           # (B,C,d)
    B, C = tokens.shape
    fresh = positions[:, 0] == 0      # first chunk: reset recurrent state
    inchunk = jnp.arange(C)[None, :] < valid[:, None]           # real tokens

    def attn_fn(ap, hn, kv, layer, flag):
        if cfg.hybrid:
            win = jnp.where(flag, jnp.int32(2**30),
                            jnp.int32(cfg.sliding_window))
            win = jnp.broadcast_to(win, (B,))    # dynamic -> reference path
        else:
            win = 0
        return attn.attention_paged_prefill(
            ap, cfg, hn, positions, kv, layer, block_tables, valid,
            window=win)

    def ssm_fn(sp, hn, lc):
        conv = jnp.where(fresh[:, None, None], 0, lc["conv"][slots])
        state = jnp.where(fresh[:, None, None, None], 0, lc["state"][slots])
        delta, new_sc = ssm_mod.ssm_prefill(
            sp, cfg, hn, ssm_mod.SSMCache(conv, state), valid)
        # rows riding the fixed-shape chunk batch with no tokens this step
        # (valid == 0: idle or decode-phase slots) must keep their
        # recurrent state — their "fresh" zeroing above is trace-time
        # scaffolding, not progress
        act = valid > 0
        new_conv = jnp.where(act[:, None, None], new_sc.conv,
                             lc["conv"][slots])
        new_state = jnp.where(act[:, None, None, None], new_sc.state,
                              lc["state"][slots])
        return delta, ssm_mod.SSMCache(lc["conv"].at[slots].set(new_conv),
                                       lc["state"].at[slots].set(new_state))

    return _run_decode_layers(params, cfg, cache, x, attn_fn, ssm_fn,
                              moe_mask=inchunk)


def paged_prefill_step(params: dict, cfg: ArchConfig, cache: dict,
                       tokens: jax.Array, positions: jax.Array,
                       slots: jax.Array, block_tables: jax.Array,
                       valid: jax.Array) -> tuple[jax.Array, dict]:
    """Chunked prefill: push a fixed-size chunk of known tokens through the
    layer stack, scattering K/V into the paged pool and advancing the
    recurrent SSM state — O(P/chunk) engine steps for a P-token prompt
    instead of the O(P) token-by-token warmup.

    tokens (B, C) int32, right-padded; positions (B, C) absolute indices
    (``num_cached + arange(C)``); slots (B,) int32 rows of the per-slot
    SSM state tensors; block_tables (B, NB); valid (B,) real-token counts.
    Returns (logits of each sequence's last valid token (B, V), cache) —
    the engine samples from them when the chunk covers the last known
    token.
    """
    h, new_cache = _paged_chunk_forward(params, cfg, cache, tokens,
                                        positions, slots, block_tables,
                                        valid)
    h_last = jnp.take_along_axis(
        h, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)    # (B,1,d)
    return logits_from_hidden(params, cfg, h_last)[:, 0], new_cache


def paged_verify_step(params: dict, cfg: ArchConfig, cache: dict,
                      tokens: jax.Array, positions: jax.Array,
                      slots: jax.Array, block_tables: jax.Array,
                      valid: jax.Array) -> tuple[jax.Array, dict]:
    """Speculative-verify scoring step: one multi-token pass that returns
    the target model's logits at *every* drafted position.

    Same contract as ``paged_prefill_step`` — tokens (B, K+1) are
    ``[last sampled token, K drafted tokens]`` per sequence, right-padded,
    with ``valid`` counting the real ones — but the full (B, K+1, V)
    logits come back, so the engine can accept/reject each draft against
    the exact distribution a token-by-token decode would have produced.
    K/V for all valid positions (including drafts that end up rejected)
    are scattered into the pool; rejection rolls the write cursor back on
    the host and the stale entries are overwritten by the next write
    (kv_cache.truncate).

    Recurrent SSM/conv state advances through all valid tokens and cannot
    be rewound the same way, which is why the engine gates speculation to
    attention-only families (DESIGN.md §9 capability matrix).
    """
    h, new_cache = _paged_chunk_forward(params, cfg, cache, tokens,
                                        positions, slots, block_tables,
                                        valid)
    return logits_from_hidden(params, cfg, h), new_cache


# ---------------------------------------------------------------------------
# stack/unstack helpers for the pruning engine's unrolled analysis mode
# ---------------------------------------------------------------------------

def unstack_layers(params: dict, num_layers: int) -> dict:
    out = dict(params)
    out["layers"] = [jax.tree.map(lambda a, i=i: a[i], params["layers"])
                     for i in range(num_layers)]
    return out


def stack_layers(params: dict) -> dict:
    out = dict(params)
    out["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return out
