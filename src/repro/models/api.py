"""Model facade: one object per ArchConfig with init/loss/decode/input_specs.

``input_specs`` returns ShapeDtypeStruct stand-ins (no allocation) for the
multi-pod dry-run; ``dummy_batch`` returns small concrete arrays for smoke
tests.  All functions are pure — the facade only binds the config.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import cnn as cnn_mod
from repro.models import transformer as tf
from repro.models.layers import dtype_of
from repro.models.transformer import AUDIO_FRAME_DIM


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ----- init -----
    def init(self, key) -> dict:
        if self.cfg.family == "cnn":
            params, state = cnn_mod.cnn_init(self.cfg, key)
            return {"params": params, "state": state}
        return tf.init_params(self.cfg, key)

    def param_axes(self) -> dict:
        if self.cfg.family == "cnn":
            raise ValueError("CNNs are CPU-scale; no sharding axes")
        return tf.param_axes(self.cfg)

    # ----- training -----
    def loss(self, params, batch, unroll: bool = False):
        if self.cfg.family == "cnn":
            loss, (_state, metrics) = cnn_mod.cnn_loss(
                self.cfg, params["params"], params["state"], batch,
                train=False)
            return loss, metrics
        return tf.loss_fn(params, self.cfg, batch, unroll=unroll)

    def forward(self, params, batch, unroll: bool = False):
        if self.cfg.family == "cnn":
            logits, _ = cnn_mod.cnn_forward(
                self.cfg, params["params"], params["state"], batch["images"])
            return logits
        h, _ = tf.forward(params, self.cfg, batch, unroll=unroll)
        return tf.logits_from_hidden(params, self.cfg, h)

    # ----- serving -----
    def init_cache(self, batch: int, max_len: int) -> dict:
        return tf.init_cache(self.cfg, batch, max_len)

    def cache_axes(self, long_context: bool = False) -> dict:
        return tf.cache_axes(self.cfg, long_context)

    def decode_step(self, params, cache, tokens, pos):
        return tf.decode_step(params, self.cfg, cache, tokens, pos)

    # ----- paged serving (continuous batching; repro.serve) -----
    def init_paged_cache(self, num_blocks: int, block_size: int,
                         max_seqs: int, dtype: str | None = None) -> dict:
        return tf.init_paged_cache(self.cfg, num_blocks, block_size, max_seqs,
                                   dtype=dtype)

    def paged_decode_step(self, params, cache, tokens, positions,
                          block_tables, active=None):
        return tf.paged_decode_step(params, self.cfg, cache, tokens,
                                    positions, block_tables, active)

    def paged_prefill_step(self, params, cache, tokens, positions, slots,
                           block_tables, valid):
        return tf.paged_prefill_step(params, self.cfg, cache, tokens,
                                     positions, slots, block_tables, valid)

    def paged_verify_step(self, params, cache, tokens, positions, slots,
                          block_tables, valid):
        """Multi-token scoring step for speculative decoding: logits at
        every position (B, K+1, V), not just the last valid one."""
        return tf.paged_verify_step(params, self.cfg, cache, tokens,
                                    positions, slots, block_tables, valid)

    def paged_cache_axes(self, quantized: bool = False) -> dict:
        return tf.paged_cache_axes(self.cfg, quantized=quantized)

    # ----- shapes -----
    def batch_spec(self, shape: ShapeConfig, with_targets: bool) -> dict:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        act_dt = dtype_of(cfg.dtype)
        spec: dict[str, Any] = {}
        if cfg.family == "audio":
            spec["frames"] = sds((B, S, AUDIO_FRAME_DIM), act_dt)
            if with_targets:
                spec["targets"] = sds((B, S), jnp.int32)
        elif cfg.family == "vlm":
            spec["patches"] = sds((B, cfg.vision_tokens, cfg.vision_embed_dim),
                                  act_dt)
            spec["tokens"] = sds((B, S - cfg.vision_tokens), jnp.int32)
        elif cfg.family == "cnn":
            spec["images"] = sds((B, cfg.image_size, cfg.image_size, 3),
                                 jnp.float32)
            if with_targets:
                spec["labels"] = sds((B,), jnp.int32)
        else:
            spec["tokens"] = sds((B, S), jnp.int32)
        return spec

    def cache_spec(self, shape: ShapeConfig) -> dict:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        dt = dtype_of(cfg.dtype)
        L = cfg.num_layers
        sds = jax.ShapeDtypeStruct
        spec: dict[str, Any] = {}
        if cfg.family != "ssm":
            KH, hd = cfg.n_kv_heads, cfg.head_dim_
            spec["k"] = sds((L, B, S, KH, hd), dt)
            spec["v"] = sds((L, B, S, KH, cfg.v_head_dim_), dt)
        if cfg.family == "ssm" or cfg.hybrid:
            nh, hp, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state
            conv_ch = nh * hp + 2 * n
            spec["conv"] = sds((L, B, cfg.ssm_conv - 1, conv_ch), dt)
            spec["state"] = sds((L, B, nh, hp, n), jnp.float32)
        return spec

    def decode_input_spec(self, shape: ShapeConfig) -> dict:
        B = shape.global_batch
        return {
            "cache": self.cache_spec(shape),
            "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def paged_cache_spec(self, shape: ShapeConfig, block_size: int) -> dict:
        """Pool-shaped cache SDS: worst-case blocks for (batch, seq_len).
        ``shape.cache_dtype`` quantizes the KV pools (narrow elements plus
        per-(block, token, kv-head) f32 scale pools — the dry-run grid's
        ``paged_decode_q8`` cell, DESIGN.md §11)."""
        from repro.kernels.paged_attention import is_quantized, pool_dtype
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        quant = is_quantized(shape.cache_dtype)
        dt = pool_dtype(shape.cache_dtype) if quant \
            else dtype_of(shape.cache_dtype or cfg.dtype)
        L = cfg.num_layers
        num_blocks = B * (-(-S // block_size)) + 1
        sds = jax.ShapeDtypeStruct
        spec: dict[str, Any] = {}
        if cfg.family != "ssm":
            KH = cfg.n_kv_heads
            spec["k"] = sds((L, num_blocks, KH, block_size, cfg.head_dim_), dt)
            spec["v"] = sds((L, num_blocks, KH, block_size, cfg.v_head_dim_),
                            dt)
            if quant:
                for name in ("k_scale", "v_scale"):
                    spec[name] = sds((L, num_blocks, KH, block_size),
                                     jnp.float32)
        if cfg.family == "ssm" or cfg.hybrid:
            nh, hp, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state
            conv_ch = nh * hp + 2 * n
            spec["conv"] = sds((L, B, cfg.ssm_conv - 1, conv_ch), dt)
            spec["state"] = sds((L, B, nh, hp, n), jnp.float32)
        return spec

    def paged_decode_input_spec(self, shape: ShapeConfig,
                                block_size: int = 64) -> dict:
        B, S = shape.global_batch, shape.seq_len
        nb = -(-S // block_size)
        sds = jax.ShapeDtypeStruct
        return {
            "cache": self.paged_cache_spec(shape, block_size),
            "tokens": sds((B,), jnp.int32),
            "positions": sds((B,), jnp.int32),
            "block_tables": sds((B, nb), jnp.int32),
            "active": sds((B,), jnp.bool_),
        }

    def paged_prefill_input_spec(self, shape: ShapeConfig,
                                 block_size: int = 64) -> dict:
        """shape.seq_len doubles as the prefill chunk length here."""
        B, C = shape.global_batch, shape.seq_len
        nb = -(-C // block_size)
        sds = jax.ShapeDtypeStruct
        return {
            "cache": self.paged_cache_spec(shape, block_size),
            "tokens": sds((B, C), jnp.int32),
            "positions": sds((B, C), jnp.int32),
            "slots": sds((B,), jnp.int32),
            "block_tables": sds((B, nb), jnp.int32),
            "valid": sds((B,), jnp.int32),
        }

    def paged_verify_input_spec(self, shape: ShapeConfig,
                                block_size: int = 64,
                                chunk: int | None = None) -> dict:
        """Speculative verify: ``chunk`` = K+1 scored tokens per sequence
        against a shape.seq_len-deep paged history (unlike prefill, the
        chunk width and the context depth are independent axes here)."""
        from repro.configs.base import SPEC_VERIFY_CHUNK
        chunk = chunk or SPEC_VERIFY_CHUNK
        B, S = shape.global_batch, shape.seq_len
        nb = -(-S // block_size)
        sds = jax.ShapeDtypeStruct
        return {
            "cache": self.paged_cache_spec(shape, block_size),
            "tokens": sds((B, chunk), jnp.int32),
            "positions": sds((B, chunk), jnp.int32),
            "slots": sds((B,), jnp.int32),
            "block_tables": sds((B, nb), jnp.int32),
            "valid": sds((B,), jnp.int32),
        }

    # ----- concrete dummy data (smoke tests) -----
    def dummy_batch(self, key, batch: int, seq: int,
                    with_targets: bool = True) -> dict:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        out: dict[str, Any] = {}
        if cfg.family == "audio":
            out["frames"] = jax.random.normal(
                k1, (batch, seq, AUDIO_FRAME_DIM), jnp.float32
            ).astype(dtype_of(cfg.dtype))
            if with_targets:
                hi = cfg.vocab_size
                if hi <= 16:
                    out["targets"] = jax.random.randint(k2, (batch,), 0, hi)
                else:
                    out["targets"] = jax.random.randint(k2, (batch, seq), 0, hi)
        elif cfg.family == "vlm":
            nv = cfg.vision_tokens
            out["patches"] = jax.random.normal(
                k1, (batch, nv, cfg.vision_embed_dim), jnp.float32
            ).astype(dtype_of(cfg.dtype))
            out["tokens"] = jax.random.randint(
                k2, (batch, max(seq - nv, 4)), 0, cfg.vocab_size)
        elif cfg.family == "cnn":
            out["images"] = jax.random.normal(
                k1, (batch, cfg.image_size, cfg.image_size, 3), jnp.float32)
            out["labels"] = jax.random.randint(k2, (batch,), 0, cfg.num_classes)
        else:
            out["tokens"] = jax.random.randint(
                k1, (batch, seq), 0, cfg.vocab_size)
        return out


def build(cfg: ArchConfig) -> Model:
    return Model(cfg)
