"""jit wrapper for flash attention in model layout (B, S, H, D)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_reference


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128):
    """q (B, Sq, H, D); k/v (B, Sk, KH, D/DV) -> (B, Sq, H, DV).  Compiled
    on the TPU, interpreted elsewhere."""
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                              scale=scale, block_q=block_q, block_k=block_k,
                              interpret=not _on_tpu())
    return jnp.transpose(ot, (0, 2, 1, 3))


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = attention_reference(qt, kt, vt, causal=causal, window=window,
                             scale=scale)
    return jnp.transpose(ot, (0, 2, 1, 3))
