"""Symmetric per-write K/V quantization for the paged block pools.

Steady-state decode is KV-bandwidth-bound: every step streams the full
K/V history of every active slot through the paged-attention kernel, so
bytes-per-token is the capacity *and* the latency knob.  A quantized pool
stores K/V in int8 or fp8-e4m3 (1 byte/element) plus one f32 scale per
written (token slot, kv-head) — the scale pools mirror the KV pools'
block layout ``(num_blocks, KH, block_size)``, so a scale is addressed by
exactly the same ``(block, kv_head, offset)`` coordinates as the vector
it scales and travels with its block through prefix aliasing, COW copies
and speculative rollback for free (DESIGN.md §11).

Granularity: the head_dim vector of one token for one kv-head is the
quantization group — the same "compress the coupled unit, not the
scalar" rule SPA inherits from DepGraph, applied to the cache: the
elements that are read together (one dot-product operand) share a scale.
A coarser per-(block, kv-head) scale would need write-time
*re*quantization of already-committed entries (a decode step writes one
token into a partially-filled block; growing the block scale would
invalidate its neighbours), accumulating rounding error with every write.
Per-write scales make quantization a pure function of the written vector:
deterministic, history-free, and exactly reproducible by the jnp
reference.

Everything here is shared by ``models.attention._scatter_kv`` (the only
writer), the Pallas kernel's fused dequant, and the
reference oracle — so "what do the stored bytes mean" exists once.
"""
from __future__ import annotations

import jax.numpy as jnp

# pool element dtype and the absmax the scale maps onto it
QUANT_SPECS: dict[str, tuple] = {
    "int8": (jnp.int8, 127.0),
    "fp8_e4m3": (jnp.float8_e4m3fn, 448.0),   # max finite e4m3 value
}

# every ServeConfig.cache_dtype the engine accepts ("" = model dtype)
CACHE_DTYPES = ("", "float32", "bfloat16", "int8", "fp8_e4m3")


def is_quantized(dtype_name: str | None) -> bool:
    return (dtype_name or "") in QUANT_SPECS


def pool_dtype(dtype_name: str):
    """Element dtype of a quantized pool."""
    return QUANT_SPECS[dtype_name][0]


def qmax_of(dtype) -> float:
    """The absmax a stored element can represent, by pool *dtype*."""
    for dt, qmax in QUANT_SPECS.values():
        if jnp.dtype(dtype) == jnp.dtype(dt):
            return qmax
    raise ValueError(f"{dtype} is not a quantized pool dtype")


def quantize(x, dtype):
    """x (..., hd) -> (q (..., hd) in ``dtype``, scale (...) f32).

    Symmetric: scale = absmax/qmax over the trailing (head_dim) axis, so
    dequantization is ``q.astype(f32) * scale[..., None]``.  An all-zero
    vector (idle-slot null-block writes) gets scale 0 and quantizes to 0.
    """
    qmax = qmax_of(dtype)
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / qmax
    qv = xf / jnp.maximum(scale, 1e-30)[..., None]
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        qv = jnp.clip(jnp.round(qv), -qmax, qmax)
    return qv.astype(dtype), scale


def dequantize(q, scale):
    """q (..., hd) quantized, scale (...) f32 -> f32 (..., hd)."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
