"""Pallas TPU paged attention over a block-table-indexed KV pool.

One kernel serves decode (one query token per sequence) and chunked
prefill (C query tokens per sequence): queries ride in as a (KH, C*G, D)
tile per sequence, and each query row r masks against its absolute
position ``q_start + r // G`` — prefill-aware causal masking inside the
online-softmax loop.

Pool layout is head-major within a block: K/V pools are (P, KH, bs, D)
and scale pools (P, KH, bs).  One block of one sequence is therefore a
single contiguous (KH, bs, D) DMA, each head's tile is a clean (bs, D)
slab, and each head's scales are a (1, bs) lane row — the shapes whose
trailing two dimensions equal the array's, which is what the TPU
compiler accepts for BlockSpecs that are not (8, 128)-aligned.

Grid (B, NB); the block dimension is innermost so the f32 online-softmax
accumulators (acc, running max m, running sum l, one set per KV head)
persist in VMEM scratch across the KV blocks of one sequence.  Every KV
head is processed in the same grid step, so each pool block is read from
HBM exactly once per sequence.  The block table, per-sequence lengths
and query start positions ride in as *scalar prefetch* operands
(``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
``tables[b, j]`` to DMA the j-th logical block of sequence b from
wherever it lives in the pool — the gathered (B, S, KH, D) history is
never materialized, which is the whole point of paging.

Fully-masked blocks are skipped: table entries past a sequence's length
(``j * block_size >= kv_len``) and, under a sliding window, blocks wholly
left of every query's window are neither computed nor DMA'd — their
BlockSpec index degrades to the null block 0 in both cases, so a
window-dead block costs neither FLOPs nor HBM bandwidth.  A per-sequence
visit counter is emitted alongside the output so tests can assert the
skip actually fires (tests/test_serve.py): the counter and the index map
share one liveness predicate (``_block_live``), so "was computed" and
"was DMA'd" cannot drift apart.

Quantized pools (DESIGN.md §11): when the pool stores int8/fp8-e4m3, the
f32 scale pools ride in as two extra operands addressed by the *same*
index map as K/V, and dequantization is fused into the score and value
products: a key's scale multiplies its score column and a value's scale
multiplies its probability column, so only the narrow bytes cross HBM
and the online softmax stays f32 end-to-end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_live(j, kv_len, q_start, *, window: int, block_size: int):
    """One liveness predicate for compute AND DMA: a block is dead when
    every one of its positions is masked for every query row — past the
    sequence's length, or (sliding window) wholly left of even the
    oldest query's window."""
    first = j * block_size
    live = first < kv_len
    if window:
        live &= first + block_size - 1 > q_start - window
    return live


def _kernel(lens_ref, starts_ref, tables_ref, q_ref, k_ref, v_ref, *refs,
            scale: float, window: int, block_size: int, group: int,
            quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, visits_ref, acc_ref, m_ref, l_ref, \
            cnt_ref = refs
    else:
        o_ref, visits_ref, acc_ref, m_ref, l_ref, cnt_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    kh = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        cnt_ref[0] = 0

    kv_len = lens_ref[b]
    q_start = starts_ref[b]
    first = j * block_size
    visited = _block_live(j, kv_len, q_start, window=window,
                          block_size=block_size)

    @pl.when(visited)
    def _update():
        cg = q_ref.shape[2]
        idx = first + jax.lax.broadcasted_iota(jnp.int32, (cg, block_size), 1)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (cg, block_size), 0) // group
        mask = (idx <= qpos) & (idx < kv_len)             # (CG, bs)
        if window:
            mask &= idx > qpos - window
        for h in range(kh):
            q = q_ref[0, h].astype(jnp.float32)           # (CG, D)
            k = k_ref[0, h].astype(jnp.float32)           # (bs, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:          # fused dequant: scale each key's column
                s = s * ks_ref[0, pl.ds(h, 1), :]         # (1, bs)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                             # (CG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # (CG, bs)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            if quantized:          # ... and each value's probability
                p = p * vs_ref[0, pl.ds(h, 1), :]
            v = v_ref[0, h].astype(jnp.float32)           # (bs, DV)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv
        cnt_ref[0] += 1

    @pl.when(j == nb - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        visits_ref[b] = cnt_ref[0]


def _paged_attention(q, k_pool, v_pool, block_tables, q_starts, kv_lens, *,
                     window: int, scale: float | None, interpret: bool,
                     k_scale=None, v_scale=None):
    """q (B, C, H, D); pools (P, KH, bs, D/DV); tables (B, NB);
    q_starts/kv_lens (B,); k/v_scale (P, KH, bs) f32 when the pools are
    quantized.  Returns (out (B, C, H, DV), visits (B, KH))."""
    B, C, H, D = q.shape
    KH, bs, DV = k_pool.shape[1], k_pool.shape[2], v_pool.shape[3]
    NB = block_tables.shape[1]
    G = H // KH
    CG = C * G
    scale = scale if scale is not None else D ** -0.5
    quantized = k_scale is not None

    # (B, C, KH, G, D) -> (B, KH, C*G, D): row r is query (r // G, r % G)
    qg = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KH, CG, D)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               block_size=bs, group=G, quantized=quantized)

    def _block(b, j, lens, starts, tables):
        # skip the DMA for fully-masked blocks — past the sequence's
        # length, or wholly left of the sliding window: read null block 0
        live = _block_live(j, lens[b], starts[b], window=window,
                           block_size=bs)
        return jnp.where(live, tables[b, j], 0)

    in_specs = [
        pl.BlockSpec((1, KH, CG, D),
                     lambda b, j, lens, starts, tables: (b, 0, 0, 0)),
        pl.BlockSpec((1, KH, bs, D),
                     lambda *a: (_block(*a), 0, 0, 0)),
        pl.BlockSpec((1, KH, bs, DV),
                     lambda *a: (_block(*a), 0, 0, 0)),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # scales ride as two extra operands addressed by the same index
        # map as their pools, so a skipped KV DMA skips its scales too
        in_specs += [pl.BlockSpec((1, KH, bs), lambda *a: (_block(*a), 0, 0))
                     ] * 2
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NB),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, KH, CG, DV),
                         lambda b, j, lens, starts, tables: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((KH, CG, DV), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KH, CG, DV), q.dtype),
                   jax.ShapeDtypeStruct((B,), jnp.int32)],
        interpret=interpret,
        name="paged_attention",
    )(kv_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
      block_tables.astype(jnp.int32), *operands)
    out = out.reshape(B, KH, C, G, DV).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, DV)
    # every KV head of a sequence visits the same blocks
    return out, jnp.broadcast_to(visits[:, None], (B, KH))


def paged_attention_kernel(q, k_pool, v_pool, block_tables, kv_lens, *,
                           window: int = 0, scale: float | None = None,
                           interpret: bool = True,
                           return_visits: bool = False,
                           k_scale=None, v_scale=None):
    """Decode entry point: q (B, H, D), one query token at ``kv_len - 1``."""
    out, visits = _paged_attention(
        q[:, None], k_pool, v_pool, block_tables, kv_lens - 1, kv_lens,
        window=window, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    out = out[:, 0]
    return (out, visits) if return_visits else out


def paged_prefill_attention_kernel(q, k_pool, v_pool, block_tables,
                                   q_starts, kv_lens, *, window: int = 0,
                                   scale: float | None = None,
                                   interpret: bool = True,
                                   return_visits: bool = False,
                                   k_scale=None, v_scale=None):
    """Prefill entry point: q (B, C, H, D), C query tokens starting at
    ``q_starts``; ``kv_lens = q_starts + valid`` (rows past a sequence's
    valid count produce garbage the caller discards)."""
    out, visits = _paged_attention(
        q, k_pool, v_pool, block_tables, q_starts, kv_lens,
        window=window, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return (out, visits) if return_visits else out
