"""Pallas TPU paged attention over a block-table-indexed KV pool.

One kernel serves decode (one query token per sequence) and chunked
prefill (C query tokens per sequence): queries ride in as a (KH, C*G, D)
tile per sequence, and each query row r masks against its absolute
position ``q_start + r // G`` — prefill-aware causal masking inside the
online-softmax loop.

K pools are head-major within a block, (L, P, KH, bs, D); V pools are
token-major with the heads side by side on the lanes, (L, P, bs, KH*DV);
scale pools are (L, P, KH, bs).  Every layer's pool is stacked, and the
kernel reads the one at its ``layer`` index (a scalar-prefetch operand).
The serving step carries the stacked pool through its layer scan and
hands it here whole, so no layer's pool is ever sliced out and copied
(DESIGN.md §8).  One block of one sequence is a single contiguous (KH,
bs, D) DMA of K and one (bs, KH*DV) DMA of V; each head's K tile is a
clean (bs, D) slab, and each head's scales are a (1, bs) lane row — the
shapes whose trailing two dimensions equal the array's, which is what
the TPU compiler accepts for BlockSpecs that are not (8, 128)-aligned;
the layer axis is squeezed.  V is token-major so that a value width
narrower than the 128 lanes (a structurally pruned head, DV 64) still
fills them: the TPU stores a leaf whose minor dimension is under 128
lanes padded and in another layout than the kernel's row-major tiles,
which would make every step program convert the whole pool.  The value
product reads V in 128-lane groups at static, aligned lane offsets: a
head of DV >= 128 owns whole groups, and heads narrower than that share
a group, each keeping its own lanes of the group's product
(``_lane_groups``).  The output is lane-dense the same way, (CG, KH*DV)
per sequence.

Grid (B, NB); the block dimension is innermost so the f32 online-softmax
accumulators (acc, running max m, running sum l, one set per KV head)
persist in VMEM scratch across the KV blocks of one sequence.  Every KV
head is processed in the same grid step, so each pool block is read from
HBM exactly once per sequence.  The block table, per-sequence lengths,
query start positions and the layer index ride in as *scalar prefetch*
operands (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index
maps read ``layer`` and ``tables[b, j]`` to DMA the j-th logical block
of sequence b from wherever it lives in that layer's pool — the gathered
(B, S, KH, D) history is never materialized, which is the whole point of
paging.

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                  # the TPU vector register's lane count


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


def _lane_groups(kh: int, dv: int) -> tuple[int, int]:
    """(heads per group, group width in lanes) of a V row of ``kh`` heads
    of ``dv`` values: the value product reads one group at a time.  A
    head of 128 or more values is its own group (aligned when ``dv`` is
    a multiple of 128); narrower heads that divide the 128 lanes share
    one, as many as divide ``kh``."""
    per = math.gcd(kh, LANES // dv) if dv < LANES and LANES % dv == 0 else 1
    return per, per * dv


def _kernel(lens_ref, starts_ref, tables_ref, layer_ref, q_ref, k_ref,
            v_ref, *refs, scale: float, window: int, block_size: int,
            group: int, quantized: bool):
    del layer_ref                 # addresses the pools in the index maps
    if quantized:
        ks_ref, vs_ref, o_ref, visits_ref, acc_ref, m_ref, l_ref, \
            cnt_ref = refs
    else:
        o_ref, visits_ref, acc_ref, m_ref, l_ref, cnt_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    kh = k_ref.shape[1]
    dv = v_ref.shape[-1] // kh
    per, width = _lane_groups(kh, dv)
    groups = [range(g * per, (g + 1) * per) for g in range(kh // per)]

    def by_lane(vals):
        """One group's lanes from its heads' values ((CG, 1) or (CG,
        width) each): lane i takes the value of the group's head i // dv."""
        if per == 1:
            return vals[0]
        head = jax.lax.broadcasted_iota(
            jnp.int32, (acc_ref.shape[0], width), 1) // dv
        out = vals[-1]
        for i in range(per - 1):
            out = jnp.where(head == i, vals[i], out)
        return out

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
        probs, alphas = [], []
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
            probs.append(p)
            alphas.append(alpha)
        for g, heads in enumerate(groups):
            lanes = pl.ds(g * width, width)
            v = v_ref[0, :, lanes].astype(jnp.float32)    # (bs, width)
            # every head of the group multiplies the whole group; each
            # keeps its own lanes of the product
            pv = [jax.lax.dot_general(probs[h], v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                  for h in heads]                         # (CG, width) each
            acc_ref[:, lanes] = (acc_ref[:, lanes]
                                 * by_lane([alphas[h] for h in heads])
                                 + by_lane(pv))
        cnt_ref[0] += 1

    @pl.when(j == nb - 1)
    def _done():
        for g, heads in enumerate(groups):
            lanes = pl.ds(g * width, width)
            l = by_lane([jnp.maximum(l_ref[h], 1e-30) for h in heads])
            o_ref[0, :, lanes] = (acc_ref[:, lanes] / l).astype(o_ref.dtype)
        visits_ref[b] = cnt_ref[0]


def _paged_attention(q, k_pool, v_pool, block_tables, q_starts, kv_lens, *,
                     window: int, scale: float | None, interpret: bool,
                     layer=None, k_scale=None, v_scale=None):
    """q (B, C, H, D); k_pool (L, P, KH, bs, D) and v_pool (L, P, bs,
    KH*DV) read at ``layer`` (an int32 scalar, traced or not), or one
    layer's (P, KH, bs, D) and (P, bs, KH*DV) when ``layer`` is None;
    tables (B, NB); q_starts/kv_lens (B,); k/v_scale (L, P, KH, bs) (or
    (P, KH, bs)) f32 when the pools are quantized.  Returns (out (B, C,
    H, DV), visits (B, KH))."""
    quantized = k_scale is not None
    if layer is None:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    B, C, H, D = q.shape
    KH, bs, KDV = k_pool.shape[2], k_pool.shape[3], v_pool.shape[3]
    DV = KDV // KH
    NB = block_tables.shape[1]
    G = H // KH
    CG = C * G
    scale = scale if scale is not None else D ** -0.5

    # (B, C, KH, G, D) -> (B, KH, C*G, D): row r is query (r // G, r % G)
    qg = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KH, CG, D)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               block_size=bs, group=G, quantized=quantized)

    def _block(b, j, lens, starts, tables, layer_idx):
        # skip the DMA for fully-masked blocks — past the sequence's
        # length, or wholly left of the sliding window: read null block 0
        # of the same layer
        live = _block_live(j, lens[b], starts[b], window=window,
                           block_size=bs)
        return layer_idx[0], jnp.where(live, tables[b, j], 0)

    in_specs = [
        pl.BlockSpec((1, KH, CG, D), lambda b, *_: (b, 0, 0, 0)),
        pl.BlockSpec((None, 1, KH, bs, D),
                     lambda *a: (*_block(*a), 0, 0, 0)),
        pl.BlockSpec((None, 1, bs, KDV),
                     lambda *a: (*_block(*a), 0, 0)),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # scales ride as two extra operands addressed by the same index
        # map as their pools, so a skipped KV DMA skips its scales too
        in_specs += [pl.BlockSpec((None, 1, KH, bs),
                                  lambda *a: (*_block(*a), 0, 0))] * 2
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, NB),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, CG, KDV), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((CG, KDV), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, CG, KDV), q.dtype),
                   jax.ShapeDtypeStruct((B,), jnp.int32)],
        interpret=interpret,
        name="paged_attention",
    )(kv_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
      block_tables.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)
    # (B, C*G, KH*DV) -> (B, C, KH, G, DV): lanes of row r hold query
    # (r // G, r % G) of every KV head
    out = out.reshape(B, C, G, KH, DV).transpose(0, 1, 3, 2, 4) \
        .reshape(B, C, H, DV)
    # every KV head of a sequence visits the same blocks
    return out, jnp.broadcast_to(visits[:, None], (B, KH))


def paged_attention_kernel(q, k_pool, v_pool, block_tables, kv_lens, *,
                           layer=None, window: int = 0,
                           scale: float | None = None,
                           interpret: bool = True,
                           return_visits: bool = False,
                           k_scale=None, v_scale=None):
    """Decode entry point: q (B, H, D), one query token at ``kv_len - 1``;
    pools and ``layer`` as in ``_paged_attention``."""
    out, visits = _paged_attention(
        q[:, None], k_pool, v_pool, block_tables, kv_lens - 1, kv_lens,
        layer=layer, window=window, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    out = out[:, 0]
    return (out, visits) if return_visits else out


def paged_prefill_attention_kernel(q, k_pool, v_pool, block_tables,
                                   q_starts, kv_lens, *, layer=None,
                                   window: int = 0,
                                   scale: float | None = None,
                                   interpret: bool = True,
                                   return_visits: bool = False,
                                   k_scale=None, v_scale=None):
    """Prefill entry point: q (B, C, H, D), C query tokens starting at
    ``q_starts``; ``kv_lens = q_starts + valid`` (rows past a sequence's
    valid count produce garbage the caller discards)."""
    out, visits = _paged_attention(
        q, k_pool, v_pool, block_tables, q_starts, kv_lens,
        layer=layer, window=window, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return (out, visits) if return_visits else out
