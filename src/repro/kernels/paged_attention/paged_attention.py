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
(DESIGN.md §8).  One page of one sequence is a single contiguous (KH,
bs, D) copy of K and one (bs, KH*DV) copy of V, each the whole of the
pool's two minor dimensions, and each head's K tile is a clean (bs, D)
slab.  V is token-major so that a value width narrower than the 128
lanes (a structurally pruned head, DV 64) still fills them: the TPU
stores a leaf whose minor dimension is under 128 lanes padded and in
another layout than the kernel's row-major tiles, which would make
every step program convert the whole pool.  The value
product reads V in 128-lane groups at static, aligned lane offsets: a
head of DV >= 128 owns whole groups, and heads narrower than that share
a group, each keeping its own lanes of the group's product
(``_lane_groups``).  The output is lane-dense the same way, (CG, KH*DV)
per sequence.

Grid (B,): one step per sequence.  The step loops over the row's live
pages only (``live_pages``: from the first page a query row can see up
to the page of its last key), ``pages`` at a time (``chunk_pages``:
enough that a chunk holds 128 keys, a full lane row of scores).  K and V
stay in HBM (``pl.ANY``): each live page is one contiguous (KH, bs, D)
DMA of K and one (bs, KH*DV) DMA of V from the stacked pools at
``(layer, tables[b, j])`` into a VMEM chunk buffer, one DMA semaphore a
page, and the buffer is double-buffered, so chunk c+1's copies run while
chunk c is computed.  The gathered (B, S, KH, D) history is never
materialized, which is the whole point of paging.  Each chunk is one
online-softmax update over its pages * bs keys per KV head, with the f32
accumulators (acc, running max m, running sum l, one set per KV head) in
VMEM scratch; every KV head is processed in the same chunk, so each pool
page crosses HBM once per sequence.  The block table, per-sequence
lengths, query start positions and the layer index ride in as *scalar
prefetch* operands (``pltpu.PrefetchScalarGridSpec``).

Pages outside the live range are neither fetched nor computed: a row
with no live page (an idle prefill row, ``kv_len`` 0) runs no iteration
and writes zeros, and the page slots of a row's last chunk past its last
page copy nothing and are masked, like every position past ``kv_len``,
ahead of a query or left of its window.  The cost of a call therefore
follows the live KV, not the block table's width.  A per-sequence visit
counter (the pages the loop computed) is emitted alongside the output,
and ``ops.grid_steps`` counts the same pages and the page slots launched
on the host with the same ``live_pages``, so "was computed" and "was
counted" cannot drift apart.

Quantized pools (DESIGN.md §11): when the pool stores int8/fp8-e4m3,
dequantization is fused into the score and value products: a key's f32
scale multiplies its score column and a value's its probability column,
so only the narrow bytes of K and V cross HBM and the online softmax
stays f32 end-to-end.  The scale pools' minor dimension is the block's
tokens, under the 128 lanes, and the TPU compiler refuses a copy of
part of such a leaf; so the call gathers the scales of each row's chunks
lane-dense, (B, chunks, KH, pages*bs), with the same ``live_pages``, and
each row's scales ride in as one block beside its queries.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                  # the TPU vector register's lane count
CHUNK_KEYS = 128             # keys a chunk holds at least: a lane row
CHUNK_VMEM = 4 << 20         # bytes of VMEM for the double-buffered K/V chunk


def live_pages(kv_len, q_start, *, window: int, block_size: int):
    """(first, end): the pages [first, end) of a row that hold a position
    some query row sees — up to the page of the row's last key and, under
    a sliding window, from the first page not wholly left of the oldest
    query's window.  ``end >= first``.  One definition for the kernel's
    loop (traced scalars), the call's scale gather and the host's count
    (numpy arrays)."""
    xp = jnp if isinstance(kv_len, jax.Array) else np
    end = (kv_len + block_size - 1) // block_size
    if not window:
        return end * 0, end
    first = xp.maximum(q_start - window + 1, 0) // block_size
    return first, xp.maximum(end, first)


def chunk_pages(k_pool, v_pool, table_width: int) -> int:
    """Pages one loop iteration fetches and computes: enough that a chunk
    holds ``CHUNK_KEYS`` keys, no more than the block table holds, and
    few enough that the double-buffered K and V chunk fits in
    ``CHUNK_VMEM`` bytes beside the prefill tile.  It reads the pools'
    shapes and dtypes only, so the host can count the kernel's iterations
    from the pools it built."""
    bs = k_pool.shape[-2]
    token = (k_pool.shape[-3] * k_pool.shape[-1] * k_pool.dtype.itemsize
             + v_pool.shape[-1] * v_pool.dtype.itemsize)
    return max(1, min(-(-CHUNK_KEYS // bs), CHUNK_VMEM // (2 * bs * token),
                      table_width))


def _lane_groups(kh: int, dv: int) -> tuple[int, int]:
    """(heads per group, group width in lanes) of a V row of ``kh`` heads
    of ``dv`` values: the value product reads one group at a time.  A
    head of 128 or more values is its own group (aligned when ``dv`` is
    a multiple of 128); narrower heads that divide the 128 lanes share
    one, as many as divide ``kh``."""
    per = math.gcd(kh, LANES // dv) if dv < LANES and LANES % dv == 0 else 1
    return per, per * dv


def _kernel(lens_ref, starts_ref, tables_ref, layer_ref, q_ref, k_hbm,
            v_hbm, *refs, scale: float, window: int, block_size: int,
            group: int, pages: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, visits_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    kh = k_buf.shape[2]
    dv = v_buf.shape[-1] // kh
    per, width = _lane_groups(kh, dv)
    groups = [range(g * per, (g + 1) * per) for g in range(kh // per)]
    cg = q_ref.shape[2]
    keys = pages * block_size
    nb = tables_ref.shape[1]
    kv_len, q_start, layer = lens_ref[b], starts_ref[b], layer_ref[0]
    first, end = live_pages(kv_len, q_start, window=window,
                            block_size=block_size)
    chunks = (end - first + pages - 1) // pages

    def by_lane(vals):
        """One group's lanes from its heads' values ((CG, 1) or (CG,
        width) each): lane i takes the value of the group's head i // dv."""
        if per == 1:
            return vals[0]
        head = jax.lax.broadcasted_iota(jnp.int32, (cg, width), 1) // dv
        out = vals[-1]
        for i in range(per - 1):
            out = jnp.where(head == i, vals[i], out)
        return out

    def fetch(c, slot, wait: bool):
        """Start (or wait for) the K and V copies of chunk c's live pages
        into buffer ``slot``; slots past the row's last page copy nothing."""
        for i in range(pages):
            j = first + c * pages + i
            page = tables_ref[b, jnp.minimum(j, nb - 1)]
            copies = [pltpu.make_async_copy(k_hbm.at[layer, page],
                                            k_buf.at[slot, i],
                                            sems.at[0, slot, i]),
                      pltpu.make_async_copy(v_hbm.at[layer, page],
                                            v_buf.at[slot, i],
                                            sems.at[1, slot, i])]

            @pl.when(j < end)
            def _():
                for cp in copies:
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    def update(c, slot):
        """One online-softmax step over chunk c's keys, every KV head."""
        idx = (first + c * pages) * block_size + \
            jax.lax.broadcasted_iota(jnp.int32, (cg, keys), 1)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (cg, keys), 0) // group
        mask = (idx <= qpos) & (idx < kv_len)             # (CG, keys)
        if window:
            mask &= idx > qpos - window
        probs, alphas = [], []
        for h in range(kh):
            q = q_ref[0, h].astype(jnp.float32)           # (CG, D)
            k = k_buf[slot, :, h].astype(jnp.float32)     # (pages, bs, D)
            s = jax.lax.dot_general(
                q, k.reshape(keys, k.shape[-1]), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:          # fused dequant: scale each key's column
                s = s * ks_ref[0, c, pl.ds(h, 1), :]      # (1, keys)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                             # (CG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # (CG, keys)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            if quantized:          # ... and each value's probability
                p = p * vs_ref[0, c, pl.ds(h, 1), :]
            probs.append(p)
            alphas.append(alpha)
        # a masked key still multiplies its value by a zero probability:
        # the slots past a row's last page, which copy nothing, must read
        # as zeros and not as whatever the buffer held
        real = (first + c * pages) * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (keys, width), 0) < kv_len
        for g, heads in enumerate(groups):
            lanes = pl.ds(g * width, width)
            v = v_buf[slot, :, :, lanes].astype(jnp.float32)
            v = jnp.where(real, v.reshape(keys, width), 0.0)  # (keys, width)
            # every head of the group multiplies the whole group; each
            # keeps its own lanes of the product
            pv = [jax.lax.dot_general(probs[h], v, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                  for h in heads]                         # (CG, width) each
            acc_ref[:, lanes] = (acc_ref[:, lanes]
                                 * by_lane([alphas[h] for h in heads])
                                 + by_lane(pv))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(chunks > 0)
    def _first():
        fetch(0, 0, wait=False)

    def step(c, seen):
        slot = c % 2

        @pl.when(c + 1 < chunks)
        def _next():
            fetch(c + 1, 1 - slot, wait=False)

        fetch(c, slot, wait=True)
        update(c, slot)
        return seen + jnp.minimum(pages, end - first - c * pages)

    visits_ref[b] = jax.lax.fori_loop(0, chunks, step, jnp.int32(0))
    for g, heads in enumerate(groups):
        lanes = pl.ds(g * width, width)
        l = by_lane([jnp.maximum(l_ref[h], 1e-30) for h in heads])
        o_ref[0, :, lanes] = (acc_ref[:, lanes] / l).astype(o_ref.dtype)


def _chunk_scales(scales, layer, block_tables, q_starts, kv_lens, *,
                  window: int, pages: int):
    """The scales of each row's chunks, lane-dense: (B, chunks, KH,
    pages*bs), chunk c of row b holding pages ``first + c*pages + i`` of
    its table (block 0 past its last page) side by side on the lanes."""
    B, NB = block_tables.shape
    KH, bs = scales.shape[-2:]
    chunks = -(-NB // pages)
    first, end = live_pages(kv_lens, q_starts, window=window, block_size=bs)
    j = first[:, None] + jnp.arange(chunks * pages, dtype=jnp.int32)
    idx = jnp.where(j < end[:, None], jnp.take_along_axis(
        block_tables, jnp.minimum(j, NB - 1), axis=1), 0)
    s = scales[layer, idx]               # (B, chunks * pages, KH, bs)
    return s.reshape(B, chunks, pages, KH, bs).transpose(0, 1, 3, 2, 4) \
        .reshape(B, chunks, KH, pages * bs)


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
    pages = chunk_pages(k_pool, v_pool, NB)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    q_starts = q_starts.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)

    # (B, C, KH, G, D) -> (B, KH, C*G, D): row r is query (r // G, r % G)
    qg = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KH, CG, D)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               block_size=bs, group=G, pages=pages,
                               quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, KH, CG, D), lambda b, *_: (b, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        rows = [_chunk_scales(s.astype(jnp.float32), layer[0], tables,
                              q_starts, kv_lens, window=window, pages=pages)
                for s in (k_scale, v_scale)]
        in_specs += [pl.BlockSpec((1, *rows[0].shape[1:]),
                                  lambda b, *_: (b, 0, 0, 0))] * 2
        operands += rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, CG, KDV), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, pages, KH, bs, D), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, KDV), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pages)),
            pltpu.VMEM((CG, KDV), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
            pltpu.VMEM((KH, CG, 1), jnp.float32),
        ],
    )
    out, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, CG, KDV), q.dtype),
                   jax.ShapeDtypeStruct((B,), jnp.int32)],
        interpret=interpret,
        name="paged_attention",
    )(kv_lens, q_starts, tables, layer, *operands)
    # (B, C*G, KH*DV) -> (B, C, KH, G, DV): lanes of row r hold query
    # (r // G, r % G) of every KV head
    out = out.reshape(B, C, G, KH, DV).transpose(0, 1, 3, 2, 4) \
        .reshape(B, C, H, DV)
    # every KV head of a sequence visits the same pages
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
