"""Dispatch for paged attention: Pallas kernel vs jnp reference.

The backend chooses.  On the TPU the compiled kernel serves every call
whose window is a *static* python int (mask folded into the kernel at
trace time).  A per-sequence dynamic window (Hymba hybrid layers, where
the window is data under ``lax.scan``) and every call on another backend
take the reference, which takes window as an array and is the test
oracle.  ``use_kernel=True`` forces the kernel, which then runs in
interpret mode off the TPU — that is how tests exercise it on the CPU.

``return_visits`` exposes the kernel's per-(seq, kv-head) page-visit
counter (the pages its live-page loop computed); it is kernel-only —
the reference materializes every table entry by construction, so asking
it for visit counts is a bug.

``grid_steps`` counts, on the host, the pages a call computes and the
page slots it launches (its loop iterations times the pages of a
chunk), from the lengths the call hands the kernel and with the
kernel's own ``live_pages``: the one place the kernel's work is counted
outside it (the serving engine's ``serve/*_attn_*_steps`` counters).
``pool_lanes`` counts the lanes a pool leaf fills of those the TPU
stores for it (the engine's ``serve/v_pool_lanes_*`` counters).

Sharded serving (DESIGN.md §10): when the serving engine traces with an
active mesh (``distributed.sharding.use_rules(rules, mesh=mesh)``), the
kernel call wraps itself in ``shard_map`` — sequences split over the
``serve_batch`` (data) axis, KV heads over the ``kv_heads`` (model) axis
— so each device runs the Pallas kernel on its own slice of the block
pools with its own slots' block tables scalar-prefetched locally (the
layer axis of stacked pools and the layer index are replicated).  GSPMD
cannot partition an opaque ``pallas_call``; without the wrap a sharded
step would all-gather the pools onto every device, which is exactly what
paging exists to avoid.  Attention needs no cross-device reduction in
either direction: every (sequence, kv-head) pair is computed wholly on
one device, so the wrap emits zero collectives — the only gather in the
sharded serve step is the final logits all-gather before sampling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import active_mesh, active_rules
from repro.kernels.paged_attention.paged_attention import (
    LANES, chunk_pages, live_pages, paged_attention_kernel,
    paged_prefill_attention_kernel)
from repro.kernels.paged_attention.ref import (
    paged_attention_reference, paged_prefill_attention_reference)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_path(window) -> bool:
    """Whether the compiled kernel serves this call: on the TPU, for a
    static window.  Tests that must steer the choice patch this."""
    return _on_tpu() and isinstance(window, int)


def grid_steps(kv_lens, q_starts, pages: int, block_size: int,
               window: int = 0) -> tuple[int, int]:
    """(live, launched) page slots of one kernel call over a batch with
    per-row ``kv_lens`` and ``q_starts`` (numpy, inactive rows included as
    the kernel sees them), ``pages`` to a chunk (``chunk_pages`` of the
    call's pools and table).  Each row loops over its ``live_pages`` a
    chunk at a time: the live pages are what it computes (the per-row
    count is the kernel's ``visits``), and each iteration launches a
    chunk's worth of page slots, so the table's width costs nothing."""
    first, end = live_pages(np.asarray(kv_lens, np.int32),
                            np.asarray(q_starts, np.int32), window=window,
                            block_size=block_size)
    live = end - first
    return int(live.sum()), int((-(-live // pages)).sum()) * pages


def pool_lanes(shape) -> tuple[int, int]:
    """(value, stored) lanes of a pool leaf of ``shape``: the lanes of its
    minor dimension that hold values, and the lanes the TPU stores for
    them (its tiles are ``LANES`` wide, so a minor dimension is padded up
    to a multiple), each summed over the leaf's rows."""
    rows, minor = math.prod(shape[:-1]), shape[-1]
    return rows * minor, rows * -(-minor // LANES) * LANES


def _serve_partition(B: int, H: int, KH: int):
    """(mesh, batch_axes, head_axes) when a serving mesh is active and at
    least one axis can actually split the work; None otherwise.

    Head axes must divide both H and KH — the kernel's GQA tiling needs
    every shard to hold whole (kv-head, query-group) bundles; batch axes
    must divide B.  Non-dividing axes drop to replication (the same
    fallback ``ShardingRules._fit`` applies everywhere else).
    """
    mesh, rules = active_mesh(), active_rules()
    if mesh is None or rules is None or mesh.devices.size == 1:
        return None

    def fit(name: str, *dims: int) -> tuple[str, ...]:
        axes = tuple(a for a in rules.rules.get(name, ())
                     if a in mesh.axis_names)
        while axes:
            sz = math.prod(mesh.shape[a] for a in axes)
            if all(d % sz == 0 for d in dims):
                return axes
            axes = axes[:-1]
        return ()

    batch_axes = fit("serve_batch", B)
    head_axes = tuple(a for a in fit("kv_heads", H, KH)
                      if a not in batch_axes)
    if not batch_axes and not head_axes:
        return None
    return mesh, batch_axes, head_axes


def _kernel_call(entry, q, k_pool, v_pool, block_tables, rows, *, heads,
                 layer, k_scale, v_scale, return_visits, **static):
    """Run kernel ``entry`` on ``(q, k_pool, v_pool, block_tables, *rows)``
    (``rows``: the per-sequence (B,) operands), wrapped in ``shard_map``
    when a serving mesh splits the work.  Pools keep every leading axis
    (layer, block) replicated; K and the scales split their KV-head axis,
    V its lanes, which hold the heads side by side, so a shard's lanes
    are its own heads' values; ``heads`` is q's head count."""
    fn = functools.partial(entry, interpret=not _on_tpu(),
                           return_visits=return_visits, **static)
    if layer is not None:
        layer = jnp.asarray(layer, jnp.int32)   # an operand of shard_map
    kw = {n: a for n, a in (("layer", layer), ("k_scale", k_scale),
                            ("v_scale", v_scale)) if a is not None}
    part = _serve_partition(q.shape[0], heads, k_pool.shape[-3])
    if part is None:
        return fn(q, k_pool, v_pool, block_tables, *rows, **kw)
    mesh, bd, hd = part
    bd, hd = (bd or None), (hd or None)
    lead = (None,) * (k_pool.ndim - 3)
    q_spec = P(bd, *(None,) * (q.ndim - 3), hd, None)
    kw_specs = {"layer": P(), "k_scale": P(*lead, hd, None),
                "v_scale": P(*lead, hd, None)}
    in_specs = (q_spec, P(*lead, hd, None, None), P(*lead, None, hd),
                P(bd, None), *(P(bd),) * len(rows),
                *(kw_specs[n] for n in kw))
    names = tuple(kw)

    def positional(*args):
        # shard_map passes operands positionally; the entry points take
        # the layer index and the scales keyword-only
        n = len(args) - len(names)
        return fn(*args[:n], **dict(zip(names, args[n:])))

    return jax.shard_map(
        positional, mesh=mesh, in_specs=in_specs,
        out_specs=(q_spec, P(bd, hd)) if return_visits else q_spec,
        check_vma=False)(q, k_pool, v_pool, block_tables, *rows,
                         *kw.values())


def paged_attention(q, k_pool, v_pool, block_tables, kv_lens, *,
                    layer=None, window=0, scale: float | None = None,
                    use_kernel: bool | None = None,
                    return_visits: bool = False,
                    k_scale=None, v_scale=None):
    """Decode: q (B, H, D); pools (L, P, KH, bs, D) and (L, P, bs, KH*DV)
    read at ``layer`` (the serving step's layer scan carries them whole),
    or one layer's (P, KH, bs, D) and (P, bs, KH*DV) with no layer -> (B,
    H, DV).

    ``k_scale``/``v_scale`` ((L,) P, KH, bs) mark the pools as quantized:
    the kernel fuses dequantization into its score and value products;
    the reference dequantizes the gathered history.  Scale pools shard
    like the K pool (kv_heads over the model axis) — same placement,
    same shard_map specs minus the head_dim axis."""
    if use_kernel is None:
        use_kernel = _kernel_path(window)
    if use_kernel:
        return _kernel_call(
            paged_attention_kernel, q, k_pool, v_pool, block_tables,
            (kv_lens,), heads=q.shape[1], layer=layer, k_scale=k_scale,
            v_scale=v_scale, return_visits=return_visits, window=window,
            scale=scale)
    if return_visits:
        raise ValueError("visit counts are a kernel-path observable")
    return paged_attention_reference(
        q, k_pool, v_pool, block_tables, kv_lens, layer=layer,
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, q_starts,
                            kv_lens, *, layer=None, window=0,
                            scale: float | None = None,
                            use_kernel: bool | None = None,
                            return_visits: bool = False,
                            k_scale=None, v_scale=None):
    """Chunked prefill: q (B, C, H, D) -> (B, C, H, DV); pools and
    ``layer`` as in ``paged_attention``."""
    if use_kernel is None:
        use_kernel = _kernel_path(window)
    if use_kernel:
        return _kernel_call(
            paged_prefill_attention_kernel, q, k_pool, v_pool, block_tables,
            (q_starts, kv_lens), heads=q.shape[2], layer=layer,
            k_scale=k_scale, v_scale=v_scale, return_visits=return_visits,
            window=window, scale=scale)
    if return_visits:
        raise ValueError("visit counts are a kernel-path observable")
    return paged_prefill_attention_reference(
        q, k_pool, v_pool, block_tables, q_starts, kv_lens, layer=layer,
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale)
