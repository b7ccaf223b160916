"""Dispatch for paged attention: Pallas kernel vs jnp reference.

The backend chooses.  On the TPU the compiled kernel serves every call
whose window is a *static* python int (mask folded into the kernel at
trace time).  A per-sequence dynamic window (Hymba hybrid layers, where
the window is data under ``lax.scan``) and every call on another backend
take the reference, which takes window as an array and is the test
oracle.  ``use_kernel=True`` forces the kernel, which then runs in
interpret mode off the TPU — that is how tests exercise it on the CPU.

``return_visits`` exposes the kernel's per-(seq, kv-head) block-visit
counter (the fully-masked-block skip's observable); it is kernel-only —
the reference materializes every table entry by construction, so asking
it for visit counts is a bug.

Sharded serving (DESIGN.md §10): when the serving engine traces with an
active mesh (``distributed.sharding.use_rules(rules, mesh=mesh)``), the
kernel call wraps itself in ``shard_map`` — sequences split over the
``serve_batch`` (data) axis, KV heads over the ``kv_heads`` (model) axis
— so each device runs the Pallas kernel on its own slice of the block
pools with its own slots' block tables scalar-prefetched locally.  GSPMD
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
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import active_mesh, active_rules
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_kernel, paged_prefill_attention_kernel)
from repro.kernels.paged_attention.ref import (
    paged_attention_reference, paged_prefill_attention_reference)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_path(window) -> bool:
    """Whether the compiled kernel serves this call: on the TPU, for a
    static window.  Tests that must steer the choice patch this."""
    return _on_tpu() and isinstance(window, int)


def _positional_scales(fn):
    """shard_map passes operands positionally; the kernel entry points
    take the quantization scales keyword-only — adapt."""
    def wrapped(*args):
        *rest, ks, vs = args
        return fn(*rest, k_scale=ks, v_scale=vs)
    return wrapped


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


def paged_attention(q, k_pool, v_pool, block_tables, kv_lens, *,
                    window=0, scale: float | None = None,
                    use_kernel: bool | None = None,
                    return_visits: bool = False,
                    k_scale=None, v_scale=None):
    """Decode: q (B, H, D); pools (P, KH, bs, D/DV) -> (B, H, DV).

    ``k_scale``/``v_scale`` (P, KH, bs) mark the pools as quantized: the
    kernel fuses dequantization into its score and value products; the
    reference dequantizes the gathered history.  Scale pools shard
    exactly like their KV pools (kv_heads over the model axis) — same
    placement, same shard_map specs minus the head_dim axis."""
    if use_kernel is None:
        use_kernel = _kernel_path(window)
    if use_kernel:
        fn = functools.partial(paged_attention_kernel, window=window,
                               scale=scale, interpret=not _on_tpu(),
                               return_visits=return_visits)
        part = _serve_partition(q.shape[0], q.shape[1], k_pool.shape[1])
        if part is not None:
            mesh, bd, hd = part
            bd, hd = (bd or None), (hd or None)
            in_specs = (P(bd, hd, None), P(None, hd, None, None),
                        P(None, hd, None, None), P(bd, None), P(bd))
            if k_scale is not None:
                in_specs += (P(None, hd, None), P(None, hd, None))
                fn = _positional_scales(fn)
            fn = jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs,
                out_specs=(P(bd, hd, None), P(bd, hd)) if return_visits
                else P(bd, hd, None),
                check_vma=False)
            if k_scale is not None:
                return fn(q, k_pool, v_pool, block_tables, kv_lens,
                          k_scale, v_scale)
            return fn(q, k_pool, v_pool, block_tables, kv_lens)
        return fn(q, k_pool, v_pool, block_tables, kv_lens,
                  k_scale=k_scale, v_scale=v_scale)
    if return_visits:
        raise ValueError("visit counts are a kernel-path observable")
    return paged_attention_reference(
        q, k_pool, v_pool, block_tables, kv_lens,
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, q_starts,
                            kv_lens, *, window=0,
                            scale: float | None = None,
                            use_kernel: bool | None = None,
                            return_visits: bool = False,
                            k_scale=None, v_scale=None):
    """Chunked prefill: q (B, C, H, D) -> (B, C, H, DV)."""
    if use_kernel is None:
        use_kernel = _kernel_path(window)
    if use_kernel:
        fn = functools.partial(paged_prefill_attention_kernel, window=window,
                               scale=scale, interpret=not _on_tpu(),
                               return_visits=return_visits)
        part = _serve_partition(q.shape[0], q.shape[2], k_pool.shape[1])
        if part is not None:
            mesh, bd, hd = part
            bd, hd = (bd or None), (hd or None)
            in_specs = (P(bd, None, hd, None), P(None, hd, None, None),
                        P(None, hd, None, None), P(bd, None), P(bd),
                        P(bd))
            if k_scale is not None:
                in_specs += (P(None, hd, None), P(None, hd, None))
                fn = _positional_scales(fn)
            fn = jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs,
                out_specs=(P(bd, None, hd, None), P(bd, hd))
                if return_visits else P(bd, None, hd, None),
                check_vma=False)
            if k_scale is not None:
                return fn(q, k_pool, v_pool, block_tables, q_starts,
                          kv_lens, k_scale, v_scale)
            return fn(q, k_pool, v_pool, block_tables, q_starts, kv_lens)
        return fn(q, k_pool, v_pool, block_tables, q_starts, kv_lens,
                  k_scale=k_scale, v_scale=v_scale)
    if return_visits:
        raise ValueError("visit counts are a kernel-path observable")
    return paged_prefill_attention_reference(
        q, k_pool, v_pool, block_tables, q_starts, kv_lens,
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale)
