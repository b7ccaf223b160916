"""Pallas TPU kernel: OBSPA in-block reconstruction sweep.

TPU adaptation of SparseGPT's per-column GPU sweep (DESIGN.md §2): the
serial rank-1 chain only runs *within* a 128-wide column block resident in
VMEM (VPU work); the cross-block compensation ``W[:, rest] -= E @
Hinv[block, rest]`` is a dense GEMM that ops.py issues on the MXU.  The
kernel therefore computes, per column block:

    for j in 0..B-1:                       # sequential, in VMEM
        err        = W[:, j] / Hinv[j, j]
        W[:, j:B] -= pruned[j] * err ⊗ Hinv[j, j:B]
        E[:, j]    = pruned[j] * err

Grid: one program per row block of W; Hinv's diagonal block and the prune
mask are broadcast to every program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _inblock_kernel(w_ref, h_ref, m_ref, wout_ref, e_ref, *, block: int):
    w = w_ref[...].astype(jnp.float32)          # (BR, B)
    m = m_ref[...].astype(jnp.float32)          # (1, B)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def body(j, carry):
        # column j of a value is read as a one-hot reduction (exact: every
        # other term is a zero) — the TPU lowers no dynamic lane slice
        w, e = carry
        onehot = (cols == j).astype(jnp.float32)                 # (1, B)
        hrow = h_ref[pl.ds(j, 1), :].astype(jnp.float32)         # (1, B)
        hjj = jnp.sum(hrow * onehot, axis=1, keepdims=True)      # (1, 1)
        wj = jnp.sum(w * onehot, axis=1, keepdims=True)          # (BR, 1)
        pj = jnp.sum(m * onehot, axis=1, keepdims=True)          # (1, 1)
        err = wj / hjj
        upd = (err * pj) * jnp.where(cols >= j, hrow, 0.0)       # (BR, B)
        w = w - upd
        e = e + (err * pj) * onehot
        return w, e

    w, e = jax.lax.fori_loop(0, block, body, (w, jnp.zeros_like(w)))
    wout_ref[...] = w
    e_ref[...] = e


def inblock_sweep(w: jax.Array, hinv_bb: jax.Array, mask: jax.Array,
                  row_block: int = 256, interpret: bool = True
                  ) -> tuple[jax.Array, jax.Array]:
    """Run the in-block sweep.  w (R, B) f32, hinv_bb (B, B), mask (B,) bool.

    Returns (updated w, errors E) — both (R, B) f32.
    """
    R, B = w.shape
    assert hinv_bb.shape == (B, B) and mask.shape == (B,)
    pad = (-R) % row_block
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    Rp = w.shape[0]
    m2 = mask.astype(jnp.float32)[None, :]       # (1, B)

    kernel = functools.partial(_inblock_kernel, block=B)
    wout, e = pl.pallas_call(
        kernel,
        grid=(Rp // row_block,),
        in_specs=[
            pl.BlockSpec((row_block, B), lambda i: (i, 0)),
            pl.BlockSpec((B, B), lambda i: (0, 0)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, B), lambda i: (i, 0)),
            pl.BlockSpec((row_block, B), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, B), jnp.float32),
            jax.ShapeDtypeStruct((Rp, B), jnp.float32),
        ],
        interpret=interpret,
        name="obspa_inblock_sweep",
    )(w.astype(jnp.float32), hinv_bb.astype(jnp.float32), m2)
    return wout[:R], e[:R]
