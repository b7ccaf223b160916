"""jit-able OBSPA sweep: Pallas in-block kernel + MXU panel GEMMs.

``obspa_sweep`` is bit-equivalent (up to f32 rounding) to the sequential
Eq. 13/14 oracle in ref.py: within each 128-column block the Pallas kernel
runs the serial chain in VMEM; across blocks the accumulated errors are
applied as one dense ``E @ Hinv[block, rest]`` matmul — the decomposition
that makes the sweep MXU-friendly on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.obspa_update.obspa_update import inblock_sweep
from repro.kernels.obspa_update import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def obspa_sweep(W: jax.Array, Hinv: jax.Array, prune_mask: jax.Array,
                col_block: int = 128, row_block: int = 256) -> jax.Array:
    """Blocked OBSPA reconstruction.  W (R, K), Hinv (K, K), mask (K,).
    The in-block kernel is compiled on the TPU, interpreted elsewhere."""
    interpret = not _on_tpu()
    W = jnp.asarray(W, jnp.float32)
    Hinv = jnp.asarray(Hinv, jnp.float32)
    mask = jnp.asarray(prune_mask, bool)
    R, K = W.shape
    pad = (-K) % col_block
    if pad:
        W = jnp.pad(W, ((0, 0), (0, pad)))
        Hinv = jnp.pad(Hinv, ((0, pad), (0, pad)))
        # padded diag must be non-zero; padded cols are never pruned
        Hinv = Hinv.at[jnp.arange(K, K + pad), jnp.arange(K, K + pad)].set(1.0)
        mask = jnp.pad(mask, (0, pad))
    Kp = W.shape[1]

    for b0 in range(0, Kp, col_block):
        sl = slice(b0, b0 + col_block)
        w_blk, e_blk = inblock_sweep(
            W[:, sl], Hinv[sl, sl], mask[sl],
            row_block=row_block, interpret=interpret)
        W = W.at[:, sl].set(w_blk)
        if b0 + col_block < Kp:
            panel = Hinv[sl, b0 + col_block:]           # (B, rest)
            # full f32 passes: at default precision the TPU multiplies f32
            # in one bf16 pass, and the compensation error compounds
            # across column blocks
            W = W.at[:, b0 + col_block:].add(-jnp.matmul(
                e_blk, panel, precision=jax.lax.Precision.HIGHEST))
    return W[:, :K] if pad else W


def obspa_sweep_batched(W: jax.Array, Hinv: jax.Array, prune_mask: jax.Array,
                        **kw) -> jax.Array:
    """Batched variant: W (E, R, K), Hinv (E, K, K), mask (K,) shared."""
    outs = [obspa_sweep(W[e], Hinv[e], prune_mask, **kw)
            for e in range(W.shape[0])]
    return jnp.stack(outs)


def sweep_oracle(W, Hinv, prune_mask):
    """Ground-truth (numpy Eq. 13/14) — exported for tests/benchmarks."""
    return ref.sweep_numpy(np.asarray(W), np.asarray(Hinv),
                           np.asarray(prune_mask))
