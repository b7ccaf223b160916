"""Pallas TPU kernel: Mamba-2 SSD chunked scan (state-space duality).

Grid (B, H, n_chunks) with the chunk dimension innermost: the (hp, n) f32
inter-chunk state lives in VMEM scratch and carries across chunk
iterations of one (b, h) stream — the sequential recurrence never touches
HBM.  Per chunk the kernel does four MXU matmuls (the "dual" quadratic
form of SSD):

  G      = C @ Bᵀ ⊙ exp(segsum(dA))          (Q, Q)  intra-chunk kernel
  y_diag = G @ (dt ⊙ x)                       (Q, hp)
  y_off  = exp(cs) ⊙ (C @ stateᵀ)             (Q, hp) contribution of carry
  state  = exp(cs_Q) · state + (B ⊙ decay)ᵀ @ (dt ⊙ x)     (n, hp)

VMEM per program: x/dt/B/C chunk tiles + (Q, Q) decay kernel + (hp, n)
state ≈ a few hundred KiB at Q=128, hp=64, n=128 — comfortably resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
            chunk: int):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, hp), dt pre-applied
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[pl.program_id(1)]                      # scalar, from SMEM
    Bm = b_ref[0, 0].astype(jnp.float32)             # (Q, n)
    Cm = c_ref[0, 0].astype(jnp.float32)             # (Q, n)

    dA = dt * A                                      # (Q, 1)
    # prefix sums as products with triangular ones matrices (the TPU
    # lowers no cumsum), in both orientations for the pairwise segsum
    # sum_{j<k<=i} dA_k = cs_i - cs_j, plus the suffix sums that decay
    # each position's state contribution to the chunk's end
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (ii >= jj).astype(jnp.float32)

    def mm(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    cs = mm(tril, dA, ((1,), (0,)))                  # (Q, 1)
    cs_row = mm(dA, tril, ((0,), (1,)))              # (1, Q)
    L = jnp.where(ii >= jj, jnp.exp(cs - cs_row), 0.0)   # (Q, Q)
    decay_states = jnp.exp(mm((jj > ii).astype(jnp.float32), dA,
                              ((1,), (0,))))         # (Q, 1) exp(cs_Q - cs)
    p = x.shape[1]
    decay_chunk = jnp.exp(mm(jnp.ones((p, chunk), jnp.float32), dA,
                             ((1,), (0,))))          # (hp, 1) exp(cs_Q)

    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, Q)
    y_diag = jax.lax.dot_general(G * L, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_ref[...]                           # (hp, n)
    y_off = jnp.exp(cs) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, hp)

    upd = jax.lax.dot_general(x, Bm * decay_states,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (hp, n)
    state_ref[...] = state * decay_chunk + upd

    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)


def ssd_scan_pallas(x, dt, A, B, C, chunk: int, interpret: bool = True):
    """x (b, l, h, p) [pre-multiplied by dt], dt (b, l, h), A (h,),
    B/C (b, l, n) -> y (b, l, h, p).  l must divide into chunks."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    # kernel layouts: chunk-major per (b, h) stream
    xk = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtk = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk, 1)
    bk = B.reshape(b, nc, chunk, n)
    ck = C.reshape(b, nc, chunk, n)

    kernel = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda i, j, c: (i, j, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda i, j, c: (i, j, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),       # A (h,) whole
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda i, j, c: (i, j, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xk, dtk, A.astype(jnp.float32), bk, ck)
    return y.reshape(b, h, l, p).transpose(0, 2, 1, 3)
