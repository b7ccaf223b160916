"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e described
here, not attached: the TPU compiler refuses tilings, lowerings and
memory use that interpret mode accepts.  Widths are the registered
configs' own (Qwen3-1.7B attention, full and SPA-pruned 50%; its MLP for
OBSPA; Mamba2-1.3B's SSD heads).  Every case must come out holding a
compiled kernel (``tpu_custom_call``).

The topology is described inside a fixture: only one process at a time
may load the TPU library, so it must not happen while modules are
imported or tests collected.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.obspa_update.obspa_update import inblock_sweep
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_kernel, paged_prefill_attention_kernel)
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

QWEN = get_config("qwen3-1.7b")
MAMBA = get_config("mamba2-1.3b")
# chip_smoke.py's serving shape: 8 slots x 2,048 tokens of 16-token blocks
SLOTS, BLOCK, MAX_LEN, CHUNK = 8, 16, 2048, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_kernel(fn, one_chip, *shapes) -> bool:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


NB = MAX_LEN // BLOCK
POOL_BLOCKS = SLOTS * NB + 1


def _pool(kh, dv, dtype):
    """(k pool, v pool, tables, lens) shapes at the serving shape."""
    return ([((POOL_BLOCKS, kh, BLOCK, QWEN.head_dim_), dtype),
             ((POOL_BLOCKS, kh, BLOCK, dv), dtype),
             ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32)])


PRUNED = dict(heads=8, kh=4, dv=64)     # qwen3-1.7b after SPA-L1 at 50%
FULL = dict(heads=QWEN.n_heads, kh=QWEN.n_kv_heads, dv=QWEN.v_head_dim_)


@pytest.mark.parametrize("widths", [FULL, PRUNED], ids=["full", "pruned"])
def test_paged_decode_bf16(one_chip, widths):
    q = ((SLOTS, widths["heads"], QWEN.head_dim_), jnp.bfloat16)
    fn = lambda q, k, v, t, l: paged_attention_kernel(  # noqa: E731
        q, k, v, t, l, interpret=False)
    assert _compiled_kernel(fn, one_chip, q, *_pool(
        widths["kh"], widths["dv"], jnp.bfloat16))


@pytest.mark.parametrize("widths", [FULL, PRUNED], ids=["full", "pruned"])
def test_paged_prefill_bf16(one_chip, widths):
    q = ((SLOTS, CHUNK, widths["heads"], QWEN.head_dim_), jnp.bfloat16)
    fn = lambda q, k, v, t, l, s: paged_prefill_attention_kernel(  # noqa
        q, k, v, t, s, l, interpret=False)
    assert _compiled_kernel(fn, one_chip, q, *_pool(
        widths["kh"], widths["dv"], jnp.bfloat16), ((SLOTS,), jnp.int32))


def test_paged_decode_int8(one_chip):
    q = ((SLOTS, QWEN.n_heads, QWEN.head_dim_), jnp.bfloat16)
    pool = _pool(QWEN.n_kv_heads, QWEN.v_head_dim_, jnp.int8)
    scale = ((POOL_BLOCKS, QWEN.n_kv_heads, BLOCK), jnp.float32)
    fn = lambda q, k, v, t, l, ks, vs: paged_attention_kernel(  # noqa
        q, k, v, t, l, k_scale=ks, v_scale=vs, interpret=False)
    assert _compiled_kernel(fn, one_chip, q, *pool, scale, scale)


def test_obspa_inblock_sweep(one_chip):
    # one 128-column block of the MLP up-projection, as W (d_ff, d_model)
    fn = lambda w, h, m: inblock_sweep(w, h, m, interpret=False)  # noqa
    assert _compiled_kernel(fn, one_chip, ((QWEN.d_ff, 128), jnp.float32),
                            ((128, 128), jnp.float32), ((128,), jnp.bool_))


def test_flash_attention(one_chip):
    S = 2048
    fn = lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False)  # noqa
    assert _compiled_kernel(
        fn, one_chip, ((1, QWEN.n_heads, S, QWEN.head_dim_), jnp.bfloat16),
        ((1, QWEN.n_kv_heads, S, QWEN.head_dim_), jnp.bfloat16),
        ((1, QWEN.n_kv_heads, S, QWEN.head_dim_), jnp.bfloat16))


def test_ssd_scan(one_chip):
    b, L, h, p, n = 1, 1024, MAMBA.ssm_n_heads, MAMBA.ssm_head_dim, \
        MAMBA.ssm_state
    fn = lambda x, dt, A, B, C: ssd_scan_pallas(  # noqa: E731
        x, dt, A, B, C, MAMBA.ssm_chunk, interpret=False)
    assert _compiled_kernel(
        fn, one_chip, ((b, L, h, p), jnp.float32), ((b, L, h), jnp.float32),
        ((h,), jnp.float32), ((b, L, n), jnp.bfloat16),
        ((b, L, n), jnp.bfloat16))
