"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e described
here, not attached: the TPU compiler refuses tilings, lowerings and
memory use that interpret mode accepts.  Widths are the registered
configs' own (Qwen3-1.7B attention, full and SPA-pruned 50%; its MLP for
OBSPA; Mamba2-1.3B's SSD heads).  Every case must come out holding a
compiled kernel (``tpu_custom_call``).  The whole paged step programs
are compiled too, at the benchmark's serving shape, to guard that the KV
pool rides the layer scan in place (no pool-sized copies).

The topology is described inside a fixture: only one process at a time
may load the TPU library, so it must not happen while modules are
imported or tests collected.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import ops
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.obspa_update.obspa_update import inblock_sweep
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_kernel, paged_prefill_attention_kernel)
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
from repro.models import build

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
             ((POOL_BLOCKS, BLOCK, kh * dv), dtype),
             ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32)])


PRUNED = dict(heads=8, kh=4, dv=64, ff=3072)  # qwen3-1.7b, SPA-L1 at 50%
FULL = dict(heads=QWEN.n_heads, kh=QWEN.n_kv_heads, dv=QWEN.v_head_dim_,
            ff=QWEN.d_ff)


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


# ---------------------------------------------------------------------------
# whole step programs: the KV pool rides the layer scan in place
# ---------------------------------------------------------------------------

# the benchmark cell's serving shape: 16 slots, 8,192-token tables of
# 16-token blocks, a 3,000-block pool, 256-token prefill chunks
STEP_SLOTS, STEP_NB, STEP_POOL, STEP_CHUNK = 16, 512, 3000, 256
MIB = 1 << 20
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(")
_ARRAY = re.compile(r"\b[a-z][a-z0-9]*\[([\d,]*)\]")


def _pool_ops(hlo: str, sizes: set[int]) -> list[tuple[str, int]]:
    """(op, element count) of every copy, copy-start, dynamic-slice,
    dynamic-update-slice and AllocateBuffer whose result holds an array
    of one of ``sizes`` elements (a pool leaf's layer slice or the
    stacked pool, in any shape or layout)."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = m.group(2)
        if op == "custom-call" and 'custom_call_target="AllocateBuffer"' \
                in line:
            op = "AllocateBuffer"
        if op not in ("copy", "copy-start", "dynamic-slice",
                      "dynamic-update-slice", "AllocateBuffer"):
            continue
        for dims in _ARRAY.findall(m.group(1)):
            n = math.prod(int(d) for d in dims.split(",") if d)
            if n in sizes:
                found.append((op, n))
    return found


def _lane_padded_bytes(leaf) -> int:
    """Bytes of one row-major copy of ``leaf`` with its minor dimension
    padded to the TPU's 128 lanes."""
    return math.prod(leaf.shape[:-1]) * 128 * leaf.dtype.itemsize


@pytest.mark.parametrize("widths,cache_dtype,relaid", [
    (FULL, None, ()),
    (FULL, "int8", ("k_scale", "v_scale")),
    (PRUNED, None, ()),
], ids=["full-bf16", "full-int8", "pruned-bf16"])
def test_paged_steps_carry_pool_in_place(one_chip, monkeypatch, widths,
                                         cache_dtype, relaid):
    """Decode and chunked prefill of all 28 layers at the serving shape,
    cache donated as the engine donates it.  No program may copy, slice
    or allocate a layer's slice of any pool leaf, nor slice, update or
    allocate a whole stacked leaf; the scan's only pool writes are
    in-place scatters.  Leaves whose minor dimension is narrower than the
    128 lanes (``relaid``: the f32 scale pools' 16-token rows) are stored
    by the TPU in another layout than the kernel's row-major tiles, so
    the program converts them on entry and exit: those whole-leaf copies,
    and their temporaries, are the only pool-sized ones allowed.  The V
    pool is token-major, its heads side by side on the lanes, so the
    pruned model's 64-wide value heads fill 256 lanes and need no
    conversion."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = QWEN.replace(n_heads=widths["heads"], n_kv_heads=widths["kh"],
                       v_head_dim=widths["dv"], d_ff=widths["ff"])
    model = build(cfg)
    B, NB, C = STEP_SLOTS, STEP_NB, STEP_CHUNK

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(functools.partial(
        model.init_paged_cache, num_blocks=STEP_POOL, block_size=BLOCK,
        max_seqs=B, dtype=cache_dtype)))
    assert set(relaid) <= set(cache)
    i32 = jnp.int32
    programs = {
        "decode": jax.jit(model.paged_decode_step, donate_argnums=(1,))
        .lower(params, cache, sds((B,), i32), sds((B,), i32),
               sds((B, NB), i32), sds((B,), jnp.bool_)),
        "prefill": jax.jit(model.paged_prefill_step, donate_argnums=(1,))
        .lower(params, cache, sds((B, C), i32), sds((B, C), i32),
               sds((B,), i32), sds((B, NB), i32), sds((B,), i32)),
    }
    slices = {math.prod(a.shape[1:]) for a in cache.values()}
    stacked = {n: math.prod(a.shape) for n, a in cache.items()}
    conversions = {stacked[n] for n in relaid}
    room = 64 * MIB + sum(_lane_padded_bytes(cache[n]) for n in relaid)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo, name
        bad = [(op, n) for op, n in _pool_ops(
            hlo, slices | set(stacked.values()))
            if n in slices or op not in ("copy", "copy-start")
            or n not in conversions]
        assert not bad, (name, bad)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < room, (name, temp, room)
