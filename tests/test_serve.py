"""Serving subsystem tests: paged cache invariants, scheduler behavior,
paged-attention kernel parity, and engine-vs-sequential-generate parity."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_reference, v_rows)
from repro.launch.serve import generate
from repro.models import build
from repro.serve import (BlockAllocator, Engine, FCFSScheduler, OutOfBlocks,
                         PagedCache, Request, ServeConfig)

rng = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# Block allocator / paged cache
# ---------------------------------------------------------------------------

def test_allocator_invariants():
    a = BlockAllocator(16)
    assert a.num_free == 15                      # block 0 reserved
    got = a.alloc(5)
    assert len(set(got)) == 5 and 0 not in got
    a.check()
    with pytest.raises(OutOfBlocks):
        a.alloc(11)
    a.free(got[:2])
    a.check()
    assert a.num_free == 12
    with pytest.raises(ValueError):              # double free
        a.free([got[0]])
    a.free(got[2:])
    a.check()
    assert a.num_free == 15 and a.num_used == 0


def test_paged_cache_grow_release():
    c = PagedCache(max_seqs=3, num_blocks=9, block_size=4,
                   max_blocks_per_seq=4)          # 8 usable blocks
    c.ensure(0, 1)
    assert len(c.owned(0)) == 1
    c.ensure(0, 4)                               # still one block
    assert len(c.owned(0)) == 1
    c.ensure(0, 5)                               # crosses a boundary
    assert len(c.owned(0)) == 2
    c.ensure(1, 16)
    assert len(c.owned(1)) == 4
    # distinct slots never share blocks; table rows match ownership
    assert not set(c.owned(0)) & set(c.owned(1))
    np.testing.assert_array_equal(c.tables[0, :2], c.owned(0))
    with pytest.raises(OutOfBlocks):             # 2 free < 3 needed
        c.ensure(2, 12)
    with pytest.raises(OutOfBlocks):             # beyond per-seq capacity
        c.ensure(1, 17)
    c.release(0)
    assert c.owned(0) == [] and (c.tables[0] == 0).all()
    c.ensure(2, 12)                              # reuses freed blocks
    assert len(c.owned(2)) == 3
    c.allocator.check()


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------

def _mk_sched(max_seqs=2, num_blocks=9, block_size=4, nb_per_seq=4):
    cache = PagedCache(max_seqs=max_seqs, num_blocks=num_blocks,
                       block_size=block_size, max_blocks_per_seq=nb_per_seq)
    return FCFSScheduler(cache), cache


def test_scheduler_fcfs_admission():
    s, cache = _mk_sched(max_seqs=2)
    for rid in range(3):
        s.add(Request(rid, prompt=(1, 2, 3), max_new_tokens=4))
    running = s.schedule()
    assert [r.req.rid for r in running] == [0, 1]       # 2 slots only
    assert len(s.waiting) == 1
    # finish rid 0 -> rid 2 admitted next round
    running[0].stopped = True
    running = s.schedule()
    assert sorted(r.req.rid for r in running) == [1, 2]
    assert len(s.finished) == 1 and s.finished[0].req.rid == 0


def test_scheduler_rejects_oversized_request():
    s, _ = _mk_sched()
    with pytest.raises(ValueError):
        s.add(Request(0, prompt=tuple(range(15)), max_new_tokens=4))


def test_scheduler_rejects_request_pool_can_never_admit():
    """A request within per-seq capacity but beyond the whole pool must be
    rejected at add() — otherwise admit() can never fire and run() spins."""
    s, _ = _mk_sched(max_seqs=2, num_blocks=4, block_size=4, nb_per_seq=8)
    with pytest.raises(ValueError, match="blocks"):
        s.add(Request(0, prompt=tuple(range(20)), max_new_tokens=4))


def test_scheduler_preempts_youngest_on_pool_exhaustion():
    # 2 slots, 5 usable blocks of 4 -> two seqs can't both reach 9 tokens
    s, cache = _mk_sched(max_seqs=2, num_blocks=6)
    s.add(Request(0, prompt=(1,) * 7, max_new_tokens=8))
    s.add(Request(1, prompt=(2,) * 7, max_new_tokens=8))
    running = s.schedule()
    assert len(running) == 2                     # 2 blocks each, 1 spare
    # drive both to where each needs a third block (token 9)
    for r in list(s.running):
        r.num_cached = 8
        r.generated.extend([9, 9])               # seq_len 9
    s.schedule()
    rids = sorted(r.req.rid for r in s.running)
    assert rids == [0]                           # youngest (1) was preempted
    victim = s.waiting[0]
    assert victim.req.rid == 1 and victim.preemptions == 1
    assert victim.num_cached == 0                # will re-prefill
    assert victim.generated == [9, 9]            # keeps its progress
    cache.allocator.check()


# ---------------------------------------------------------------------------
# Paged attention kernel vs reference
# ---------------------------------------------------------------------------

def _chunk_edges(kp, vp, NB):
    """kv lengths at the edges of the kernel's chunks: 1, one block, a
    chunk less one, a chunk, a chunk and one, the whole table."""
    from repro.kernels.paged_attention.ops import chunk_pages
    bs = kp.shape[-2]
    keys = chunk_pages(kp, vp, NB) * bs
    return np.array([1, bs, keys - 1, keys, keys + 1, NB * bs], np.int32)


@pytest.mark.parametrize("B,H,KH,D,DV,bs,NB,window,dtype,lens", [
    pytest.param(2, 4, 2, 16, 16, 8, 4, 0, jnp.float32, "random",
                 id="2-4-2-16-16-8-4-0-float32"),
    pytest.param(3, 4, 1, 32, 16, 4, 8, 0, jnp.float32, "random",
                 id="3-4-1-32-16-4-8-0-float32"),
    pytest.param(1, 8, 8, 16, 16, 16, 2, 0, jnp.float32, "random",
                 id="1-8-8-16-16-16-2-0-float32"),
    pytest.param(2, 4, 2, 16, 16, 8, 4, 5, jnp.float32, "random",
                 id="2-4-2-16-16-8-4-5-float32"),
    pytest.param(2, 2, 2, 32, 32, 8, 4, 0, jnp.bfloat16, "random",
                 id="2-2-2-32-32-8-4-0-bfloat16"),
    # chunk edges (16 pages of 8 tokens): a value head per lane group,
    # two 64-wide heads sharing one, and a sliding window
    pytest.param(6, 4, 2, 16, 128, 8, 40, 0, jnp.bfloat16, "edges",
                 id="edges-dense-bfloat16"),
    pytest.param(6, 8, 4, 16, 64, 8, 40, 0, jnp.bfloat16, "edges",
                 id="edges-pruned-bfloat16"),
    pytest.param(6, 4, 2, 16, 16, 8, 40, 37, jnp.float32, "edges",
                 id="edges-window-float32"),
])
def test_paged_attention_kernel_parity(B, H, KH, D, DV, bs, NB, window,
                                       dtype, lens):
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), dtype)
    vp = v_rows(jnp.asarray(rng.normal(size=(P, KH, bs, DV)), dtype))
    # non-contiguous tables: shuffled pool blocks, none uses block 0
    tables = jnp.asarray(
        1 + rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    lens = jnp.asarray(_chunk_edges(kp, vp, NB) if lens == "edges" else
                       rng.integers(1, NB * bs + 1, size=(B,)), jnp.int32)
    out = paged_attention(q, kp, vp, tables, lens, window=window,
                          use_kernel=True)
    ref = paged_attention_reference(q, kp, vp, tables, lens, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_skips_fully_masked_blocks():
    """Tables that are mostly empty (short sequences in a long table) must
    not be visited past their length: the visit counter proves the skip
    actually fires, and parity vs the reference proves it is harmless."""
    B, H, KH, D, bs, NB = 3, 4, 2, 16, 4, 16          # 64-token tables
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    vp = v_rows(jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32))
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    lens = jnp.asarray([1, 5, 9], jnp.int32)          # 1-3 of 16 blocks live
    out, visits = paged_attention(q, kp, vp, tables, lens, use_kernel=True, return_visits=True)
    ref = paged_attention_reference(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    expect = [-(-int(l) // bs) for l in lens]          # ceil(len / bs)
    np.testing.assert_array_equal(np.asarray(visits),
                                  np.tile(np.asarray(expect)[:, None], KH))
    assert int(np.asarray(visits).sum()) < B * NB * KH  # skip really fired


def test_paged_attention_window_skips_left_of_window():
    """Sliding window: blocks wholly left of every query's window are
    skipped too (they are fully masked regardless of length)."""
    B, H, KH, D, bs, NB = 1, 2, 2, 16, 4, 8
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    vp = v_rows(jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32))
    tables = jnp.asarray(1 + rng.permutation(NB)[None], jnp.int32)
    lens = jnp.asarray([NB * bs], jnp.int32)           # full table...
    out, visits = paged_attention(q, kp, vp, tables, lens, window=6,
                                  use_kernel=True, return_visits=True)
    ref = paged_attention_reference(q, kp, vp, tables, lens, window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert int(np.asarray(visits)[0, 0]) == 2          # ...but 2 blocks seen


@pytest.mark.parametrize("B,C,H,KH,D,bs,NB", [
    (2, 4, 4, 2, 16, 8, 4),
    (3, 7, 4, 1, 32, 4, 8),
    (1, 16, 8, 8, 16, 16, 2),
    (3, 8, 4, 2, 128, 8, 40),          # several 16-page chunks a row
])
def test_paged_prefill_kernel_parity(B, C, H, KH, D, bs, NB):
    """Prefill-aware masking: C queries per sequence at absolute positions
    q_start + i, kernel vs gather reference, including partial chunks."""
    from repro.kernels.paged_attention import (
        paged_prefill_attention, paged_prefill_attention_reference)
    P = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    vp = v_rows(jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32))
    tables = jnp.asarray(1 + rng.permutation(B * NB).reshape(B, NB),
                         jnp.int32)
    starts = jnp.asarray(rng.integers(0, NB * bs - C + 1, size=(B,)),
                         jnp.int32)
    valid = rng.integers(1, C + 1, size=(B,))
    lens = starts + jnp.asarray(valid, jnp.int32)
    out = paged_prefill_attention(q, kp, vp, tables, starts, lens,
                                  use_kernel=True)
    ref = paged_prefill_attention_reference(q, kp, vp, tables, starts, lens)
    for b in range(B):                 # rows past valid are don't-care
        np.testing.assert_allclose(np.asarray(out)[b, :valid[b]],
                                   np.asarray(ref)[b, :valid[b]],
                                   rtol=1e-5, atol=1e-5)


def test_paged_attention_matches_contiguous_flash():
    """Paged ref with an identity table == dense attention over the prefix."""
    from repro.kernels.flash_attention import flash_attention_ref
    B, H, KH, D, bs, NB = 2, 4, 2, 16, 4, 4
    S = bs * NB
    k = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    q1 = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    # full causal attention of the LAST token == paged decode with len=S
    ref = flash_attention_ref(
        jnp.concatenate([jnp.zeros((B, S - 1, H, D), jnp.float32), q1], 1),
        k, v, causal=True)[:, -1]
    # pools: per-sequence contiguous layout packed into one pool
    kp = k.reshape(B * NB, bs, KH, D).transpose(0, 2, 1, 3)
    vp = v.reshape(B * NB, bs, KH * D)
    tables = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    lens = jnp.full((B,), S, jnp.int32)
    out = paged_attention(q1[:, 0], kp, vp, tables, lens, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------

FAMS = ["tinyllama-1.1b", "mamba2-1.3b", "hymba-1.5b"]


@pytest.mark.parametrize("name", FAMS)
def test_engine_matches_sequential_generate(name, key):
    cfg = reduced(get_config(name))
    m = build(cfg)
    params = m.init(key)
    B, P, GEN = 3, 9, 8
    prompt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0,
                                cfg.vocab_size)
    ref = np.asarray(generate(m, params, prompt, GEN))

    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4, max_len=32))
    for b in range(B):
        eng.add_request([int(t) for t in prompt[b]], max_new_tokens=GEN)
    out, stats = eng.run()
    for b in range(B):
        assert out[b].tokens == list(ref[b, P:]), name
    assert stats["decode_tokens"] == B * GEN


def test_engine_parity_under_preemption(key):
    """A pool too small for all requests forces eviction + re-prefill; the
    recomputed sequences must still match the sequential oracle exactly."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    B, P, GEN = 4, 9, 12
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                                cfg.vocab_size)
    ref = np.asarray(generate(m, params, prompt, GEN))
    eng = Engine(m, params, ServeConfig(max_seqs=4, block_size=4, max_len=64,
                                        num_blocks=13))
    for b in range(B):
        eng.add_request([int(t) for t in prompt[b]], max_new_tokens=GEN)
    out, _ = eng.run()
    assert sum(r.preemptions for r in out.values()) > 0   # pressure was real
    for b in range(B):
        assert out[b].tokens == list(ref[b, P:])


def test_engine_mixed_lengths_and_stop_tokens(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    eng = Engine(m, params, ServeConfig(max_seqs=3, block_size=4, max_len=48))
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12]]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    # per-request stop token: first greedy token of request 0
    ref = np.asarray(generate(
        m, params, jnp.asarray(prompts[0], jnp.int32)[None], 2))
    stop = int(ref[0, len(prompts[0])])
    rid_stop = eng.add_request(prompts[0], max_new_tokens=6,
                               stop_tokens=(stop,))
    out, _ = eng.run()
    assert set(out) == set(rids + [rid_stop])
    for rid, p in zip(rids, prompts):
        assert len(out[rid].tokens) == 6
        assert out[rid].prompt == tuple(p)
    assert out[rid_stop].tokens[-1] == stop and len(out[rid_stop].tokens) == 1


def test_engine_temperature_sampling_differs_and_is_valid(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    prompt = [3, 1, 4, 1, 5, 9]
    eng = Engine(m, params, ServeConfig(max_seqs=4, block_size=4, max_len=32,
                                        seed=11))
    r_greedy = eng.add_request(prompt, max_new_tokens=8, temperature=0.0)
    r_hot = [eng.add_request(prompt, max_new_tokens=8, temperature=5.0)
             for _ in range(3)]
    out, _ = eng.run()
    hot = [tuple(out[r].tokens) for r in r_hot]
    assert len(set(hot)) > 1                      # sampling actually samples
    for toks in hot:
        assert all(0 <= t < cfg.vocab_size for t in toks)
    assert len(out[r_greedy].tokens) == 8


def test_engine_moe_family(key):
    """MoE models serve through the same engine path."""
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    m = build(cfg)
    params = m.init(key)
    B, P, GEN = 2, 6, 5
    prompt = jax.random.randint(jax.random.PRNGKey(9), (B, P), 0,
                                cfg.vocab_size)
    ref = np.asarray(generate(m, params, prompt, GEN))
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4, max_len=16))
    for b in range(B):
        eng.add_request([int(t) for t in prompt[b]], max_new_tokens=GEN)
    out, _ = eng.run()
    for b in range(B):
        assert out[b].tokens == list(ref[b, P:])


@pytest.mark.parametrize("name", ["mamba2-1.3b", "hymba-1.5b"])
def test_engine_ssm_state_reset_on_slot_reuse(name, key):
    """Recurrent SSM/conv state must be zeroed when a slot is reused:
    serve a long request, then a short one in the SAME slot — its tokens
    must match a fresh sequential decode (regression: stale state)."""
    cfg = reduced(get_config(name))
    m = build(cfg)
    params = m.init(key)
    long_p = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(21), (12,), 0, cfg.vocab_size)]
    short_p = [5, 3]
    ref = np.asarray(generate(
        m, params, jnp.asarray(short_p, jnp.int32)[None], 6))
    eng = Engine(m, params, ServeConfig(max_seqs=1, block_size=4, max_len=32))
    eng.add_request(long_p, max_new_tokens=4)       # pollutes slot 0 state
    r2 = eng.add_request(short_p, max_new_tokens=6)
    out, _ = eng.run()
    assert out[r2].tokens == list(ref[0, len(short_p):]), name


def test_engine_run_twice_without_reset(key):
    """A second run() must report only its own drain: no stale finished
    requests, and stats computed from this run's tokens/steps."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4, max_len=16))
    r1 = eng.add_request([1, 2, 3], max_new_tokens=4)
    out1, stats1 = eng.run()
    r2 = eng.add_request([4, 5], max_new_tokens=4)
    out2, stats2 = eng.run()
    assert set(out1) == {r1} and set(out2) == {r2}
    assert stats1["decode_tokens"] == 4 and stats2["decode_tokens"] == 4
    assert stats2["prefill_tokens"] == 1           # 2-token prompt


def test_engine_reset_reuses_compiled_step(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    prompt = [4, 2, 8, 6]
    ref = np.asarray(generate(
        m, params, jnp.asarray(prompt, jnp.int32)[None], 5))
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4, max_len=16))
    for _ in range(2):
        eng.reset()
        rid = eng.add_request(prompt, max_new_tokens=5)
        out, _ = eng.run()
        assert out[rid].tokens == list(ref[0, len(prompt):])
        assert rid == 0                             # rid counter reset too


def test_engine_rejects_degenerate_requests(key):
    """Degenerate requests must fail fast at add_request with no engine
    state left behind — not hang admission or crash mid-run."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=16))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.add_request([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.add_request([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.add_request([1, 2], max_new_tokens=-3)
    with pytest.raises(ValueError, match="capacity"):
        eng.add_request(list(range(14)), max_new_tokens=4)  # 18 > 16
    # rejected requests left nothing behind: queue empty, rids unburned,
    # run() is a clean no-op
    assert not eng.scheduler.has_work
    assert not eng._submit_wall
    out, _ = eng.run()
    assert out == {}
    assert eng.add_request([1, 2], max_new_tokens=4) == 0


def test_engine_serves_pruned_model(key):
    """The SPA-pruned model runs the same engine path (paper's core claim)."""
    from repro.core.pruner import prune_model
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    pr = prune_model(m, params, 0.5)
    m2 = build(pr.cfg)
    prompt = [2, 7, 1, 8]
    ref = np.asarray(generate(
        m2, pr.params, jnp.asarray(prompt, jnp.int32)[None], 6))
    eng = Engine(m2, pr.params, ServeConfig(max_seqs=2, block_size=4,
                                            max_len=16))
    rid = eng.add_request(prompt, max_new_tokens=6)
    out, _ = eng.run()
    assert out[rid].tokens == list(ref[0, len(prompt):])


@pytest.mark.parametrize("name,path,cache_dtype", [
    ("tinyllama-1.1b", "reference", ""),
    ("tinyllama-1.1b", "kernel", ""),
    ("tinyllama-1.1b", "kernel", "int8"),
    ("hymba-1.5b", "reference", ""),
])
def test_engine_scan_and_unrolled_layers_agree(name, path, cache_dtype, key,
                                               monkeypatch):
    """The layer scan (pool in the loop carry, read and written at a traced
    layer index) and the unrolled loop (a python index into the same
    stacked pool) serve byte-identical greedy tokens, through chunked
    prefill and decode."""
    from repro.kernels.paged_attention import ops
    monkeypatch.setattr(ops, "_kernel_path",
                        lambda window: path == "kernel"
                        and isinstance(window, int))
    cfg = reduced(get_config(name))
    params = build(cfg).init(key)
    prompts = [[int(t) for t in jax.random.randint(
        jax.random.PRNGKey(7 + i), (5 + 3 * i,), 0, cfg.vocab_size)]
        for i in range(3)]
    tokens = []
    for use_scan in (True, False):
        eng = Engine(build(cfg.replace(use_scan=use_scan)), params,
                     ServeConfig(max_seqs=2, block_size=4, max_len=32,
                                 chunk_size=4, cache_dtype=cache_dtype))
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        out, _ = eng.run()
        tokens.append([out[r].tokens for r in rids])
    assert tokens[0] == tokens[1]
