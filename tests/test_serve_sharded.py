"""Sharded serving: the mesh-aware engine vs the 1-device oracle.

The engine with ``mesh=`` must produce byte-identical outputs to the
single-device engine across decode, chunked prefill, prefix caching/COW,
speculative decoding and recompute preemption (DESIGN.md §10).

These tests build a (data, model) mesh over the devices the running jax
process actually has, so they exercise *real* multi-device sharding when
the session is launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
multi-device lane does exactly that) and degrade to a 1x1 mesh — which
still traces the full sharded code path: NamedSharding'd jits, shard
rules, scheduler shard placement — on a plain single-device run.  A
subprocess test at forced 4 devices keeps multi-device parity covered in
single-device sessions too.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core.pruner import prune_model
from repro.launch.mesh import make_serve_mesh, serve_rules
from repro.models import build
from repro.serve import Engine, ServeConfig


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _mesh_shapes():
    """Mesh shapes the current process can actually build."""
    n = len(jax.devices())
    shapes = [(1, 1)]
    if n >= 2:
        shapes += [(2, 1), (1, 2)]
    if n >= 4:
        shapes += [(4, 1), (2, 2)]
    return shapes


def _models(key, pruned: bool):
    cfg = reduced(get_config("tinyllama-1.1b")).replace(
        n_kv_heads=2, n_heads=4)
    m = build(cfg)
    params = m.init(key)
    if pruned:
        pr = prune_model(m, params, 0.5, criterion="l1")
        m, params = build(pr.cfg), pr.params
    return m, params


def _prompts(cfg, n=6, base=5):
    rng = np.random.default_rng(3)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size, base + i % 3)]
            for i in range(n)]


def _serve(eng, prompts, gen=8):
    for p in prompts:
        eng.add_request(p, max_new_tokens=gen)
    out, stats = eng.run()
    return {r: out[r].tokens for r in out}, stats


@pytest.mark.parametrize("pruned", [False, True],
                         ids=["dense", "pruned50"])
def test_sharded_decode_matches_one_device(pruned, key):
    m, params = _models(key, pruned)
    prompts = _prompts(m.cfg)
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=32)
    ref, _ = _serve(Engine(m, params, sc), prompts)
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
        out, _ = _serve(eng, prompts)
        assert out == ref, (dm, eng.shard_mode)


def test_sharded_chunked_prefill_matches_one_device(key):
    m, params = _models(key, False)
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(0, m.cfg.vocab_size, 21 - i)]
               for i in range(4)]
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=40, chunk_size=8,
                     prefill_budget=16)
    ref, rstats = _serve(Engine(m, params, sc), prompts)
    assert rstats["prefill_chunks"] > 4          # chunking actually engaged
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
        out, _ = _serve(eng, prompts)
        assert out == ref, dm


def test_sharded_prefix_cow_and_allocator_invariants(key):
    """Shared-prefix batch under a sharded mesh: byte parity with the
    1-device engine, allocator conservation oracle after every step, and
    (single-shard meshes only) the block-saving the prefix cache buys."""
    m, params = _models(key, False)
    rng = np.random.default_rng(11)
    common = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 12)]
    prompts = [common + [int(t) for t in rng.integers(0, 100, 2 + i)]
               for i in range(4)]
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=40, chunk_size=8)
    ref_eng = Engine(m, params, sc)
    ref, _ = _serve(ref_eng, prompts)
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
        for p in prompts:
            eng.add_request(p, max_new_tokens=8)
        while eng.scheduler.has_work:
            eng.step()
            eng.cache_host.check()               # conservation + index oracle
        out = {s.req.rid: list(s.generated) for s in eng.scheduler.finished}
        assert out == ref, dm
        if eng.scheduler.data_shards == 1:
            # global prefix index: all 4 requests alias the common blocks
            assert eng.cache_host.allocator.total_allocated <= \
                ref_eng.cache_host.allocator.total_allocated


def test_sharded_preemption_matches_one_device(key):
    m, params = _models(key, False)
    prompts = _prompts(m.cfg, n=4, base=8)
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=64, num_blocks=13)
    ref, _ = _serve(Engine(m, params, sc), prompts, gen=12)
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
        out, _ = _serve(eng, prompts, gen=12)
        assert out == ref, dm
        preempts = sum(s.preemptions for s in eng.scheduler.finished)
        assert preempts > 0, dm                  # pressure was real


def test_sharded_spec_decode_matches_one_device(key):
    m, params = _models(key, False)
    pr = prune_model(m, params, 0.5, criterion="l1")
    dm_model, dp = build(pr.cfg), pr.params
    prompts = _prompts(m.cfg)
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=48, spec_k=4,
                     chunk_size=4)
    ref, _ = _serve(Engine(m, params, sc, draft_model=dm_model,
                           draft_params=dp), prompts)
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, draft_model=dm_model, draft_params=dp,
                     mesh=make_serve_mesh(*dm))
        assert eng.spec_active
        out, stats = _serve(eng, prompts)
        assert out == ref, dm
        assert stats["spec_cycles"] > 0


def test_sharded_quantized_cache_matches_one_device(key):
    """Quantized pools under a sharded mesh: the scale pools shard
    exactly like their KV pools (kv_heads tensor-parallel, per-device
    replicas in pure DP), so an int8 engine on any mesh must be
    byte-identical to the 1-device int8 engine — decode, chunked prefill,
    prefix/COW and all (DESIGN.md §11)."""
    m, params = _models(key, False)
    rng = np.random.default_rng(23)
    common = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 8)]
    prompts = [common + [int(t) for t in rng.integers(0, 100, 2 + i % 3)]
               for i in range(4)]
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=40, chunk_size=8,
                     cache_dtype="int8")
    ref, _ = _serve(Engine(m, params, sc), prompts)
    for dm in _mesh_shapes():
        eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
        assert eng.cache["k"].dtype == jnp.int8
        assert "k_scale" in eng.cache
        out, _ = _serve(eng, prompts)
        assert out == ref, (dm, eng.shard_mode)
        eng.cache_host.check()


def test_sharded_pallas_kernel_matches_one_device(key, monkeypatch):
    """Engines whose paged attention runs the kernel (the TPU choice,
    forced here on the CPU in interpret mode); under a sharded mesh the
    kernel call is shard_map'd per device (gspmd mode) and must stay
    byte-identical."""
    from repro.kernels.paged_attention import ops
    monkeypatch.setattr(ops, "_kernel_path",
                        lambda window: isinstance(window, int))
    mk, params = _models(key, False)
    prompts = _prompts(mk.cfg, n=4)
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=32)
    ref, _ = _serve(Engine(mk, params, sc), prompts)
    for dm in _mesh_shapes():
        if dm[1] == 1 and dm[0] > 1:
            continue          # dp mode runs the kernel per-shard already
        eng = Engine(mk, params, sc, mesh=make_serve_mesh(*dm))
        out, _ = _serve(eng, prompts)
        assert out == ref, dm


def test_kernel_shard_map_wrap_matches_unsharded():
    """The ops-level shard_map wrap itself: paged attention under an
    active serve mesh vs the plain kernel, decode + prefill entries."""
    from repro.distributed.sharding import use_rules
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_prefill_attention)

    rng = np.random.default_rng(0)
    B, H, KH, D, bs, NB = 4, 4, 2, 8, 4, 3
    P = B * NB + 1
    kp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, bs, D)), jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB))
    lens = jnp.asarray([5, 9, 12, 7], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    ref = paged_attention(q, kp, vp, tables, lens, use_kernel=True)

    mesh = make_serve_mesh(len(jax.devices()), 1)
    rules = serve_rules(get_config("tinyllama-1.1b").replace(
        n_kv_heads=KH, n_heads=H), mesh)
    with use_rules(rules, mesh=mesh):
        out = paged_attention(q, kp, vp, tables, lens, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    C = 4
    qc = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    starts = jnp.asarray([2, 4, 0, 3], jnp.int32)
    refc = paged_prefill_attention(qc, kp, vp, tables, starts, starts + C,
                                   use_kernel=True)
    with use_rules(rules, mesh=mesh):
        outc = paged_prefill_attention(qc, kp, vp, tables, starts,
                                       starts + C, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(outc), np.asarray(refc))


def test_scheduler_balances_slots_across_shards():
    """Admission must spread slots across data shards (jax chunks slot i
    to shard i // (max_seqs/dp)), so no device idles while another runs a
    full sub-batch."""
    from repro.serve.kv_cache import PagedCache
    from repro.serve.scheduler import FCFSScheduler, Request

    cache = PagedCache(max_seqs=8, num_blocks=64, block_size=4,
                       max_blocks_per_seq=8, data_shards=4)
    sched = FCFSScheduler(cache)
    for i in range(4):
        sched.add(Request(rid=i, prompt=(1, 2, 3), max_new_tokens=4))
    sched.admit()
    shards = sorted(sched.shard_of(s.slot) for s in sched.running)
    assert shards == [0, 1, 2, 3], shards
    # a fifth request lands on the least-loaded (=any) shard without
    # stacking: after 8 admissions every shard holds exactly 2
    for i in range(4, 8):
        sched.add(Request(rid=i, prompt=(1, 2, 3), max_new_tokens=4))
    sched.admit()
    from collections import Counter
    loads = Counter(sched.shard_of(s.slot) for s in sched.running)
    assert all(v == 2 for v in loads.values()), loads


def test_shard_local_prefix_index():
    """data_shards > 1: a block registered by one shard's slot must not
    be aliased into a slot on another shard (per-replica pools)."""
    from repro.serve.kv_cache import PagedCache

    cache = PagedCache(max_seqs=4, num_blocks=32, block_size=4,
                       max_blocks_per_seq=4, prefix_caching=True,
                       data_shards=2)
    toks = tuple(range(8))
    cache.ensure(0, 8)                    # slot 0 -> shard 0
    cache.commit(0, toks)
    # same shard (slot 1) aliases; other shard (slot 2) must not
    assert cache.assign_prefix(1, toks) == 8
    assert cache.assign_prefix(2, toks) == 0
    cache.check()


def _staged_cross_shard(m, params, mesh, migrate=True):
    """Staggered admission forcing a cross-shard prefix hit: request A
    registers a prefix on shard 0, a filler then occupies shard 0, and
    request B (same prefix) lands on shard 1.  Returns A's tokens, B's
    tokens and the engine for counter inspection."""
    rng = np.random.default_rng(17)
    common = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 12)]
    pa = common + [1, 2]
    pb = common + [3, 4]
    filler = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 6)]
    eng = Engine(m, params, ServeConfig(
        max_seqs=2, block_size=4, max_len=48, chunk_size=8,
        migrate_on_alias=migrate), mesh=mesh)
    ra = eng.add_request(pa, max_new_tokens=6)
    while eng.scheduler.has_work:               # A runs alone on slot 0
        eng.step()
    eng.add_request(filler, max_new_tokens=16)
    eng.step()                                  # filler takes slot 0
    rb = eng.add_request(pb, max_new_tokens=6)
    while eng.scheduler.has_work:
        eng.step()
        eng.cache_host.check()
    done = {s.req.rid: list(s.generated) for s in eng.scheduler.finished}
    return done[ra], done[rb], eng


def test_dp_cross_shard_prefix_hit_migrates(key):
    """Cross-shard prefix hits in DP mode alias via block migration
    (ROADMAP item 2 stage (a)): request B's replica re-homes A's prefix
    blocks with an intra-mesh copy instead of re-prefilling.  Outputs
    must match the 1-device oracle byte for byte, ``shard_moves``
    proves the copy happened, and the migrated path spends fewer
    prefill tokens than the legacy refusal path."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    m, params = _models(key, False)
    ref_a, ref_b, _ = _staged_cross_shard(m, params, None)
    out_a, out_b, eng = _staged_cross_shard(m, params,
                                            make_serve_mesh(2, 1))
    assert eng.shard_mode == "dp"
    assert out_a == ref_a
    assert out_b == ref_b
    assert eng._c["shard_moves"].value > 0, "expected a block migration"
    assert eng.cache_host.alias_refusals == 0


def test_dp_cross_shard_refusal_counter_without_migration(key):
    """migrate_on_alias=False keeps the PR-4 behavior: the cross-shard
    hit is refused (counted in ``serve/alias_refusals``), B re-prefills
    its prefix, and outputs still match the oracle."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    m, params = _models(key, False)
    ref_a, ref_b, _ = _staged_cross_shard(m, params, None)
    out_a, out_b, eng = _staged_cross_shard(
        m, params, make_serve_mesh(2, 1), migrate=False)
    assert eng.shard_mode == "dp"
    assert out_a == ref_a
    assert out_b == ref_b
    assert eng._c["shard_moves"].value == 0
    assert eng.cache_host.alias_refusals > 0
    assert eng._c["alias_refusals"].value > 0   # synced into run counters


def test_dp_cross_shard_migration_four_shards(key):
    """Stage-(a) acceptance on a real 4-shard data-parallel mesh:
    request A homes a prefix on shard 0, three fillers occupy shards
    0-2, request B lands on shard 3 and aliases A's blocks via
    migration — byte-identical to the 1-device oracle."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    m, params = _models(key, False)
    rng = np.random.default_rng(23)
    common = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 12)]
    pa = common + [1, 2]
    pb = common + [3, 4]
    fillers = [[int(t) for t in rng.integers(0, m.cfg.vocab_size, 6)]
               for _ in range(3)]

    def staged(mesh):
        eng = Engine(m, params, ServeConfig(
            max_seqs=4, block_size=4, max_len=48, chunk_size=8),
            mesh=mesh)
        ra = eng.add_request(pa, max_new_tokens=6)
        while eng.scheduler.has_work:           # A runs alone on shard 0
            eng.step()
        for f in fillers:                       # occupy shards 0..2
            eng.add_request(f, max_new_tokens=16)
        eng.step()
        rb = eng.add_request(pb, max_new_tokens=6)
        while eng.scheduler.has_work:
            eng.step()
            eng.cache_host.check()
        done = {s.req.rid: list(s.generated)
                for s in eng.scheduler.finished}
        return done[ra], done[rb], eng

    ref_a, ref_b, _ = staged(None)
    out_a, out_b, eng = staged(make_serve_mesh(4, 1))
    assert eng.shard_mode == "dp"
    assert eng.scheduler.data_shards == 4
    assert out_a == ref_a
    assert out_b == ref_b
    assert eng._c["shard_moves"].value > 0
    assert eng.cache_host.alias_refusals == 0


@pytest.mark.parametrize("dm", [(3, 1)])
def test_non_dividing_slot_count_falls_back(dm, key):
    """max_seqs not divisible by the data axis: the engine must still be
    correct (gspmd mode, replicated batch) rather than crash."""
    if len(jax.devices()) < 3:
        pytest.skip("needs 3 devices")
    m, params = _models(key, False)
    prompts = _prompts(m.cfg, n=4)
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=32)
    ref, _ = _serve(Engine(m, params, sc), prompts)
    eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
    assert eng.scheduler.data_shards in (1, 4)
    out, _ = _serve(eng, prompts)
    assert out == ref


def test_multi_device_parity_subprocess():
    """Real 4-device parity from a single-device session: run the decode
    sweep in a subprocess with forced host-platform devices."""
    if len(jax.devices()) >= 4:
        pytest.skip("session already multi-device; in-process tests cover")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(repo, "tests", "test_serve_sharded.py"),
         "-k", "decode_matches and dense"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


def test_cross_shard_migration_subprocess():
    """Stage-(a) acceptance from a single-device session: the cross-
    shard alias-migration tests (2-shard pair + the 4-shard variant) on
    forced host-platform devices."""
    if len(jax.devices()) >= 4:
        pytest.skip("session already multi-device; in-process tests cover")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(repo, "tests", "test_serve_sharded.py"),
         "-k", "cross_shard"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
