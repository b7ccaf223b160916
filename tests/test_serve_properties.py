"""Property-based tests for the serving allocator / scheduler invariants.

DepGraph-style lesson (arXiv:2301.12900): coupled-state invariants are
where silent corruption hides.  Here the coupled state is block ownership:
the allocator's refcounts, the per-slot block tables, the prefix index and
the scheduler's admit/grow/preempt/release transitions must stay mutually
consistent under *any* interleaving.  Four drivers exercise them (the
third is the real engine under recoverable fault schedules; the fourth
migrates sequences between two real engines over the
``export_slot``/``import_slot`` transport — strategy-chosen handoff
times against alloc-hold and sync-error faults, with a conservation
oracle spanning both engines).  The first two:

  1. a raw ``BlockAllocator`` state machine (random
     alloc/incref/decref/free against a pure-python mirror — conservation,
     refcount bookkeeping, double-free detection);
  2. a full ``FCFSScheduler`` + ``PagedCache`` run with a fake engine loop
     (random small-vocab prompts so prefix hits, COW and eviction all
     fire; random chunk sizes/budgets; pools sized to force preemption;
     random speculative lookaheads so the K+1 reservation, partial
     acceptance and ``truncate`` rollback interleave with everything
     else — including rollback into COW-shared prefix blocks).

``BlockAllocator.check()`` / ``PagedCache.check()`` run as the oracle
after every operation.  The hypothesis variants explore the same drivers
from minimized counterexamples; the seeded fallback keeps the properties
exercised where hypothesis isn't installed (it is optional, see
requirements.txt).

The scheduler driver additionally carries a *device-pool shadow* for
quantized caches (DESIGN.md §11): per-block write stamps for the KV
bytes and their dequant scales.  The engine writes both through one
``_scatter_kv`` and COWs both through one ``_cow_impl``; the host moves
blocks purely by index, so scale blocks must obey exactly the KV blocks'
conservation/COW/truncate oracle — the shadow replays every block
movement the plan exposes and asserts the two pools can never disagree
about a block's contents, and that every COW pair is scale-safe (dst
freshly allocated sole-owner, src still holding valid bytes+scales).
"""
import os
import random

import pytest

from repro.serve import (FCFSScheduler, Fault, FaultInjector, OutOfBlocks,
                         PagedCache, Request)
from repro.serve.kv_cache import BlockAllocator

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

# CI's nightly-style lane raises the search budget (e.g. 200) without a
# test-code change; the default keeps local runs fast
_MAX_EX = int(os.environ.get("REPRO_HYPOTHESIS_MAX_EXAMPLES", "40"))


# ---------------------------------------------------------------------------
# Driver 1: allocator state machine vs a pure-python mirror
# ---------------------------------------------------------------------------

def drive_allocator(seed: int, steps: int = 300) -> None:
    rng = random.Random(seed)
    num_blocks = rng.randint(2, 24)
    evicted = []
    a = BlockAllocator(num_blocks, on_evict=evicted.append)
    refs: dict[int, int] = {}          # mirror of live refcounts
    cached: set[int] = set()

    for _ in range(steps):
        op = rng.choice(["alloc", "incref", "decref", "free", "bad_free"])
        if op == "alloc":
            n = rng.randint(1, 3)
            if n > len(a._free) + len(cached):
                with pytest.raises(OutOfBlocks):
                    a.alloc(n)
            else:
                got = a.alloc(n)
                assert len(set(got)) == n and 0 not in got
                for b in got:
                    assert b not in refs
                    refs[b] = 1
                cached -= set(evicted)
                evicted.clear()
        elif op == "incref" and (refs or cached):
            b = rng.choice(sorted(refs) + sorted(cached))
            a.incref(b)
            refs[b] = refs.get(b, 0) + 1
            cached.discard(b)
        elif op == "decref" and refs:
            b = rng.choice(sorted(refs))
            retain = rng.random() < 0.5
            freed = a.decref(b, retain=retain)
            refs[b] -= 1
            assert freed == (refs[b] == 0)
            if refs[b] == 0:
                del refs[b]
                if retain:
                    cached.add(b)
        elif op == "free":
            singles = [b for b, r in refs.items() if r == 1]
            if singles:
                b = rng.choice(sorted(singles))
                a.free([b])
                del refs[b]
        elif op == "bad_free":
            dead = [b for b in range(1, num_blocks)
                    if b not in refs and rng.random() < 0.5]
            if dead:
                with pytest.raises(ValueError):   # never double-free
                    a.free([dead[0]])
        a.check()
        assert a._ref == refs                      # refcounts exact
        assert set(a._cached) == cached
        assert a.num_free + a.num_live + a.num_cached == num_blocks - 1


# ---------------------------------------------------------------------------
# Driver 2: scheduler + cache under a fake engine loop
# ---------------------------------------------------------------------------

def drive_scheduler(seed: int, rounds: int = 120,
                    fault_plan: tuple = ()) -> None:
    """``fault_plan`` folds fault injection into the property driver:
    each ``(round, fraction, hold_rounds)`` entry sequesters that
    fraction of the currently-free blocks via the allocator's held
    state at the given round, releasing them ``hold_rounds`` rounds
    later — so the conservation oracle (which now includes held) and
    every grow/preempt path are exercised under induced exhaustion.
    A plan-time OutOfBlocks while holds are live hands them back (the
    engine's ``_unjam``) instead of ending the run."""
    rng = random.Random(seed)
    bs = rng.choice([2, 4])
    max_seqs = rng.randint(1, 4)
    nb_per_seq = rng.randint(3, 6)
    # undersized pools force grow/preempt; oversized ones exercise caching
    usable = rng.randint(nb_per_seq, max_seqs * nb_per_seq)
    cache = PagedCache(max_seqs=max_seqs, num_blocks=usable + 1,
                       block_size=bs, max_blocks_per_seq=nb_per_seq,
                       prefix_caching=rng.random() < 0.7)
    sched = FCFSScheduler(cache)
    chunk = rng.choice([0, 1, 2, 3, 5])
    budget = rng.choice([0, 1, 4])
    spec_k = rng.choice([0, 0, 2, 3])
    rid = 0

    # quantized-pool shadow: (kv bytes, scales) write stamps per block
    kv_stamp: dict[int, int] = {}
    sc_stamp: dict[int, int] = {}
    clock = [0]

    # injected allocator-pressure holds: (release_round, blocks)
    held: list[tuple[int, list[int]]] = []
    plan_at: dict[int, list[tuple[float, int]]] = {}
    for r, frac, hold_rounds in fault_plan:
        plan_at.setdefault(r % rounds, []).append((frac, hold_rounds))

    def write_blocks(slot, lo, hi):
        """Simulate _scatter_kv over token positions [lo, hi): the engine
        stamps a block's KV bytes and its scales in the same scatter."""
        clock[0] += 1
        for bi in range(lo // bs, (max(hi, lo + 1) - 1) // bs + 1):
            b = int(cache.tables[slot][bi])
            assert b != 0                  # never writes the null block
            kv_stamp[b] = clock[0]
            sc_stamp[b] = clock[0]

    for rnd in range(rounds):
        for exp, blocks in list(held):     # expire due holds first
            if rnd >= exp:
                cache.allocator.unhold(blocks)
                held.remove((exp, blocks))
                cache.check()
        for frac, hold_rounds in plan_at.get(rnd, ()):
            n = min(int(cache.allocator.num_free * frac) or 1,
                    cache.allocator.num_free)
            if n > 0:
                held.append((rnd + hold_rounds, cache.allocator.hold(n)))
                cache.check()
        if rng.random() < 0.4:
            # vocab {0,1} prompts: prefix collisions (and so sharing, COW
            # and eviction) are the common case, not the rare one
            plen = rng.randint(1, max(1, cache.max_len - 2))
            gen = rng.randint(1, cache.max_len - plen)
            if cache.blocks_for(plen + gen) <= usable:
                sched.add(Request(rid, tuple(rng.randint(0, 1)
                                             for _ in range(plen)),
                                  max_new_tokens=gen))
                rid += 1
        try:
            plan = sched.plan_step(chunk, budget, spec_k)
        except OutOfBlocks:
            if held:
                # injected exhaustion: hand the holds back (the engine's
                # _unjam) and keep driving
                for _, blocks in held:
                    cache.allocator.unhold(blocks)
                held.clear()
                cache.check()
                continue
            # a lone request legitimately outgrew an undersized pool
            cache.check()
            return
        cache.check()
        for src, dst in plan.copies:
            # scale-safety of COW: the target is a freshly-allocated
            # sole-owner block, and the source still holds valid
            # bytes+scales (live for a donor, never already freed)
            assert cache.allocator.ref(dst) == 1
            assert cache.allocator.ref(src) >= 1 \
                or src in cache.allocator._cached
            if src in kv_stamp:            # _cow_impl copies all 4 pools
                kv_stamp[dst] = kv_stamp[src]
                sc_stamp[dst] = sc_stamp[src]
        for s, n in plan.prefill:
            assert 0 < n <= max(chunk, 1)
            covered = s.num_cached + n == s.seq_len
            write_blocks(s.slot, s.num_cached, s.num_cached + n)
            s.num_cached += n
            if covered:
                s.generated.append(rng.randint(0, 1))
        spec_rids = {s.req.rid for s in plan.spec}
        for s in plan.decode:
            was_last = s.num_cached == s.seq_len - 1
            if s.req.rid in spec_rids:
                # speculative cycle: partial acceptance appends 1..K
                # tokens, then rollback releases the rejected suffix —
                # possibly rolling into a COW-shared or indexed block
                assert was_last
                # the engine writes the base token + K drafts up front,
                # then truncates the rejected suffix — the shadow stamps
                # every reserved block the device pass would touch
                hi = min(s.num_cached + spec_k + 1,
                         len(cache.owned(s.slot)) * bs)
                write_blocks(s.slot, s.num_cached, hi)
                a = rng.randint(0, spec_k)
                emit = a + (1 if a < spec_k else 0)
                for _ in range(emit):
                    s.num_cached += 1
                    s.generated.append(rng.randint(0, 1))
                    if s.done:
                        break
                cache.truncate(s.slot, s.num_cached)
                cache.check()
                continue
            write_blocks(s.slot, s.num_cached, s.num_cached + 1)
            s.num_cached += 1
            if was_last:
                s.generated.append(rng.randint(0, 1))
                if rng.random() < 0.1:
                    s.stopped = True
        sched.commit_progress()
        cache.check()
        # conservation, stated exactly as the issue demands (held blocks
        # are first-class state, not a leak):
        alloc = cache.allocator
        assert alloc.num_free + alloc.num_live + alloc.num_cached \
            + alloc.num_held == usable
        # scale lockstep: no host transition (alias, COW, truncate,
        # release, eviction) can make the scale pool disagree with the
        # KV pool about any block — addressing is shared, so the stamps
        # can only diverge if a path moved KV without its scales
        assert kv_stamp == sc_stamp
    # drain what's left so release paths run too; holds must all expire
    for _, blocks in held:
        cache.allocator.unhold(blocks)
    for s in list(sched.running):
        s.stopped = True
    sched.retire_finished()
    cache.check()
    assert cache.allocator.num_held == 0


# ---------------------------------------------------------------------------
# Driver 3: the real engine under a strategy-chosen fault schedule
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_ref(key):
    """A real (reduced) engine plus its cached fault-free reference —
    module-scoped so hypothesis examples reuse one compile."""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build
    from repro.serve import Engine, ServeConfig
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    eng = Engine(m, m.init(key),
                 ServeConfig(max_seqs=3, block_size=4, num_blocks=24,
                             max_len=48, chunk_size=8,
                             audit_level="full"))
    prng = np.random.default_rng(53)
    prompts = [[int(t) for t in prng.integers(0, cfg.vocab_size,
                                              10 - (i % 3))]
               for i in range(4)]
    return eng, prompts, _drive_engine(eng, prompts)


def _drive_engine(eng, prompts, faults=None, gen=6):
    """Fault-free and faulted runs share one drive; the crash-safety
    postconditions (bounded steps, zero live/held, conservation audit)
    are asserted on every example."""
    eng.reset()
    eng.faults = faults
    for p in prompts:
        eng.add_request(p, max_new_tokens=gen)
    n = 0
    while eng.scheduler.has_work or eng.pending_step:
        eng.step()
        n += 1
        assert n <= 400, "no progress: deadlock under fault schedule"
    eng.faults = None
    a = eng.cache_host.allocator
    assert a.num_live == 0 and a.num_held == 0
    eng.cache_host.check()
    return {r: (tuple(rec.tokens), rec.finish_reason)
            for r, rec in eng.pop_finished().items()}


# ---------------------------------------------------------------------------
# Driver 4: two-engine block migration under strategy-chosen faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_pair(key):
    """Two identical reduced engines plus the cached fault-free
    single-engine reference — the export_slot/import_slot migration
    transport must be invisible at the token level no matter when the
    handoff lands or what faults surround it."""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build
    from repro.serve import Engine, ServeConfig
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    params = m.init(key)
    sc = ServeConfig(max_seqs=3, block_size=4, num_blocks=24, max_len=48,
                     chunk_size=8, audit_level="full")
    e1, e2 = Engine(m, params, sc), Engine(m, params, sc)
    prng = np.random.default_rng(59)
    prompts = [[int(t) for t in prng.integers(0, cfg.vocab_size,
                                              10 - (i % 3))]
               for i in range(4)]
    return e1, e2, prompts, _drive_engine(e1, prompts)


def _drive_migration(e1, e2, prompts, migrate_at, faults1=None,
                     faults2=None, gen=6):
    """Drive two engines with decode-phase requests migrating e1 -> e2
    at the given steps (the cluster's disaggregation handoff, §16).
    The conservation oracle spans both engines: each allocator balances
    every round, and at the end every submitted request has finished on
    exactly one engine — migration can neither lose nor duplicate a
    sequence.  Returns ({submission index: (tokens, reason)}, #migrated)."""
    e1.reset()
    e2.reset()
    e2._rid = 1 << 20              # disjoint rid namespaces (cluster-style)
    e1.faults, e2.faults = faults1, faults2
    idx = {}                       # rid (either engine) -> submission index
    for i, p in enumerate(prompts):
        idx[e1.add_request(p, max_new_tokens=gen)] = i
    totals = {e: e.cache_host.allocator.num_free for e in (e1, e2)}
    migrate_at = set(migrate_at)
    migrated = 0
    n = 0
    while any(e.scheduler.has_work or e.pending_step for e in (e1, e2)):
        if n in migrate_at:
            for s in list(e1.scheduler.running):
                if s.phase == "decode" and not s.done:
                    rid = s.req.rid
                    h = e1.export_request(rid, remove=True)
                    idx[e2.adopt(h)] = idx.pop(rid)
                    migrated += 1
        for e in (e1, e2):
            if e.scheduler.has_work or e.pending_step:
                e.step()
        for e in (e1, e2):
            e.cache_host.check()
            a = e.cache_host.allocator
            assert a.num_free + a.num_live + a.num_cached \
                + a.num_held == totals[e], "cross-engine conservation"
        n += 1
        assert n <= 500, "no progress under migration schedule"
    e1.faults = e2.faults = None
    out = {}
    for e in (e1, e2):
        a = e.cache_host.allocator
        assert a.num_live == 0 and a.num_held == 0
        e.cache_host.check()
        for rid, rec in e.pop_finished().items():
            i = idx.pop(rid)
            assert i not in out, "request finished on both engines"
            out[i] = (tuple(rec.tokens), rec.finish_reason)
    assert not idx, "requests lost in migration"
    return out, migrated


def test_migration_hands_back_holds_of_an_emptied_engine(engine_pair):
    """A pool hold injected at step 5 for 3 steps, then every request
    migrates away at step 8: the emptied engine is never stepped again,
    so exporting its last request must hand the hold back, or it reads
    as leaked blocks (a counterexample hypothesis found)."""
    e1, e2, prompts, ref = engine_pair
    hold = Fault(kind="alloc_hold", step=5, hold_steps=3)
    out, migrated = _drive_migration(
        e1, e2, prompts, [8], faults1=FaultInjector([hold], seed=0),
        faults2=FaultInjector([], seed=1))
    assert migrated and out == ref


# ---------------------------------------------------------------------------
# hypothesis variants (preferred when available)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    # a strategy-chosen fault schedule: each entry holds a fraction of
    # the free pool at a round and releases it a few rounds later
    _fault_plans = st.lists(
        st.tuples(st.integers(0, 119),        # round the hold lands
                  st.floats(0.1, 1.0),        # fraction of free to hold
                  st.integers(1, 5)),         # rounds until release
        max_size=4)

    @given(st.integers(0, 2**16))
    @settings(max_examples=_MAX_EX, deadline=None)
    def test_allocator_state_machine_hypothesis(seed):
        drive_allocator(seed)

    @given(st.integers(0, 2**16), _fault_plans)
    @settings(max_examples=max(int(_MAX_EX * 0.75), 1), deadline=None)
    def test_scheduler_conservation_hypothesis(seed, fault_plan):
        drive_scheduler(seed, fault_plan=tuple(fault_plan))

    # -- engine-level: hypothesis chooses a *recoverable* fault schedule
    # (allocator pressure, transient sync errors, straggler steps) and
    # the run must stay byte-identical to the cached fault-free
    # reference.  Only recoverable shapes are drawn: sync_error steps
    # are unique (a lone failure is inside the engine's retry budget),
    # and slow_step has no deadline to trip.
    @st.composite
    def _recoverable_schedules(draw):
        faults = [Fault("alloc_hold", step=s, blocks=draw(
                      st.integers(0, 10)),
                      hold_steps=draw(st.integers(1, 3)))
                  for s in draw(st.lists(st.integers(0, 20),
                                         max_size=3))]
        faults += [Fault("sync_error", step=s)
                   for s in draw(st.lists(st.integers(0, 20),
                                          unique=True, max_size=2))]
        faults += [Fault("slow_step", step=s, delay_s=0.001)
                   for s in draw(st.lists(st.integers(0, 20),
                                          max_size=2))]
        return faults

    @given(_recoverable_schedules())
    @settings(max_examples=max(_MAX_EX // 8, 3), deadline=None)
    def test_engine_byte_identical_under_fault_schedule(engine_ref,
                                                        schedule):
        eng, prompts, ref = engine_ref
        fi = FaultInjector(schedule, seed=0)
        assert _drive_engine(eng, prompts, faults=fi) == ref

    # -- two-engine migration: hypothesis chooses WHEN sequences hand
    # off (including mid-alloc-hold and around sync errors on either
    # side) and the whole run must stay byte-identical to the cached
    # fault-free single-engine reference
    @given(st.lists(st.integers(0, 30), min_size=1, max_size=3),
           _recoverable_schedules(), _recoverable_schedules())
    @settings(max_examples=max(_MAX_EX // 8, 3), deadline=None)
    def test_migration_byte_identical_under_fault_schedule(
            engine_pair, migrate_at, sched1, sched2):
        e1, e2, prompts, ref = engine_pair
        out, _ = _drive_migration(
            e1, e2, prompts, migrate_at,
            faults1=FaultInjector(sched1, seed=0),
            faults2=FaultInjector(sched2, seed=1))
        assert out == ref


# ---------------------------------------------------------------------------
# seeded fallback (always runs; hypothesis is an optional dependency)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(25))
def test_allocator_state_machine(seed):
    drive_allocator(seed * 7919)


@pytest.mark.parametrize("seed", range(20))
def test_scheduler_conservation(seed):
    drive_scheduler(seed * 104729)


@pytest.mark.parametrize("seed", range(10))
def test_scheduler_conservation_under_faults(seed):
    """Fixed fault plans keep the held-block conservation oracle and
    the unjam path exercised where hypothesis isn't installed."""
    drive_scheduler(seed * 31337,
                    fault_plan=((5, 0.5, 3), (40, 1.0, 2), (80, 0.3, 4)))


def test_engine_fixed_fault_schedule_byte_identical(engine_ref):
    """Seeded fallback for the engine-level property: one schedule
    mixing all three recoverable kinds stays byte-identical."""
    eng, prompts, ref = engine_ref
    fi = FaultInjector([Fault("alloc_hold", step=2, blocks=8,
                              hold_steps=2),
                        Fault("sync_error", step=4),
                        Fault("slow_step", step=6, delay_s=0.001)],
                       seed=0)
    assert _drive_engine(eng, prompts, faults=fi) == ref
    assert sum(fi.fired.values()) >= 2


def test_migration_fixed_fault_schedule_byte_identical(engine_pair):
    """Seeded fallback for the two-engine migration property: handoffs
    land mid-alloc-hold on the adopter and bracket a sync error on the
    exporter, and the run stays byte-identical with every sequence
    accounted for exactly once."""
    eng1, eng2, prompts, ref = engine_pair
    fi1 = FaultInjector([Fault("sync_error", step=4)], seed=0)
    fi2 = FaultInjector([Fault("alloc_hold", step=1, blocks=12,
                               hold_steps=3),
                         Fault("sync_error", step=5)], seed=1)
    out, migrated = _drive_migration(eng1, eng2, prompts,
                                     migrate_at=(2, 4, 7),
                                     faults1=fi1, faults2=fi2)
    assert out == ref
    assert migrated > 0, "schedule never exercised a migration"


def test_cached_blocks_are_reclaimed_lru_first():
    a = BlockAllocator(5)
    got = a.alloc(4)
    order = []
    a.on_evict = order.append
    for b in got:
        a.decref(b, retain=True)      # all cached, LRU = got[0]
    assert a.num_cached == 4 and a.num_free == 0
    fresh = a.alloc(2)                 # must evict the two oldest
    assert order == got[:2]
    assert set(fresh) == set(got[:2])
    a.check()


def test_truncate_rollback_into_cow_shared_block():
    """Speculative rollback landing inside a block another slot still
    references: the surplus blocks decref (not hard-free), the shared
    boundary block keeps its prefix-index entry (donors hold the
    content), and conservation holds throughout."""
    c = PagedCache(max_seqs=2, num_blocks=8, block_size=2,
                   max_blocks_per_seq=4, prefix_caching=True)
    toks = (1, 2, 3, 4)
    c.ensure(0, 4)
    c.commit(0, toks)                  # slot 0 registers two full blocks
    assert c.assign_prefix(1, toks) == 4          # slot 1 aliases both
    shared = c.owned(1)
    assert c.allocator.ref(shared[0]) == 2
    c.ensure(1, 7)                     # speculative growth: +2 blocks
    c.check()
    # rollback to 3 tokens: cursor lands inside shared block 1
    c.truncate(1, 3)
    c.check()
    assert c.owned(1) == shared[:2]    # surplus released, aliases kept
    assert c.allocator.ref(shared[1]) == 2
    # the entry survives: slot 0 still holds that content
    assert shared[1] in c._hash_of
    # and a third request can still prefix-match through it
    c.release(1)
    c.check()
    assert c.assign_prefix(1, toks) == 4


def test_truncate_unregisters_sole_owner_boundary_block():
    """Rolling back into a registered block this slot alone owns drops
    the index entry — the block's content is about to be rewritten, and
    a stale entry would hand later requests wrong KV."""
    c = PagedCache(max_seqs=1, num_blocks=6, block_size=2,
                   max_blocks_per_seq=4, prefix_caching=True)
    toks = (1, 2, 3, 4, 5, 6)
    c.ensure(0, 6)
    c.commit(0, toks)                  # three registered full blocks
    b = c.owned(0)
    c.truncate(0, 3)                   # cursor inside block 1 (ref == 1)
    c.check()
    assert c.owned(0) == b[:2]
    assert b[0] in c._hash_of          # intact full block keeps its entry
    assert b[1] not in c._hash_of      # boundary entry dropped
    assert b[2] in c._hash_of          # released block cached via index
    assert len(c._chain[0]) == 1
    # a new request can only match the still-valid first block
    c.release(0)
    assert c.assign_prefix(0, toks) == 2


def test_prefix_index_drops_entries_on_eviction():
    c = PagedCache(max_seqs=2, num_blocks=4, block_size=2,
                   max_blocks_per_seq=3, prefix_caching=True)
    toks = (1, 2, 3, 4)
    assert c.assign_prefix(0, toks) == 0        # empty index: no match
    c.ensure(0, 4)
    c.commit(0, toks)                            # two full blocks registered
    c.release(0)                                 # -> cached, still indexed
    assert c.assign_prefix(0, toks) == 4         # round-trips via the index
    c.release(0)
    c.ensure(1, 6)                               # forces eviction of both
    c.check()
    assert c.assign_prefix(0, toks) == 0         # index entries were dropped
    c.check()
