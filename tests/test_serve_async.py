"""Async double-buffered engine stepping (DESIGN.md §13): sync-vs-async
byte parity across engine configurations, the serving surface that rides
on it (streaming callbacks, cancellation, deadlines, backpressure), and
property-style drivers exercising predicted-state rollback."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.launch.serve import generate
from repro.models import build
from repro.serve import Engine, EngineOverloaded, ServeConfig

rng = np.random.default_rng(13)


@pytest.fixture(scope="module")
def mp(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    return m, m.init(key)


def _prompts(cfg, n=5, base=10):
    return [[int(t) for t in rng.integers(0, cfg.vocab_size,
                                          base - (i % 4))]
            for i in range(n)]


def _serve(eng, prompts, use_async, gen=8, check=True, **kw):
    """Drive one run in the chosen mode; returns {rid: (tokens, reason)}.

    Manual driving (not run()) so ONE engine — one compiled program —
    serves both sides of every A/B; the async drain condition includes
    ``pending_step`` for the last in-flight reconcile."""
    eng.reset()
    for p in prompts:
        eng.add_request(p, max_new_tokens=gen, **kw)
    step = eng.step_async if use_async else eng.step
    while eng.scheduler.has_work or eng.pending_step:
        step()
        if check:
            eng.cache_host.check()
    return {r: (tuple(rec.tokens), rec.finish_reason)
            for r, rec in eng.pop_finished().items()}


# ---------------------------------------------------------------------------
# Byte parity: async == sync at temperature 0
# ---------------------------------------------------------------------------

def test_async_parity_dense(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=3, block_size=4,
                                        max_len=32, chunk_size=4))
    ps = _prompts(m.cfg)
    ref = _serve(eng, ps, use_async=False)
    out = _serve(eng, ps, use_async=True)
    assert out == ref
    assert all(len(t) == 8 for t, _ in out.values())


def test_async_parity_matches_sequential_oracle(mp):
    """Not just self-consistent: the async pipeline must match the
    contiguous-cache sequential decode token-for-token."""
    m, params = mp
    B, P, GEN = 3, 9, 8
    prompt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0,
                                m.cfg.vocab_size)
    ref = np.asarray(generate(m, params, prompt, GEN))
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32, async_step=True))
    for b in range(B):
        eng.add_request([int(t) for t in prompt[b]], max_new_tokens=GEN)
    out, stats = eng.run()                 # run() drives step_async here
    for b in range(B):
        assert out[b].tokens == list(ref[b, P:])
    assert stats["decode_tokens"] == B * GEN


def test_async_parity_stop_tokens(mp):
    """A stop token lands while the *next* predicted step is already in
    flight: reconcile must cancel the in-flight row and truncate the
    speculatively grown blocks (rollback), with byte-equal output."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=3, block_size=4,
                                        max_len=48, chunk_size=4))
    ps = _prompts(m.cfg, n=4)
    base = _serve(eng, ps, use_async=False, gen=10)
    # stop on a token each request actually emits mid-stream
    stops = tuple({toks[3] for toks, _ in base.values()})
    ref = _serve(eng, ps, use_async=False, gen=10, stop_tokens=stops)
    out = _serve(eng, ps, use_async=True, gen=10, stop_tokens=stops)
    assert out == ref
    assert any(reason == "stop" for _, reason in out.values())


def test_async_parity_under_preemption(mp):
    """A pool too small for every request forces preemption; the overlap
    gate must prove headroom or fall back to lockstep — outputs stay
    byte-equal and someone was actually preempted."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=4, block_size=4,
                                        max_len=64, num_blocks=13))
    ps = _prompts(m.cfg, n=4, base=9)
    eng.reset()
    for p in ps:
        eng.add_request(p, max_new_tokens=12)
    ref, _ = eng.run()
    assert sum(r.preemptions for r in ref.values()) > 0
    out = _serve(eng, ps, use_async=True, gen=12)
    assert out == {r: (tuple(rec.tokens), rec.finish_reason)
                   for r, rec in ref.items()}


def test_async_parity_prefill_budget_and_token_by_token(mp):
    m, params = mp
    for chunk, budget in ((4, 6), (0, 0)):
        eng = Engine(m, params, ServeConfig(
            max_seqs=3, block_size=4, max_len=32, chunk_size=chunk,
            prefill_budget=budget))
        ps = _prompts(m.cfg)
        assert _serve(eng, ps, True) == _serve(eng, ps, False)


def test_async_parity_quantized(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32, chunk_size=4,
                                        cache_dtype="int8"))
    ps = _prompts(m.cfg, n=3)
    assert _serve(eng, ps, True) == _serve(eng, ps, False)


def test_async_spec_decode_falls_back_to_lockstep(mp, key):
    """Speculative decode's growth is value-dependent (acceptance counts
    ride the fetch), so async driving must lockstep — and still match
    sync byte-for-byte with stop tokens in play."""
    from repro.core.pruner import prune_model
    m, params = mp
    dr = prune_model(m, params, 0.5, criterion="l1")
    dm, dp = build(dr.cfg), dr.params
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=48, spec_k=3),
                 draft_model=dm, draft_params=dp)
    assert eng.spec_active
    ps = _prompts(m.cfg, n=3)
    ref = _serve(eng, ps, use_async=False, gen=10)
    stops = tuple({toks[4] for toks, _ in ref.values()})
    a = _serve(eng, ps, use_async=False, gen=10, stop_tokens=stops)
    b = _serve(eng, ps, use_async=True, gen=10, stop_tokens=stops)
    assert a == b


def test_async_overlap_engages_and_is_observable(mp):
    """Steady decode with pool headroom must actually take the overlap
    path (phase/overlap recorded), and the sync phase must be timed."""
    from repro.obs import Telemetry
    m, params = mp
    tel = Telemetry(enabled=True)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32, chunk_size=4),
                 telemetry=tel)
    _serve(eng, _prompts(m.cfg, n=2), use_async=True, check=False)
    hists = tel.registry.histograms
    assert hists["phase/overlap"].count > 0
    assert hists["phase/step"].count >= hists["phase/overlap"].count
    assert hists["phase/sync"].count > 0


def test_mixed_step_and_step_async_driving(mp):
    """Interleaving the two drivers is safe: step() reconciles any
    in-flight async step before planning."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    ps = _prompts(m.cfg, n=3)
    ref = _serve(eng, ps, use_async=False)
    eng.reset()
    for p in ps:
        eng.add_request(p, max_new_tokens=8)
    i = 0
    while eng.scheduler.has_work or eng.pending_step:
        (eng.step_async if i % 3 else eng.step)()
        i += 1
    out = {r: (tuple(rec.tokens), rec.finish_reason)
           for r, rec in eng.pop_finished().items()}
    assert out == ref


# ---------------------------------------------------------------------------
# Sharded parity (multi-device only; subprocess runner below forces 4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dm", [(2, 1), (2, 2)])
def test_async_parity_sharded(dm, mp):
    if len(jax.devices()) < dm[0] * dm[1]:
        pytest.skip(f"needs {dm[0] * dm[1]} devices")
    from repro.launch.mesh import make_serve_mesh
    m, params = mp
    sc = ServeConfig(max_seqs=4, block_size=4, max_len=32, chunk_size=4)
    ps = _prompts(m.cfg, n=6)
    ref = _serve(Engine(m, params, sc), ps, use_async=False)
    eng = Engine(m, params, sc, mesh=make_serve_mesh(*dm))
    assert _serve(eng, ps, use_async=False, check=False) == ref
    assert _serve(eng, ps, use_async=True, check=False) == ref


def test_async_sharded_parity_subprocess():
    """Real 4-device async parity from a single-device session."""
    if len(jax.devices()) >= 4:
        pytest.skip("session already multi-device; in-process test covers")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(repo, "tests", "test_serve_async.py"),
         "-k", "parity_sharded"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


# ---------------------------------------------------------------------------
# Streaming, cancellation, deadlines, backpressure
# ---------------------------------------------------------------------------

def test_streaming_callback_order_async(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32, async_step=True))
    seen = []
    prompt = _prompts(m.cfg, n=1)[0]
    rid = eng.add_request(prompt, max_new_tokens=7,
                          on_token=lambda t, d: seen.append((t, d)))
    out, _ = eng.run()
    assert [t for t, _ in seen] == out[rid].tokens
    assert [d for _, d in seen] == [False] * 6 + [True]


def test_stream_iterator(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32, async_step=True))
    prompt = _prompts(m.cfg, n=1)[0]
    ref = _serve(eng, [prompt], use_async=False, gen=6)
    toks = list(eng.stream(prompt, max_new_tokens=6))
    assert tuple(toks) == next(iter(ref.values()))[0]


def test_cancel_running_mid_flight(mp):
    """Cancel while the request's next sample is literally in flight:
    the in-flight token is discarded, blocks are truncated, and the
    partial output is a prefix of the uncancelled run."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=48))
    prompt = _prompts(m.cfg, n=1)[0]
    full = _serve(eng, [prompt], use_async=False, gen=12)
    full_toks = next(iter(full.values()))[0]

    eng.reset()
    fired = []
    rid = eng.add_request(prompt, max_new_tokens=12,
                          on_token=lambda t, d: fired.append((t, d)))
    for _ in range(6):
        eng.step_async()
    assert eng.cancel(rid)
    while eng.scheduler.has_work or eng.pending_step:
        eng.step_async()
    eng.cache_host.check()
    rec = eng.pop_finished()[rid]
    assert rec.finish_reason == "cancelled"
    assert 0 < len(rec.tokens) < 12
    assert tuple(rec.tokens) == full_toks[:len(rec.tokens)]
    assert fired[-1] == (None, True)       # tokenless finish notification


def test_cancel_waiting_before_admission(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=1, block_size=4,
                                        max_len=32))
    p1, p2 = _prompts(m.cfg, n=2)
    eng.add_request(p1, max_new_tokens=6)
    r2 = eng.add_request(p2, max_new_tokens=6)   # waits: one slot only
    assert eng.cancel(r2)
    assert not eng.cancel(r2)                    # already finished
    out, _ = eng.run()
    assert out[r2].tokens == [] and out[r2].finish_reason == "cancelled"


def test_cancel_is_idempotent_for_unknown_and_finished(mp):
    """cancel() is safe to call with anything: unknown rids, rids that
    already finished (naturally or by cancel), and repeats — all return
    False without touching engine state."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    assert not eng.cancel(0)                     # never submitted
    assert not eng.cancel(999)
    rid = eng.add_request(_prompts(m.cfg, n=1)[0], max_new_tokens=4)
    out, _ = eng.run()
    assert out[rid].finish_reason == "length"
    assert not eng.cancel(rid)                   # finished + retired
    assert not eng.cancel(rid)                   # still False on repeat
    eng.cache_host.check()


def test_cancel_during_prefill_chunk(mp):
    """Cancel a request whose prompt is mid-chunked-prefill: the
    remaining chunks never dispatch, its blocks free fully (conservation
    check), and other requests are unaffected."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=64, chunk_size=4))
    long_p = [int(t) for t in rng.integers(0, m.cfg.vocab_size, 24)]
    short_p = _prompts(m.cfg, n=1)[0]
    ref = _serve(eng, [short_p], use_async=False, gen=6)

    eng.reset()
    victim = eng.add_request(long_p, max_new_tokens=6)
    other = eng.add_request(short_p, max_new_tokens=6)
    eng.step()                        # first prefill chunk only (4 < 24)
    assert eng.cancel(victim)
    assert not eng.cancel(victim)                # idempotent
    while eng.scheduler.has_work or eng.pending_step:
        eng.step()
    out = eng.pop_finished()
    assert out[victim].finish_reason == "cancelled"
    assert out[victim].tokens == []
    assert (tuple(out[other].tokens), out[other].finish_reason) \
        == ref[next(iter(ref))]
    a = eng.cache_host.allocator
    assert a.num_live == 0, "cancelled prefill leaked blocks"
    eng.cache_host.check()


def test_deadline_expiry(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=1, block_size=4,
                                        max_len=32))
    p1, p2 = _prompts(m.cfg, n=2)
    eng.add_request(p1, max_new_tokens=8)
    # one slot: r2 queues behind r1 and its zero budget expires at the
    # first step boundary, before it ever holds blocks
    r2 = eng.add_request(p2, max_new_tokens=8, deadline_s=0.0)
    out, _ = eng.run()
    assert out[r2].finish_reason == "deadline" and out[r2].tokens == []


def test_backpressure_overload(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=1, block_size=4,
                                        max_len=32, max_waiting=2))
    ps = _prompts(m.cfg, n=3)
    eng.add_request(ps[0], max_new_tokens=4)
    eng.add_request(ps[1], max_new_tokens=4)
    with pytest.raises(EngineOverloaded):
        eng.add_request(ps[2], max_new_tokens=4)
    eng.run()                                    # queue drains fine
    eng.add_request(ps[2], max_new_tokens=4)     # room again
    out, _ = eng.run()
    assert len(out) == 1


def test_pop_finished_bounds_host_state(mp):
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    ps = _prompts(m.cfg, n=3)
    eng.reset()
    for p in ps:
        eng.add_request(p, max_new_tokens=4)
    while eng.scheduler.has_work or eng.pending_step:
        eng.step_async()
    assert len(eng.finished()) == 3              # non-destructive
    recs = eng.pop_finished()
    assert len(recs) == 3
    assert not eng.scheduler.finished
    assert not eng._submit_wall and not eng._on_token


# ---------------------------------------------------------------------------
# Property-style rollback driver
# ---------------------------------------------------------------------------

def test_async_random_stop_and_cancel_property(mp):
    """Randomized stop tokens + mid-run cancels under async driving:
    every surviving output must byte-match the greedy oracle prefix, the
    pool invariants must hold on every step, and nothing leaks."""
    m, params = mp
    eng = Engine(m, params, ServeConfig(max_seqs=3, block_size=4,
                                        max_len=48, chunk_size=4))
    prng = np.random.default_rng(29)
    for round_ in range(3):
        ps = _prompts(m.cfg, n=5)
        stops = tuple(int(t) for t in prng.integers(
            0, m.cfg.vocab_size, 2))
        ref = _serve(eng, ps, use_async=False, gen=10, stop_tokens=stops)

        eng.reset()
        rids = [eng.add_request(p, max_new_tokens=10, stop_tokens=stops)
                for p in ps]
        cancel_at = {int(prng.integers(2, 10)): r
                     for r in prng.choice(rids, 2, replace=False)}
        i = 0
        while eng.scheduler.has_work or eng.pending_step:
            if i in cancel_at:
                eng.cancel(cancel_at[i])
            eng.step_async()
            eng.cache_host.check()
            i += 1
        out = eng.pop_finished()
        assert set(out) == set(rids)
        for r in rids:
            toks, reason = tuple(out[r].tokens), out[r].finish_reason
            if reason == "cancelled":
                # prefix of the same stop-token run it was cut from
                assert toks == ref[r][0][:len(toks)]
            else:
                assert (toks, reason) == ref[r], (round_, r)
        # every block returned to the pool
        a = eng.cache_host.allocator
        assert a.num_live == 0
