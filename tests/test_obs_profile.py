"""The engine's measurements where the work happens (DESIGN.md §12): step
phases as ``serve.*`` annotations on the profiler's clock, the dispatch
counters of what the step programs carry (real prefill tokens against
token slots; live against launched grid steps of the paged-attention
kernel), and the count of programs traced while the engine stepped."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import v_rows
from repro.kernels.paged_attention.ops import grid_steps
from repro.kernels.paged_attention.paged_attention import _paged_attention
from repro.models import build
from repro.obs import Telemetry
from repro.serve import Engine, ServeConfig

# the children of a step that tile it, and emit inside fold
STEP_CHILDREN = {"admin", "plan", "cow", "prefill_dispatch",
                 "decode_dispatch", "sync", "fold"}


@pytest.fixture(scope="module")
def tiny(key):
    cfg = reduced(get_config("tinyllama-1.1b"))
    m = build(cfg)
    return m, m.init(key)


def _engine(tiny, telemetry=None):
    m, params = tiny
    return Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                         max_len=32, chunk_size=4),
                  telemetry=telemetry)


def _serve_one(eng, prompt, gen):
    eng.add_request(prompt, max_new_tokens=gen,
                    on_token=lambda tok, done: None)
    eng.run()


def _counters(eng) -> dict[str, int]:
    return {k.split("/", 1)[1]: v for k, v in
            eng.obs.registry.counter_values("serve/").items()}


# ---------------------------------------------------------------------------
# serve.* spans in a jax.profiler trace
# ---------------------------------------------------------------------------

def _profiled_serve_events(tiny, tmp_path, enabled: bool) -> list:
    """Host events named ``serve.*`` of a profiler trace around a tiny
    engine's steps: (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    eng = _engine(tiny, Telemetry(enabled=enabled))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_one(eng, list(range(1, 11)), 3)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name[len("serve."):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


@pytest.mark.parametrize("enabled", [True, False])
def test_profiler_trace_holds_step_phases(tiny, tmp_path, enabled):
    """With telemetry on, every step is a ``serve.step`` span carrying its
    ``step`` and ``replica`` stats, and every phase lies inside the step
    of its number; with telemetry off, no ``serve.*`` span is made."""
    evs = _profiled_serve_events(tiny, tmp_path, enabled)
    if not enabled:
        assert evs == []
        return
    steps = [e for e in evs if e[0] == "step"]
    assert steps
    assert all(e[3].get("replica") == 0 for e in evs)
    assert all("step" in e[3] for e in evs)
    inner = [e for e in evs if e[0] != "step"]
    assert {e[0] for e in inner} >= STEP_CHILDREN | {"emit"}
    for name, t0, t1, stats in inner:
        assert any(s[3]["step"] == stats["step"] and s[1] <= t0 and
                   t1 <= s[2] for s in steps), name


# ---------------------------------------------------------------------------
# dispatch counters
# ---------------------------------------------------------------------------

def test_dispatch_counters_match_hand_counts(tiny):
    """One 10-token prompt, chunks of 4 tokens, blocks of 4, 2 slots and
    8-block tables (max_len 32), 3 output tokens, telemetry off:

    - prefill: three calls carry 4, 4 and 2 real tokens in 2 x 4 slots
      each; the live row's kv lengths 4, 8, 10 touch 1, 2, 3 blocks, the
      idle row (length 0) none; each call launches 2 x 8 grid steps;
    - decode: two calls at positions 10 and 11 (kv 11, 12: 3 blocks
      each); the idle row sits at position 0 and reads block 0."""
    eng = _engine(tiny)
    c0 = _counters(eng)
    _serve_one(eng, list(range(1, 11)), 3)
    d = {k: v - c0[k] for k, v in _counters(eng).items()}
    assert d["prefill_chunk_tokens"] == 10
    assert d["prefill_tokens"] == 9            # leaves the last token out
    assert d["prefill_token_slots"] == 3 * 2 * 4
    assert d["prefill_attn_live_steps"] == 1 + 2 + 3
    assert d["prefill_attn_grid_steps"] == 3 * 2 * 8
    assert d["decode_attn_live_steps"] == 2 * (3 + 1)
    assert d["decode_attn_grid_steps"] == 2 * 2 * 8


@pytest.mark.parametrize("pattern", [
    # (q_starts, kv_lens, query tokens per row, window)
    ([0, 8, 22, 12], [1, 9, 23, 13], 1, 0),       # decode; row 0 idle
    ([0, 4, 0, 8], [4, 8, 0, 10], 4, 0),          # prefill; row 2 idle
    ([20, 16, 0, 0], [24, 20, 3, 0], 4, 6),       # sliding window
], ids=["decode", "prefill", "window"])
def test_grid_steps_equals_kernel_visits(pattern):
    """The host count of live grid steps is the sum of the per-row block
    visits the kernel reports (interpret mode), and the grid launched is
    rows x table width."""
    q_starts, kv_lens, C, window = pattern
    B, NB, bs, P, KH, H, D = 4, 6, 4, 32, 2, 4, 8
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, H, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], (P, KH, bs, D), jnp.float32)
    v_pool = v_rows(jax.random.normal(ks[2], (P, KH, bs, D), jnp.float32))
    tables = rng.integers(1, P, (B, NB)).astype(np.int32)
    _, visits = _paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables),
        jnp.asarray(q_starts, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
        window=window, scale=None, interpret=True)
    live, launched = grid_steps(np.array(kv_lens), np.array(q_starts), NB,
                                bs, window)
    assert live == int(np.asarray(visits)[:, 0].sum())
    assert launched == B * NB


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

def test_compiles_counted_while_stepping(tiny):
    """A fresh engine's first steps trace its step programs; warmed steps
    trace nothing; the first copy-on-write traces the copy program, once
    (the library functions traced inside it count with it); with telemetry
    on the seconds land in the ``compile`` phase."""
    tel = Telemetry(enabled=True)
    eng = _engine(tiny, tel)
    a = list(range(1, 16))
    _serve_one(eng, a, 4)
    first = _counters(eng)
    assert first["compiles"] > 0
    assert tel.registry.histograms["phase/compile"].count > 0

    _serve_one(eng, list(range(20, 35)), 4)      # same shapes, warm
    warm = _counters(eng)
    assert warm["compiles"] == first["compiles"]

    # two live requests share a's first blocks; the shorter one writes
    # inside a shared block, so the step copies it first
    eng.add_request(a[:12], max_new_tokens=6)
    eng.add_request(a[:8], max_new_tokens=2)
    eng.run()
    cow = _counters(eng)
    assert cow["cow_copies"] > warm["cow_copies"]
    assert cow["compiles"] == warm["compiles"] + 1   # the copy program
