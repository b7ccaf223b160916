"""The engine's measurements where the work happens (DESIGN.md §12): step
phases as ``serve.*`` annotations on the profiler's clock, the dispatch
counters of what the step programs carry (real prefill tokens against
token slots; live against launched page slots of the paged-attention
kernel), and the count of programs traced while the engine stepped."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import v_rows
from repro.kernels.paged_attention.ops import chunk_pages, grid_steps
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
    8-block tables (max_len 32), 3 output tokens, telemetry off.  The
    kernel's chunk is 8 pages (128 keys would be 32 blocks of 4; the
    8-block table caps it), so each row with a live page launches one
    iteration of 8 page slots:

    - prefill: three calls carry 4, 4 and 2 real tokens in 2 x 4 slots
      each; the live row's kv lengths 4, 8, 10 touch 1, 2, 3 blocks, one
      iteration each; the idle row (length 0) runs none: 3 x 8 slots;
    - decode: two calls at positions 10 and 11 (kv 11, 12: 3 blocks
      each); the idle row sits at position 0 and reads block 0: each
      call runs one iteration a row, 2 x 2 x 8 slots."""
    eng = _engine(tiny)
    assert eng._chunk_pages == 8
    c0 = _counters(eng)
    _serve_one(eng, list(range(1, 11)), 3)
    d = {k: v - c0[k] for k, v in _counters(eng).items()}
    assert d["prefill_chunk_tokens"] == 10
    assert d["prefill_tokens"] == 9            # leaves the last token out
    assert d["prefill_token_slots"] == 3 * 2 * 4
    assert d["prefill_attn_live_steps"] == 1 + 2 + 3
    assert d["prefill_attn_grid_steps"] == 3 * 8
    assert d["decode_attn_live_steps"] == 2 * (3 + 1)
    assert d["decode_attn_grid_steps"] == 2 * 2 * 8


def _visits(q_starts, kv_lens, C, window, NB, bs, P=40):
    """(per-row page visits the kernel reports in interpret mode, pages a
    chunk) of one call over a random ``NB``-wide table of ``bs``-token
    blocks."""
    B, KH, H, D = len(kv_lens), 2, 4, 8
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
    return np.asarray(visits)[:, 0], chunk_pages(k_pool, v_pool, NB)


@pytest.mark.parametrize("pattern", [
    # (q_starts, kv_lens, query tokens per row, window, page slots
    # launched): 6-block tables of 4-token blocks, chunks of 6 pages
    ([0, 8, 22, 12], [1, 9, 23, 13], 1, 0, 4 * 6),   # decode; row 0 idle
    ([0, 4, 0, 8], [4, 8, 0, 10], 4, 0, 3 * 6),      # prefill; row 2 idle
    ([20, 16, 0, 0], [24, 20, 3, 0], 4, 6, 3 * 6),   # sliding window
], ids=["decode", "prefill", "window"])
def test_grid_steps_equals_kernel_visits(pattern):
    """The host count of live pages is the sum of the per-row page visits
    the kernel reports (interpret mode), and the page slots launched are
    one chunk (6 pages: 128 keys would be 32 blocks, the table caps it)
    per iteration, one iteration per row with a live page:

    - decode: kv 1, 9, 23, 13 are 1, 3, 6, 4 live pages: 4 iterations;
    - prefill: kv 4, 8, 0, 10 are 1, 2, 0, 3 pages: the idle row runs no
      iteration, 3 in all;
    - window 6: the oldest queries at 20 and 16 see from 15 and 11, so
      pages 3-5 and 2-4 of kv 24 and 20 are live, 1 page of kv 3 and
      none of kv 0: 3 iterations."""
    q_starts, kv_lens, C, window, launched = pattern
    visits, pages = _visits(q_starts, kv_lens, C, window, NB=6, bs=4)
    assert pages == 6
    assert grid_steps(np.array(kv_lens), np.array(q_starts), pages, 4,
                      window) == (int(visits.sum()), launched)


def test_launched_page_slots_follow_live_length_not_table_width():
    """The same lengths on a 6-wide and a 64-wide table of 32-token
    blocks (chunks of 4 pages, 128 keys) launch the same page slots: kv
    1, 40, 130 and 0 are 1, 2, 5 and 0 live pages, 1 + 1 + 2 iterations
    of 4 slots each, whatever the table's width."""
    q_starts, kv_lens = [0, 39, 129, 0], [1, 40, 130, 0]
    counts = []
    for NB in (6, 64):
        visits, pages = _visits(q_starts, kv_lens, 1, 0, NB=NB, bs=32)
        assert pages == 4
        np.testing.assert_array_equal(visits, [1, 2, 5, 0])
        counts.append(grid_steps(np.array(kv_lens), np.array(q_starts),
                                 pages, 32))
    assert counts == [(8, 4 * 4)] * 2


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
