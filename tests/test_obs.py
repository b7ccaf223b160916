"""Observability tests (DESIGN.md §12): telemetry must be invisible to
the device — metrics-on and metrics-off engines produce byte-identical
outputs on the dense, speculative, and sharded paths — while the
host-side surfaces (histograms, lifecycle latency fields, Chrome trace,
Prometheus export) must be correct, and the disabled path must cost a
negligible fraction of a step."""
import json
import time

import numpy as np
import pytest

import jax

from repro.configs import get_config, reduced
from repro.core.pruner import prune_model
from repro.models import build
from repro.obs import (DEFAULT_TIME_BUCKETS, Histogram, MetricsRegistry,
                       Telemetry, json_snapshot, prometheus_text, to_chrome)
from repro.serve import Engine, ServeConfig


def _build(key, name="tinyllama-1.1b"):
    cfg = reduced(get_config(name))
    m = build(cfg)
    return cfg, m, m.init(key)


def _prompts(cfg, n=4, base=9, seed=3):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size,
                                          base - (i % 3))]
            for i in range(n)]


def _serve(eng, prompts, gen=8, temperature=0.0):
    rids = [eng.add_request(p, max_new_tokens=gen, temperature=temperature)
            for p in prompts]
    out, stats = eng.run()
    return [out[r].tokens for r in rids], stats


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_registry():
    reg = MetricsRegistry()
    c = reg.counter("serve/steps")
    assert reg.counter("serve/steps") is c       # get-or-create
    c.inc()
    c.inc(4)
    reg.counter("serve/decode_tokens").inc(7)
    reg.gauge("pool/free").set(3)
    assert reg.counter_values("serve/") == {"serve/steps": 5,
                                            "serve/decode_tokens": 7}
    assert reg.counter_values() == {"serve/steps": 5,
                                    "serve/decode_tokens": 7}
    snap = reg.snapshot()
    assert snap["gauges"]["pool/free"] == 3.0
    json.dumps(snap)                             # JSON-serializable as-is
    reg.reset()
    assert c.value == 0 and reg.gauge("pool/free").value == 0.0


def test_histogram_percentiles_uniform():
    """1..1000 into decade-ish buckets: interpolated p50/p90/p99 must land
    within one bucket width of the exact order statistic."""
    buckets = tuple(float(b) for b in
                    (1, 2, 5, 10, 20, 50, 100, 200, 500, 1000))
    h = Histogram("t", buckets)
    for v in range(1, 1001):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 1000 and s["min"] == 1.0 and s["max"] == 1000.0
    assert s["mean"] == pytest.approx(500.5)
    # exact percentiles: 500 / 900 / 990; winning buckets are
    # (200,500] / (500,1000] / (500,1000]
    assert 200 <= s["p50"] <= 500
    assert 500 <= s["p90"] <= 1000
    assert s["p99"] > s["p90"] >= s["p50"]
    assert abs(s["p50"] - 500) <= 300            # within the winning bucket
    assert abs(s["p90"] - 900) <= 500
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 1000.0


def test_histogram_single_value_and_overflow():
    h = Histogram("t", (1.0, 2.0))
    h.observe(1.5)
    s = h.summary()
    # one sample: every percentile is that sample (min/max clamping)
    assert s["p50"] == s["p90"] == s["p99"] == 1.5
    h.observe(99.0)                              # lands in +inf overflow
    assert h.counts[-1] == 1
    assert h.percentile(99) <= 99.0              # clamped to observed max
    assert h.summary()["max"] == 99.0


def test_histogram_default_buckets_cover_phase_times():
    h = Histogram("t")
    assert h.buckets == DEFAULT_TIME_BUCKETS
    assert h.buckets[0] == pytest.approx(1e-6)
    assert h.buckets[-1] > 30.0                  # cold compile fits
    h.observe(0.003)
    assert h.summary()["count"] == 1


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serve/steps").inc(3)
    reg.gauge("pool/hit-rate").set(0.5)
    h = reg.histogram("phase/sync", (0.001, 0.01))
    h.observe(0.0005)
    h.observe(0.5)
    text = prometheus_text(reg)
    assert "repro_serve_steps_total 3" in text
    assert "repro_pool_hit_rate 0.5" in text     # '-' and '/' sanitized
    # cumulative buckets + +Inf + sum/count
    assert 'repro_phase_sync_bucket{le="0.001"} 1' in text
    assert 'repro_phase_sync_bucket{le="0.01"} 1' in text
    assert 'repro_phase_sync_bucket{le="+Inf"} 2' in text
    assert "repro_phase_sync_count 2" in text
    snap = json_snapshot(reg)
    assert snap["counters"]["serve/steps"] == 3


def test_prometheus_name_collisions_disambiguated():
    """_sanitize is lossy (serve/steps and serve_steps both map to
    repro_serve_steps): colliding metrics must get distinct exported
    series, not silently merge, and every series carries a HELP line
    naming its original metric."""
    reg = MetricsRegistry()
    reg.counter("serve/steps").inc(1)
    reg.counter("serve_steps").inc(2)
    reg.counter("serve-steps").inc(4)
    reg.gauge("pool/free").set(7)
    reg.gauge("pool_free").set(9)
    text = prometheus_text(reg)
    lines = text.splitlines()
    # three distinct counter series with the right values
    samples = {ln.split()[0]: ln.split()[1] for ln in lines
               if ln and not ln.startswith("#") and "{" not in ln}
    counter_vals = sorted(int(v) for n, v in samples.items()
                          if n.startswith("repro_serve") and
                          n.endswith("_total"))
    assert counter_vals == [1, 2, 4]
    assert len({n for n in samples if n.startswith("repro_serve")}) == 3
    gauge_vals = sorted(int(v) for n, v in samples.items()
                        if n.startswith("repro_pool"))
    assert gauge_vals == [7, 9]
    # HELP maps each exported name back to the un-sanitized original
    helps = {ln.split()[2]: ln.split(None, 3)[3] for ln in lines
             if ln.startswith("# HELP")}
    assert set(helps.values()) >= {"serve/steps", "serve_steps",
                                   "serve-steps", "pool/free", "pool_free"}
    assert len(helps) == len(set(helps))         # exported names unique
    # first-seen (sorted order) keeps the clean name; suffixes count up
    assert helps["repro_serve_steps_total"] in ("serve/steps",
                                                "serve-steps")
    assert any(n.startswith("repro_serve_steps_2") for n in helps)


def test_trace_buffer_is_bounded_ring():
    """A long-lived server must not leak host memory through the trace:
    each event kind is a bounded ring that drops the OLDEST events and
    counts the drops."""
    from repro.obs.trace import TraceBuffer
    buf = TraceBuffer(capacity=8)
    for i in range(20):
        buf.add_phase(i, "step", float(i), float(i) + 0.5)
        buf.add_span(i, "submit", float(i))
        buf.add_counter("pool", {"free": float(i)}, t=float(i))
    assert len(buf.phases) == 8 and len(buf.spans) == 8
    assert len(buf.counters) == 8
    assert buf.dropped_events == 3 * 12          # oldest 12 of each kind
    assert buf.phases[0].step == 12              # most recent window kept
    assert buf.phases[-1].step == 19
    buf.clear()
    assert buf.dropped_events == 0 and not buf.phases
    # default capacity is big enough that normal runs never drop
    assert TraceBuffer().capacity == 65536


# ---------------------------------------------------------------------------
# Byte parity: telemetry must not perturb outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_metrics_on_off_byte_identical_dense(key, temperature):
    """Same seed, same requests: outputs (greedy AND sampled — telemetry
    must not touch the engine RNG) are byte-identical with metrics on."""
    cfg, m, params = _build(key)
    prompts = _prompts(cfg)
    sc = ServeConfig(max_seqs=2, block_size=4, max_len=32)
    off, s_off = _serve(Engine(m, params, sc), prompts,
                        temperature=temperature)
    tel = Telemetry(enabled=True)
    on, s_on = _serve(Engine(m, params, sc, telemetry=tel), prompts,
                      temperature=temperature)
    assert on == off
    for k in ("steps", "decode_tokens", "prefill_chunks", "host_syncs"):
        assert s_on[k] == s_off[k], k
    # and the instrumentation actually recorded the run (every step()
    # call gets a phase slice, including empty-plan steps that don't
    # count as productive engine steps)
    assert tel.registry.histograms["phase/step"].count >= s_on["steps"]
    assert tel.registry.counters["lifecycle/finish"].value == len(prompts)


def test_metrics_on_off_byte_identical_spec(key):
    cfg, m, params = _build(key)
    dr = prune_model(m, params, 0.5, criterion="l1")
    dm, dp = build(dr.cfg), dr.params
    prompts = _prompts(cfg)
    sc = ServeConfig(max_seqs=2, block_size=4, max_len=40, spec_k=3)
    off, s_off = _serve(Engine(m, params, sc, draft_model=dm,
                               draft_params=dp), prompts)
    tel = Telemetry(enabled=True)
    eng = Engine(m, params, sc, draft_model=dm, draft_params=dp,
                 telemetry=tel)
    assert eng.spec_active
    on, s_on = _serve(eng, prompts)
    assert on == off
    assert s_on["spec_proposed"] == s_off["spec_proposed"]
    assert s_on["spec_accepted"] == s_off["spec_accepted"]
    # acceptance histograms recorded per drafted slot-cycle; their mass
    # must reconcile with the run counter
    acc = tel.registry.histograms["spec/accepted_per_cycle"]
    assert acc.count > 0
    assert acc.total == s_on["spec_accepted"]


def test_metrics_on_off_byte_identical_sharded(key):
    from repro.launch.mesh import make_serve_mesh
    cfg, m, params = _build(key)
    prompts = _prompts(cfg)
    sc = ServeConfig(max_seqs=2, block_size=4, max_len=32)
    off, _ = _serve(Engine(m, params, sc, mesh=make_serve_mesh(1, 1)),
                    prompts)
    on, _ = _serve(Engine(m, params, sc, mesh=make_serve_mesh(1, 1),
                          telemetry=Telemetry(enabled=True)), prompts)
    assert on == off


# ---------------------------------------------------------------------------
# Lifecycle latency fields (queue wait, preempt stall, manual-step TTFT)
# ---------------------------------------------------------------------------

def test_queue_wait_recorded_under_slot_pressure(key):
    """More requests than slots: late requests wait for a slot, and that
    wait shows up in both queue_wait_s and ttft_s."""
    cfg, m, params = _build(key)
    prompts = _prompts(cfg, n=5)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    out, _ = eng.run()
    for r in rids:
        assert out[r].ttft_s >= out[r].queue_wait_s >= 0.0
    # FCFS: the last request cannot start before an earlier one frees a
    # slot, so it must have measurably waited
    assert out[rids[-1]].queue_wait_s > 0.0
    assert out[rids[0]].queue_wait_s <= out[rids[-1]].queue_wait_s


def test_preempt_stall_recorded(key):
    """A pool too small for all requests forces eviction; the evicted
    request's time off the engine is charged to preempt_stall_s."""
    cfg, m, params = _build(key)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 9)]
               for _ in range(4)]
    eng = Engine(m, params, ServeConfig(max_seqs=4, block_size=4,
                                        max_len=64, num_blocks=13))
    rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    out, _ = eng.run()
    preempted = [r for r in rids if out[r].preemptions > 0]
    assert preempted                             # pressure was real
    for r in preempted:
        assert out[r].preempt_stall_s > 0.0
    for r in rids:
        if out[r].preemptions == 0:
            assert out[r].preempt_stall_s == 0.0


def test_ttft_correct_under_manual_step_driving(key):
    """Drive the engine with step() and an idle gap before the first
    step: TTFT must span submit -> first token (run()'s old t0 fallback
    under-reported it as ~0 for already-finished requests)."""
    cfg, m, params = _build(key)
    prompts = _prompts(cfg, n=2)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
    gap = 0.05
    time.sleep(gap)                              # queue sits idle
    t0 = time.perf_counter()
    while eng.scheduler.has_work:
        eng.step()
    wall = time.perf_counter() - t0
    recs = eng.finished()
    assert sorted(recs) == sorted(rids)
    for r in rids:
        # the idle gap is real time-to-first-token under open-loop driving
        assert recs[r].ttft_s >= gap
        assert recs[r].ttft_s <= gap + wall + 0.5
        assert recs[r].tpot_s >= 0.0
    # records are stable: finished() is non-destructive, so a second
    # read reports the same latencies; run() on the NOT-yet-drained
    # engine reports them too (manual-step finishes drain through the
    # next run(), same as requests cancelled between runs), and after
    # that destructive drain nothing reports again
    recs2 = eng.finished()
    assert {r: recs2[r].ttft_s for r in recs2} == \
           {r: recs[r].ttft_s for r in recs}
    out, _ = eng.run()
    assert sorted(out) == sorted(rids)
    assert eng.run()[0] == {}                     # nothing left to drain
    eng.pop_finished()
    assert eng.finished() == {}                   # history fully retired


def test_run_stats_keys_backward_compatible(key):
    cfg, m, params = _build(key)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))
    _, stats = _serve(eng, _prompts(cfg, n=2))
    for k in ("steps", "decode_tokens", "prefill_tokens", "prefill_chunks",
              "decode_tok_per_s", "total_tok_per_s", "mean_ttft_s",
              "cow_copies", "host_syncs", "spec_cycles", "spec_proposed",
              "spec_accepted", "spec_acceptance", "wall_s"):
        assert k in stats, k
    assert stats["host_syncs"] == stats["steps"]  # ONE device_get per step
    # back-compat attribute views used by older tests
    assert eng._steps == int(stats["steps"])
    assert eng._host_syncs == int(stats["host_syncs"])


# ---------------------------------------------------------------------------
# Chrome trace schema
# ---------------------------------------------------------------------------

def _traced_run(key, n=4):
    """A fresh engine's run with streaming consumers: its first steps
    compile, and every token goes through an ``on_token`` callback."""
    cfg, m, params = _build(key)
    tel = Telemetry(enabled=True)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32), telemetry=tel)
    for p in _prompts(cfg, n=n):
        eng.add_request(p, max_new_tokens=8, on_token=lambda tok, done: None)
    eng.run()
    return tel, to_chrome(tel.trace)


def test_chrome_trace_schema(key):
    tel, doc = _traced_run(key)
    assert doc["displayTimeUnit"] == "ms"
    ev = doc["traceEvents"]
    json.dumps(doc)                              # serializable
    assert {e["ph"] for e in ev} >= {"M", "X", "b", "e", "n", "C"}
    for e in ev:
        assert e["ts"] >= 0 if "ts" in e else True
    xs = [e for e in ev if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["pid"] == 0 and e["tid"] == 0
               for e in xs)
    assert {e["name"] for e in xs} >= {"step", "plan", "sync", "fold"}
    counters = [e for e in ev if e["ph"] == "C"]
    assert any(e["name"] == "pool" for e in counters)
    assert any(e["name"] == "prefix" for e in counters)
    for e in counters:
        assert all(isinstance(v, (int, float)) for v in e["args"].values())


def test_chrome_trace_phases_nest_inside_step(key):
    """Chrome nests same-tid X events by time containment: every inner
    phase slice must sit inside its step's enclosing slice — the phases
    that tile a step, the consumer callbacks (``emit``) and the step's
    compile seconds (``compile``) alike."""
    _, doc = _traced_run(key)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    steps = {e["args"]["step"]: e for e in xs if e["name"] == "step"}
    inner = [e for e in xs if e["name"] != "step"]
    assert steps and inner
    assert {e["name"] for e in inner} >= {
        "admin", "plan", "cow", "prefill_dispatch", "decode_dispatch",
        "sync", "fold", "emit", "compile"}
    eps = 1.0                                    # us; clock granularity
    for e in inner:
        outer = steps[e["args"]["step"]]
        assert e["ts"] >= outer["ts"] - eps, e["name"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + eps, \
            e["name"]


def test_chrome_trace_spans_open_and_close(key):
    _, doc = _traced_run(key)
    ev = doc["traceEvents"]
    opens = {e["id"] for e in ev if e["ph"] == "b"}
    closes = {e["id"] for e in ev if e["ph"] == "e"}
    assert opens and opens == closes             # every span closes
    # per-request ordering: b <= every n <= e
    for rid in opens:
        ts = {ph: [e["ts"] for e in ev
                   if e["ph"] == ph and e.get("id") == rid]
              for ph in ("b", "n", "e")}
        assert len(ts["b"]) == 1 and len(ts["e"]) == 1
        assert ts["n"], "lifecycle instants missing"
        assert ts["b"][0] <= min(ts["n"]) and max(ts["n"]) <= ts["e"][0]
    kinds = {e["args"]["kind"] for e in ev if e["ph"] == "n"}
    assert {"admit", "first_chunk", "first_token"} <= kinds


def test_chrome_trace_closes_dangling_spans():
    """A request still in flight at export time gets a synthetic close so
    the trace always validates."""
    from repro.obs.trace import TraceBuffer
    buf = TraceBuffer()
    buf.add_span(7, "submit")
    buf.add_span(7, "admit")
    doc = to_chrome(buf)
    es = [e for e in doc["traceEvents"] if e["ph"] == "e"]
    assert len(es) == 1 and es[0]["id"] == 7
    assert es[0]["args"]["kind"] == "eof"


# ---------------------------------------------------------------------------
# Disabled path: no-op, and negligible against a real step
# ---------------------------------------------------------------------------

def test_disabled_telemetry_records_nothing(key):
    cfg, m, params = _build(key)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=32))                # default
    assert eng.obs.enabled is False
    _serve(eng, _prompts(cfg, n=2))
    assert not eng.obs.trace.phases and not eng.obs.trace.spans
    assert not eng.obs.registry.histograms and not eng.obs.registry.gauges
    # only the always-on run counters exist
    assert all(k.startswith("serve/") for k in eng.obs.registry.counters)


def test_disabled_path_overhead_bounded(key):
    """The disabled instrumentation (null phase contexts, gated events /
    samples) must cost < 2% of a measured engine step.  Measured as
    per-call cost of the gated no-ops x calls-per-step vs the fastest
    observed decode step; best-of-3 on both sides against CI noise."""
    tel = Telemetry(enabled=False)
    N = 20000
    percall = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(N):
            with tel.phase("x"):
                pass
            tel.event("e", 0)
            tel.sample("g", {})
            tel.observe("h", 0.0)
        percall = min(percall, (time.perf_counter() - t0) / N)

    cfg, m, params = _build(key)
    eng = Engine(m, params, ServeConfig(max_seqs=2, block_size=4,
                                        max_len=48), telemetry=tel)
    _serve(eng, _prompts(cfg, n=2), gen=16)      # compile
    step_s = float("inf")
    for _ in range(3):
        eng.reset()
        for p in _prompts(cfg, n=2):
            eng.add_request(p, max_new_tokens=16)
        _, stats = eng.run()
        step_s = min(step_s, stats["wall_s"] / stats["steps"])

    # ~8 phase/event/sample call sites fire per engine step
    overhead = 8 * percall / step_s
    assert overhead < 0.02, \
        f"disabled telemetry {overhead:.2%} of a {step_s * 1e3:.2f}ms step"
