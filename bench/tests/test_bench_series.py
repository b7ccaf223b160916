"""Percentiles and rates are taken over every sample of a run."""
import numpy as np
import pytest

import series
from harness import Req, RunData


def _data(reqs, window=(1.0, 3.0)):
    return RunData(widths={}, peak={}, block_size=16, window=window,
                   reqs=reqs, setup_s=1.0, counters={}, phases={},
                   calls={"decode": [], "prefill": []})


def test_percentile_over_all_samples():
    rng = np.random.default_rng(0)
    chunks = [rng.lognormal(0, 1, n) for n in (5, 50, 500)]
    allv = np.concatenate(chunks)
    assert series.percentile(allv, 99) == np.percentile(allv, 99)
    # not the mean of per-chunk percentiles
    per = np.mean([np.percentile(c, 99) for c in chunks])
    assert series.percentile(allv, 99) != pytest.approx(per)
    assert series.percentile([], 50) is None


def test_latencies_from_due_times_and_every_gap():
    a = Req(due=1.0, prompt=[1], max_new=3, submit=1.2,
            times=[1.5, 1.6, 1.8])
    b = Req(due=2.0, prompt=[1], max_new=2, submit=2.0, times=[2.1, 2.5])
    early = Req(due=0.5, prompt=[1], max_new=2, times=[1.1, 1.2])
    d = _data([a, b, early])
    # TTFT from the due time, not from submission
    assert series.ttfts(d) == pytest.approx([0.5, 0.1])
    # every gap of the window's requests, pooled
    assert sorted(series.itl_gaps(d)) == pytest.approx([0.1, 0.2, 0.4])
    # tokens inside [1, 3) from any request, the lead-in's too
    assert series.window_tokens(d) == 7


def test_end_to_end_readers():
    from harness import read_metrics
    reqs = [Req(due=1.0 + i * 0.01, prompt=[1], max_new=2,
                times=[1.0 + i * 0.01 + 0.001 * (i + 1),
                       1.0 + i * 0.01 + 0.001 * (i + 1) + 0.02])
            for i in range(100)]
    m = read_metrics(["ttft_p50_ms", "itl_p50_ms", "itl_p99_ms",
                      "output_tokens_per_s", "setup_s"], _data(reqs))
    ttft = [0.001 * (i + 1) for i in range(100)]
    assert m["ttft_p50_ms"]["value"] == pytest.approx(
        np.percentile(ttft, 50) * 1e3)
    assert m["itl_p50_ms"]["value"] == pytest.approx(20.0)
    assert m["output_tokens_per_s"] == {"value": 100.0, "unit": "tokens/s"}
    assert m["setup_s"]["value"] == 1.0
