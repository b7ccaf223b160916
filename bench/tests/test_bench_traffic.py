"""The traffic generator: deterministic by seed, the same work for every
seed, and the distributions, sharing and rate its mix file states."""
import numpy as np
import pytest

import traffic

VOCAB = 151_936


def _gen(mix, seed, rate=3.0, phases=(10.0, 40.0, 10.0)):
    return traffic.generate(traffic.load_mix(mix), rate, seed, list(phases),
                            VOCAB)


@pytest.mark.parametrize("mix", ["chat-prefix", "long-prompt"])
def test_same_seed_same_requests(mix):
    a, b = _gen(mix, 2**33 + 1), _gen(mix, 2**33 + 1)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("mix", ["chat-prefix", "long-prompt"])
def test_every_seed_gets_the_same_work_in_each_phase(mix):
    a, b = _gen(mix, 11), _gen(mix, 2**31 + 12)
    for lo, hi, n in ((0, 10, 30), (10, 50, 120), (50, 60, 30)):
        pa = [r for r in a if lo <= r.due < hi]
        pb = [r for r in b if lo <= r.due < hi]
        assert len(pa) == len(pb) == n
        assert sorted((len(r.prompt), r.max_new, r.prefix) for r in pa) == \
            sorted((len(r.prompt), r.max_new, r.prefix) for r in pb)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.due for r in a] != [r.due for r in b]


def test_rate_and_poisson_gaps():
    rate = 4.0
    reqs = _gen("long-prompt", 5, rate, (100.0,))
    assert len(reqs) == 400
    assert reqs[0].due == 0 and reqs[-1].due < 100.0
    gaps = np.diff([r.due for r in reqs])
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    # exponential: the median gap is ln 2 / rate
    assert np.median(gaps) == pytest.approx(np.log(2) / rate, rel=0.05)
    assert all(np.diff([r.due for r in reqs]) >= 0)


def test_chat_prefix_sharing_and_lengths():
    mix = traffic.load_mix("chat-prefix")
    reqs = _gen("chat-prefix", 9, 5.0, (100.0,))
    shared = [r for r in reqs if r.prefix >= 0]
    assert len(shared) == round(0.75 * len(reqs))
    n = mix["prompt"]["shared"]["length"]
    heads = {}
    for r in shared:
        heads.setdefault(r.prefix, tuple(r.prompt[:n]))
        assert tuple(r.prompt[:n]) == heads[r.prefix]
    assert len(heads) == 4 and len(set(heads.values())) == 4
    counts = np.bincount([r.prefix for r in shared])
    assert list(counts) == sorted(counts, reverse=True)   # Zipf skew
    own = np.array([len(r.prompt) - (n if r.prefix >= 0 else 0)
                    for r in reqs])
    assert own.min() >= 32 and own.max() <= 1024
    assert np.median(own) == pytest.approx(256, rel=0.05)
    out = np.array([r.max_new for r in reqs])
    assert out.min() >= 16 and out.max() <= 512
    assert np.median(out) == pytest.approx(128, rel=0.05)
    assert all(0 <= t < VOCAB for r in reqs for t in r.prompt)


def test_long_prompt_lengths():
    reqs = _gen("long-prompt", 3, 5.0, (100.0,))
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 1024 and p.max() <= 7168
    assert np.median(p) == pytest.approx(3072, rel=0.05)
    assert o.min() == 8 and o.max() == 32
    assert all(r.prefix == -1 for r in reqs)
    # unshared: no two prompts open with the same block of tokens
    assert len({tuple(r.prompt[:16]) for r in reqs}) == len(reqs)


def test_zipf_and_uniform_quantiles():
    u = (np.arange(1000) + 0.5) / 1000
    k = np.bincount(traffic.zipf_choice(4, 1.0, u))
    w = 1 / np.arange(1, 5)
    assert k / 1000 == pytest.approx(w / w.sum(), abs=0.002)
    x = traffic.lengths({"dist": "uniform", "min": 8, "max": 32}, u)
    assert set(x) == set(range(8, 33))
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "pareto", "min": 1, "max": 2}, u)
