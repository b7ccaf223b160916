"""The one traffic generator: an open loop of requests from a mix's file.

A mix (``bench/traffic/<mix>.json``) gives the parameters: the share of
prompts that open with a shared system prompt and how those prompts are
chosen, the distributions of the prompts' own lengths and of the output
lengths, the sampling temperature, and how long the lead-in before the
measured window and the tail after it last.  The arrival rate is the
cell's own (``bench/cells/<cell>.json``).

Every seed gets the same work in another order.  The schedule has three
phases (the lead-in, the measured window and the tail after it), and
each phase holds ``round(rate * length)`` requests.  For its ``n``
requests a phase takes the ``n`` stratified quantiles ``(i + 0.5) / n``
of each distribution (inter-arrival gaps, own prompt lengths, output
lengths, which system prompt), paired once by a fixed draw, so the
multiset of request shapes in each phase and the number of shared
prompts are equal for every seed.  The seed orders the requests and the
gaps of each phase and draws every token id.  The gaps are the quantiles
of an exponential distribution scaled to fill the phase: a Poisson
process, stratified and conditioned on its count.
"""
from __future__ import annotations

import dataclasses
import json
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PAIRING_SEED = 0


@dataclasses.dataclass
class Request:
    due: float                  # seconds after the schedule starts
    prompt: list[int]
    max_new: int
    prefix: int                 # shared system prompt index, -1 for none
    temperature: float = 0.0


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``u`` of ``dist``: ``lognormal``
    (median, sigma) or ``uniform`` over [min, max]; clipped to
    [min, max]."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def zipf_choice(k: int, s: float, u: np.ndarray) -> np.ndarray:
    """Indices in [0, k) at quantiles ``u`` of a Zipf(s) law over k."""
    w = 1.0 / np.arange(1, k + 1) ** s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, u, side="right"), k - 1)


def generate(mix: dict, rate: float, seed: int, phases: list[float],
             vocab: int) -> list[Request]:
    """Requests at ``rate`` per second over consecutive phases of the
    given lengths (seconds), in due order."""
    rng = np.random.default_rng(seed)
    pair = np.random.default_rng(PAIRING_SEED)
    shared = mix["prompt"].get("shared")
    systems = [rng.integers(0, vocab, shared["length"]).tolist()
               for _ in range(shared["prefixes"])] if shared else []
    rows: list[tuple[float, int, int, int]] = []
    start = 0.0
    for length in phases:
        n = max(1, round(rate * length))
        u = _quantiles(n)
        # the shapes of the phase's requests, paired by a fixed draw
        own = pair.permutation(lengths(mix["prompt"]["own"], u))
        out = pair.permutation(lengths(mix["output"], u))
        prefix = np.full(n, -1)
        if shared and shared["share"] > 0:
            m = round(shared["share"] * n)
            prefix[:m] = zipf_choice(shared["prefixes"], shared["zipf_s"],
                                     _quantiles(m))
            prefix = pair.permutation(prefix)
        # the seed orders them and their gaps
        order = rng.permutation(n)
        gaps = rng.permutation(-np.log1p(-u))
        due = start + length * (np.cumsum(gaps) - gaps) / gaps.sum()
        rows += [(float(due[i]), int(own[order[i]]), int(out[order[i]]),
                  int(prefix[order[i]])) for i in range(n)]
        start += length
    temp = float(mix.get("temperature", 0.0))
    reqs = []
    for due, own, out, prefix in rows:
        head = systems[prefix] if prefix >= 0 else []
        body = rng.integers(0, vocab, own).tolist()
        reqs.append(Request(due=due, prompt=head + body, max_new=out,
                            prefix=prefix, temperature=temp))
    return reqs
