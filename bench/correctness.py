"""How ``correct`` is decided: served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample of
the requests due in the window is drawn from the seed, always with the
longest of them, until it holds ``SAMPLE_TOKENS`` served tokens.  The
reference runs once over each sampled prompt with its served tokens, and
each served token is scored by how far its reference logit lies below the
reference's best logit at that position.  The mean of those gaps is
compared with the cell's limit.  Greedy serving puts every token at a gap
of 0 up to rounding, where bf16 flips a near-tie now and then; a wrong
attention, cache or width puts tokens far below.  The widest gap is
printed too but not compared: a single near-tie flipped by bf16 rounding
reads as wide as the control's.

The control (``quant=True``) is the reference itself in int8 arithmetic,
the step below the configuration's bf16: at the same positions, the gap
of the token the int8 reference puts first.  ``bench/calibrate.py``
judges it against the cell's limits; the benchmark's runs never run it.
"""
from __future__ import annotations

import numpy as np

SAMPLE_TOKENS = 300         # served tokens compared per run



def sample(records: list, seed: int, min_tokens: int | None = None
           ) -> list:
    """Finished records (each with ``prompt`` and ``tokens``) to compare:
    the longest, then others in a seeded order until ``min_tokens``
    (default ``SAMPLE_TOKENS``) served tokens are held."""
    if min_tokens is None:
        min_tokens = SAMPLE_TOKENS
    done = [r for r in records if r["tokens"]]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"]))
    order = [longest] + [i for i in np.random.default_rng(seed).permutation(
        len(done)).tolist() if i != longest]
    out, n = [], 0
    for i in order:
        out.append(done[i])
        n += len(done[i]["tokens"])
        if n >= min_tokens:
            break
    return out


def gaps(ref, params, conf: dict, recs: list, quant: bool = False
         ) -> np.ndarray:
    """Per served token, the best reference logit minus the reference
    logit of the token compared: the served token, or with ``quant`` the
    token the int8 control puts first."""
    out = []
    for r in recs:
        prompt, toks = list(r["prompt"]), list(r["tokens"])
        seq = prompt + toks[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = ref.logits_at(params, conf, seq, rows)
        pick = np.asarray(toks)
        if quant:
            pick = ref.logits_at(params, conf, seq, rows, quant=True
                                 ).argmax(-1)
        out.append(lg.max(-1) - lg[np.arange(len(rows)), pick])
    return np.concatenate(out) if out else np.zeros(0)


def gap_readings(g: np.ndarray) -> dict:
    """The mean and the widest of the gaps; infinite when nothing was
    compared."""
    if not g.size:
        return {"mean_logit_gap": float("inf"),
                "max_logit_gap": float("inf")}
    return {"mean_logit_gap": float(g.mean()),
            "max_logit_gap": float(g.max())}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading that has a limit against it: correct when none
    exceeds it."""
    checks = {k: {"value": float(readings[k]), "limit": float(limits[k])}
              for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
