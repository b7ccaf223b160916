"""Operations and bytes the traffic needs, from shapes and live lengths.

Work is counted as the algorithm needs it, not as an implementation pads
it: live KV tokens and not the width of a block table, real tokens and
not padded rows.  A share computed from these counts cannot exceed 100%
for an implementation that does the work, and rises when padding or dead
grid steps are removed.

``w`` is a configuration's widths (``reference.dims``): L, d, H, KH, hd,
vhd, F, V.  Element sizes are in bytes.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    """The device kind has no row in peaks.json."""


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")
          ) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(w: dict) -> int:
    """Weights one token multiplies in one decoder layer."""
    d, H, KH, hd, vhd, F = (w[k] for k in ("d", "H", "KH", "hd", "vhd", "F"))
    return d * H * hd + d * KH * hd + d * KH * vhd + H * vhd * d + 3 * d * F


def attn_pair_flops(w: dict) -> int:
    """FLOPs of one (query, key) pair in one layer, all heads: the score
    (2·H·hd) and the value product (2·H·vhd)."""
    return 2 * w["H"] * (w["hd"] + w["vhd"])


def kv_bytes_per_token(w: dict, elt: int = 2) -> int:
    """KV bytes of one token in one layer."""
    return w["KH"] * (w["hd"] + w["vhd"]) * elt


def decode_attn(w: dict, kv_lens, elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged-attention decode call, one layer: each
    live row's query over its ``kv_len`` live keys and values.  Bytes:
    the live KV read once, the query read and the output written."""
    kv = np.asarray(kv_lens, np.float64)
    rows = len(kv)
    flops = attn_pair_flops(w) * kv.sum()
    byts = (kv_bytes_per_token(w, elt) * kv.sum()
            + rows * w["H"] * (w["hd"] + w["vhd"]) * elt)
    return float(flops), float(byts)


def causal_pairs(starts, valids) -> float:
    """Pairs (query, key <= query) of chunks of ``valid`` queries that
    start at ``start`` and see the ``start`` keys before them."""
    s = np.asarray(starts, np.float64)
    n = np.asarray(valids, np.float64)
    return float((n * s + n * (n + 1) / 2).sum())


def prefill_attn(w: dict, starts, valids, elt: int = 2
                 ) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged-attention prefill call, one layer: each
    row's ``valid`` queries causally over its ``start + valid`` keys.
    Bytes: each row's live KV read once, its real queries read and
    outputs written."""
    s = np.asarray(starts, np.float64)
    n = np.asarray(valids, np.float64)
    flops = attn_pair_flops(w) * causal_pairs(s, n)
    byts = (kv_bytes_per_token(w, elt) * (s + n).sum()
            + n.sum() * w["H"] * (w["hd"] + w["vhd"]) * elt)
    return float(flops), float(byts)


def decode_step_flops(w: dict, kv_lens) -> float:
    """Useful FLOPs of one decode step over its live rows: every layer's
    matmuls and attention for each row, and the output head."""
    kv = np.asarray(kv_lens, np.float64)
    rows = len(kv)
    per_token = 2 * (w["L"] * layer_matmul_params(w) + w["d"] * w["V"])
    return float(per_token * rows
                 + w["L"] * attn_pair_flops(w) * kv.sum())


def prefill_step_flops(w: dict, starts, valids) -> float:
    """Useful FLOPs of one prefill step: every layer's matmuls and causal
    attention for the real tokens, and the output head once for each row
    that carries tokens."""
    n = np.asarray(valids, np.float64)
    rows = float((n > 0).sum())
    return float(2 * w["L"] * layer_matmul_params(w) * n.sum()
                 + 2 * w["d"] * w["V"] * rows
                 + w["L"] * attn_pair_flops(w) * causal_pairs(starts, n))


def least_time(flops: float, byts: float, peak: dict) -> tuple[float, str]:
    """Roofline least time (s) and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
