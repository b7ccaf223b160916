"""Roofline share of the paged-attention kernel in the chunked-prefill
step of a model whose value heads are narrower than the 128 lanes (the
SPA-pruned configuration): what ``paged_attn_roofline.prefill`` reads,
counted at the cell's own widths (``vhd``), under a name of its own."""
import os

import harness

_BASE = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "paged_attn_roofline.prefill.py"),
    "metric_paged_attn_roofline_prefill")
UNIT = _BASE.UNIT
read = _BASE.read
