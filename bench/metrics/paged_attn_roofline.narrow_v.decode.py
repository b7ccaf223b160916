"""Roofline share of the paged-attention kernel in the decode step of a
model whose value heads are narrower than the 128 lanes (the SPA-pruned
configuration): what ``paged_attn_roofline.decode`` reads, counted at the
cell's own widths (``vhd``), under a name of its own so that the
narrow-V kernel's share is never compared with the dense one."""
import os

import harness

_BASE = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "paged_attn_roofline.decode.py"),
    "metric_paged_attn_roofline_decode")
UNIT = _BASE.UNIT
read = _BASE.read
