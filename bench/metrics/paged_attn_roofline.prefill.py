"""Roofline share of the paged-attention kernel in the chunked-prefill
step: least time of the causal work of the real query rows over their
live KV (bench/counts.py) over the kernel's device time."""
import counts
import trace_reduce

UNIT = "%"
PROGRAM, KERNEL = "_prefill_impl", "paged_attention"


def read(data):
    if data.trace is None or not data.calls["prefill"]:
        return None
    t = trace_reduce.op_time(data.trace, PROGRAM, KERNEL)
    if t <= 0:
        return None
    w = data.widths
    f = b = 0.0
    for starts, valids in data.calls["prefill"]:
        pf, pb = counts.prefill_attn(w, starts, valids)
        f += pf * w["L"]
        b += pb * w["L"]
    return 100.0 * counts.least_time(f, b, data.peak)[0] / t
