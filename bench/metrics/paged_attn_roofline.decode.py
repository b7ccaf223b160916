"""Roofline share of the paged-attention kernel in the decode step: the
least time the live KV work needs on this chip (operations over peak or
bytes over bandwidth, whichever is larger) over the kernel's device time.
The work is counted from the live rows' KV lengths of every decode call in
the traced window (bench/counts.py), one call per layer."""
import counts
import trace_reduce

UNIT = "%"
PROGRAM, KERNEL = "_step_impl", "paged_attention"


def read(data):
    if data.trace is None or not data.calls["decode"]:
        return None
    t = trace_reduce.op_time(data.trace, PROGRAM, KERNEL)
    if t <= 0:
        return None
    w = data.widths
    f = b = 0.0
    for (kv,) in data.calls["decode"]:
        df, db = counts.decode_attn(w, kv)
        f += df * w["L"]
        b += db * w["L"]
    return 100.0 * counts.least_time(f, b, data.peak)[0] / t
