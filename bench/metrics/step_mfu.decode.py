"""Model FLOP/s utilization of the decode step program: useful FLOPs of
the live rows (matmuls, attention over live KV, output head;
bench/counts.py) over the program's device time times the bf16 peak."""
import counts
import trace_reduce

UNIT = "%"
PROGRAM = "_step_impl"


def read(data):
    if data.trace is None or not data.calls["decode"]:
        return None
    t, n = trace_reduce.module_time(data.trace, PROGRAM)
    if t <= 0:
        return None
    f = sum(counts.decode_step_flops(data.widths, kv)
            for (kv,) in data.calls["decode"])
    return 100.0 * f / (t * data.peak["bf16_flops_per_s"])
