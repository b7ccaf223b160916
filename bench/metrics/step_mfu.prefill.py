"""Model FLOP/s utilization of the chunked-prefill step program: useful
FLOPs of the real prompt tokens (bench/counts.py) over the program's
device time times the bf16 peak."""
import counts
import trace_reduce

UNIT = "%"
PROGRAM = "_prefill_impl"


def read(data):
    if data.trace is None or not data.calls["prefill"]:
        return None
    t, n = trace_reduce.module_time(data.trace, PROGRAM)
    if t <= 0:
        return None
    f = sum(counts.prefill_step_flops(data.widths, s, v)
            for s, v in data.calls["prefill"])
    return 100.0 * f / (t * data.peak["bf16_flops_per_s"])
