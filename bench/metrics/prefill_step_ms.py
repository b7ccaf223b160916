"""Device time per execution of the chunked-prefill step program (device
trace)."""
import trace_reduce

UNIT = "ms"
PROGRAM = "_prefill_impl"


def read(data):
    if data.trace is None:
        return None
    t, n = trace_reduce.module_time(data.trace, PROGRAM)
    return t / n * 1e3 if n else None
