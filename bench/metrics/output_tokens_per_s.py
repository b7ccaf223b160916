"""Output tokens made in the window over its length (host clock)."""
import series

UNIT = "tokens/s"


def read(data):
    t0, t1 = data.window
    return series.window_tokens(data) / (t1 - t0)
