"""99th percentile of the gaps between output tokens (host clock)."""
import series

UNIT = "ms"


def read(data):
    v = series.percentile(series.itl_gaps(data), 99)
    return None if v is None else v * 1e3
