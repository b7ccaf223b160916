"""Median of time to first token, from the due time (host clock)."""
import series

UNIT = "ms"


def read(data):
    v = series.percentile(series.ttfts(data), 50)
    return None if v is None else v * 1e3
