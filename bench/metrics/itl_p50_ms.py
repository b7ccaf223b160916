"""Median gap between output tokens over every gap of the window's requests."""
import series

UNIT = "ms"


def read(data):
    v = series.percentile(series.itl_gaps(data), 50)
    return None if v is None else v * 1e3
