"""Median of the wait before admission: the generator's lateness
(due to submit) plus the engine's queue wait (submit to first admission)."""
import math

import series

UNIT = "ms"


def read(data):
    v = series.percentile(
        [r.submit - r.due + r.queue_wait_s for r in data.in_window()
         if not math.isnan(r.queue_wait_s)], 50)
    return None if v is None else v * 1e3
