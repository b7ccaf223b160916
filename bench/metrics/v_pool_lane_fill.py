"""Share of the lanes the TPU stores for the V pool that hold values: the
engine's counters over the window, ``serve/v_pool_lanes_value`` over
``serve/v_pool_lanes_stored`` (each summed over the pool once for every
step program that carries it).  The TPU's tiles are 128 lanes wide, so a
pool whose minor dimension is 64 values reads 50%, one whose heads lie
side by side on the lanes 100%."""
UNIT = "%"


def read(data):
    stored = data.counters.get("v_pool_lanes_stored")
    if not stored:
        return None
    return 100.0 * data.counters.get("v_pool_lanes_value", 0.0) / stored
