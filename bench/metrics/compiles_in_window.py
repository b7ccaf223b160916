"""Programs the engine traced while it stepped in the window (the engine's
``serve/compiles``): every shape is warmed in set-up, so any is a stall."""
UNIT = "count"


def read(data):
    return data.counters.get("compiles")
