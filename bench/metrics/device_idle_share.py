"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"


def read(data):
    if data.trace is None or data.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - data.trace["busy_s"] / data.trace["window_s"])
