"""Set-up: from process start to the window's opening (loading, weights,
compilation or cache loads, warm-up and the lead-in)."""
UNIT = "s"


def read(data):
    return data.setup_s
