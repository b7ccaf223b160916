"""Host time per engine step: step wall minus the device sync, over the
window's steps (the engine's own phase timers)."""
UNIT = "ms"


def read(data):
    step, n = data.phases.get("step", (0.0, 0))
    sync, _ = data.phases.get("sync", (0.0, 0))
    return (step - sync) / n * 1e3 if n else None
