"""From a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three lists, all in nanoseconds on the profiler's clock:

  ops      device operations (XLA Ops line of each TPU plane): the HLO
           instruction's name (``paged_attention.10``, ``fusion.166``),
           start, duration, the program (XLA module) that ran it, whether
           it is a loop or call that holds other operations, and the chip
  modules  device program executions (XLA Modules line): name, start,
           duration, chip
  host     the benchmark's own host annotations (``bench.*``): name,
           start, duration

``summary`` reduces them: the traced window, the device's busy time (the
union of operation intervals, averaged over chips), device time and count
per program, device time per (program, operation), and the breakdown the
result line carries: the operations that took most time and the longest
idle gaps with what the host was doing in each.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

TOP = 10
_SUFFIX = re.compile(r"\(\d+\)$")
# the trace names an operation by its HLO text: "%name = type opcode(...)"
_HLO = re.compile(r"%([\w.\-]+) = ")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_CONTAINERS = ("while", "conditional", "call")


def program_name(name: str) -> str:
    """``jit__step_impl(123)`` -> ``jit__step_impl``."""
    return _SUFFIX.sub("", name)


def hlo_name(text: str) -> tuple[str, bool]:
    """(instruction name, holds other operations) of an HLO op event."""
    m = _HLO.match(text)
    if m is None:
        return text, False
    rest = _LAYOUT.sub("", text[m.end():])
    depth, i = 0, 0
    for i, ch in enumerate(rest):         # skip the (possibly tuple) type
        depth += (ch == "(") - (ch == ")")
        if depth == 0 and ch in " )":
            break
    opcode = rest[i + 1:].lstrip().split("(", 1)[0]
    return m.group(1), opcode in _CONTAINERS


def options():
    """Profiler options of a traced run: device and annotated host
    activity, without tracing every Python call (which slows the host)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: dict = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        out["modules"].append(
                            [program_name(ev.name), ev.start_ns,
                             ev.duration_ns, chip])
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name, holds = hlo_name(ev.name)
                        st = dict(ev.stats)
                        out["ops"].append(
                            [name, ev.start_ns, ev.duration_ns,
                             program_name(str(st.get("hlo_module", ""))),
                             holds, chip])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append([ev.name, ev.start_ns,
                                            ev.duration_ns])
    _attach_modules(out)
    return out


def _attach_modules(prof: dict) -> None:
    """Give each operation without a program name the program whose
    execution on the same chip contains its start."""
    by_chip: dict[str, list] = {}
    for name, s, d, chip in prof["modules"]:
        by_chip.setdefault(chip, []).append((s, s + d, name))
    for v in by_chip.values():
        v.sort()
    for op in prof["ops"]:
        if op[3]:
            continue
        mods = by_chip.get(op[5], [])
        i = bisect.bisect_right(mods, (op[1], float("inf"), "")) - 1
        if i >= 0 and mods[i][0] <= op[1] < mods[i][1]:
            op[3] = mods[i][2]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(prof: dict) -> tuple[float, float]:
    """The traced window: from the first to the last event, host or
    device."""
    ends = [(s, s + d) for _, s, d in prof["host"]]
    ends += [(o[1], o[1] + o[2]) for o in prof["ops"]]
    return min(s for s, _ in ends), max(e for _, e in ends)


def summary(prof: dict) -> dict:
    w0, w1 = window_of(prof)
    chips = sorted({o[5] for o in prof["ops"]}) or [""]
    busy = 0.0
    gaps: list[tuple[float, float]] = []
    for chip in chips:
        iv = union([(o[1], o[1] + o[2]) for o in prof["ops"]
                    if o[5] == chip])
        busy += sum(e - s for s, e in iv)
        if chip == chips[0]:
            edges = [w0] + [x for se in iv for x in se] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    modules: dict[str, list] = {}
    for name, s, d, chip in prof["modules"]:
        m = modules.setdefault(name, [0.0, 0])
        m[0] += d * 1e-9 / len(chips)
        m[1] += 1
    ops: dict[str, dict[str, float]] = {}
    for name, s, d, mod, holds, chip in prof["ops"]:
        if holds:               # a loop's time is its operations' time
            continue
        per = ops.setdefault(mod, {})
        per[name] = per.get(name, 0.0) + d * 1e-9 / len(chips)
    by_name: dict[str, float] = {}
    for mod, per in ops.items():
        for name, t in per.items():
            by_name[f"{mod}/{name}"] = t
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    host = sorted((s, s + d, n) for n, s, d in prof["host"])
    starts = [h[0] for h in host]

    def doing(t: float) -> str:
        """The benchmark's host annotation open at ``t`` (they do not
        nest: submit, step and idle follow each other)."""
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < host[i][1]:
            return host[i][2]
        return "host outside the benchmark's calls"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9 / len(chips),
        "modules": modules,
        "ops": ops,
        "breakdown": {
            "device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": [[doing((s + e) / 2), (e - s) * 1e-9]
                          for s, e in longest]},
    }


def op_time(summ: dict, module_part: str, op_part: str) -> float:
    """Device seconds of operations named with ``op_part`` inside
    programs named with ``module_part``."""
    return sum(t for mod, per in summ["ops"].items() if module_part in mod
               for name, t in per.items() if op_part in name)


def module_time(summ: dict, module_part: str) -> tuple[float, int]:
    """(device seconds, executions) of programs named with
    ``module_part``."""
    t = n = 0
    for name, (s, c) in summ["modules"].items():
        if module_part in name:
            t += s
            n += c
    return t, n
