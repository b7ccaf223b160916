"""The reduction from a profiler trace to device numbers: busy union,
per-program device time, kernel time and the breakdown."""
import glob
import gzip
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP = "/device:TPU:0"


def _synthetic():
    # two programs on one chip; ops overlap inside the first; a host
    # step annotation spans the gap between them
    return {
        "modules": [["jit__step_impl", 0, 100, CHIP],
                    ["jit__prefill_impl", 150, 250, CHIP]],
        "ops": [["while.1", 0, 100, "jit__step_impl", True, CHIP],
                ["fusion.1", 0, 60, "jit__step_impl", False, CHIP],
                ["paged_attention", 40, 40, "jit__step_impl", False, CHIP],
                ["paged_attention", 150, 50, "jit__prefill_impl", False,
                 CHIP],
                ["fusion.2", 200, 200, "jit__prefill_impl", False, CHIP]],
        "host": [["bench.step", 0, 120], ["bench.idle", 120, 30],
                 ["bench.step", 150, 300]],
    }


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_summary_of_a_synthetic_trace():
    s = tr.summary(_synthetic())
    assert s["window_s"] == pytest.approx(450e-9)
    # busy: [0, 100) and [150, 400)
    assert s["busy_s"] == pytest.approx(350e-9)
    assert tr.module_time(s, "_step_impl") == (pytest.approx(100e-9), 1)
    assert tr.module_time(s, "_prefill_impl") == (pytest.approx(250e-9), 1)
    assert tr.op_time(s, "_step_impl", "paged_attention") == \
        pytest.approx(40e-9)
    assert tr.op_time(s, "_prefill_impl", "paged_attention") == \
        pytest.approx(50e-9)
    bd = s["breakdown"]
    assert bd["device_ops"][0] == ["jit__prefill_impl/fusion.2",
                                   pytest.approx(200e-9)]
    assert "jit__step_impl/while.1" not in dict(bd["device_ops"])
    # the idle gaps, each named by the host annotation open at its
    # middle: [100, 150) while the host idled, [400, 450) in a step
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.idle", "bench.step"]
    assert [g[1] for g in bd["idle_gaps"]] == [pytest.approx(50e-9),
                                               pytest.approx(50e-9)]


def test_program_name_drops_the_run_id():
    assert tr.program_name("jit__step_impl(1234)") == "jit__step_impl"
    assert tr.program_name("jit_f") == "jit_f"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A 0.4 s device trace of the pruned chat cell on one TPU v5e."""
    src = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))[0]
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(src) as f, open(d / "chip.xplane.pb", "wb") as g:
        g.write(f.read())
    return str(d)


def test_recorded_trace(recorded):
    from jax.profiler import ProfileData
    prof = tr.load(recorded)
    s = tr.summary(prof)
    # independent reading of the same file: module events and op events
    pd = ProfileData.from_file(os.path.join(recorded, "chip.xplane.pb"))
    plane = next(p for p in pd.planes if p.name == CHIP)
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    per: dict = {}
    for ev in lines["XLA Modules"]:
        per.setdefault(ev.name.split("(")[0], []).append(ev.duration_ns)
    for name, durs in per.items():
        t, n = tr.module_time(s, name)
        assert n == len(durs) and t == pytest.approx(sum(durs) * 1e-9)
    assert {"jit__step_impl", "jit__prefill_impl"} <= set(per)
    # busy: the union of op intervals, by a sweep over sorted edges
    iv = sorted((e.start_ns, e.start_ns + e.duration_ns)
                for e in lines["XLA Ops"])
    busy, end = 0.0, -1.0
    for a, b in iv:
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    assert s["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    # the kernel: one paged_attention per layer (28) per step program run
    kernel = [e for e in lines["XLA Ops"] if e.name.startswith(
        "%paged_attention")]
    assert len(kernel) == 28 * (len(per["jit__step_impl"])
                                + len(per["jit__prefill_impl"]))
    k = tr.op_time(s, "_step_impl", "paged_attention") + \
        tr.op_time(s, "_prefill_impl", "paged_attention")
    assert k == pytest.approx(sum(e.duration_ns for e in kernel) * 1e-9)
    assert 0 < tr.op_time(s, "_step_impl", "paged_attention") < \
        tr.module_time(s, "_step_impl")[0]
    # breakdown: at most ten of each, longest first, no loop containers
    bd = s["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    times = [t for _, t in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert not any("/while" in n for n, _ in bd["device_ops"])
    gaps = [t for _, t in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= s["window_s"] - s["busy_s"] + 1e-9
