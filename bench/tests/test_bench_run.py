"""The entry point refuses a machine without a TPU: a non-zero exit and
no result line."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@pytest.mark.parametrize("seed", [7, 2**33 + 1])
def test_no_tpu_exits_nonzero_without_metrics(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-1.7b.chat-prefix", "--seed", str(seed),
                        "--seconds", "10", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_every_named_file_exists():
    """Each configuration, mix, cell and metric named by BENCHMARK.json is
    a file of its own under bench/."""
    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in cell.end_to_end
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


class _FakeEngine:
    """Step programs whose parameters come in another order than the
    engine's, and one that lacks a parameter the call log needs."""

    def _step_impl(self, params, active, cache, positions):
        return None

    def _prefill_impl(self, params, cache, tokens):
        return None

    def __init__(self):
        self._step_fn = lambda *a, **k: "stepped"
        self._prefill_fn = lambda *a, **k: "prefilled"


def test_call_log_binds_arguments_by_name(capsys):
    import numpy as np

    import harness
    eng = _FakeEngine()
    log = harness._CallLog(eng)
    log.on = True
    assert eng._step_fn("p", np.array([True, False]), "c",
                        np.array([4, 9])) == "stepped"
    assert eng._prefill_fn("p", "c", "t") == "prefilled"
    got = log.read()
    assert [kv.tolist() for (kv,) in got["decode"]] == [[5]]
    assert got["prefill"] == []
    assert "_prefill_impl has no parameters" in capsys.readouterr().err
