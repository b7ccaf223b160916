"""chip_smoke.py's phases at a reduced size on the CPU (kernels in
interpret mode), its refusal to run without a TPU, and the compile-cache
helper its entry points share."""
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.core.pruner import prune_model  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import build  # noqa: E402

FLAGS = ("--max-seqs", "4", "--block-size", "4", "--max-len", "64",
         "--chunk-size", "8", "--gen", "4")


@pytest.fixture
def kernel_path(monkeypatch):
    """Serve through the paged-attention kernel (interpreted here), as the
    TPU backend does."""
    from repro.kernels.paged_attention import ops
    monkeypatch.setattr(ops, "_kernel_path",
                        lambda window: isinstance(window, int))


@pytest.fixture(scope="module")
def small():
    cfg = reduced(get_config(chip_smoke.ARCH))
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg):
    return chip_smoke.make_prompts(cfg.vocab_size, seed=1, n=6,
                                   lens=(6, 40), prefix=8)


def test_make_prompts_shares_prefix():
    ps = chip_smoke.make_prompts(1000, seed=3, n=8, lens=(20, 60), prefix=16)
    assert len(ps) == 8 and all(20 <= len(p) <= 60 for p in ps)
    assert all(p[:16] == ps[0][:16] for p in ps[::2])
    assert ps[1][:16] != ps[0][:16]


def test_kernel_parity_phase(small):
    model, _ = small
    rep = chip_smoke.kernel_parity(model.cfg, max_seqs=3, block_size=4,
                                   max_len=32, chunk=8, seed=0)
    for case in ("decode", "prefill", "int8_decode"):
        assert rep[case + "_err"] <= chip_smoke.KERNEL_TOL
        assert rep[case + "_calls"] == 0      # interpreted off the TPU


def test_serve_phases_dense_and_pruned(small, kernel_path):
    model, params = small
    prompts = _prompts(model.cfg)
    dense, rep = chip_smoke.serve_phase(model, params, prompts, FLAGS)
    assert [len(t) for t in dense] == [4] * len(prompts)
    assert rep["prefix_hits"] > 0
    assert rep["max_logit_gap"] <= chip_smoke.LOGIT_TOL
    assert rep["kernel_calls"] == {"step": 0, "prefill": 0}
    pr = prune_model(model, params, chip_smoke.PRUNE_RATIO)
    pruned, rep = chip_smoke.serve_phase(build(pr.cfg), pr.params, prompts,
                                         FLAGS)
    assert [len(t) for t in pruned] == [4] * len(prompts)


def test_logit_gap_check_rejects_wrong_tokens():
    ref = np.array([[0.0, 3.0, 2.9], [1.0, 0.0, -1.0]], np.float32)
    assert list(chip_smoke.logit_gaps(ref, [1, 0])) == [0.0, 0.0]
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.logit_gaps(ref, [1, 2])


def test_obspa_phase(small):
    _, params = small
    rep = chip_smoke.obspa_phase(params, seed=0)
    assert rep["rel_err"] <= chip_smoke.OBSPA_TOL
    assert rep["pruned_columns"] > 0


def test_main_refuses_cpu_host(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.compile_cache_dir() == \
        compile_cache.REPO_ROOT / ".jax_cache"
    assert str(compile_cache.REPO_ROOT) == os.path.realpath(repo)
