"""The pruned configuration's widths are what SPA-L1 pruning at 0.5 gives
Qwen3-1.7B: ``prune_model`` on one layer at published widths (the
vocabulary cut, since it plays no part in the widths; per_group mode
prunes every layer alike)."""
import json
import os

import jax

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_prune_model_gives_the_spa50_widths():
    from repro.configs.base import ArchConfig
    from repro.core.pruner import prune_model
    from repro.models import build
    ref = harness.load_module(os.path.join(BENCH, "reference", "qwen3.py"),
                              "ref_widths")
    dense = _conf("qwen3-1.7b")
    dense.update(num_hidden_layers=1, vocab_size=1024)
    model = build(ArchConfig(**ref.program_arch(dense)))
    params = ref.make_params(dense, 0)
    pr = prune_model(model, params, 0.5)
    want = ref.dims(_conf("qwen3-1.7b-spa50"))
    c = pr.cfg
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim_, c.v_head_dim_,
            c.d_ff) == (want["d"], want["H"], want["KH"], want["hd"],
                        want["vhd"], want["F"])
    got = ref.param_shapes({**_conf("qwen3-1.7b-spa50"),
                            "num_hidden_layers": 1, "vocab_size": 1024})
    shapes = jax.tree.map(lambda a: tuple(a.shape), pr.params)
    assert shapes == jax.tree.map(tuple, got,
                                  is_leaf=lambda x: isinstance(x, tuple))
