#!/usr/bin/env python3
"""Size a configuration's KV pool from an AOT compile of its step programs.

    JAX_PLATFORMS=cpu python3 bench/size_pool.py qwen3-1.7b --blocks 3300

Compiles the engine's decode and chunked-prefill step programs (the
engine's own step bodies, pools donated) at the configuration's serve
flags for a described TPU v5e, with no chip attached, and prints each
program's memory analysis and the pool this rule allows:

    params + pool + max(decode, prefill temporaries) + margin <= limit

where the temporaries of a program grow with the pool (the decode step
holds a pool-sized temporary), so the rule is solved from two compiles
at two pool sizes, linearly.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 1 << 30


def analyse(conf: dict, blocks: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.configs.base import ArchConfig
    from repro.kernels.paged_attention import ops
    from repro.launch.serve import build_parser
    from repro.models import build
    from repro.serve.engine import Engine

    ops._on_tpu = lambda: True          # the kernel path, as on the chip
    ref = harness.load_module(os.path.join(HERE, "reference",
                                           conf["reference"] + ".py"), "ref")
    arch = ArchConfig(**ref.program_arch(conf))
    model = build(arch)
    a = build_parser().parse_args(["--arch", arch.name,
                                   *conf["serve"]["flags"]])
    B, C, bs = a.max_seqs, a.chunk_size, a.block_size
    NB = -(-a.max_len // bs)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype), shapes)
    cache = jax.tree.map(lambda s: sds(s.shape, s.dtype), jax.eval_shape(
        functools.partial(model.init_paged_cache, num_blocks=blocks,
                          block_size=bs, max_seqs=B)))
    ns = types.SimpleNamespace(model=model, _sample=functools.partial(
        Engine._sample, None))
    i32, f32 = jnp.int32, jnp.float32
    key = sds((2,), jnp.uint32)
    out = {}
    step = jax.jit(functools.partial(Engine._step_impl, ns),
                   donate_argnums=(1,))
    c = step.lower(params, cache, sds((B,), i32), sds((B,), i32),
                   sds((B, NB), i32), sds((B,), f32), sds((B,), jnp.bool_),
                   key).compile()
    out["decode"] = c.memory_analysis()
    pre = jax.jit(functools.partial(Engine._prefill_impl, ns),
                  donate_argnums=(1,))
    c = pre.lower(params, cache, sds((B, C), i32), sds((B, C), i32),
                  sds((B,), i32), sds((B, NB), i32), sds((B,), i32),
                  sds((B,), f32), key).compile()
    out["prefill"] = c.memory_analysis()
    pbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    kbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    return {"params": pbytes, "pool": kbytes,
            "temp": {k: v.temp_size_in_bytes for k, v in out.items()},
            "args": {k: v.argument_size_in_bytes for k, v in out.items()}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--blocks", type=int, nargs=2, default=(1000, 3000))
    ap.add_argument("--limit-gib", type=float, default=15.75,
                    help="device bytes_limit in GiB")
    ap.add_argument("--margin-gib", type=float, default=1.0)
    args = ap.parse_args()
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        conf = json.load(f)
    (b0, r0), (b1, r1) = [(b, analyse(conf, b)) for b in args.blocks]
    for b, r in ((b0, r0), (b1, r1)):
        print(json.dumps({"blocks": b, **r}))
    per_block = (r1["pool"] - r0["pool"]) / (b1 - b0)
    worst = max(r1["temp"].values())
    t_per_block = (max(r1["temp"].values()) - max(r0["temp"].values())) \
        / (b1 - b0)
    fixed = r1["params"] + worst - t_per_block * b1
    room = (args.limit_gib - args.margin_gib) * GIB - fixed
    print(json.dumps({"pool_bytes_per_block": per_block,
                      "temp_bytes_per_block": t_per_block,
                      "num_blocks": int(room // (per_block + t_per_block))}))


if __name__ == "__main__":
    main()
