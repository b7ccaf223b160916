import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count at first init).  Do not move them.  CPU-only tool: it compiles
# for 512 virtual host devices and must never claim an accelerator.

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and record memory / cost / collective analysis for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both

Each cell lowers the *production* step function:
  train_4k        -> jit(train_step)   (fwd + bwd + AdamW, donated state)
  prefill_32k     -> jit(prefill_step) (full-sequence forward to logits)
  decode_*        -> jit(serve_step)   (one token through the KV/SSM cache)
  paged_decode_*  -> jit(paged_decode_step)  (serving engine: block-pool
                     cache + block tables + per-slot positions)
  paged_prefill_* -> jit(paged_prefill_step) (serving engine: one chunked
                     prefill chunk per slot into the block pool)
  spec_verify_*   -> jit(paged_verify_step)  (speculative decode: one
                     multi-token verify pass, logits at every position)
"""
import argparse
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax import tree_util as jtu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import (ASSIGNED_ARCHS, SHAPES, cell_supported, get_config)
from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.collectives import collective_bytes
from repro.distributed.sharding import (ShardingRules, tree_shardings,
                                        use_rules)
from repro.launch.mesh import arch_rules, make_production_mesh, serve_rules
from repro.models import build
from repro.train.optim import OptConfig, init_opt_state, make_train_step

# Logical axes -> NamedShardings over a pytree (moved to
# distributed.sharding so the serving engine shares it; old name kept for
# callers of the dry-run module).
shardings_for = tree_shardings


def batch_axes(cfg: ArchConfig, with_targets: bool) -> dict:
    ax: dict[str, Any] = {}
    if cfg.family == "audio":
        ax["frames"] = ("batch", "seq", None)
        if with_targets:
            ax["targets"] = ("batch", "seq")
    elif cfg.family == "vlm":
        ax["patches"] = ("batch", None, None)
        ax["tokens"] = ("batch", "seq")
    else:
        ax["tokens"] = ("batch", "seq")
    return ax


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               rule_overrides: dict | None = None,
               opt_overrides: dict | None = None):
    """Lower + compile one cell; returns (record, compiled)."""
    cfg = get_config(arch)
    if cfg.n_experts:
        # hierarchical dispatch: one local group per DP shard (§Perf B1:
        # 3.1x collective bytes).  token count must divide the group count.
        dp = 32 if multi_pod else 16
        shape0 = SHAPES[shape_name]
        if (shape0.global_batch * shape0.seq_len) % dp == 0:
            cfg = cfg.replace(moe_dispatch_groups=dp)
    for k, v in (opt_overrides or {}).items():
        cfg = cfg.replace(**{k: v})
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}, None

    # the sharding rules are a tracing side-channel (module state read by
    # constrain()); jax's trace cache keys on function/closure equality and
    # would otherwise replay a previous cell's trace with different rules
    jax.clear_caches()
    mesh = make_production_mesh(multi_pod=multi_pod)
    # the sharded serving cell lowers under the engine's rule set (slots
    # data-parallel, pools tensor-parallel; launch/mesh.serve_rules) with
    # the concrete mesh threaded through — exactly what Engine(mesh=...)
    # traces, so the grid measures the production serve step
    serve_cell = shape.kind == "paged_decode_sharded"
    if serve_cell:
        rules = serve_rules(cfg, mesh, extra=rule_overrides)
    else:
        rules = arch_rules(cfg, mesh, shape, extra=rule_overrides)
    model = build(cfg)
    key = jax.random.PRNGKey(0)

    params_sds = jax.eval_shape(model.init, key)
    params_sh = shardings_for(mesh, rules, model.param_axes(), params_sds)

    t0 = time.time()
    with use_rules(rules, mesh=mesh if serve_cell else None), mesh:
        if shape.kind == "train":
            opt_sds = jax.eval_shape(init_opt_state, params_sds)
            opt_sh = {"m": params_sh, "v": params_sh,
                      "step": NamedSharding(mesh, P())}
            batch_sds = model.batch_spec(shape, with_targets=True)
            batch_sh = shardings_for(mesh, rules, batch_axes(cfg, True),
                                     batch_sds)
            step = make_train_step(model, OptConfig())
            lowered = jax.jit(
                step,
                in_shardings=(params_sh, opt_sh, batch_sh),
                donate_argnums=(0, 1),
            ).lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_sds = model.batch_spec(shape, with_targets=False)
            batch_sh = shardings_for(mesh, rules, batch_axes(cfg, False),
                                     batch_sds)

            def prefill_step(params, batch):
                logits = model.forward(params, batch)
                return logits[:, -1, :]

            lowered = jax.jit(
                prefill_step, in_shardings=(params_sh, batch_sh),
            ).lower(params_sds, batch_sds)
        elif shape.kind in ("paged_decode", "paged_prefill", "spec_verify",
                            "paged_decode_sharded"):
            # serving-engine steps over the paged block pool
            # (DESIGN.md §8/§9/§10)
            block_size = 64
            if shape.kind in ("paged_decode", "paged_decode_sharded"):
                spec = model.paged_decode_input_spec(shape, block_size)
            elif shape.kind == "paged_prefill":
                spec = model.paged_prefill_input_spec(shape, block_size)
            else:
                spec = model.paged_verify_input_spec(shape, block_size)
            from repro.kernels.paged_attention import is_quantized
            cache_sh = shardings_for(
                mesh, rules,
                model.paged_cache_axes(
                    quantized=is_quantized(shape.cache_dtype)),
                spec["cache"])
            slot_axis = "serve_batch" if serve_cell else "batch"
            batch_sh = {
                k: NamedSharding(mesh, rules.spec(
                    (slot_axis,) + (None,) * (len(v.shape) - 1),
                    shape=v.shape))
                for k, v in spec.items() if k != "cache"}

            if shape.kind in ("paged_decode", "paged_decode_sharded"):
                def paged_step(params, cache, tokens, positions,
                               block_tables, active):
                    return model.paged_decode_step(
                        params, cache, tokens, positions, block_tables,
                        active)
                order = ("tokens", "positions", "block_tables", "active")
            else:                  # paged_prefill / spec_verify: same ABI
                chunk_fn = (model.paged_prefill_step
                            if shape.kind == "paged_prefill"
                            else model.paged_verify_step)

                def paged_step(params, cache, tokens, positions, slots,
                               block_tables, valid):
                    return chunk_fn(params, cache, tokens, positions,
                                    slots, block_tables, valid)
                order = ("tokens", "positions", "slots", "block_tables",
                         "valid")
            lowered = jax.jit(
                paged_step,
                in_shardings=(params_sh, cache_sh)
                + tuple(batch_sh[k] for k in order),
                donate_argnums=(1,),
            ).lower(params_sds, spec["cache"],
                    *(spec[k] for k in order))
        else:                                   # decode
            dec = model.decode_input_spec(shape)
            cache_sh = shardings_for(
                mesh, rules,
                model.cache_axes(long_context=shape.name == "long_500k"),
                dec["cache"])
            in_sh = (params_sh, cache_sh,
                     NamedSharding(mesh, rules.spec(
                         ("batch",), shape=dec["tokens"].shape)),
                     NamedSharding(mesh, P()))

            def serve_step(params, cache, tokens, pos):
                return model.decode_step(params, cache, tokens, pos)

            lowered = jax.jit(
                serve_step, in_shardings=in_sh, donate_argnums=(1,),
            ).lower(params_sds, dec["cache"], dec["tokens"], dec["pos"])
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = collective_bytes(compiled.as_text())

    record = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok",
        "mesh": dict(zip(mesh.axis_names, [int(s) for s in
                                           mesh.devices.shape])),
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "param_count": int(sum(
            int(jnp.prod(jnp.array(x.shape))) for x in
            jtu.tree_leaves(params_sds))),
    }
    return record, compiled


def run_cells(archs, shapes, pods, out_path=None, print_analysis=True):
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec, compiled = lower_cell(arch, shape, mp)
                    if rec["status"] == "ok" and print_analysis:
                        print(f"[ok]   {tag}: compile={rec['compile_s']}s "
                              f"flops/dev={rec['flops_per_device']:.3e} "
                              f"peak={rec['memory']['peak_est_bytes']/2**30:.2f}GiB "
                              f"coll={rec['collectives']['total_bytes']/2**30:.3f}GiB",
                              flush=True)
                    elif rec["status"] == "skipped":
                        print(f"[skip] {tag}: {rec['reason']}", flush=True)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[ERR]  {tag}: {e!r}", flush=True)
                results.append(rec)
                if out_path:
                    with open(out_path, "w") as f:
                        json.dump(results, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="both")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]

    results = run_cells(archs, shapes, pods, out_path=args.out)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
