import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"     # CPU-only: 512 virtual host devices

"""Roofline analysis (EXPERIMENTS.md §Roofline).

Per (arch x shape) on the single-pod 16x16 mesh, derive the three terms:

  compute    = HLO_FLOPs/device   / 197e12 FLOP/s   (TPU v5e bf16 peak)
  memory     = HLO_bytes/device   / 819e9  B/s      (HBM bandwidth)
  collective = coll_bytes/device  / 50e9   B/s      (ICI per link)

``compiled.cost_analysis()`` counts a scan body ONCE regardless of trip
count, so per-cell numbers come from depth-1 and depth-2 *unrolled*
lowerings: per-layer = f(2) - f(1); total = f(1) + (L-1)·per-layer.  (The
unrolled path remats exactly like the production scan, so recompute FLOPs
are included.)  Peak memory comes from the full-depth scan compile
(results/dryrun_baseline.json).

MODEL_FLOPS uses 6·N·D (train) / 2·N·D (inference), N = active params,
plus the quadratic attention term — the "useful compute" yardstick.
"""
import argparse
import json
from typing import Any

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES, cell_supported, get_config
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch.dryrun import lower_cell

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Analytic useful FLOPs per step (global, all devices)."""
    N = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        T = B * S
        f = 6.0 * N * T
        if cfg.n_heads:
            f += 3 * 4 * B * cfg.n_heads * cfg.head_dim_ * S * S * 0.5
        return f
    if shape.kind == "prefill":
        T = B * S
        f = 2.0 * N * T
        if cfg.n_heads:
            f += 4 * B * cfg.n_heads * cfg.head_dim_ * S * S * 0.5
        return f
    if shape.kind == "spec_verify":
        # C = K+1 speculative tokens scored against an S-token cache
        from repro.configs import SPEC_VERIFY_CHUNK
        C = SPEC_VERIFY_CHUNK
        f = 2.0 * N * B * C
        if cfg.n_heads:
            f += 4 * B * C * cfg.n_heads * cfg.head_dim_ * S
        return f
    # decode: one token against an S-token cache
    f = 2.0 * N * B
    if cfg.n_heads:
        f += 4 * B * cfg.n_heads * cfg.head_dim_ * S
    return f


def measure_cell(arch: str, shape_name: str, extra_overrides: dict | None = None,
                 rule_overrides: dict | None = None) -> dict:
    """Depth-extrapolated per-device FLOPs/bytes/collective-bytes."""
    cfg = get_config(arch)
    L = cfg.num_layers
    vals = {}
    for depth in (1, 2):
        ov = {"num_layers": depth, "use_scan": False}
        ov.update(extra_overrides or {})
        rec, _ = lower_cell(arch, shape_name, multi_pod=False,
                            rule_overrides=rule_overrides, opt_overrides=ov)
        if rec["status"] != "ok":
            return rec
        vals[depth] = rec
    f1, f2 = vals[1]["flops_per_device"], vals[2]["flops_per_device"]
    b1, b2 = vals[1]["bytes_per_device"], vals[2]["bytes_per_device"]
    c1 = vals[1]["collectives"]["total_bytes"]
    c2 = vals[2]["collectives"]["total_bytes"]
    flops = f1 + (L - 1) * max(f2 - f1, 0.0)
    bytes_ = b1 + (L - 1) * max(b2 - b1, 0.0)
    coll = c1 + (L - 1) * max(c2 - c1, 0.0)
    return {
        "status": "ok", "arch": arch, "shape": shape_name,
        "num_layers": L,
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll,
        "per_layer_flops": f2 - f1,
        "collectives_kinds": vals[2]["collectives"]["per_kind"],
    }


def min_memory_bytes(cfg: ArchConfig, shape: ShapeConfig,
                     n_devices: int = 256) -> float:
    """Analytic *lower bound* on per-device HBM traffic: parameters (+opt
    state for train) and the KV/state cache touched once.  The HLO number
    is the unfused upper bound; truth lies between."""
    N = cfg.param_count()
    if shape.kind == "train":
        per_param = 2 + 4 + 16 + 2      # read bf16, grad f32, m/v rw, write
        t = per_param * N / n_devices
    elif shape.kind == "prefill":
        t = 2 * N / n_devices
    else:
        t = 2 * N / n_devices
        if cfg.n_heads:                  # KV cache read+write
            # quantized pools (paged_decode_q8): 1 byte/element plus one
            # f32 scale per (token, kv-head) per pool — ~4x fewer cache
            # bytes/token than the f32 cell, ~2x vs bf16 (DESIGN.md §11)
            from repro.kernels.paged_attention import is_quantized
            elt = 1 if is_quantized(shape.cache_dtype) else 2
            kv = (cfg.num_layers * shape.global_batch * shape.seq_len
                  * cfg.n_kv_heads * (cfg.head_dim_ + cfg.v_head_dim_) * elt)
            if is_quantized(shape.cache_dtype):
                kv += (cfg.num_layers * shape.global_batch * shape.seq_len
                       * cfg.n_kv_heads * 2 * 4)        # k+v scale pools
            t += 2 * kv / n_devices
        if cfg.ssm_state:
            st = (cfg.num_layers * shape.global_batch * cfg.ssm_n_heads
                  * cfg.ssm_head_dim * cfg.ssm_state * 4)
            t += 2 * st / n_devices
    return t


def analyze(meas: dict, cfg: ArchConfig, shape: ShapeConfig,
            n_devices: int = 256) -> dict:
    t_comp = meas["flops_per_device"] / PEAK_FLOPS
    t_mem = meas["bytes_per_device"] / HBM_BW
    t_mem_min = min_memory_bytes(cfg, shape, n_devices) / HBM_BW
    t_coll = meas["collective_bytes_per_device"] / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    mf_dev = mf / n_devices
    useful_ratio = mf_dev / max(meas["flops_per_device"], 1.0)
    # roofline fraction: useful compute time / achievable step time bound
    step_bound = max(terms.values())
    frac = (mf_dev / PEAK_FLOPS) / max(step_bound, 1e-12)
    hints = {
        "compute": "reduce redundant/replicated FLOPs (sharding or remat policy)",
        "memory": "cut HBM traffic: fuse, reshard activations, smaller stash",
        "collective": "re-route collectives: 2D sharding, overlap, or compress",
    }
    return dict(
        meas,
        compute_s=t_comp, memory_s=t_mem, memory_s_min=t_mem_min,
        collective_s=t_coll,
        dominant=dominant,
        model_flops_global=mf,
        model_flops_per_device=mf_dev,
        useful_flops_ratio=useful_ratio,
        roofline_fraction=frac,
        suggestion=hints[dominant],
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="results/roofline.json")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)

    rows = []
    for arch in archs:
        cfg = get_config(arch)
        for sn in shapes:
            shape = SHAPES[sn]
            ok, why = cell_supported(cfg, shape)
            if not ok:
                rows.append({"arch": arch, "shape": sn, "status": "skipped",
                             "reason": why})
                print(f"[skip] {arch} x {sn}: {why}", flush=True)
                continue
            try:
                meas = measure_cell(arch, sn)
                if meas["status"] != "ok":
                    rows.append(meas)
                    continue
                row = analyze(meas, cfg, shape)
                rows.append(row)
                print(f"[ok] {arch} x {sn}: comp={row['compute_s']*1e3:.1f}ms "
                      f"mem={row['memory_s']*1e3:.1f}ms "
                      f"coll={row['collective_s']*1e3:.1f}ms "
                      f"dom={row['dominant']} "
                      f"frac={row['roofline_fraction']:.2%} "
                      f"useful={row['useful_flops_ratio']:.2f}", flush=True)
            except Exception as e:
                rows.append({"arch": arch, "shape": sn, "status": "error",
                             "error": repr(e)})
                print(f"[ERR] {arch} x {sn}: {e!r}", flush=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
