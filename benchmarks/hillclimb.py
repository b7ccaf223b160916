import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"     # CPU-only: 512 virtual host devices

"""Perf hillclimb driver (EXPERIMENTS.md §Perf).

Three cells, chosen per the spec:
  A. phi3-medium-14b x prefill_32k — worst useful-FLOPs ratio (0.05):
     40 heads don't divide the 16-way model axis, so baseline replicates
     attention compute 16x.  Changes: context-parallel attention (seq_q ->
     model), then sequence-parallel residual (seq_sp -> model).
  B. qwen3-moe-30b-a3b x train_4k — most collective-bound (155 s of
     collective time vs 4 s compute).  Changes: grouped (hierarchical)
     dispatch with one group per DP shard, then + seq_sp.
  C. qwen3-1.7b x train_4k — the paper-representative cell: SPA 2x
     hardware-aligned structured pruning (the paper's own technique) as a
     roofline move, then + seq_sp on the pruned model.

Each experiment re-measures the depth-extrapolated roofline terms.
"""
import json
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.roofline import analyze, measure_cell
from repro.configs import SHAPES, get_config

# SPA-pruned qwen3-1.7b, *mesh-aligned*: iteration C1 (see §Perf log)
# pruned KV groups 8->4 (q heads 16->8) and REGRESSED 2.3x — 8 heads no
# longer divide the 16-way model axis, so attention replicated.  The
# revised prune set keeps the head count and takes the 2x from d_ff
# (6144->3072, 128-aligned) + the v/output head_dim group (128->64) —
# exactly what prune_model(kinds={"mlp", v-hd}, align_units=128) emits.
QWEN3_PRUNED_NAIVE = {"d_ff": 3072, "n_kv_heads": 4, "n_heads": 8}
QWEN3_PRUNED_ALIGNED = {"d_ff": 3072, "v_head_dim": 64}

EXPERIMENTS = [
    # (tag, arch, shape, rule_overrides, opt_overrides)
    ("A0_phi3_prefill_baseline", "phi3-medium-14b", "prefill_32k", None, None),
    ("A1_phi3_ctx_parallel", "phi3-medium-14b", "prefill_32k",
     {"seq_q": ("model",)}, None),
    ("A2_phi3_ctx+seqsp", "phi3-medium-14b", "prefill_32k",
     {"seq_q": ("model",), "seq_sp": ("model",)}, None),
    ("A3_phi3_train_baseline", "phi3-medium-14b", "train_4k", None, None),
    ("A4_phi3_train_ctx+seqsp", "phi3-medium-14b", "train_4k",
     {"seq_q": ("model",), "seq_sp": ("model",)}, None),

    ("B0_moe_train_baseline", "qwen3-moe-30b-a3b", "train_4k", None, None),
    ("B1_moe_grouped_dispatch", "qwen3-moe-30b-a3b", "train_4k",
     None, {"moe_dispatch_groups": 16}),
    ("B2_moe_grouped+ctx", "qwen3-moe-30b-a3b", "train_4k",
     {"seq_q": ("model",), "seq_sp": ("model",)},
     {"moe_dispatch_groups": 16}),
    ("B3_moe_grouped+cap1", "qwen3-moe-30b-a3b", "train_4k",
     None, {"moe_dispatch_groups": 16, "capacity_factor": 1.0}),

    ("C0_qwen3_train_baseline", "qwen3-1.7b", "train_4k", None, None),
    ("C1_qwen3_pruned_naive", "qwen3-1.7b", "train_4k", None,
     QWEN3_PRUNED_NAIVE),
    ("C2_qwen3_pruned_mesh_aligned", "qwen3-1.7b", "train_4k", None,
     QWEN3_PRUNED_ALIGNED),
    ("C3_qwen3_pruned+ctx+seqsp", "qwen3-1.7b", "train_4k",
     {"seq_q": ("model",), "seq_sp": ("model",)}, QWEN3_PRUNED_ALIGNED),
    ("C4_qwen3_dense+ctx+seqsp", "qwen3-1.7b", "train_4k",
     {"seq_q": ("model",), "seq_sp": ("model",)}, None),
]


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "results/hillclimb.json"
    rows = []
    for tag, arch, shape, ro, oo in EXPERIMENTS:
        try:
            meas = measure_cell(arch, shape, extra_overrides=oo,
                                rule_overrides=ro)
            if meas.get("status") != "ok":
                rows.append(dict(meas, tag=tag))
                print(f"[{tag}] -> {meas}", flush=True)
                continue
            cfg = get_config(arch)
            if oo:
                cfg = cfg.replace(**{k: v for k, v in oo.items()
                                     if k != "use_scan"})
            row = analyze(meas, cfg, SHAPES[shape])
            row["tag"] = tag
            rows.append(row)
            print(f"[{tag}] comp={row['compute_s']*1e3:8.1f}ms "
                  f"mem={row['memory_s']*1e3:9.1f}ms "
                  f"coll={row['collective_s']*1e3:9.1f}ms "
                  f"dom={row['dominant']:10s} "
                  f"frac={row['roofline_fraction']:.4f} "
                  f"useful={row['useful_flops_ratio']:.3f}", flush=True)
        except Exception as e:
            rows.append({"tag": tag, "status": "error", "error": repr(e)})
            print(f"[{tag}] ERROR {e!r}", flush=True)
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
