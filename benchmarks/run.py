"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo convention.  The
roofline/dry-run artifacts (results/*.json) are produced by their own
drivers (they need a 512-device subprocess); ``table_roofline`` summarizes
them here if present.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --only table4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def table_roofline() -> list[str]:
    base = os.path.join(os.path.dirname(__file__), "..", "results")
    path = os.path.join(base, "roofline_final.json")
    if not os.path.exists(path):
        path = os.path.join(base, "roofline_baseline.json")
    if not os.path.exists(path):
        return ["table_roofline,0,missing (run benchmarks/roofline.py)"]
    rows = []
    for r in json.load(open(path)):
        if r.get("status") != "ok":
            continue
        rows.append(
            f"roofline_{r['arch']}_{r['shape']},0,"
            f"dom={r['dominant']} comp={r['compute_s']*1e3:.1f}ms "
            f"mem={r['memory_s']*1e3:.1f}ms coll={r['collective_s']*1e3:.1f}ms "
            f"frac={r['roofline_fraction']:.4f}")
    return rows


def table_dryrun() -> list[str]:
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun_baseline.json")
    if not os.path.exists(path):
        return ["table_dryrun,0,missing (run repro.launch.dryrun --all)"]
    rows = json.load(open(path))
    ok = sum(r["status"] == "ok" for r in rows)
    sk = sum(r["status"] == "skipped" for r in rows)
    er = sum(r["status"] == "error" for r in rows)
    return [f"table_dryrun,0,{ok} ok / {sk} skipped / {er} errors "
            f"across {len(rows)} (arch x shape x mesh) cells"]


SUITES = {
    "table1": ("benchmarks.table1_frontends", "run", {}),
    "table2": ("benchmarks.table2_architectures", "run", {}),
    "fig3": ("benchmarks.fig3_criteria", "run", {}),
    "table4": ("benchmarks.table4_obspa", "run", {}),
    "table13": ("benchmarks.table13_time", "run", {}),
    "serving": ("benchmarks.serving", "run", {}),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    all_rows: list[str] = []
    for name, (mod, fn, kw) in SUITES.items():
        if args.only and args.only not in name:
            continue
        print(f"## {name}", flush=True)
        try:
            import importlib
            m = importlib.import_module(mod)
            rows = getattr(m, fn)(**kw)
            all_rows.extend(rows)
        except Exception:
            traceback.print_exc()
            all_rows.append(f"{name},0,ERROR")
    if not args.only:
        all_rows.extend(table_dryrun())
        all_rows.extend(table_roofline())

    print("\n=== CSV (name,us_per_call,derived) ===")
    for r in all_rows:
        print(r)
    n_err = sum("ERROR" in r for r in all_rows)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
