"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable summaries)
and writes per-experiment CSVs under results/workflow.

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run --only table1,table12
    PYTHONPATH=src python -m benchmarks.run --quick      # small slices

Exits nonzero when any phase raised.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import tables

    benches = {
        "table1": lambda: tables.table1_main(full=not args.quick),
        "table2": tables.table2_prefix,
        "table3": tables.table3_ablation,
        "table8": tables.table8_families,
        "table9": tables.table9_conflict,
        "table10": tables.table10_sensitivity,
        "table11": tables.table11_perturbation,
        "table12": tables.table12_solver,
        "fig2": tables.fig2_ecdf,
        "roofline": _roofline_summary,
    }
    all_rows: list[str] = []
    failed: list[str] = []
    t_start = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn() or []
            all_rows.extend(rows)
            print(f"[{name} done in {time.time()-t0:.1f}s]")
        except Exception as e:   # run the other phases, then fail
            import traceback
            traceback.print_exc()
            all_rows.append(f"{name}/ERROR,0,{type(e).__name__}")
            failed.append(name)
    print("\n# CSV (name,us_per_call,derived)")
    for row in all_rows:
        print(row)
    print(f"# total wall time {time.time()-t_start:.1f}s")
    if failed:
        sys.exit(f"phases failed: {', '.join(failed)}")


def _roofline_summary() -> list[str]:
    """§Roofline: summarize the dry-run artifacts (single-pod mesh)."""
    import json
    root = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    rows = []
    if not root.exists():
        print("no dry-run artifacts; run: python -m repro.launch.dryrun")
        return rows
    print("\n# Roofline terms per (arch × shape), single-pod 256 chips:")
    print(f"{'cell':46s} {'comp(s)':>9s} {'mem(s)':>9s} {'coll(s)':>9s} "
          f"{'dominant':>12s} {'useful':>7s}")
    for f in sorted(root.glob("*__single.json")):
        r = json.loads(f.read_text())
        if "error" in r:
            continue
        cell = f"{r['arch']}/{r['shape']}"
        print(f"{cell:46s} {r['compute_s']:9.3f} {r['memory_s']:9.3f} "
              f"{r['collective_s']:9.3f} {r['dominant']:>12s} "
              f"{r['useful_flop_ratio']:7.3f}")
        rows.append(f"roofline/{cell}/bound_s,0,"
                    f"{r['roofline_bound_s']:.4f}")
        rows.append(f"roofline/{cell}/useful,0,"
                    f"{r['useful_flop_ratio']:.4f}")
    return rows


if __name__ == "__main__":
    main()
