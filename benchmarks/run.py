"""Benchmark orchestrator — one function per paper table + roofline.

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU scale)
    PYTHONPATH=src python -m benchmarks.run --full
    PYTHONPATH=src python -m benchmarks.run --only table2 table8

Prints ``name,us_per_call,derived`` CSV lines at the end (harness
contract) plus human-readable tables; JSON artifacts land in
benchmarks/results/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)

    from benchmarks import paper_tables as PT
    from benchmarks import graph_build_scaling as GBS
    from benchmarks import lifecycle_faults as LF
    from benchmarks import lifecycle_swap as LS
    from benchmarks import obs_overhead as OO
    from benchmarks import roofline as RL
    from benchmarks import serving_concurrency as SC
    from benchmarks import serving_kernels as SK
    from benchmarks import serving_scaleout as SSC
    from benchmarks import train_throughput as TT
    from benchmarks import vmem_report as VMR

    jobs = [
        ("table2_user_recall", PT.table2_user_recall),
        ("table3_item_recall", PT.table3_item_recall),
        ("table4_index_hitrate", PT.table4_index_hitrate),
        ("table5_edge_types", PT.table5_edge_types),
        ("table6_neighbors", PT.table6_neighbors),
        ("table7_popbias", PT.table7_popbias),
        ("table8_serving_cost", PT.table8_serving_cost),
        ("graph_build_scaling", GBS.run),
        ("serving_kernels", SK.run),
        ("train_throughput", TT.run),
        ("lifecycle_swap", LS.run),
        ("lifecycle_faults", LF.run),
        ("serving_concurrency", SC.run),
        ("serving_scaleout", SSC.run),
        ("obs_overhead", OO.run),
        ("roofline", RL.run),
        ("vmem_report", VMR.run),
    ]
    if args.only:
        jobs = [(n, f) for n, f in jobs
                if any(o in n for o in args.only)]

    csv_rows = []
    failures = []
    for name, fn in jobs:
        print(f"\n=== {name} ===")
        t0 = time.perf_counter()
        try:
            out = fn(full=args.full)
            dt = time.perf_counter() - t0
            derived = ""
            if isinstance(out, dict):
                if "thread_speedup" in out:
                    derived = (f"thread_speedup="
                               f"{out['thread_speedup']:.2f}x")
                elif "device_speedup_4t" in out:
                    derived = (f"device_speedup="
                               f"{out['device_speedup_4t']:.2f}x;"
                               f"shard_scaling="
                               + "/".join(f"{x:.2f}"
                                          for x in out["shard_scaling"]))
                elif "overhead_pct" in out:
                    derived = (f"obs_overhead="
                               f"{out['overhead_pct']:+.2f}%")
                elif "speedup_dedup_ids" in out:
                    derived = (f"train_speedup="
                               f"{out['speedup_dedup_ids']:.2f}x")
                elif "rankgraph2" in out:
                    derived = f"recall@100={out['rankgraph2'].get(100, 0):.3f}"
                elif "modeled_cost_reduction" in out:
                    derived = (f"cost_reduction="
                               f"{out['modeled_cost_reduction']*100:.0f}%")
                elif "max_recovery_cycles" in out:
                    derived = (f"recovery_cycles="
                               f"{out['max_recovery_cycles']};"
                               f"corrupt_serves={out['corrupt_serves']}")
                elif "n_over_budget" in out:
                    derived = (f"kernels={out['n_kernels']};over_budget="
                               f"{out['n_over_budget']}")
                elif "rows" in out and name == "roofline" and out["rows"]:
                    worst = min(out["rows"],
                                key=lambda r: r["projected_mfu"])
                    derived = (f"cells={len(out['rows'])};worst_mfu="
                               f"{worst['projected_mfu']*100:.1f}%")
            csv_rows.append(f"{name},{dt*1e6:.0f},{derived}")
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
            csv_rows.append(f"{name},-1,FAILED")

    print("\nname,us_per_call,derived")
    for row in csv_rows:
        print(row)
    if failures:
        print(f"\n{len(failures)} benchmark(s) FAILED: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
