#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and
the planted faults', on several seeds in one process.

    python3 bench/control.py --workload train-burst --seeds 1 2 3
    python3 bench/control.py --workload serve-read --seeds 1 2 3 \
        --seconds 5

For each seed this builds the cell as a run does and drives the same
set-up.  A training cell needs no window: its readings come from the
checked set-up steps, against the float32 reference, of the program, of
the control (the reference with fp8 operands in the program's place) and
of the reference with half of each batch left out.  A serving cell runs
a short window at its own load and compares the program's answers, and
the control's (the reference that keeps duplicate items), with the
reference.  One JSON line per seed; the benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness as H                                          # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    entry = H.workload(H.load_benchmark(), args.workload)
    cfg, tr = H.load_config(entry["config"]), H.load_traffic(entry["traffic"])
    devices = H.require_accelerator(int(entry["chips"]))
    import jax
    import jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = H.load_driver(tr["driver"])
    for seed in args.seeds:
        run = H.RunRecord(args.workload, seed, args.seconds, False)
        if tr["driver"] == "train":
            cell = driver.Cell(cfg, dict(tr, warm_bursts=0), seed, run,
                               devices)
            cell.setup()
            cell.release()
            gc.collect()
            ref = cell._reference(jnp.float32)
            out = {"program": driver.gaps(cell.readings, ref, cell.params0),
                   "control_fp8": driver.gaps(
                       cell._reference(jnp.float8_e4m3fn), ref,
                       cell.params0),
                   "half_batch": driver.gaps(
                       cell._reference(jnp.float32, edge_share=0.5), ref,
                       cell.params0),
                   "feed_mismatches": sum(e[1] for e in cell.expanded),
                   "losses": {"program": cell.readings["loss"],
                              "reference": ref["loss"]}}
        else:
            cell = driver.Cell(cfg, tr, seed, run, devices)
            cell.setup()
            cell.measure(args.seconds)
            cell.release()
            gc.collect()
            out = {"program": dict((n, v) for n, v, _ in cell.check()),
                   "control_keep_duplicates": dict(
                       (n, v) for n, v, _ in cell.check(control=True))}
        print(json.dumps({"seed": seed, **out}), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
