"""Benchmark driver: one module per paper figure/table + framework benches.

  PYTHONPATH=src python -m benchmarks.run            # quick mode (minutes)
  PYTHONPATH=src python -m benchmarks.run --only fig3,fig9
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale counts
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: seconds, tiny counts

Roofline/dry-run artifacts (benchmarks/results/{dryrun,roofline}.json) are
produced by ``repro.launch.dryrun`` / ``repro.launch.roofline`` — see
EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import importlib
import json
import time
import traceback

from repro.launch.compile_cache import use_compile_cache

MODULES = {
    "fig2": "benchmarks.fig2_io_latency",
    "fig3": "benchmarks.fig3_table2_e2e",     # includes table2
    "fig4": "benchmarks.fig4_caching_skew",
    "fig5": "benchmarks.fig5_rw_ratio",
    "fig6": "benchmarks.fig6_txn_length",
    "fig7": "benchmarks.fig7_single_node",
    "fig8": "benchmarks.fig8_distributed",
    "fig9": "benchmarks.fig9_gc",
    "fig10": "benchmarks.fig10_fault_tolerance",
    "figw": "benchmarks.fig_workflow",
    "figp": "benchmarks.fig_pool",
    "figr": "benchmarks.fig_routing",
    "figc": "benchmarks.fig_chain",
    "figa": "benchmarks.fig_async",
    "fige": "benchmarks.fig_elastic",
    "figh": "benchmarks.fig_hotpath",
    "figs": "benchmarks.fig_serve",   # needs the [jax] extra
    "ckpt": "benchmarks.ckpt_bench",
}

# fast, representative subset for CI smoke runs (seconds each)
SMOKE_DEFAULT = ["fig2", "figw", "figp", "figr", "figc", "figa", "fige",
                 "figh"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig3,fig9")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale txn counts (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny counts, fast subset unless --only")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        # modules that support it shrink their counts further than quick mode
        import os
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    names = [n.strip() for n in args.only.split(",") if n.strip()] \
        or (SMOKE_DEFAULT if args.smoke else list(MODULES))
    failures = 0
    for name in names:
        mod = importlib.import_module(MODULES[name])
        t0 = time.time()
        print(f"=== {name} ({MODULES[name]}) ===", flush=True)
        try:
            result = mod.run(quick=not args.full)
            dt = time.time() - t0
            summary = json.dumps(result, indent=1, default=str)
            if len(summary) > 1800:
                summary = summary[:1800] + "\n ...(see benchmarks/results)"
            print(summary)
            print(f"=== {name} done in {dt:.1f}s ===", flush=True)
        except Exception:
            failures += 1
            print(f"=== {name} FAILED ===")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
