"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,fig15,...] [--fast]

Prints ``name,us_per_call,derived`` CSV.  Simulator-based figures run the
paper's cluster/model scale on the analytic estimator; estimator accuracy
(fig12) and kernels measure real wall time on this host.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig7,fig8,table6,fig12,fig13,fig14,"
                         "fig15,fig16,fig17,kernels,roofline,rollout,serve,"
                         "moe,pipeline,chaos,packed,spec")
    ap.add_argument("--fast", action="store_true",
                    help="fewer MCMC iterations (CI-friendly)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (chaos_bench, estimator_acc, kernels_bench,
                            moe_bench, packed_bench, paper_figs,
                            pipeline_bench, roofline_table, rollout_bench,
                            serve_bench, spec_bench)
    it = 150 if args.fast else 600

    benches = {
        "fig7": lambda: paper_figs.fig7_weak_scaling(iters=it),
        "fig8": lambda: paper_figs.fig8_context_scaling(iters=it),
        "table6": lambda: paper_figs.table6_breakdown(iters=2 * it),
        "fig12": estimator_acc.run,
        "fig13": paper_figs.fig13_search_progress,
        "fig14": paper_figs.fig14_pruning,
        "fig15": paper_figs.fig15_optimality,
        "fig16": lambda: paper_figs.fig16_algorithms(iters=it),
        "fig17": lambda: paper_figs.fig17_strong_scaling(iters=max(it // 2, 100)),
        "kernels": kernels_bench.run,
        "roofline": roofline_table.run,
        "rollout": rollout_bench.run,
        "serve": serve_bench.run,
        "moe": moe_bench.run,
        "pipeline": lambda: pipeline_bench.run(smoke=args.fast),
        "chaos": lambda: chaos_bench.run(smoke=args.fast, scenario="all"),
        "packed": lambda: packed_bench.run(smoke=args.fast),
        "spec": lambda: spec_bench.run(smoke=args.fast),
    }
    only = args.only.split(",") if args.only else list(benches)

    print("name,us_per_call,derived")
    failed = []
    for name in only:
        t0 = time.time()
        try:
            rows = benches[name]()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
            continue
        for r, us, derived in rows:
            print(f"{r},{us:.1f},{derived}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmarks failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
