"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Heavy shared setup (the trained
SCOPE estimator) is cached under benchmarks/_cache.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only routing,tokens
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    bench_ablation, bench_adaptation, bench_budget, bench_kernels,
    bench_pareto, bench_portfolio, bench_predictive, bench_roofline,
    bench_routing, bench_serve_latency, bench_serve_throughput, bench_tokens)

BENCHES = {
    "routing": bench_routing,          # Table 1
    "predictive": bench_predictive,    # Table 2
    "pareto": bench_pareto,            # Fig. 4 / 6 / 13
    "portfolio": bench_portfolio,      # Fig. 5 / 14
    "ablation": bench_ablation,        # Fig. 7
    "budget": bench_budget,            # Fig. 8 / App. D
    "tokens": bench_tokens,            # Fig. 9 / App. E
    "adaptation": bench_adaptation,    # App. F
    "kernels": bench_kernels,          # kernel latency
    "roofline": bench_roofline,        # §Roofline (from dry-run artifacts)
    "serve_latency": bench_serve_latency,  # serve-path p50/p95 + transfer
    "serve_throughput": bench_serve_throughput,  # streaming q/s + recompiles
}

NEEDS_BUNDLE = {"routing", "predictive", "pareto", "portfolio", "ablation",
                "budget", "tokens", "adaptation", "serve_latency",
                "serve_throughput"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args(argv)
    names = list(BENCHES) if not args.only else args.only.split(",")
    enable_compile_cache()

    bundle = None
    if any(n in NEEDS_BUNDLE for n in names):
        from benchmarks.common import get_bundle
        t0 = time.time()
        bundle = get_bundle()
        print(f"# bundle ready in {time.time()-t0:.0f}s", file=sys.stderr)

    print("name,us_per_call,derived")
    failed = 0
    for n in names:
        mod = BENCHES[n]
        try:
            t0 = time.time()
            rows = mod.run(bundle)
            for name, us, derived in rows:
                print(f"{name},{us:.2f},{derived}")
            print(f"# {n} done in {time.time()-t0:.0f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{n},0.00,EXCEPTION", flush=True)
            traceback.print_exc()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
