# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

    PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only ...]

Modules map to the paper's tables/figures:
    bench_pareto      — Fig 6 / Table 3 (F1 vs flows, SpliDT vs NB/Leo)
    bench_resources   — Fig 9 (TCAM), Fig 11 (registers), Fig 12
                        (precision), Table 1 (feature density)
    bench_recirc_ttd  — Table 5 (recirc bandwidth), Fig 10 (TTD)
    bench_dse         — Fig 7 (BO convergence), Table 4 (stage timing)
    bench_kernels     — kernel micro-benchmarks
    bench_engine      — looped vs fused vs streaming engine throughput
    bench_fit         — numpy vs jitted trainer, serial vs batched DSE
    bench_roofline    — EXPERIMENTS.md §Roofline table (from dry-run)

``--smoke`` is the CI guard: every module must import, and modules with
smoke support run one tiny iteration; the rest are import-checked only.
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import time
import traceback

MODULES = ["pareto", "resources", "recirc_ttd", "dse", "kernels", "engine",
           "fit", "serve", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full dataset/table sizes (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: import every module, run one tiny "
                         "iteration where supported")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args()
    only = [m.strip() for m in args.only.split(",") if m.strip()]
    unknown = sorted(set(only) - set(MODULES))
    if unknown:
        ap.error(f"unknown --only module(s) {unknown}; "
                 f"options: {','.join(MODULES)}")

    from repro.compile_cache import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    print("name,us_per_call,derived")
    failures = []
    for mod in MODULES:
        if only and mod not in only:
            continue
        t0 = time.time()
        try:
            m = __import__(f"benchmarks.bench_{mod}", fromlist=["run"])
            takes_smoke = "smoke" in inspect.signature(m.run).parameters
            if args.smoke and not takes_smoke:
                print(f"# bench_{mod} import-checked (no smoke mode)",
                      file=sys.stderr)
                continue
            kw = {"smoke": True} if args.smoke else {}
            for row in m.run(quick=not args.full, **kw):
                print(row.csv(), flush=True)
            print(f"# bench_{mod} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures.append(mod)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
