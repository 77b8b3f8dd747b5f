"""Benchmark entrypoint: one section per paper table/figure + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--skip-tables]

Sections:
  Table I   (MNIST)  — accuracy / CO2 / time across the six variants + claims
  Table II  (CIFAR)  — same on the harder dataset
  kernels            — Pallas kernel micro-bench (interpret) + oracle check
                       (prints the scaffold's ``name,us_per_call,derived`` CSV)
  roofline           — §Roofline table from the dry-run artifacts (if present)

Figure benchmarks run standalone (their point/curve data is a superset of the
table runs): ``python -m benchmarks.fig_tradeoff`` (Figs 1/4) and
``python -m benchmarks.fig_curves`` (Figs 2/3).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="third-size datasets, half rounds")
    ap.add_argument("--skip-tables", action="store_true", help="kernels + roofline only")
    args = ap.parse_args()
    enable_compile_cache()
    t0 = time.time()

    from benchmarks import fig_tradeoff, kernel_bench, roofline_table, table_compare

    print("#" * 72)
    print("# MetaFed reproduction benchmarks (reduced protocol; see EXPERIMENTS.md)")
    print("#" * 72)

    failures = []
    if not args.skip_tables:
        # registry names from repro.data.synthetic.DATASETS (paper §IV: both)
        for ds in ("mnist_synthetic", "cifar_synthetic"):
            try:
                _, checks = table_compare.main(ds, fast=args.fast, out=f"results/table_{ds}.json")
                failures += [c for c in checks if c.startswith("[FAIL]")]
            except Exception as e:  # pragma: no cover
                failures.append(f"table {ds}: {e!r}")
                print(f"table {ds} FAILED: {e!r}")
            print()

    print("=== kernel micro-benchmarks (name,us_per_call,derived) ===")
    kernel_bench.main()
    print()

    print("=== roofline table (from dry-run artifacts) ===")
    roofline_table.main()

    print(f"\ntotal bench time: {time.time()-t0:.0f}s")
    if failures:
        print(f"{len(failures)} claim-check failures:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("all benchmark claim-checks passed")


if __name__ == "__main__":
    main()
