"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload cifar10-secagg-l8 --seed 7 --seconds 20 --trace 0

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the checkout's root; ``bench/harness.py`` says what
a run does.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit, which also close stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's persistent compilation cache is
kept in ``<checkout>/.jax_cache``, so only a checkout's first run of a cell
compiles: the benchmark hands the directory to JAX through
``JAX_COMPILATION_CACHE_DIR``, the variable that the program's own
``repro.utils.enable_compile_cache`` defers to.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
CACHE = ROOT / ".jax_cache"


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache in the checkout, unbounded (a
    bound of the environment's, set for a directory shared by everything
    on the machine, would evict this cell's programs).  JAX reads both
    variables when it is imported, so this comes first."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_cache()
    from bench import harness

    print(f"compile cache {CACHE}: {harness.cache_size(CACHE)}", file=sys.stderr)

    try:
        result, _ = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                     bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing ran", file=sys.stderr)
        return 2
    print(f"compile cache after the run: {harness.cache_size(CACHE)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
