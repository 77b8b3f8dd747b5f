"""Readings that the limits of a cell's check are set from, on the chip.

    python3 bench/calibrate.py --workload cifar10-secagg-l8 --seeds 2001-2012 \
        --control 3 --faults 3 --out calibrate.jsonl

For each seed it drives the cell's set-up and checked rounds as a run
does (no window) and compares the program with the float32 reference: the
lower readings.  On the first ``--control`` seeds it also compares the
control, the reference computed in bfloat16, with the float32 reference;
on the first ``--faults`` seeds, the program with each fault of
``bench/faults.py`` planted (but the unchanged state, which reads 1 by the
measure): the upper readings.  On the first ``--look``
seeds it follows all warm-up rounds with the reference, for the program as
it runs and for the program at float32 matrix precision ("highest"): the
look at why only round 1 is compared.  One JSON line per
reading goes to ``--out`` and to stdout.  All runs share one process, so
every program compiles once.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--look", type=int, default=0)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()

    from bench.run import CACHE, use_checkout_cache

    use_checkout_cache()
    import jax
    import jax.numpy as jnp

    from bench import harness

    print(f"compile cache {CACHE}: {harness.cache_size(CACHE)}", file=sys.stderr)
    from bench.faults import FAULTS

    args.out.parent.mkdir(parents=True, exist_ok=True)
    with args.out.open("a") as out:
        def emit(**row):
            line = json.dumps({"workload": args.workload, **row})
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()

        def drift(run) -> dict:
            """Gaps over all checked rounds, against a reference that
            follows all of them: the look behind comparing round 1 only."""
            cell, check = run["cell"], run["cell"]["check"]
            ref = check.reference_rounds(cell["reference"], cell, run["data"], run["parts"],
                                         run["seeds"]["clients"], run["params0"],
                                         [c["selected"] for c in run["program"]])
            return {"loss_gaps": [abs(p["loss"] - r["loss"]) / abs(r["loss"])
                                  for p, r in zip(run["program"], ref)],
                    "change_gap_last": check.worst_leaf_gap(run["program"][-1]["params"],
                                                            ref[-1]["params"], run["params0"]),
                    "losses": [c["loss"] for c in run["program"]], "ref_losses": [r["loss"] for r in ref]}

        for i, seed in enumerate(args.seeds):
            t = time.perf_counter()
            _, run = harness.run_cell(ROOT, args.workload, seed, 0.0, False, measure=False)
            emit(seed=seed, kind="program", readings=run["readings"], s=time.perf_counter() - t)
            cell, check = run["cell"], run["cell"]["check"]
            if i < args.control:
                control = check.reference_rounds(
                    cell["reference"], cell, run["data"], run["parts"], run["seeds"]["clients"],
                    run["params0"], [run["program"][0]["selected"]], dtype=jnp.bfloat16)
                emit(seed=seed, kind="control", readings=check.readings(control, run["reference"], run["params0"]))
            if i < args.faults:
                for name, fault in FAULTS.items():
                    if name == "state_unchanged":  # reads 1 by the measure; needs no run
                        continue
                    _, bad = harness.run_cell(ROOT, args.workload, seed, 0.0, False, measure=False, fault=fault)
                    emit(seed=seed, kind=f"fault:{name}", readings=bad["readings"])
            if i < args.look:
                emit(seed=seed, kind="look:default", **drift(run))
                with jax.default_matmul_precision("highest"):
                    _, high = harness.run_cell(ROOT, args.workload, seed, 0.0, False, measure=False)
                emit(seed=seed, kind="look:highest", readings=high["readings"], **drift(high))
            del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
