#!/usr/bin/env python3
"""Planted faults, on the chip: the check must judge each not correct.

    python3 bench/plant.py --workload <cell> --seconds <s> --seed <n>

Runs the cell in one process once per fault in its app's ``FAULTS`` (a
step with its signature that leaves part of the model out), each through
the whole served path at the cell's own load, and prints one JSON line per
run: the fault, whether the run read correct, and each number the check
compares.  A host without a TPU is refused.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("plant: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.CompileCounter.install()
    bench = harness.Bench(ROOT)
    app = bench.app(bench.config(bench.cell(args.workload)["config"])["app"])
    t0 = T_START
    for name, fault in app.FAULTS.items():
        run = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               False, t_start=t0, step=fault)
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "fault": name, "correct": run.extra["correct"],
                          "notes": run.extra["notes"],
                          "checks": run.extra["checks"]}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
