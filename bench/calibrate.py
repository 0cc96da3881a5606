#!/usr/bin/env python3
"""Readings that the check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

Runs the cell in one process, once per seed with the program's step and
once per control seed with the app's ``control`` step (the reference at
the precision just below the configuration's) in its place, each through
the whole served path at the cell's own load, and prints one JSON line
per run: the seed, which step ran, and each number the check compares.
The benchmark's own runs never run the control.  A host without a TPU is
refused.
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
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.CompileCounter.install()
    bench = harness.Bench(ROOT)
    app = bench.app(bench.config(bench.cell(args.workload)["config"])["app"])
    runs = [(int(s), "program", None) for s in args.seeds.split(",") if s]
    runs += [(int(s), "control", app.control)
             for s in args.control_seeds.split(",") if s]
    t0 = T_START
    for seed, kind, step in runs:
        run = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, t_start=t0, step=step)
        line = harness.result_line(bench, run, False, jax.devices()[:1])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "step": kind, "correct": line["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      line["metrics"].items()},
                          "notes": run.extra["notes"],
                          "checks": run.extra["checks"]}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
