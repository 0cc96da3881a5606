#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the program (``src/``) and
``BENCHMARK.json``.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``: each number
compared with the reference beside its limit.  The checks are also the
last lines of standard error.  A host without a TPU, or with fewer chips
than the cell asks for, is refused with a non-zero exit and no result.
"""

import time

T_START = time.perf_counter()   # the set-up time counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    t_jax = time.perf_counter()
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu":
        say(f"no TPU: JAX's devices are {devices[0].platform}; "
            "the benchmark never runs on another platform")
        return 2
    if len(devices) < cell["chips"]:
        say(f"{cell['name']} needs {cell['chips']} chips, JAX sees {len(devices)}")
        return 2
    devices = devices[:cell["chips"]]
    say(f"set-up: import jax {t_jax - T_START:.3f} s, the harness and "
        f"jax.devices() {t_devices - t_jax:.3f} s")
    say(f"compile cache {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.CompileCounter.install()
    run = harness.run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, log=say)
    if run.extra["backlog_left"] == 0:
        say("the backlog emptied inside the window: the cell measured no "
            "capacity; no result")
        return 3
    line = harness.result_line(bench, run, bool(args.trace), devices)
    say(f"applied {run.extra['applied']} steps, near ties left out "
        f"{run.extra['near_ties']}, setup_s {run.setup_s}")
    for key, c in line["checks"].items():
        say(f"check {key} = {c['value']} limit {c['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
