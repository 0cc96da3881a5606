"""Streaming serving driver: the paper's event-driven processing mode applied
to LM inference.

Requests arrive as broker messages; the engine hands each partition's
micro-batches to compute-units on a pilot — a ``jax://`` mesh pilot when a
TPU is present, the ``local://`` pilot otherwise — and the unit serves each
micro-batch through ``models.model.serve_request``: the prompts prefilled
in one program, then one program per greedy token, the tokens staying on
the device.  StreamInsight instruments the run (L^br, L^px, T^px per run-id)
and the USL-based autoscaler recommends the partition count for an
offered load.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-4.0-h-small \
        --reduced --requests 24 --partitions 2 --new-tokens 8
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.metrics import MetricRegistry, new_run_id, percentile_summary
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.pilot.api import PilotComputeService, PilotDescription
from repro.streaming.broker import Broker
from repro.streaming.engine import ThreadedStreamingEngine, Workload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch-max", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    metrics = MetricRegistry()
    run_id = new_run_id(f"serve-{cfg.name}")

    def handle(msgs):
        prompts = jnp.stack([jnp.asarray(m.value["tokens"]) for m in msgs])
        out = M.serve_request(params, cfg, prompts, args.new_tokens)
        return np.asarray(out["tokens"])

    pcs = PilotComputeService()
    if jax.default_backend() == "tpu":
        desc = PilotDescription(resource="jax://mesh", partitions=args.partitions,
                                attrs={"mesh_shape": (1,), "mesh_axes": ("data",)})
    else:
        desc = PilotDescription(resource="local://", concurrency=args.partitions)
    pilot = pcs.submit_pilot(desc)
    broker = Broker()
    broker.create_topic("requests", args.partitions)
    engine = ThreadedStreamingEngine(
        broker, "requests", pilot, Workload(fn=handle, name="generate"),
        metrics, run_id, batch_max=args.batch_max)
    engine.start()

    import time
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        tokens = rng.integers(0, cfg.vocab_size, size=(args.prompt_len,))
        msg_id = f"{run_id}/{i}"
        broker.append("requests", {"tokens": tokens}, ts=time.perf_counter(),
                      run_id=run_id, msg_id=msg_id,
                      size_bytes=args.prompt_len * 4)
        metrics.record(run_id, "broker", "append", time.perf_counter(),
                       msg_id=msg_id)
    engine.drain(args.requests, timeout=600)
    dt = time.perf_counter() - t0
    engine.stop()
    pcs.close()

    lat = metrics.latencies(run_id, "append", "complete")
    print(f"served {engine.core.processed}/{args.requests} requests "
          f"in {dt:.2f}s  T^px={engine.core.processed / dt:.2f} req/s")
    print("L^px:", {k: round(v, 4) for k, v in percentile_summary(lat).items()})
    print(f"retries={engine.core.retried} failed={engine.core.failed_batches}")


if __name__ == "__main__":
    main()
