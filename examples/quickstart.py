"""Quickstart: the paper's stack in ~50 lines of user code.

A pilot (local backend), a 2-partition broker topic, a producer, and the
streaming engine running REAL JAX MiniBatch K-Means on every message —
the paper's Streaming Mini-App end to end, with run-id-traced metrics.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import MetricRegistry, new_run_id, percentile_summary
from repro.launch.compile_cache import use_compile_cache
from repro.models import kmeans
from repro.pilot.api import PilotComputeService, PilotDescription
from repro.streaming.broker import Broker
from repro.streaming.engine import ThreadedStreamingEngine, Workload

N_MESSAGES, POINTS, DIM, CENTROIDS = 24, 512, 9, 32
use_compile_cache()

# 1. resources: a pilot on the local backend (swap the URL for
#    serverless://aws-sim, hpc://wrangler-sim, or jax://mesh)
pcs = PilotComputeService()
pilot = pcs.submit_pilot(PilotDescription(resource="local://", concurrency=2))

# 2. a broker topic with 2 partitions (Kinesis shards / Kafka partitions)
broker = Broker()
broker.create_topic("points", 2)

# 3. the workload: MiniBatch K-Means model update per message (real JAX).
#    The model is shared across partitions -> guard the read-modify-write
#    (exactly the paper's consistency concern; on Lambda/S3 it would be
#    lock-free last-writer-wins instead).
import threading

state = kmeans.init_state(jax.random.PRNGKey(0), CENTROIDS, DIM)
inertias = []
model_lock = threading.Lock()


def process(msgs):
    global state
    for m in msgs:
        pts = jnp.asarray(m.value)
        with model_lock:
            state = kmeans.minibatch_step(state, pts)
            inertias.append(float(kmeans.inertia(pts, state.centroids)))


# 4. the engine binds the workload to the topic on the pilot
metrics = MetricRegistry()
run_id = new_run_id("quickstart")
engine = ThreadedStreamingEngine(broker, "points", pilot,
                                 Workload(fn=process, name="kmeans"),
                                 metrics, run_id, batch_max=2)
engine.start()

# 5. produce a clustered stream and let the engine drain it
rng = np.random.default_rng(0)
centers = rng.normal(size=(4, DIM)) * 3
for i in range(N_MESSAGES):
    pts = centers[rng.integers(0, 4, POINTS)] + rng.normal(size=(POINTS, DIM))
    broker.append("points", pts.astype(np.float32), ts=time.perf_counter(),
                  run_id=run_id, msg_id=f"{run_id}/{i}",
                  size_bytes=POINTS * DIM * 4)
    metrics.record(run_id, "broker", "append", time.perf_counter(),
                   msg_id=f"{run_id}/{i}")
engine.drain(N_MESSAGES, timeout=120)
engine.stop()
pcs.close()

lat = metrics.latencies(run_id, "append", "complete")
print(f"processed {engine.core.processed}/{N_MESSAGES} messages")
print(f"L^px p50={percentile_summary(lat)['p50'] * 1e3:.1f} ms")
print(f"inertia first->last: {inertias[0]:.3f} -> {inertias[-1]:.3f} "
      f"(model converged: {inertias[-1] < inertias[0]})")
assert inertias[-1] < inertias[0]
print("quickstart OK")
