"""Program-side spans (``repro.core.metrics.span``) at the threaded
engine's and the ``jax://`` pilot's boundaries, read back from the JAX
profiler's trace."""

from collections import defaultdict
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.metrics import MetricRegistry, new_run_id, span
from repro.pilot.api import PilotComputeService, PilotDescription
from repro.streaming.broker import Broker
from repro.streaming.engine import ThreadedStreamingEngine, Workload


def test_span_without_a_session_is_one_shared_no_op():
    first = span("engine.fetch", partition=0)
    assert isinstance(first, nullcontext)
    assert not isinstance(first, TraceAnnotation)
    assert span("pilot.unit", partition=1, unit=7) is first
    with first:
        pass


def _trace_events(log_dir):
    """name -> [(thread line, start ns, end ns, metadata)] on the host."""
    [path] = list(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    events = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                events[e.name].append((i, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats)))
    return events


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """About 20 messages through a threaded engine over a ``jax://``
    pilot, with the profiler recording."""
    n_msgs, partitions = 20, 2
    broker = Broker()
    broker.create_topic("points", partitions)
    pcs = PilotComputeService()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", partitions=partitions,
        attrs={"mesh_shape": (1,), "mesh_axes": ("data",)}))
    total = jax.jit(jnp.sum)

    def process(msgs):
        return [total(jnp.asarray(m.value)) for m in msgs]

    engine = ThreadedStreamingEngine(
        broker, "points", pilot, Workload(fn=process, name="sum"),
        MetricRegistry(), new_run_id("trace"), batch_max=1, max_retries=0)
    for i in range(n_msgs):
        broker.append("points", np.full(8, i, np.float32), ts=0.0,
                      msg_id=str(i), partition=i % partitions)
    process([broker.fetch("points", 0, 0, 1)[0]])     # compile untraced
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(str(log_dir), profiler_options=opts):
            engine.start()
            engine.drain(n_msgs, timeout=60.0)
    finally:
        engine.stop()
        pcs.close()
    return n_msgs, pilot, _trace_events(log_dir)


def test_engine_and_pilot_spans_land_in_the_trace(traced_run):
    n_msgs, pilot, events = traced_run
    for name in ("engine.fetch", "engine.commit", "pilot.unit", "pilot.fn",
                 "pilot.block"):
        assert events[name], f"no {name} span in the trace"
    assert len(events["pilot.unit"]) == len(pilot.compute_units) == n_msgs
    assert len(events["engine.commit"]) == n_msgs
    assert len(events["pilot.fn"]) == len(events["pilot.block"]) == n_msgs


def test_pilot_fn_lies_inside_its_unit(traced_run):
    _, _, events = traced_run
    units = {meta["unit"]: (line, lo, hi)
             for line, lo, hi, meta in events["pilot.unit"]}
    for line, lo, hi, meta in events["pilot.fn"] + events["pilot.block"]:
        u_line, u_lo, u_hi = units[meta["unit"]]
        assert line == u_line and u_lo <= lo <= hi <= u_hi


def test_spans_carry_partition_and_batch(traced_run):
    n_msgs, _, events = traced_run
    commits = [meta for *_, meta in events["engine.commit"]]
    assert sorted((m["partition"], m["offset"]) for m in commits) == sorted(
        (i % 2, i // 2) for i in range(n_msgs))
    assert all("partition" in meta for *_, meta in events["engine.fetch"])
    assert {meta["partition"] for *_, meta in events["pilot.unit"]} == {0, 1}
