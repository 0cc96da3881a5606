"""Runs one cell of ``BENCHMARK.json``: set-up, a measured window through
the system's served path, the check against the reference, and the result
line.

The served path is the paper's streaming mini-app (arXiv 1909.06055 §IV):
the benchmark's open-loop generator appends messages to a partitioned
``Broker`` topic; a ``ThreadedStreamingEngine`` hands each one to a
``jax://`` pilot, where the mini-app's plain user function converts the
message with ``jnp.asarray`` and applies ``kmeans.minibatch_step`` to the
shared model under the model lock (``full_fit_locked`` sharing).  The
function returns nothing: the model is its only output.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name: ``BENCHMARK.json`` names the configuration's file,
``bench/traffic/<name>.json`` holds a mix and ``bench/metrics/<name>.py``
a metric's reader, ``read(run) -> number or None``.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic
from bench.peaks import peaks_for
from bench.trace import reduce_trace

__all__ = ["Bench", "Run", "run_cell", "result_line", "CompileCounter"]

ROOT = Path(__file__).resolve().parents[1]
TOPIC, GROUP = "points", "engine"
TRACE_SECONDS = 2.0           # traced part of a --trace 1 window
TRACE_DIR = ".bench_trace"    # inside the checkout, emptied after reading
STEP_MODULE = "minibatch_step"
# the kmeans_distance kernels' operations, as the trace names them
KERNEL_NAMES = ("pairwise_sq_dists_pallas", "assign_pallas")
clock = time.perf_counter


class Bench:
    """The benchmark's definition and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT) -> None:
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.spec[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind} entry {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json")
                          .read_text())

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        """The metrics a run of ``cell`` reports: the end-to-end ones
        untraced, the per-layer ones traced; an entry with a
        ``workloads`` list only in those cells."""
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class CompileCounter:
    """Backend compiles in this process, persistent-cache hits included."""

    n = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if not cls._installed:
            jax.monitoring.register_event_duration_secs_listener(cls._event)
            cls._installed = True

    @classmethod
    def _event(cls, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.n += 1


@dataclass
class Run:
    """What one run recorded, for the metric readers.  Times are
    ``perf_counter`` seconds; per-message arrays are indexed by message
    number and hold NaN where the event did not happen."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    due: np.ndarray          # generator's due time (NaN: backlog)
    appended: np.ndarray     # when the generator appended it
    stamps: np.ndarray       # (n, 5): entry, converted, locked, dispatched, done
    committed: np.ndarray    # offset commit (the engine's complete event)
    lag_end: int             # Broker.lag at the window's close
    peaks: dict
    trace: dict | None = None
    extra: dict = field(default_factory=dict)

    def in_window(self, t: np.ndarray) -> np.ndarray:
        return (t >= self.t_open) & (t < self.t_close)

    def lpx_ms(self) -> np.ndarray:
        """L^px of every message due in the window: due time to offset
        commit, or to the close for one not committed by then."""
        ok = self.in_window(self.due)
        end = np.minimum(np.nan_to_num(self.committed[ok], nan=np.inf),
                         self.t_close)
        return (end - self.due[ok]) * 1e3

    def span_ms(self, a: int, b: int) -> np.ndarray:
        """Durations (ms) between two stamps, of messages whose later
        stamp falls in the window."""
        s = self.stamps
        ok = self.in_window(s[:, b])
        return (s[ok, b] - s[ok, a]) * 1e3


def _check_positions(cfg: dict, mix: dict, sched: traffic.Schedule,
                     seconds: float, seed: int) -> set:
    """Places in the lock's order at which the timed path copies the model
    before and after its step, for the check: the window's first
    ``check_first`` steps, where the model is young and each step moves it
    most, and ``check_steps`` more drawn from the seed among those a run
    surely reaches: of a backlog, the mix's ``reach_per_s`` × ``seconds``
    (below what the system drains), and nine tenths of the arrivals."""
    queued = min(sched.backlog, int(mix["reach_per_s"] * seconds)) \
        if sched.backlog else 0
    reach = max(1, queued + int(0.9 * len(sched.due)))
    first = set(range(min(cfg["check_first"], reach)))
    rng = np.random.default_rng(traffic.seed_stream(seed, traffic.STREAM_CHECK))
    k = min(cfg["check_steps"], reach)
    return first | set(int(p) for p in rng.choice(reach, size=k, replace=False))


@jax.jit
def _copy_state(state):
    return jax.tree.map(jnp.copy, state)


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def _no_span(_name: str):
    return nullcontext()


def run_cell(bench: Bench, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, step=None, log=None) -> Run:
    """One run of cell ``name``: set-up, the window, the check.  ``step``
    replaces the program's step (the control, or a planted fault)."""
    from repro.core.metrics import MetricRegistry
    from repro.models import kmeans
    from repro.pilot.api import PilotComputeService, PilotDescription, State
    from repro.streaming.broker import Broker
    from repro.streaming.engine import ThreadedStreamingEngine, Workload

    log = log or (lambda *_: None)
    step = step or kmeans.minibatch_step
    cell = bench.cell(name)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    dim, k = cfg["dim"], cfg["centroids"]
    if cfg["sharing"] != "full_fit_locked":
        raise ValueError(f"sharing {cfg['sharing']!r}: only full_fit_locked "
                         f"is driven by this harness")
    device = jax.devices()[0]
    peaks = peaks_for(device.device_kind) if device.platform == "tpu" else {}

    t_data = clock()
    sched = traffic.schedule(mix, seconds, seed)
    pool = traffic.make_pool(cfg, seed)
    n_msgs = sched.backlog + len(sched.due)
    positions = _check_positions(cfg, mix, sched, seconds, seed)
    key = jax.random.PRNGKey(int(traffic.seed_stream(
        seed, traffic.STREAM_MODEL).generate_state(1)[0]))
    init = jax.jit(kmeans.init_state, static_argnums=(1, 2, 3))
    scale = cfg["data"]["init_scale"]

    t_warm = clock()
    pcs = PilotComputeService()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", partitions=cfg["partitions"],
        attrs={"mesh_shape": (cell["chips"],), "mesh_axes": ("data",)}))
    with pilot.mesh:   # units run under the pilot's mesh: warm up there
        warm = jax.device_put(init(key, k, dim, scale), device)
        pts0 = jnp.asarray(pool[0])
        for _ in range(2):    # a fresh state, then a step's own output
            _copy_state(warm)
            warm = step(warm, pts0)
        jax.block_until_ready(warm)
        state = jax.device_put(init(key, k, dim, scale), device)
        jax.block_until_ready(state)
    del warm, pts0

    t_engine = clock()
    broker = Broker()
    broker.create_topic(TOPIC, cfg["partitions"])
    registry = MetricRegistry()
    run_id = f"{name}-{seed}"
    lock = threading.Lock()
    applied: list[int] = []           # message numbers, in the lock's order
    snaps: dict[int, tuple] = {}      # lock position -> (msg, before, after)
    stamps = np.full((n_msgs, 5), np.nan)
    where = np.full((n_msgs, 2), -1, np.int64)    # partition, offset
    appended = np.full(n_msgs, np.nan)
    span = _span if trace else _no_span

    def process(msgs):
        """The mini-app's user function: convert, then update the shared
        model under the lock.  Returns nothing held on the device."""
        nonlocal state
        for m in msgs:
            i = int(m.msg_id)
            t = stamps[i]
            where[i] = m.partition, m.offset
            t[0] = clock()
            with span("bench.convert"):
                pts = jnp.asarray(m.value)
            t[1] = clock()
            with span("bench.lock_wait"):
                lock.acquire()
            try:
                pos = len(applied)
                before = _copy_state(state) if pos in positions else None
                t[2] = clock()
                with span("bench.dispatch"):
                    state = step(state, pts)
                t[3] = clock()
                after = _copy_state(state) if before is not None else None
                with span("bench.block"):
                    jax.block_until_ready(state)
                t[4] = clock()
                applied.append(i)
                if before is not None:
                    snaps[pos] = (i, before, after)
            finally:
                lock.release()

    engine = ThreadedStreamingEngine(
        broker, TOPIC, pilot, Workload(fn=process, name="kmeans"), registry,
        run_id, group=GROUP, batch_max=cfg["batch_max"], max_retries=0)
    for i in range(sched.backlog):
        broker.append(TOPIC, pool[i % len(pool)], ts=clock(), run_id=run_id,
                      msg_id=str(i), size_bytes=pool[0].nbytes)
    stop = threading.Event()
    due = np.full(n_msgs, np.nan)

    def produce() -> None:
        for j in range(len(sched.due)):
            i = sched.backlog + j
            while (now := clock()) < due[i]:
                if stop.wait(due[i] - now):
                    return
            appended[i] = now
            with span("bench.append"):
                broker.append(TOPIC, pool[i % len(pool)], ts=now,
                              run_id=run_id, msg_id=str(i),
                              size_bytes=pool[0].nbytes)

    producer = threading.Thread(target=produce, name="bench-producer")
    trace_dir = bench.root / TRACE_DIR
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1       # the benchmark's own spans
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles0 = CompileCounter.n

    t_open = clock()
    setup_s = t_open - t_start
    t_close = t_open + seconds
    due[sched.backlog:] = t_open + sched.due
    engine.start()
    producer.start()
    try:
        if trace:
            with _span("bench.traced"):
                stop.wait(max(0.0, t_open + min(TRACE_SECONDS, seconds) - clock()))
            jax.profiler.stop_trace()
        stop.wait(max(0.0, t_close - clock()))
        lag_end = broker.lag(GROUP, TOPIC)
    finally:
        stop.set()
        engine.stop(timeout=60.0)
        producer.join(timeout=60.0)
        pcs.close()
    compiles_in_window = CompileCounter.n - compiles0
    stats = device.memory_stats() or {}
    log(f"set-up: {t_data - t_start:.3f} s to the data, data "
        f"{t_warm - t_data:.3f} s, state and warm-up {t_engine - t_warm:.3f} s, "
        f"engine and backlog {t_open - t_engine:.3f} s")

    committed = np.full(n_msgs, np.nan)
    for ev in registry.events(run_id, "engine", "complete"):
        committed[int(ev.attrs["msg_id"])] = ev.ts
    # a second with few commits marks a stall of the whole pipeline
    per_s = np.histogram(committed, bins=max(1, int(seconds)),
                         range=(t_open, t_open + max(1, int(seconds))))[0]
    log(f"window: commits per second {per_s.tolist()}")
    core = engine.core
    failed_units = [cu for cu in pilot.compute_units if cu.state == State.FAILED]
    for cu in failed_units[:3]:
        log(f"failed unit: {cu.exception!r}")

    # -- the check: delivery, then each copied step against the reference
    counted = np.flatnonzero(committed < t_close)
    times = np.bincount(np.asarray(applied, np.int64), minlength=n_msgs)
    commits = [broker.committed(GROUP, TOPIC, p)
               for p in range(broker.total_partitions(TOPIC))]
    uncommitted = sum(where[i, 1] < 0 or commits[where[i, 0]] <= where[i, 1]
                      for i in counted)
    delivery_err = (int((times > 1).sum()) + int((times[counted] != 1).sum())
                    + int(uncommitted) + len(failed_units)
                    + core.failed_batches + core.retried)
    steps = []
    for pos in sorted(snaps):
        i, before, after = snaps.pop(pos)
        host = [tuple(np.asarray(a) for a in (s.centroids, s.counts))
                for s in (before, after)]
        steps.append(reference.check_step(pool[i % len(pool)], *host))
    state = None      # the model's last device buffers go with the pilot
    limits = cfg["limits"]
    checks = {
        "count_err": [max((s["count_err"] for s in steps), default=0.0),
                      limits["count_err"]],
        "centroid_err": [max((s["centroid_err"] for s in steps), default=0.0),
                         limits["centroid_err"]],
        "delivery_err": [delivery_err, 0],
        "compiles_in_window": [compiles_in_window, 0],
        "steps_checked": [len(steps), 1],
    }
    correct = (all(v <= lim for key, (v, lim) in checks.items()
                   if key != "steps_checked")
               and checks["steps_checked"][0] >= checks["steps_checked"][1])

    run = Run(cell=cell, config=cfg, traffic=mix, seconds=seconds,
              setup_s=setup_s, t_open=t_open, t_close=t_close, due=due,
              appended=appended, stamps=stamps, committed=committed,
              lag_end=int(lag_end), peaks=peaks)
    if trace:
        files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        run.trace = reduce_trace(str(files[-1]), chips=cell["chips"],
                                 step_module=STEP_MODULE,
                                 kernel_names=KERNEL_NAMES)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.extra = {
        "correct": bool(correct),
        "checks": checks,
        "near_ties": sum(s["near_ties"] for s in steps),
        "applied": len(applied),
        "failed": len(failed_units) + core.abandoned,
        # a program's temporaries live in the allocator's reserved
        # region, which peak_bytes_in_use leaves out
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)
                                 + stats.get("peak_bytes_reserved", 0)),
        "backlog_left": int(lag_end) if sched.backlog else None,
    }
    return run


def result_line(bench: Bench, run: Run, traced: bool, devices: list) -> dict:
    """The result: the last line a run prints on standard output."""
    name = run.cell["name"]
    metrics = {}
    for m in bench.metrics(name, traced):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if run.traffic.get("backlog"):
        attempted = int((run.stamps[:, 0] < run.t_close).sum())
    else:
        attempted = int(run.in_window(run.due).sum())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.extra["memory_peak_bytes"]}
    line = {"correct": run.extra["correct"], "attempted": attempted,
            "failed": run.extra["failed"], "metrics": metrics,
            "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.extra["checks"].items()}
    return line
