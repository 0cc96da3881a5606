"""Run-id tracing and metric collection (StreamInsight instrumentation layer).

The paper (§IV): "the framework assigns a unique run id, which is propagated
to all involved components. This way events can be attributed to a specific
benchmark run."  The instrumentation system is modular — collectors can be
added/removed per component (producer, broker, processing engine, pilots).

Storage is *columnar*: events append to per-``(run_id, component, kind)``
columns of ``(ts, attrs)`` rows with interned component/kind strings,
instead of one global list of event objects.  ``record`` is the simulation hot path and is
lock-free — a single C-level ``list.append`` per event, atomic under the
GIL, so the single-threaded simulators pay no lock and the threaded engine
still cannot tear a row (each row is one tuple in one list).  Derived
queries (``latencies``, ``throughput``, ``steady_state_throughput``) read a
column directly and join/aggregate with numpy, instead of copying and
re-filtering the full event list per query.  ``TraceEvent`` objects are
materialized lazily, only when ``events()`` is called.

Pooled experiment sweeps run in worker processes with private registries;
``export_summary`` / ``merge_summary`` are the compact return channel that
carries per-(component, kind) event summaries back into the caller's
registry (see ``streaminsight.run_cells``).

``span`` is the layer's wall-clock duration instrument for the real
(threaded, on-device) path: a named span written into the JAX profiler's
trace, on the device trace's clock, while a profiler session records, and
a shared no-op otherwise.  The virtual-clock simulators record no spans.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import sys
import threading
import uuid
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

__all__ = ["new_run_id", "TraceEvent", "MetricRegistry", "span", "percentile_summary"]

_counter = itertools.count()


def new_run_id(prefix: str = "run") -> str:
    """Unique run id propagated through producer → broker → processor."""
    return f"{prefix}-{next(_counter)}-{uuid.uuid4().hex[:8]}"


@dataclass(frozen=True, slots=True)
class TraceEvent:
    """A single traced event, attributable to a run id.

    ``component`` is e.g. 'producer', 'broker', 'engine', 'pilot'.
    ``kind`` is e.g. 'produce', 'append', 'dispatch', 'complete'.
    Timestamps are in the owning clock's seconds (virtual or wall).
    """

    run_id: str
    component: str
    kind: str
    ts: float
    attrs: dict = field(default_factory=dict)


class _Column:
    """Append-only event column for one (run_id, component, kind) triple."""

    __slots__ = ("component", "rows")

    def __init__(self, component: str) -> None:
        self.component = component
        self.rows: list[tuple[float, dict]] = []   # (ts, attrs)


class MetricRegistry:
    """Modular metric/trace collector (columnar storage, see module docs).

    Collectors register interest in (component, kind) pairs; every component
    publishes events through a shared registry instance so a benchmark run
    sees a single coherent trace.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cols: dict[tuple[str, str, str], _Column] = {}
        self._series: dict[str, list[tuple[float, float]]] = defaultdict(list)
        self._counters: dict[str, float] = defaultdict(float)
        self._merged_summaries: dict[str, dict[str, list]] = {}

    # -- events ------------------------------------------------------------
    def record(self, run_id: str, component: str, kind: str, ts: float, **attrs) -> None:
        """Hot path: one dict lookup + one atomic list append, no lock."""
        col = self._cols.get((run_id, component, kind))
        if col is None:
            # setdefault is atomic; interning keeps key hashing cheap and
            # lets identical kind strings share storage across runs
            col = self._cols.setdefault(
                (sys.intern(run_id), sys.intern(component), sys.intern(kind)),
                _Column(sys.intern(component)))
        col.rows.append((ts, attrs))

    def emit(self, event: TraceEvent) -> None:
        self.record(event.run_id, event.component, event.kind, event.ts,
                    **event.attrs)

    def recorder(self, run_id: str, component: str, kind: str):
        """Pre-resolved emit function for one (run_id, component, kind)
        column.

        Hot emitters (producer, engine) publish hundreds of events per run
        into a column that is fixed for the run's lifetime; binding the
        column append once removes the per-event dict lookup.  The returned
        callable has ``record``'s tail signature: ``rec(ts, **attrs)``."""
        col = self._cols.setdefault(
            (sys.intern(run_id), sys.intern(component), sys.intern(kind)),
            _Column(sys.intern(component)))
        append = col.rows.append

        def rec(ts: float, **attrs) -> None:
            append((ts, attrs))

        return rec

    def events(self, run_id: str | None = None, component: str | None = None,
               kind: str | None = None) -> list[TraceEvent]:
        """Materialize matching events (lazy — only built when asked for)."""
        out = []
        for (rid, comp, knd), col in list(self._cols.items()):
            if run_id is not None and rid != run_id:
                continue
            if kind is not None and knd != kind:
                continue
            if component is not None and comp != component:
                continue
            out.extend(TraceEvent(rid, comp, knd, ts, attrs)
                       for ts, attrs in list(col.rows))
        return out

    def _kind_rows(self, run_id: str, kind: str) -> list[tuple[float, dict]]:
        """All rows of one kind in a run, across components (usually one
        column; multiple components emitting the same kind are merged)."""
        rows: list[tuple[float, dict]] = []
        for (rid, _comp, knd), col in list(self._cols.items()):
            if rid == run_id and knd == kind:
                rows.extend(list(col.rows))
        return rows

    # -- time series + counters ---------------------------------------------
    def observe(self, name: str, ts: float, value: float) -> None:
        with self._lock:
            self._series[name].append((ts, value))

    def series(self, name: str) -> np.ndarray:
        with self._lock:
            return np.asarray(self._series.get(name, []), dtype=np.float64).reshape(-1, 2)

    def incr(self, name: str, amount: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += amount

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    # -- derived metrics -----------------------------------------------------
    def latencies(self, run_id: str, start_kind: str, end_kind: str,
                  key: str = "msg_id") -> np.ndarray:
        """Per-message latency between two event kinds, joined on attrs[key].

        E.g. L^br = append - produce; L^px = complete - append.
        """
        start_rows = self._kind_rows(run_id, start_kind)
        end_rows = self._kind_rows(run_id, end_kind)
        if not start_rows or not end_rows:
            return np.empty(0, dtype=np.float64)
        starts = {attrs.get(key): ts for ts, attrs in start_rows}
        get = starts.get
        out = [ts - s for ts, attrs in end_rows
               if (s := get(attrs.get(key))) is not None]
        return np.asarray(out, dtype=np.float64)

    def kind_count(self, run_id: str, kind: str) -> int:
        """Events of one kind recorded so far — O(columns), not O(events).

        The adaptation control loop computes windowed throughput as the
        delta of this counter between control ticks, so observation cost
        stays independent of trace length."""
        return sum(len(col.rows)
                   for (rid, _comp, knd), col in list(self._cols.items())
                   if rid == run_id and knd == kind)

    def kind_timestamps(self, run_id: str, kind: str) -> np.ndarray:
        """Sorted timestamps of one event kind (the throughput primitive)."""
        rows = self._kind_rows(run_id, kind)
        ts = np.fromiter((t for t, _ in rows), dtype=np.float64, count=len(rows))
        ts.sort()
        return ts

    def throughput(self, run_id: str, kind: str) -> float:
        """Events/sec of a given kind over the run's active window."""
        ts = self.kind_timestamps(run_id, kind)
        if ts.size < 2:
            return 0.0
        span = float(ts[-1] - ts[0])
        if span <= 0:
            return 0.0
        return (ts.size - 1) / span

    def steady_state_throughput(self, run_id: str, kind: str = "complete",
                                warmup_frac: float = 0.25) -> float:
        """Events/sec over the post-warmup window (max sustained throughput)."""
        ts = self.kind_timestamps(run_id, kind)
        if ts.size < 4:
            return 0.0
        window = ts[int(ts.size * warmup_frac):]
        span = float(window[-1] - window[0])
        if span <= 0:
            return 0.0
        return (window.size - 1) / span

    # -- compact cross-process trace channel ---------------------------------
    def export_summary(self) -> dict[str, dict[str, list]]:
        """Compact, picklable per-run trace summary:
        ``{run_id: {"component/kind": [count, t_min, t_max]}}``.

        This is what a pooled sweep worker sends back instead of its full
        event columns (see ``streaminsight.run_cells``).
        """
        out: dict[str, dict[str, list]] = {}
        for (rid, comp, kind), col in list(self._cols.items()):
            rows = list(col.rows)
            if not rows:
                continue
            ts = [t for t, _ in rows]
            out.setdefault(rid, {})[f"{comp}/{kind}"] = [
                len(rows), min(ts), max(ts)]
        return out

    def merge_summary(self, summary: dict[str, dict[str, list]]) -> None:
        """Merge a worker's ``export_summary`` into this registry."""
        with self._lock:
            for rid, kinds in summary.items():
                dst = self._merged_summaries.setdefault(rid, {})
                for ck, (count, t_min, t_max) in kinds.items():
                    if ck in dst:
                        old = dst[ck]
                        dst[ck] = [old[0] + count, min(old[1], t_min),
                                   max(old[2], t_max)]
                    else:
                        dst[ck] = [count, t_min, t_max]

    def trace_summary(self, run_id: str) -> dict[str, list]:
        """Per-(component/kind) ``[count, t_min, t_max]`` for one run —
        computed from local columns for runs traced in-process, or served
        from merged worker summaries for pooled runs."""
        local = self.export_summary().get(run_id)
        if local:
            return local
        with self._lock:
            return dict(self._merged_summaries.get(run_id, {}))

    def run_ids(self) -> list[str]:
        """All run ids this registry knows about (local or merged)."""
        with self._lock:
            merged = set(self._merged_summaries)
        return sorted({key[0] for key in self._cols} | merged)


_NO_SPAN = contextlib.nullcontext()


@functools.cache
def _trace_annotation():
    # jax is imported on the first span asked for, never by the simulators
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def span(name: str, **meta):
    """A span ``name`` in the profiler's trace, carrying ``meta`` as its
    metadata, while a profiler session records; otherwise one shared no-op
    context manager, so an unrecorded span allocates nothing and formats
    no metadata."""
    annotation = _trace_annotation()
    if annotation.is_enabled():
        return annotation(name, **meta)
    return _NO_SPAN


def percentile_summary(values) -> dict:
    values = np.asarray(values, dtype=np.float64)
    if values.size == 0:
        return {"count": 0}
    p50, p95, p99 = np.percentile(values, (50, 95, 99))
    return {
        "count": int(values.size),
        "mean": float(values.mean()),
        "std": float(values.std()),
        "p50": float(p50),
        "p95": float(p95),
        "p99": float(p99),
        "min": float(values.min()),
        "max": float(values.max()),
    }
