"""Reduction of one profiler trace to the benchmark's device numbers.

The traced run wraps the traced part of its window in a host span
``bench.traced`` and each call it makes into the system in a span of its
own (``bench.append``, ``bench.convert``, ``bench.lock_wait``,
``bench.dispatch``, ``bench.block``), all written into the profiler's
trace by ``jax.profiler.TraceAnnotation``.  From the trace file alone this
module takes, over that window:

* the device's busy time: the union of the intervals in which an XLA
  operation ran, per chip, averaged over the chips used;
* every execution of the step's program (the ``XLA Modules`` line);
* every event of a named kernel (the ``XLA Ops`` line, by operation
  name);
* the operations that took most time;
* the device's idle gaps, each instant named by the benchmark span in
  progress then, in the order of ``GAP_PRIORITY``, else as unannotated
  engine or pilot code.
"""

from __future__ import annotations

import gzip
from collections import defaultdict

import numpy as np

__all__ = ["reduce_trace", "GAP_PRIORITY", "UNANNOTATED"]

WINDOW_SPAN = "bench.traced"
# a gap instant is named by the first of these in progress on any thread
GAP_PRIORITY = ("bench.dispatch", "bench.block", "bench.convert",
                "bench.append", "bench.lock_wait")
UNANNOTATED = "engine/pilot (unannotated)"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE, DEVICE_PREFIX = "/host:CPU", "/device:TPU:"


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (k, 2) intervals."""
    if len(iv) == 0:
        return np.empty((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def _complement(u: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Gaps of the disjoint sorted ``u`` within [lo, hi]."""
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out).reshape(-1, 2)


def _length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def name_gaps(gaps: np.ndarray, spans: dict) -> dict:
    """Seconds of ``gaps`` named by the spans in progress, in priority."""
    named, left = {}, gaps
    for name in GAP_PRIORITY:
        u = _union(np.asarray(spans.get(name, []), float).reshape(-1, 2))
        hit = _intersect(left, u)
        if len(hit):
            named[name] = _length(hit)
            left = _intersect(left, _complement(u, -np.inf, np.inf))
    if _length(left) > 0:
        named[UNANNOTATED] = _length(left)
    return named


def _events(line):
    """(start s, end s, name) of every event on a trace line."""
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def op_name(event_name: str) -> str:
    """An operation's own name: an ``XLA Ops`` event is named by its whole
    HLO instruction, ``%name = shape op(operands...)``, whose operands may
    name other operations (the argmin fusion reads the kernel's output)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_trace(path: str, *, chips: int, step_module: str,
                 kernel_names: tuple) -> dict:
    """The device numbers of one trace file (see the module's docstring).

    ``step_module`` is a substring of the step program's module name and
    ``kernel_names`` substrings of the kernel's operation names.  A path
    ending in ``.gz`` is read as a gzipped trace file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    spans = defaultdict(list)
    devices = {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for lo, hi, name in _events(line):
                    if name.startswith("bench."):
                        spans[name].append((lo, hi))
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = lines
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    if len(devices) < chips:
        raise ValueError(f"{path}: {len(devices)} device planes with "
                         f"{OPS_LINE!r}, {chips} chips used")
    lo, hi = spans.pop(WINDOW_SPAN)[0]
    used = sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):]))[:chips]

    busy, op_time, steps, kernel = [], defaultdict(float), [], [0.0, 0]
    gaps0 = None
    for name in used:
        ops = devices[name][OPS_LINE]
        iv = _clip(np.asarray([(a, b) for a, b, _ in ops]).reshape(-1, 2),
                   lo, hi)
        u = _union(iv)
        busy.append(_length(u))
        if gaps0 is None:
            gaps0 = _complement(u, lo, hi)
        for a, b, op in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            op = op_name(op)
            op_time[op] += b - a
            if any(k in op for k in kernel_names):
                kernel[0] += b - a
                kernel[1] += 1
        for a, b, mod in devices[name].get(MODULES_LINE, ()):
            if step_module in mod and a >= lo and b <= hi:
                steps.append(b - a)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(name_gaps(gaps0, spans).items(), key=lambda kv: -kv[1])
    return {
        "window_s": hi - lo,
        "busy_s": float(np.mean(busy)),
        "step_s": steps,                  # device time of each step program
        "kernel_s": kernel[0],            # summed kernel events
        "kernel_events": kernel[1],
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in gaps[:10]],
    }
