"""The program's spans and the runtime's copy and launch events in one
profiler trace, reduced over the traced window (``bench.traced``).

The engine and the ``jax://`` pilot write spans at their boundaries
(``repro.core.metrics.span``): ``engine.fetch``, ``engine.commit`` and
``engine.wait`` around the broker calls of a partition's consumer,
``pilot.unit`` around a unit's whole run, ``pilot.fn`` around the user
function in it and ``pilot.block`` around the wait on its output.  The
TPU runtime writes its own events on the same clock, on its host threads:
``XlaLinearize`` (the host lays a message out in the device's layout),
``H2D Dispatch`` and ``tpu::System::TransferToDevice`` (the copy), and
``PJRT_LoadedExecutable_Execute`` (the launch).  From the trace file
alone this module takes:

* ``spans``: ``{name: [count, seconds]}`` of those names, on any host
  thread: events that overlap the window, their time inside it;
* ``idle_h2d_s``: the device's idle time in which a transfer event is in
  progress;
* ``idle_launch_s``: its idle time in which a launch is in progress and
  no transfer is;
* ``idle_gaps``: the idle gaps as ``bench.trace.reduce_trace`` names them,
  with what that leaves "engine/pilot (unannotated)" split further by the
  program span in progress, in the order of ``PROGRAM_SPANS``.  The
  seconds of the ``bench.*`` names are the same as there.
"""

from __future__ import annotations

import gzip
from collections import defaultdict

import numpy as np

from bench import trace

__all__ = ["reduce_spans", "split_idle", "PROGRAM_SPANS", "TRANSFER", "LAUNCH"]

# a remaining gap instant is named by the first of these in progress:
# work before waiting, and a child before the span that holds it
PROGRAM_SPANS = ("engine.fetch", "engine.commit", "pilot.block", "pilot.fn",
                 "pilot.unit", "engine.wait")
TRANSFER = ("XlaLinearize", "H2D Dispatch", "tpu::System::TransferToDevice")
LAUNCH = ("PJRT_LoadedExecutable_Execute",)


def _intervals(events: list) -> np.ndarray:
    return np.asarray(events, float).reshape(-1, 2)


def _minus(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The sorted disjoint ``a`` less the union of the intervals ``b``."""
    return trace._intersect(a, trace._complement(trace._union(b),
                                                 -np.inf, np.inf))


def reduce_spans(path: str, *, chips: int) -> dict:
    """The span numbers of one trace file (see the module's docstring),
    over the window and the first chip that ``bench.trace.reduce_trace``
    takes its gaps from.  A path ending in ``.gz`` is read as gzipped."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    wanted = {trace.WINDOW_SPAN, *trace.GAP_PRIORITY, *PROGRAM_SPANS,
              *TRANSFER, *LAUNCH}
    host = defaultdict(list)
    devices = {}
    for plane in pd.planes:
        if plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for lo, hi, name in trace._events(line):
                    if name in wanted:
                        host[name].append((lo, hi))
        elif plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    devices[plane.name] = trace._events(line)
    if not host.get(trace.WINDOW_SPAN):
        raise ValueError(f"{path}: no {trace.WINDOW_SPAN} span")
    if len(devices) < chips:
        raise ValueError(f"{path}: {len(devices)} device planes with "
                         f"{trace.OPS_LINE!r}, {chips} chips used")
    first = min(devices, key=lambda n: int(n[len(trace.DEVICE_PREFIX):]))
    return split_idle(host, [(a, b) for a, b, _ in devices[first]])


def split_idle(host: dict, ops: list) -> dict:
    """The numbers of the module's docstring from the host events
    (``{name: [(start s, end s), ...]}``, the window span among them) and
    the intervals in which an operation ran on the device."""
    lo, hi = host[trace.WINDOW_SPAN][0]
    busy = trace._union(trace._clip(_intervals(ops), lo, hi))
    gaps = trace._complement(busy, lo, hi)

    spans = {}
    for name in PROGRAM_SPANS + TRANSFER + LAUNCH:
        iv = trace._clip(_intervals(host.get(name, [])), lo, hi)
        if len(iv):
            spans[name] = [len(iv), float((iv[:, 1] - iv[:, 0]).sum())]

    def during(names) -> np.ndarray:
        return _intervals([e for n in names for e in host.get(n, [])])

    in_transfer = trace._intersect(gaps, trace._union(during(TRANSFER)))
    in_launch = _minus(trace._intersect(gaps, trace._union(during(LAUNCH))),
                       during(TRANSFER))

    named = trace.name_gaps(gaps, host)
    if named.pop(trace.UNANNOTATED, 0.0) > 0:
        left = _minus(gaps, during(trace.GAP_PRIORITY))
        for name in PROGRAM_SPANS:
            u = trace._union(during((name,)))
            hit = trace._intersect(left, u)
            if len(hit):
                named[name] = trace._length(hit)
                left = _minus(left, u)
        if trace._length(left) > 0:
            named[trace.UNANNOTATED] = trace._length(left)
    gap_list = sorted(named.items(), key=lambda kv: -kv[1])
    return {
        "spans": spans,
        "idle_h2d_s": trace._length(in_transfer),
        "idle_launch_s": trace._length(in_launch),
        "idle_gaps": [[n, s] for n, s in gap_list[:10]],
    }
