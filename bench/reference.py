"""Plain float64 reference of one MiniBatch K-Means step, and the check of
a step that the timed path applied.

The reference imports nothing of the program.  It is sklearn's
MiniBatchKMeans update (Sculley 2010): each point goes to its nearest
centroid, and a centroid moves towards the mean of its points by its
batch count over its cumulative count.

A step is judged from the state the device held before it and the state
it left, both copied by the timed path itself, and the message's points:

``count_err``
    the largest gap, over centroids, between the device's count increase
    and the reference's number of points assigned, and between the
    device's total increase and the message's size.  Counts are whole
    numbers in float32, so a point that the device put on the wrong
    centroid, or left out, shows as a gap of at least 1.  Centroids that
    hold or lose a near tie (below) are left out of the per-centroid part.
``centroid_err``
    the largest gap between the device's and the reference's centroids
    after the step, over the same centroids, relative to
    M = max(1, largest reference coordinate).

A point is a near tie when its two nearest centroids lie closer together
than the rounding of the float32 sum ||x||^2 + ||c||^2 - 2 x.c, whose terms
are as large as ||x||^2 + ||c||^2: ``TIE_ULPS`` ulps of it.
"""

from __future__ import annotations

import numpy as np

__all__ = ["two_nearest", "reference_step", "check_step", "TIE_ULPS"]

EPS32 = float(np.finfo(np.float32).eps)
TIE_ULPS = 32


def two_nearest(x: np.ndarray, c: np.ndarray, rows: int = 512):
    """float64 (label, second label, gap between the two, ||x||^2 +
    ||c_label||^2).  Ranks ||c||^2 - 2 x.c: ||x||^2 is the same for every
    centroid of a point, so it changes neither the order nor the gap."""
    cn = (c * c).sum(1)
    minus_2ct = -2.0 * c.T
    label = np.empty(len(x), np.int64)
    second = np.empty(len(x), np.int64)
    gap = np.empty(len(x))
    for lo in range(0, len(x), rows):
        g = x[lo:lo + rows] @ minus_2ct
        g += cn
        idx = np.arange(len(g))
        best = g.argmin(1)
        first = g[idx, best]
        g[idx, best] = np.inf
        nxt = g.argmin(1)
        label[lo:lo + rows] = best
        second[lo:lo + rows] = nxt
        gap[lo:lo + rows] = g[idx, nxt] - first
    return label, second, gap, (x * x).sum(1) + cn[label]


def reference_step(c: np.ndarray, counts: np.ndarray, x: np.ndarray,
                   labels: np.ndarray):
    """One MiniBatch K-Means update in float64 from (centroids, counts)."""
    k, d = c.shape
    bc = np.bincount(labels, minlength=k).astype(np.float64)
    sums = np.stack([np.bincount(labels, weights=x[:, j], minlength=k)
                     for j in range(d)], axis=1)
    counts = counts + bc
    eta = np.where(counts > 0, bc / np.maximum(counts, 1.0), 0.0)
    means = sums / np.maximum(bc, 1.0)[:, None]
    return (1.0 - eta)[:, None] * c + eta[:, None] * means, counts


def check_step(points: np.ndarray, before: tuple, after: tuple) -> dict:
    """Judge one applied step.  ``before``/``after`` are the device's
    (centroids, counts) as float32 host arrays."""
    x = points.astype(np.float64)
    c0, n0 = (a.astype(np.float64) for a in before)
    c1, n1 = (a.astype(np.float64) for a in after)
    label, second, gap, scale = two_nearest(x, c0)
    tie = gap <= TIE_ULPS * EPS32 * scale
    keep = np.ones(len(c0), bool)
    keep[label[tie]] = keep[second[tie]] = False
    want_c, want_n = reference_step(c0, n0, x, label)
    count_err = max(float(np.abs(n1 - want_n)[keep].max(initial=0.0)),
                    abs(float((n1 - n0).sum()) - len(x)))
    bound = max(1.0, float(np.abs(want_c).max()))
    centroid_err = float(np.abs(c1 - want_c).max(axis=1)[keep]
                         .max(initial=0.0)) / bound
    return {"count_err": count_err, "centroid_err": centroid_err,
            "near_ties": int(tie.sum()), "kept_centroids": int(keep.sum())}
