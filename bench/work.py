"""Operations and bytes that the K-Means work requires, from its shapes.

Counted at the data's own width ``d`` (9 in the paper's mini-app), not at
the 128 lanes a kernel may pad it to: padding is the implementation's
cost, not the algorithm's work.  float32 throughout (4 bytes).
"""

from __future__ import annotations

__all__ = ["step_flops", "assign_flops", "assign_bytes", "assign_least_s"]

F32 = 4


def step_flops(n: int, c: int, d: int) -> float:
    """One MiniBatch K-Means step on n points: distances (3ncd, the
    ||x||^2 + ||c||^2 - 2x.c form), the one-hot count and sum (2nc + 2nd),
    and the per-centroid rate and move (6cd)."""
    return 3.0 * n * c * d + 2.0 * n * c + 2.0 * n * d + 6.0 * c * d


def assign_flops(n: int, c: int, d: int) -> float:
    """The assignment's distance work: 3 operations per point, centroid
    and dimension."""
    return 3.0 * n * c * d


def assign_bytes(n: int, c: int, d: int) -> float:
    """The least the assignment must move: points in, centroids in, one
    int32 label per point out."""
    return F32 * (n * d + c * d + n)


def assign_least_s(n: int, c: int, d: int, peaks: dict) -> tuple[float, str]:
    """(least seconds for one assignment on this chip, the bound that sets
    it: ``compute`` or ``memory``)."""
    t_flops = assign_flops(n, c, d) / peaks["flops_per_s"]
    t_bytes = assign_bytes(n, c, d) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
