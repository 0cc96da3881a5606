"""The one traffic generator: turns a mix file and ``--seed`` into an
open-loop schedule that does not depend on how fast the system consumes.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

``backlog``
    messages appended before the window opens (a drained cell's queue),
    several times what the system drains in a window, so that a faster
    system still finds a queue at the close;
``reach_per_s``
    with a backlog: a rate below the measured drain rate; the check's
    copied steps are drawn among the first ``reach_per_s`` × seconds;
``phases``
    a list of ``{"rate_per_s": r, "seconds": s}`` cycled through the
    window; a phase without ``seconds`` lasts the whole window.  An on/off
    burst is two phases, one of them at rate 0.

Within a phase of n = round(r·s) arrivals the gaps between arrivals are
the n stratified quantiles of the exponential distribution of mean 1/r
(Poisson arrivals), in an order drawn from the seed.  Every seed thus
offers the same number of messages and the same set of gaps, in another
order: the seed changes which bursts come when, never how much work a run
holds.  Message payloads come from a seeded pool that the configuration's
app makes (``bench/apps/<app>.py``); message ``i`` carries pool entry
``i % len(pool)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["Schedule", "schedule", "poisson_gaps", "seed_stream"]

# independent streams drawn from one --seed
STREAM_TRAFFIC, STREAM_DATA, STREAM_CHECK, STREAM_MODEL = 1, 2, 3, 4


def seed_stream(seed: int, stream: int) -> np.random.SeedSequence:
    """A seed sequence for one purpose; any whole ``seed``, however large."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream])


@dataclass(frozen=True)
class Schedule:
    backlog: int             # messages appended before the window opens
    due: np.ndarray          # due times of window arrivals, s after opening


def poisson_gaps(rng, rate: float, n: int) -> np.ndarray:
    """The n stratified quantiles of the exponential gap of mean 1/rate,
    in an order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return gaps


def _phase_arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    n = int(round(rate * seconds))
    if n <= 0:
        return np.empty(0)
    gaps = poisson_gaps(rng, rate, n)
    # the first arrival opens the phase; each later one follows its gap
    return np.cumsum(gaps) - gaps


def schedule(mix: dict, seconds: float, seed: int) -> Schedule:
    backlog = int(mix.get("backlog", 0))
    phases = mix.get("phases", [])
    if backlog < 0 or (not phases and backlog == 0):
        raise ValueError(f"traffic offers no messages: {mix}")
    rng = np.random.default_rng(seed_stream(seed, STREAM_TRAFFIC))
    due, t = [], 0.0
    while phases and t < seconds:
        for ph in phases:
            dur = min(float(ph.get("seconds", seconds)), seconds - t)
            if dur <= 0:
                raise ValueError(f"phase of no length: {ph}")
            due.append(t + _phase_arrivals(rng, float(ph["rate_per_s"]), dur))
            t += dur
            if t >= seconds:
                break
    due = np.concatenate(due) if due else np.empty(0)
    return Schedule(backlog, due[due < seconds])

