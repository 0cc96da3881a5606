"""L^px 95th percentile, over the population of ``latency_p50_ms``."""

import numpy as np


def read(run):
    lat = run.lpx_ms()
    return float(np.percentile(lat, 95)) if len(lat) else None
