"""L^px median: from each message's due time at the generator to its
offset commit, over every message due in the window; one still
uncommitted at the close counts with its age then (``Run.lpx_ms``)."""

import numpy as np


def read(run):
    lat = run.lpx_ms()
    return float(np.percentile(lat, 50)) if len(lat) else None
