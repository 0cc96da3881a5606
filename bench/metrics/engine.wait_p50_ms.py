"""From a message's due time to its entry into the user function (engine
fetch and dispatch, pilot submit), median over messages due in the
window that entered it."""

import numpy as np


def read(run):
    entry = run.stamps[:, 0]
    ok = run.in_window(run.due) & np.isfinite(entry)
    wait = (entry[ok] - run.due[ok]) * 1e3
    return float(np.percentile(wait, 50)) if len(wait) else None
