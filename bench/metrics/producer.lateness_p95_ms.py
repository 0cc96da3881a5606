"""How late the generator appended: append time minus due time, 95th
percentile over the messages due in the window."""

import numpy as np


def read(run):
    ok = run.in_window(run.due) & np.isfinite(run.appended)
    late = (run.appended[ok] - run.due[ok]) * 1e3
    return float(np.percentile(late, 95)) if len(late) else None
