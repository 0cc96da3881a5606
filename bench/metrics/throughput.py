"""Messages whose offsets committed inside the window, per second of it."""

import numpy as np


def read(run):
    return float(np.count_nonzero(run.in_window(run.committed))) / run.seconds
