"""Device time of the jitted step's program, per step, from the trace."""

import numpy as np


def read(run):
    if not run.trace or not run.trace["step_s"]:
        return None
    return float(np.mean(run.trace["step_s"])) * 1e3
