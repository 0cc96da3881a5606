"""The decode program's share of the HBM roofline: the least bytes one
decode token must move (``lm_work.decode_least_bytes``: the bf16 weights
it reads, its expected share of the held experts, the SSM state read and
written; the KV cache left out) at the chip's HBM bandwidth, over the
decode program's mean device time in the traced window."""

import numpy as np

from bench.lm_work import decode_least_bytes


def read(run):
    t = run.trace
    if not t or not t["step_s"] or not run.peaks:
        return None
    least = decode_least_bytes(run.config) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / float(np.mean(t["step_s"]))
