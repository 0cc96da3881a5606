"""The work the steps in the traced window require, as a share of the
chip's peak over that window: steps counted from the trace (programs that
ran wholly inside it), each at ``work.step_flops`` for the data's own
width (9, not the 128 lanes a kernel pads it to), over window x peak
x chips."""

from bench.work import step_flops


def read(run):
    if not run.trace or not run.trace["step_s"] or not run.peaks:
        return None
    cfg = run.config
    flops = len(run.trace["step_s"]) * step_flops(
        cfg["points_per_message"], cfg["centroids"], cfg["dim"])
    peak = run.peaks["flops_per_s"] * run.cell["chips"]
    return 100.0 * flops / (run.trace["window_s"] * peak)
