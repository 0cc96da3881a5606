"""The requests' work in the traced window as a share of the chip's peak:
requests counted from the trace (decode programs that ran wholly inside
it ÷ ``new_tokens``), each at ``lm_work.request_flops`` (the prompt's
prefill and every decode token), over window × peak × chips."""

from bench.lm_work import request_flops


def read(run):
    t = run.trace
    if not t or not t["step_s"] or not run.peaks:
        return None
    cfg = run.config
    requests = len(t["step_s"]) / cfg["new_tokens"]
    peak = run.peaks["flops_per_s"] * run.cell["chips"]
    return 100.0 * requests * request_flops(cfg) / (t["window_s"] * peak)
