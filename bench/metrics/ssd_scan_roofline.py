"""The Pallas SSD kernel's share of its roofline: the kernel's events in
the traced window (one per Mamba-2 layer of each prefill) times the least
time of one call over the prompt (``lm_work.ssd_least_s``: 4·H·P·N
operations a token, or its float32 operands' bytes, whichever bounds),
over the summed device time of those events."""

from bench.lm_work import ssd_least_s


def read(run):
    t = run.trace
    if not t or not t["kernel_events"] or not run.peaks:
        return None
    least, _bound = ssd_least_s(run.config, run.config["prompt_tokens"],
                                run.peaks)
    return 100.0 * t["kernel_events"] * least / t["kernel_s"]
