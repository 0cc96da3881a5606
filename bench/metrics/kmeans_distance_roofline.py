"""The kmeans_distance kernel's share of its roofline: the least time the
chip could take for the assignments of the steps in the traced window
(``work.assign_least_s``: 3ncd operations, or the bytes of points and
centroids in and labels out, whichever bounds), over the summed device
time of every kmeans_distance kernel event in that window.  The work is
the assignment's, whichever kernel implements it."""

from bench.work import assign_least_s


def read(run):
    t = run.trace
    if not t or not t["step_s"] or not t["kernel_events"] or not run.peaks:
        return None
    cfg = run.config
    least, _bound = assign_least_s(cfg["points_per_message"],
                                   cfg["centroids"], cfg["dim"], run.peaks)
    return 100.0 * len(t["step_s"]) * least / t["kernel_s"]
