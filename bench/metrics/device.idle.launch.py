"""Share of the traced window in which the device is idle while a launch
(``PJRT_LoadedExecutable_Execute``) is in progress and no transfer is
(``bench.spans``)."""


def read(run):
    t = run.trace
    if not t or "idle_launch_s" not in t or t["window_s"] <= 0:
        return None
    return 100.0 * t["idle_launch_s"] / t["window_s"]
