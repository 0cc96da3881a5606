"""Share of the traced window in which the device is idle while a
host-to-device transfer event (``XlaLinearize``, ``H2D Dispatch``,
``tpu::System::TransferToDevice``) is in progress on a host thread
(``bench.spans``)."""


def read(run):
    t = run.trace
    if not t or "idle_h2d_s" not in t or t["window_s"] <= 0:
        return None
    return 100.0 * t["idle_h2d_s"] / t["window_s"]
