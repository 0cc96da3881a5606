"""Mean of the runtime's ``XlaLinearize`` events in the traced window: the
host laying one message out in the device's layout before its copy."""


def read(run):
    n, s = ((run.trace or {}).get("spans") or {}).get("XlaLinearize", (0, 0.0))
    return 1e3 * s / n if n else None
