"""The ``jax://`` pilot's self time per unit in the traced window:
``pilot.unit`` seconds less the ``pilot.fn`` seconds inside them (the user
function), over the count of ``pilot.unit``.  It holds entering the
pilot's mesh, the unit's bookkeeping and the wait on its output."""


def read(run):
    spans = (run.trace or {}).get("spans") or {}
    if "pilot.unit" not in spans:
        return None
    n, unit_s = spans["pilot.unit"]
    return 1e3 * (unit_s - spans.get("pilot.fn", (0, 0.0))[1]) / n
