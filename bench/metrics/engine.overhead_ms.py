"""The engine's own time per committed batch in the traced window: the
``engine.fetch`` and ``engine.commit`` spans' seconds over the count of
``engine.commit``."""


def read(run):
    spans = (run.trace or {}).get("spans") or {}
    if "engine.commit" not in spans:
        return None
    seconds = spans["engine.commit"][1] + spans.get("engine.fetch", (0, 0.0))[1]
    return 1e3 * seconds / spans["engine.commit"][0]
