"""Suite-wide setup: import paths, opt-in lock-order instrumentation, and
the wall-clock deadline helper.

``wait_until`` is the suite's condition-polling primitive for wall-clock
(threaded-engine) tests: every wait is a *condition with a deadline*,
never a bare ``time.sleep`` — sleep-based waits are exactly the flake
source the adaptation suite audit removed before the threaded path landed.
"""

import os
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
for p in (str(_HERE), str(_HERE.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Opt-in lock-order instrumentation: when SIMLINT_LOCKWATCH_OUT names an
# output path, every threading.Lock/RLock/Condition created by this test
# session is tracked and the acquisition graph is dumped there at session
# end (see repro.analysis.lockwatch).  Installed this early so locks built
# at module-import time (engine/broker singletons in fixtures) are caught.
from repro.analysis import lockwatch as _lockwatch  # noqa: E402

_LOCKWATCH = _lockwatch.install_from_env()


def pytest_sessionfinish(session, exitstatus):
    if _LOCKWATCH is not None:
        _LOCKWATCH.uninstall()
        _LOCKWATCH.dump(os.environ[_lockwatch.ENV_OUT])


def wait_until(condition, timeout: float = 10.0, interval: float = 0.005,
               message: str = "condition") -> None:
    """Poll ``condition()`` until it is truthy or ``timeout`` wall seconds
    elapse (then ``TimeoutError``).  Import from conftest in wall-clock
    tests instead of sleeping a fixed interval and hoping."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if condition():
            return
        time.sleep(interval)
    raise TimeoutError(f"{message} not met within {timeout}s")
