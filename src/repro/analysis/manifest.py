"""The determinism & concurrency manifest: the repo's contract as data.

This file IS the contract the analyzer enforces.  Every module (and, where
one file hosts both worlds, every class/function) is classified:

* ``sim`` — code on the simulation path: the DES core, the broker and sim
  engine, the autoscale tick, USL fitting.  Sim-path code must be
  deterministic given a seed: no wall clock, no unseeded global random
  state, no salted builtin ``hash()`` routing.  The paper's USL claims are
  measured on this substrate, so nondeterminism here silently corrupts the
  science.
* ``wall`` — code that legitimately lives on the wall clock: the threaded
  engine, the real (local/jaxmesh) backends, the wall-clock producers, the
  launch tooling.  The purity rules do not apply.
* ``neutral`` — everything else (models, kernels, configs...): unchecked.

Classification is first-match-wins over ``overrides`` (path glob +
qualname glob), then ``sim_modules`` / ``wall_modules`` path globs, then
``neutral``.  Globs are ``fnmatch`` patterns against repo-relative posix
paths and dotted qualnames ("" is module level).

**Extending the manifest** (e.g. for the future multiprocess engine): add
the new engine's sim-twin modules to ``sim_modules``, its wall/process
classes to ``overrides`` (or ``wall_modules``), and register every new
``threading``/``multiprocessing`` lock in ``known_locks`` with a note
stating its place in the acquisition order.  The tier-1 gate
(``tests/test_static_analysis.py``) fails until the manifest and the code
agree — which is the point.

Worked example — the what-if engine (``core/whatif.py``): the tournament
sits squarely on the sim path (its summaries feed the paper's adaptation
claims), so the module went into ``sim_modules``.  It takes no locks —
expansion/dedupe/reduction are pure, and execution delegates to
``streaminsight.run_cells``, whose module-level pool-creation ``Lock``
was already registered — so ``known_locks`` gained no entry; a wrapper
that only *calls* locked machinery is not a new lock site.  Had it added
one (say a results-accumulator lock fed from pool callbacks), the entry's
note would state it is leaf: acquired after, never while holding, the
pool lock.

Second worked example — widening the fast replay (``sim/batched.py``):
teaching the replay fault-plan splicing, HPC coupling chains and straggler
speculation tripled the module's surface but changed nothing here.  The
new code is pure event-loop machinery over ``sim.des`` (no wall-clock, no
RNG outside the seeded ``Simulator`` streams, no locks), so the existing
``*/repro/sim/*.py`` glob already covers it and neither ``known_locks``
nor a pragma was needed.  Growth that stays inside an existing glob with
zero new findings is the manifest working as designed — the gate only
moves when the *concurrency story* changes, not when code volume does.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from fnmatch import fnmatch

__all__ = ["LockSite", "Manifest", "DEFAULT_MANIFEST"]


def _match_path(path: str, pattern: str) -> bool:
    """fnmatch that treats ``*/x/y.py`` as suffix-anchored: it matches both
    ``repo/x/y.py`` and the repo-relative ``x/y.py`` (where the leading
    ``*`` would otherwise require a component to consume)."""
    return fnmatch(path, pattern) or fnmatch("/" + path, pattern)


@dataclass(frozen=True)
class LockSite:
    """One registered lock constructor site.

    ``note`` documents the lock's role and its place in the acquisition
    order — the runtime shim (``lockwatch``) verifies the order is acyclic,
    this registry is where a human reads what the order *is*.
    """

    path: str        # path glob, e.g. "*/repro/streaming/broker.py"
    qualname: str    # qualname glob of the constructing scope
    kind: str        # "Lock" | "RLock" | "Condition"
    note: str

    def matches(self, path: str, qualname: str) -> bool:
        return _match_path(path, self.path) \
            and fnmatch(qualname, self.qualname)


@dataclass(frozen=True)
class Manifest:
    # -- sim-path purity ----------------------------------------------------
    sim_modules: tuple[str, ...] = ()
    wall_modules: tuple[str, ...] = ()
    # (path glob, qualname glob, classification) — checked before the
    # module lists, first match wins; this is the class/function-level
    # escape for files hosting both worlds (streaming/engine.py).
    overrides: tuple[tuple[str, str, str], ...] = ()
    # -- DES discipline -----------------------------------------------------
    hot_modules: tuple[str, ...] = ()
    # class-name regex: classes matching this in a hot module are per-event
    # records and must declare __slots__ (directly, dataclass(slots=True),
    # or by being a NamedTuple)
    record_class_re: str = r"(Message|Event|Record|State|Scheduled|Column)$"
    # -- concurrency --------------------------------------------------------
    known_locks: tuple[LockSite, ...] = ()
    # -- test audit ---------------------------------------------------------
    test_globs: tuple[str, ...] = ("*/tests/*.py",)
    # test files that may touch the wall clock (threaded-engine suites);
    # every other test file is sim-classified: wall-clock-free by contract
    wall_test_files: tuple[str, ...] = ()
    # files the test audit never applies to (the wait primitive itself)
    test_exempt: tuple[str, ...] = ()
    # -- scanning -----------------------------------------------------------
    exclude: tuple[str, ...] = ()
    max_pragmas: int = 10

    def classify(self, path: str, qualname: str) -> str:
        """'sim' | 'wall' | 'neutral' for a scope at ``path::qualname``."""
        for pg, qg, cls in self.overrides:
            if _match_path(path, pg) and fnmatch(qualname, qg):
                return cls
        for pg in self.sim_modules:
            if _match_path(path, pg):
                return "sim"
        for pg in self.wall_modules:
            if _match_path(path, pg):
                return "wall"
        return "neutral"

    def is_hot(self, path: str) -> bool:
        return any(_match_path(path, pg) for pg in self.hot_modules)

    def is_test_exempt(self, path: str) -> bool:
        return any(_match_path(path, pg) for pg in self.test_exempt)

    def is_test_file(self, path: str) -> bool:
        if self.is_test_exempt(path):
            return False
        return any(_match_path(path, pg) for pg in self.test_globs)

    def is_wall_test(self, path: str) -> bool:
        return any(_match_path(path, pg) for pg in self.wall_test_files)

    def is_excluded(self, path: str) -> bool:
        return any(_match_path(path, pg) for pg in self.exclude)

    def lock_registered(self, path: str, qualname: str) -> bool:
        return any(site.matches(path, qualname) for site in self.known_locks)


DEFAULT_MANIFEST = Manifest(
    sim_modules=(
        "*/repro/sim/*.py",
        "*/repro/streaming/*.py",         # broker/producer/engine (sim side)
        "*/repro/core/usl.py",
        "*/repro/core/autoscale.py",
        "*/repro/core/metrics.py",
        "*/repro/core/miniapp.py",
        "*/repro/core/streaminsight.py",
        # the what-if tournament: pure expand/dedupe/reduce around
        # streaminsight.run_cells — it creates no locks of its own (the
        # module-level pool Lock below covers its execution) and its
        # reducers (sign test, Pareto, win matrices) are seed-deterministic
        "*/repro/core/whatif.py",
        "*/repro/pilot/api.py",
        "*/repro/pilot/backends/hpcsim.py",
        "*/repro/pilot/backends/serverless.py",
        # the federation composes sim backends on one shared Simulator and
        # is lock-free: health/breaker/placement decisions are pure
        # functions of the virtual clock and CU completions
        "*/repro/pilot/backends/federated.py",
    ),
    wall_modules=(
        "*/repro/pilot/backends/local.py",
        "*/repro/pilot/backends/jaxmesh.py",
        "*/repro/launch/*.py",
    ),
    overrides=(
        # streaming/engine.py hosts both engines: the threaded driver and
        # its ticker live on the wall clock by design
        ("*/repro/streaming/engine.py", "ThreadedStreamingEngine*", "wall"),
        ("*/repro/streaming/engine.py", "_WallTicker*", "wall"),
        # Timer is the wall-clock duration context manager
        ("*/repro/core/metrics.py", "Timer*", "wall"),
        # miniapp's wall-clock adaptation path (threaded producer + runner)
        ("*/repro/core/miniapp.py", "_WallClockProducer*", "wall"),
        ("*/repro/core/miniapp.py", "_run_adaptation_threaded*", "wall"),
    ),
    hot_modules=(
        "*/repro/sim/des.py",
        "*/repro/streaming/broker.py",
        "*/repro/streaming/engine.py",
        "*/repro/streaming/producer.py",
        "*/repro/core/metrics.py",
    ),
    known_locks=(
        LockSite("*/repro/streaming/broker.py", "Broker.__init__", "RLock",
                 "broker state (topics/commits/counters); leaf on the "
                 "append path — subscribers run OUTSIDE it"),
        LockSite("*/repro/streaming/engine.py", "_EngineCore.__init__",
                 "Lock", "shared accounting counters; leaf — never held "
                 "across a broker or pilot call"),
        LockSite("*/repro/streaming/engine.py", "_WallTicker.__init__",
                 "Condition", "ticker heap; callbacks run OUTSIDE it"),
        LockSite("*/repro/streaming/engine.py",
                 "ThreadedStreamingEngine.__init__", "Lock",
                 "admin (repartition/start/ticker) serialization; may be "
                 "held while creating wakeup Events, never across broker "
                 "or compute calls"),
        LockSite("*/repro/pilot/backends/local.py", "LocalBackend.__init__",
                 "Condition", "capacity accounting; leaf"),
        LockSite("*/repro/pilot/backends/jaxmesh.py",
                 "JaxMeshBackend.__init__", "Condition",
                 "device accounting; leaf"),
        LockSite("*/repro/core/autoscale.py", "ControlLoop.__init__",
                 "Lock", "control tick vs stop(); outermost on the tick "
                 "path — may be held across metrics/broker/backend calls"),
        LockSite("*/repro/core/metrics.py", "MetricRegistry.__init__",
                 "Lock", "series/summaries (record() is lock-free); leaf"),
        LockSite("*/repro/core/streaminsight.py", "", "Lock",
                 "module-level process-pool creation; leaf"),
    ),
    wall_test_files=(
        # the cross-engine conformance suite drives the threaded engine on
        # the wall clock; test_adaptation deliberately stays SIM-classified
        # — ROADMAP: wall-clock adaptation tests assert only
        # clock-independent facts via conftest.wait_until
        "*/tests/test_engine_conformance.py",
        "*/tests/test_static_analysis.py",   # times subprocess runs of itself
    ),
    test_exempt=(
        "*/tests/conftest.py",              # implements wait_until
    ),
    exclude=(
        "*simlint_fixtures*",               # known-bad corpus, tested apart
    ),
    max_pragmas=10,
)
