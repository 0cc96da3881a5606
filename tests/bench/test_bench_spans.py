"""The reduction of the program's spans and the runtime's copy and launch
events (``bench/spans.py``), and the readers of the numbers it gives."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans, trace  # noqa: E402

# the trace of tests/bench/test_bench_trace.py: 0.0236 s of
# kmeans-c128-drain on a TPU v5 lite, recorded before the program had
# spans of its own
RECORDED = ROOT / "tests/bench/data/kmeans-n8k-c128.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    app = harness.Bench(ROOT).app("kmeans")
    accepted = trace.reduce_trace(str(RECORDED), chips=1,
                                  step_module=app.STEP_MODULE,
                                  kernel_names=app.KERNEL_NAMES)
    return accepted, spans.reduce_spans(str(RECORDED), chips=1)


def test_recorded_trace_keeps_the_accepted_gap_names(recorded):
    accepted, split = recorded
    assert split["idle_gaps"] == accepted["idle_gaps"]
    gaps = dict(split["idle_gaps"])
    assert gaps["bench.block"] == pytest.approx(0.009641765999999954, abs=1e-12)
    assert not any(n.startswith(("engine.", "pilot.")) for n in gaps)


def test_recorded_trace_splits_the_idle_time(recorded):
    accepted, split = recorded
    window = accepted["window_s"]
    assert 100 * split["idle_h2d_s"] / window == pytest.approx(
        28.727176026598507, rel=1e-9)
    assert 100 * split["idle_launch_s"] / window == pytest.approx(
        24.652459282205776, rel=1e-9)
    assert split["idle_h2d_s"] + split["idle_launch_s"] <= \
        window - accepted["busy_s"]
    n, seconds = split["spans"]["XlaLinearize"]
    assert n == 20
    assert 1e3 * seconds / n == pytest.approx(0.2779704999999997, rel=1e-9)
    assert split["spans"]["PJRT_LoadedExecutable_Execute"][0] == 28
    assert split["spans"]["H2D Dispatch"][0] == 20
    assert not set(spans.PROGRAM_SPANS) & set(split["spans"])


def _run_with(t):
    bench = harness.Bench(ROOT)
    n = 1
    return harness.Run(
        cell=bench.cell("kmeans-c128-drain"),
        config=bench.config("kmeans-n8k-c128"), traffic={"backlog": n},
        seconds=1.0, setup_s=1.0, t_open=0.0, t_close=1.0,
        due=np.full(n, np.nan), appended=np.full(n, np.nan),
        stamps=np.full((n, 5), np.nan), committed=np.full(n, np.nan),
        lag_end=0, peaks={}, trace=t)


@pytest.mark.parametrize("name, expected", [
    ("device.idle.h2d", 28.727176026598507),
    ("device.idle.launch", 24.652459282205776),
    ("h2d.linearize_ms", 0.2779704999999997),
    ("engine.overhead_ms", None),      # no program spans in this trace
    ("pilot.overhead_ms", None),
])
def test_new_readers_on_the_recorded_trace(recorded, name, expected):
    accepted, split = recorded
    value = harness.Bench(ROOT).reader(name)(_run_with({**accepted, **split}))
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, rel=1e-9)
    # what the accepted reduction alone gives: nothing to read
    assert harness.Bench(ROOT).reader(name)(_run_with(accepted)) is None
    assert harness.Bench(ROOT).reader(name)(_run_with(None)) is None


def test_copy_and_launch_shares_stay_inside_the_idle_share(recorded):
    accepted, split = recorded
    run = _run_with({**accepted, **split})
    read = harness.Bench(ROOT).reader
    assert read("device.idle.h2d")(run) + read("device.idle.launch")(run) \
        <= read("device.idle.drain")(run)


# A window of 10 s on one thread each, built by hand: the device runs in
# [0, 1] and [6, 7]; idle elsewhere.
HOST = {
    trace.WINDOW_SPAN: [(0.0, 10.0)],
    "bench.block": [(1.0, 2.0)],
    "engine.fetch": [(2.0, 2.5)],
    "pilot.unit": [(2.5, 6.0), (7.0, 9.5)],
    "pilot.fn": [(3.0, 5.0), (7.5, 9.0)],
    "pilot.block": [(5.0, 5.5)],
    "engine.commit": [(9.5, 9.75)],
    "XlaLinearize": [(3.0, 4.0)],
    "H2D Dispatch": [(3.5, 4.5)],
    "PJRT_LoadedExecutable_Execute": [(4.0, 5.0), (8.0, 8.5)],
}
OPS = [(0.0, 1.0), (6.0, 7.0)]


def test_gaps_left_by_the_bench_spans_are_named_by_program_spans():
    out = spans.split_idle(HOST, OPS)
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.block": 1.0, "engine.fetch": 0.5, "pilot.fn": 3.5,
        "pilot.block": 0.5, "pilot.unit": 2.0, "engine.commit": 0.25,
        trace.UNANNOTATED: 0.25})
    assert sum(gaps.values()) == pytest.approx(10.0 - 2.0)
    assert out["idle_h2d_s"] == pytest.approx(1.5)        # [3, 4.5]
    assert out["idle_launch_s"] == pytest.approx(0.5 + 0.5)  # [4.5, 5], [8, 8.5]
    assert out["spans"]["pilot.unit"] == [2, pytest.approx(6.0)]
    assert out["spans"]["pilot.fn"] == [2, pytest.approx(3.5)]


def test_engine_and_pilot_readers_on_program_spans():
    out = spans.split_idle(HOST, OPS)
    run = _run_with({"window_s": 10.0, "busy_s": 2.0, **out})
    read = harness.Bench(ROOT).reader
    # (fetch 0.5 s + commit 0.25 s) over one commit
    assert read("engine.overhead_ms")(run) == pytest.approx(750.0)
    # (units 6.0 s - functions 3.5 s) over two units
    assert read("pilot.overhead_ms")(run) == pytest.approx(1250.0)
    assert read("device.idle.h2d")(run) == pytest.approx(15.0)
    assert read("device.idle.launch")(run) == pytest.approx(10.0)
    assert read("h2d.linearize_ms")(run) == pytest.approx(1000.0)
