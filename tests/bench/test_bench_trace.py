"""The reduction from a profiler trace to device numbers."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402


def test_union_clip_and_complement():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0], [3.5, 3.6]])
    u = trace._union(iv)
    np.testing.assert_array_equal(u, [[0.0, 2.0], [3.0, 4.0]])
    np.testing.assert_array_equal(trace._clip(u, 1.0, 3.5), [[1.0, 2.0], [3.0, 3.5]])
    np.testing.assert_array_equal(trace._complement(u, -1.0, 5.0),
                                  [[-1.0, 0.0], [2.0, 3.0], [4.0, 5.0]])


def test_gaps_are_named_by_priority():
    gaps = np.array([[0.0, 1.0], [2.0, 3.0]])
    spans = {
        "bench.lock_wait": [(0.0, 3.0)],          # waits cover everything
        "bench.dispatch": [(0.2, 0.4)],           # outranks the wait
        "bench.block": [(0.3, 0.6), (2.9, 3.5)],  # overlaps dispatch
    }
    named = trace.name_gaps(gaps, spans)
    assert named["bench.dispatch"] == pytest.approx(0.2)
    assert named["bench.block"] == pytest.approx(0.2 + 0.1)
    assert named["bench.lock_wait"] == pytest.approx(2.0 - 0.5)
    assert trace.UNANNOTATED not in named
    named = trace.name_gaps(gaps, {})
    assert named == {trace.UNANNOTATED: pytest.approx(2.0)}


# A trace recorded on a TPU v5 lite: 0.03 s of kmeans-c128-drain (8,000
# points, 128 centroids), about 21 steps, reduced once by hand-checked
# code and pinned here.
RECORDED = ROOT / "tests/bench/data/kmeans-n8k-c128.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from bench import harness
    return trace.reduce_trace(str(RECORDED), chips=1,
                              step_module=harness.STEP_MODULE,
                              kernel_names=harness.KERNEL_NAMES)


def test_recorded_trace_reduces_to_known_numbers(recorded):
    r = recorded
    assert r["window_s"] == pytest.approx(0.023559109999999994, abs=1e-12)
    assert r["busy_s"] == pytest.approx(0.0008228699999999547, abs=1e-12)
    assert len(r["step_s"]) == 21
    assert np.mean(r["step_s"]) == pytest.approx(3.9299e-05, rel=1e-3)
    # one Pallas distance kernel per step; the argmin fusion that reads
    # its output is not counted as the kernel
    assert r["kernel_events"] == 21
    assert r["kernel_s"] == pytest.approx(0.00026800200000001023, abs=1e-12)
    ops = dict(r["device_ops"])
    assert ops["pairwise_sq_dists_pallas.1"] == pytest.approx(r["kernel_s"])
    assert "slice_reduce_fusion" in ops
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.block"] == pytest.approx(0.009641765999999954, abs=1e-12)
    assert gaps["bench.dispatch"] == pytest.approx(0.007296860000000002, abs=1e-12)
    # the named gaps and the busy time make up the window
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)


def test_recorded_trace_shares_stay_under_the_roofline(recorded):
    from bench import work
    from bench.peaks import peaks_for

    least, bound = work.assign_least_s(8000, 128, 9, peaks_for("TPU v5 lite"))
    assert bound == "memory"
    share = 100 * len(recorded["step_s"]) * least / recorded["kernel_s"]
    assert share == pytest.approx(3.1056, rel=1e-3)
    assert 0 < share < 100
