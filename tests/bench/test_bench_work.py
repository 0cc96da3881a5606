"""Operation and byte counting, and the table of peaks."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402
from bench.peaks import PEAKS, peaks_for  # noqa: E402

V5E = peaks_for("TPU v5 lite")


def test_step_flops_at_the_paper_shapes():
    # 3ncd + 2nc + 2nd + 6cd
    assert work.step_flops(26000, 8192, 9) == (
        3 * 26000 * 8192 * 9 + 2 * 26000 * 8192 + 2 * 26000 * 9 + 6 * 8192 * 9)
    assert work.step_flops(26000, 8192, 9) == pytest.approx(6.177e9, rel=1e-3)


def test_assignment_work_and_bound():
    assert work.assign_flops(8000, 128, 9) == 27_648_000
    # points in, centroids in, int32 labels out
    assert work.assign_bytes(8000, 128, 9) == 4 * (72_000 + 1_152 + 8_000)
    t, bound = work.assign_least_s(26000, 8192, 9, V5E)
    assert bound == "compute"
    assert t == pytest.approx(3 * 26000 * 8192 * 9 / 197e12)
    t, bound = work.assign_least_s(8000, 128, 9, V5E)
    assert bound == "memory"
    assert t == pytest.approx(4 * (72_000 + 1_152 + 8_000) / 819e9)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(ValueError):
        peaks_for("cpu")


def test_peaks_name_their_source():
    for kind, p in PEAKS.items():
        assert p["flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
        assert p["source"], kind
