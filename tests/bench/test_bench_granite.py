"""The Granite 4.0-H app on the CPU at a tiny size, in a scratch root that
adds only new files and entries: a whole run through the served path is
correct, and each planted fault and the float8 control reads not correct.
Then the counts of ``bench/lm_work.py`` at the published widths, and the
new readers with and without a trace."""

import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, lm_work  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

CELL = "tiny-granite-drain"
SECONDS = 1.0
SEED = 2 ** 31 + 17
TINY = json.loads((ROOT / "tests/bench/data/tiny-granite.json").read_text())
PUBLISHED = json.loads(
    (ROOT / "bench/configs/granite-4.0-h-small-l10-e18.json").read_text())
V5E = peaks_for("TPU v5 lite")
READERS = ("step.device_ms", "lm_decode_hbm_roofline",
           "ssd_scan_roofline", "lm_step_mfu")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("granite_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny-granite.json").write_text(json.dumps(TINY))
    (root / "bench/traffic/tiny-granite-drain.json").write_text(
        json.dumps({"backlog": 2000, "reach_per_s": 4}))
    spec["configs"].append({"name": "tiny-granite", "source": "test",
                            "file": "bench/configs/tiny-granite.json",
                            "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": CELL, "config": "tiny-granite",
                              "traffic": "tiny-granite-drain", "chips": 1,
                              "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "throughput" or m["name"] in READERS:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    harness.CompileCounter.install()
    return root


def _run(root, step=None):
    bench = harness.Bench(root)
    return bench, harness.run_cell(bench, CELL, SEED, SECONDS, False,
                                   t_start=0.0, step=step)


def test_granite_app_runs_correct_from_new_files(tiny_root):
    bench, run = _run(tiny_root)
    line = harness.result_line(bench, run, False, jax.devices()[:1])
    checks = line["checks"]
    assert line["correct"], checks
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(checks) == {"logit_err", "route_err", "delivery_err",
                           "compiles_in_window", "steps_checked"}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["steps_checked"]["value"] >= TINY["check_first"]
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    # every prompt token and decoded token routes k experts over 8; this
    # share holds 4 of them
    notes = run.extra["notes"]
    steps = checks["steps_checked"]["value"]
    tokens = TINY["prompt_tokens"] + TINY["new_tokens"]
    assert 0 < notes["pairs_here"] < steps * tokens * 3 * 3
    assert 0 < notes["max_expert_load"] <= steps * (TINY["prompt_tokens"])


@pytest.mark.parametrize("fault", ["ssm_state_dropped",
                                   "routed_expert_dropped",
                                   "shared_expert_dropped", "fp8_control"])
def test_planted_faults_and_the_control_are_not_correct(tiny_root, fault):
    bench = harness.Bench(tiny_root)
    app = bench.app("granite_hybrid")
    step = app.control if fault == "fp8_control" else app.FAULTS[fault]
    _, run = _run(tiny_root, step)
    logit_err, limit = run.extra["checks"]["logit_err"]
    assert run.extra["correct"] is False
    assert logit_err > limit
    if fault == "routed_expert_dropped":    # k - 1 choices: one missing
        assert run.extra["checks"]["route_err"][0] == 1.0
    assert run.extra["checks"]["compiles_in_window"][0] == 0


def test_counts_at_the_published_widths():
    """Hand totals at hidden 4,096: a Mamba-2 mixer holds in_proj 4,096 x
    16,768, conv 8,448 x 4 + bias, A_log, D and dt_bias (128 each), the
    gated norm (8,192) and out_proj 8,192 x 4,096; attention 2 x 4,096^2
    + 2 x 4,096 x 1,024; each layer's router 4,096 x 72, shared expert
    3 x 4,096 x 1,536, 18 held experts of 3 x 4,096 x 768, two norms."""
    mamba = 4096 * 16768 + 8448 * 4 + 8448 + 3 * 128 + 8192 + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    moe = 4096 * 72 + 3 * 4096 * 1536
    experts = 18 * 3 * 4096 * 768
    layer = moe + experts + 2 * 4096
    held = 9 * (mamba + layer) + (attn + layer) + 100352 * 4096 + 4096
    assert mamba == 102_286_976 and attn == 41_943_040
    assert lm_work.params_held(PUBLISHED) == held == 3_264_039_552
    # a decode token: all but the experts, 2.5 held experts a layer, bf16,
    # and nine Mamba-2 states (128 x 64 x 128 float32) read and written
    weights = 9 * mamba + attn + 10 * (moe + 2.5 * 3 * 4096 * 768 + 2 * 4096) \
        + 100352 * 4096 + 4096
    assert lm_work.decode_least_bytes(PUBLISHED) == pytest.approx(
        2 * weights + 9 * 2 * 4 * 128 * 64 * 128)
    assert lm_work.decode_least_bytes(PUBLISHED) == pytest.approx(3.678e9,
                                                                  rel=1e-3)
    prefill = lm_work.prefill_flops(PUBLISHED, 8192)
    assert 2 * 8192 * 1.2e9 < prefill < 2 * 8192 * 1.6e9
    t, bound = lm_work.ssd_least_s(PUBLISHED, 8192, V5E)
    assert bound == "memory"
    assert t == pytest.approx(4 * (2 * 8192 * 128 * 64 + 8192 * 128
                                   + 2 * 8192 * 128 + 128 * 64 * 128) / 819e9)


def _run_record(trace):
    return harness.Run(
        cell={"name": "granite-h-small-doc8k-drain", "chips": 1},
        config=PUBLISHED, traffic={"backlog": 1}, seconds=1.0, setup_s=1.0,
        t_open=0.0, t_close=1.0, due=np.full(1, np.nan),
        appended=np.full(1, np.nan), stamps=np.zeros((1, 5)),
        committed=np.full(1, np.nan), lag_end=0, peaks=V5E, trace=trace)


def test_readers_read_nothing_without_a_trace():
    bench = harness.Bench(ROOT)
    for name in READERS:
        assert bench.reader(name)(_run_record(None)) is None


def test_readers_stay_under_the_roofline_at_the_least_time():
    """A synthetic trace in which every program takes exactly its least
    time: the shares read 100 % at most, the decode time its least."""
    bench = harness.Bench(ROOT)
    decode = lm_work.decode_least_bytes(PUBLISHED) / V5E["hbm_bytes_per_s"]
    ssd, _ = lm_work.ssd_least_s(PUBLISHED, 8192, V5E)
    n = PUBLISHED["new_tokens"]
    per_request = lm_work.request_flops(PUBLISHED) / V5E["flops_per_s"]
    trace = {"window_s": 2 * per_request, "busy_s": 2 * per_request,
             "step_s": [decode] * (2 * n), "kernel_s": 18 * ssd,
             "kernel_events": 18, "device_ops": [], "idle_gaps": []}
    got = {name: bench.reader(name)(_run_record(trace)) for name in READERS}
    assert got["step.device_ms"] == pytest.approx(decode * 1e3)
    for name in ("lm_decode_hbm_roofline", "ssd_scan_roofline", "lm_step_mfu"):
        assert got[name] == pytest.approx(100.0), name
    slow = dict(trace, step_s=[2 * decode] * (2 * n), kernel_s=36 * ssd)
    assert bench.reader("lm_decode_hbm_roofline")(_run_record(slow)) \
        == pytest.approx(50.0)
    assert bench.reader("ssd_scan_roofline")(_run_record(slow)) \
        == pytest.approx(50.0)
