"""The harness on the CPU at a tiny size: discovery by name, a whole run
through the served path, and the check catching a broken timed path.

These runs skip only the harness's look for a chip (``bench/run.py``
refuses a host without one, tested below); everything else is the
benchmark's own path: generator, broker, threaded engine, ``jax://``
pilot, the program's step, the copies the check takes, the reference.
"""

import json
import os
import shutil
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402
from bench.control import control_step  # noqa: E402
from repro.models import kmeans  # noqa: E402

TINY = {"points_per_message": 512, "centroids": 16, "pool_messages": 4,
        "check_first": 4, "check_steps": 4}
# a model of another kind: read-only weights, prompts of three lengths,
# logits per message, its step outside the model lock
TINY_LM = {"name": "tiny-lm", "app": "tiny_lm", "vocab": 64, "width": 32,
           "prompt_lengths": [8, 16, 24], "pool_messages": 6,
           "partitions": 2, "batch_max": 1, "check_first": 4,
           "check_steps": 8, "limits": {"logit_err": 1e-4}}
SECONDS = 1.0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's benchmark files plus throwaway configurations, mixes,
    a metric and an app, added as new files and entries only."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/kmeans-n8k-c128.json").read_text())
    cfg.update(TINY, name="tiny")
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-steady.json").write_text(
        json.dumps({"phases": [{"rate_per_s": 150.0}]}))
    (root / "bench/traffic/tiny-drain.json").write_text(
        json.dumps({"backlog": 4000, "reach_per_s": 50}))
    (root / "bench/configs/tiny-lm.json").write_text(json.dumps(TINY_LM))
    (root / "bench/traffic/tiny-lm-drain.json").write_text(
        json.dumps({"backlog": 4000, "reach_per_s": 50}))
    shutil.copy(ROOT / "tests/bench/data/tiny_lm.py",
                root / "bench/apps/tiny_lm.py")
    (root / "bench/metrics/committed_msgs.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    return float(np.isfinite(run.committed).sum())\n")
    for c in ("tiny", "tiny-lm"):
        spec["configs"].append({"name": c, "source": "test",
                                "file": f"bench/configs/{c}.json",
                                "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": "tiny-lm-drain", "config": "tiny-lm",
                              "traffic": "tiny-lm-drain", "chips": 1,
                              "why": "tiny"})
    for t in ("tiny-steady", "tiny-drain"):
        spec["workloads"].append({"name": t, "config": "tiny", "traffic": t,
                                  "chips": 1, "why": "tiny"})
    spec["end_to_end"].append({"name": "committed_msgs", "unit": "msgs",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["tiny-drain"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "throughput" in (m["name"], m.get("moves")):
            m["workloads"].append("tiny-drain")
    # the open-loop readers are files already; a cell lists them by name
    spec["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["tiny-steady"]}
        for n in ("latency_p50_ms", "latency_p95_ms")]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": layer, "moves": "latency_p95_ms",
         "workloads": ["tiny-steady"]}
        for n, u, layer in (("producer.lateness_p95_ms", "ms", "producer"),
                            ("broker.lag_end", "msgs", "broker"),
                            ("engine.wait_p50_ms", "ms", "engine and pilot"))]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    harness.CompileCounter.install()
    return root


def _run(root, cell, seed, step=None):
    bench = harness.Bench(root)
    run = harness.run_cell(bench, cell, seed, SECONDS, False, t_start=0.0,
                           step=step)
    return bench, run


def test_every_cell_resolves_by_name():
    bench = harness.Bench(ROOT)
    spec = bench.spec
    used = {c["config"] for c in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for cell in spec["workloads"]:
        cfg = bench.config(cell["config"])
        assert cfg["name"] == cell["config"]
        app = bench.app(cfg["app"])
        for piece in ("make_pool", "size_bytes", "init_state", "warm_up",
                      "convert", "make_step", "capture", "check", "control"):
            assert callable(getattr(app, piece)), piece
        assert app.STEP_MODULE and app.KERNEL_NAMES
        assert bench.traffic(cell["traffic"])
        assert bench.metrics(cell["name"], traced=False)
        assert bench.metrics(cell["name"], traced=True)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
        for w in m.get("workloads", []):
            bench.cell(w)


@pytest.mark.parametrize("cell", ["tiny-steady", "tiny-drain"])
def test_throwaway_configuration_runs_from_new_files(tiny_root, cell):
    bench, run = _run(tiny_root, cell, 2 ** 31 + 11)
    line = harness.result_line(bench, run, False, jax.devices()[:1])
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = set(line["metrics"])
    if cell == "tiny-drain":
        assert names == {"throughput", "setup_s", "committed_msgs"}
        assert run.extra["backlog_left"] > 0
    else:
        assert names == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
        assert line["attempted"] == 150
        traced = {m["name"] for m in bench.metrics(cell, traced=True)}
        assert traced == {"producer.lateness_p95_ms", "broker.lag_end",
                          "engine.wait_p50_ms"}
        for name in traced:
            assert bench.reader(name)(run) >= 0
    assert line["checks"]["steps_checked"]["value"] >= TINY["check_first"]


def test_a_model_of_another_kind_runs_from_new_files(tiny_root):
    """A model whose step reads its weights and returns an output per
    message, outside the model lock, taken from new files alone: the
    harness's own files in the scratch root are the checkout's, byte for
    byte."""
    for path in (tiny_root / "bench").rglob("*"):
        mine = ROOT / path.relative_to(tiny_root)
        if path.is_file() and mine.exists():
            assert path.read_bytes() == mine.read_bytes(), mine
    bench, run = _run(tiny_root, "tiny-lm-drain", 2 ** 31 + 13)
    line = harness.result_line(bench, run, False, jax.devices()[:1])
    checks = line["checks"]
    assert line["correct"], checks
    assert line["failed"] == 0 and line["attempted"] > 0
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["steps_checked"]["value"] >= TINY_LM["check_first"]
    assert set(checks) == {"logit_err", "delivery_err",
                           "compiles_in_window", "steps_checked"}
    assert run.extra["notes"]["tokens"] >= 8 * TINY_LM["check_first"]
    app = bench.app("tiny_lm")
    pool = app.make_pool(bench.config("tiny-lm"), 2 ** 31 + 13)
    assert {len(x) for x in pool} == set(TINY_LM["prompt_lengths"])

    fault = jax.jit(lambda w, x: app.forward(w, x) * (1 + 1e-3))
    _, bad = _run(tiny_root, "tiny-lm-drain", 2 ** 31 + 13, step=fault)
    assert bad.extra["correct"] is False
    logit_err, limit = bad.extra["checks"]["logit_err"]
    assert logit_err > limit
    assert bad.extra["checks"]["compiles_in_window"][0] == 0


def test_check_positions_lie_within_the_stated_reach():
    """Drawn steps stay below what a run surely drains, however long the
    backlog, so that ``steps_checked`` does not fall with its size."""
    cfg = {"check_first": 4, "check_steps": 64}
    mix = {"backlog": 240_000, "reach_per_s": 900}
    seed = 2 ** 31 + 5
    sched = traffic.schedule(mix, 51.0, seed)
    pos = harness._check_positions(cfg, mix, sched, 51.0, seed)
    assert set(range(4)) <= pos and len(pos) >= 64
    assert max(pos) < 900 * 51


@partial(jax.jit, donate_argnums=(0,))
def _unchanged(state, points):
    return jax.tree.map(jnp.copy, state)


@partial(jax.jit, donate_argnums=(0,))
def _half_batch(state, points):
    return kmeans.minibatch_step.__wrapped__(state, points[: len(points) // 2])


@partial(jax.jit, donate_argnums=(0,))
def _one_label_altered(state, points):
    labels, _ = kmeans.assign(points, state.centroids)
    k = state.centroids.shape[0]
    labels = labels.at[0].set((labels[0] + 1) % k)
    onehot = jax.nn.one_hot(labels, k, dtype=points.dtype)
    counts = onehot.sum(axis=0)
    sums = jnp.matmul(onehot.T, points, precision=jax.lax.Precision.HIGHEST)
    total = state.counts + counts
    eta = jnp.where(total > 0, counts / jnp.maximum(total, 1.0), 0.0)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    moved = (1.0 - eta)[:, None] * state.centroids + eta[:, None] * means
    return kmeans.KMeansState(centroids=moved, counts=total)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _one_label_altered],
                         ids=["state_unchanged", "half_batch", "label_altered"])
def test_a_broken_step_is_not_correct(tiny_root, fault):
    bench, run = _run(tiny_root, "tiny-drain", 5, step=fault)
    line = harness.result_line(bench, run, False, jax.devices()[:1])
    assert line["correct"] is False
    assert line["checks"]["count_err"]["value"] >= 1


def test_the_control_is_not_correct(tiny_root):
    """The reference at ``high`` (three bf16 passes) in the program's
    place: the young model's first steps give it away."""
    bench, run = _run(tiny_root, "tiny-drain", 6, step=control_step)
    checks = run.extra["checks"]
    assert run.extra["correct"] is False
    assert checks["centroid_err"][0] > checks["centroid_err"][1]


def test_last_line_shape():
    bench = harness.Bench(ROOT)
    n = 6
    run = harness.Run(
        cell=bench.cell("kmeans-c8192-drain"),
        config=bench.config("kmeans-n26k-c8192"),
        traffic={"backlog": 6}, seconds=1.0, setup_s=3.0, t_open=10.0,
        t_close=11.0, due=np.full(n, np.nan), appended=np.full(n, np.nan),
        stamps=np.tile([10.1, 10.2, 10.3, 10.4, 10.5], (n, 1)),
        committed=np.r_[10.5 + 0.1 * np.arange(n - 1), np.nan],
        lag_end=2, peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"window_s": 0.5, "busy_s": 0.4, "step_s": [0.1, 0.1],
               "kernel_s": 0.05, "kernel_events": 2,
               "device_ops": [["fusion", 0.3]],
               "idle_gaps": [["bench.dispatch", 0.06]]})
    run.extra = {"correct": True, "failed": 0, "memory_peak_bytes": 1,
                 "checks": {"count_err": [0.0, 0], "steps_checked": [3, 1]}}

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    line = harness.result_line(bench, run, True, [Dev()])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["attempted"] == n
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    m = line["metrics"]
    assert set(m) == {"host.convert_ms", "step.device_ms", "step_mfu",
                      "kmeans_distance_roofline", "device.idle.drain"}
    assert m["host.convert_ms"]["value"] == pytest.approx(100.0)
    assert m["step.device_ms"]["value"] == pytest.approx(100.0)
    assert m["device.idle.drain"] == {"value": pytest.approx(20.0), "unit": "%"}
    assert m["step_mfu"]["value"] == pytest.approx(
        100 * 2 * 6_177_678_368 / (0.5 * 197e12), rel=1e-9)
    assert line["checks"]["count_err"] == {"value": 0.0, "limit": 0}
    untraced = harness.result_line(bench, run, False, [Dev()])
    assert set(untraced["metrics"]) == {"throughput", "setup_s"}
    assert untraced["metrics"]["throughput"]["value"] == pytest.approx(5.0)
    assert "breakdown" not in untraced and "busy_s" not in untraced["device"]
    json.dumps(line)


def test_a_host_without_a_tpu_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "kmeans-c128-drain", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
