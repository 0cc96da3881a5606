"""Parallel experiment runner, result cache, and grid axes (StreamInsight)."""

import dataclasses
import json

import pytest

from repro.core.metrics import MetricRegistry
from repro.core.miniapp import StreamExperiment, run_experiment
from repro.core.streaminsight import (PARALLEL_COST_THRESHOLD, _RESULT_FIELDS,
                                      ExperimentDesign, ResultCache,
                                      StreamInsight, estimated_cost, run_cells)


def small_design(**kw):
    kw.setdefault("machines", ["serverless"])
    kw.setdefault("partitions", [1, 2])
    kw.setdefault("n_messages", 16)
    return ExperimentDesign(**kw)


def test_parallel_runner_bit_identical_to_serial():
    """Cells carry their own seed, so pool execution changes nothing.

    ``parallel="force"`` pins the pool path: a small design would
    auto-switch to serial and the test would stop covering the pool."""
    serial = StreamInsight()
    serial.run(small_design(), parallel=False)
    pooled = StreamInsight()
    pooled.run(small_design(), parallel="force")
    assert serial.records() == pooled.records()
    fits_s = [(m.fit.sigma, m.fit.kappa, m.fit.gamma)
              for m in serial.fit_models()]
    fits_p = [(m.fit.sigma, m.fit.kappa, m.fit.gamma)
              for m in pooled.fit_models()]
    assert fits_s == fits_p


def test_run_cells_preserves_input_order():
    cells = [StreamExperiment(machine="serverless", partitions=n,
                              n_messages=12, seed=0) for n in (4, 1, 2)]
    results = run_cells(cells, parallel="force")
    assert [r.experiment.partitions for r in results] == [4, 1, 2]


def test_pool_workers_are_pinned_to_the_cpu(monkeypatch):
    """Pool workers simulate on the host and never start an accelerator
    backend: the chip stays with the parent process that drives it."""
    import os

    import jax

    import repro.core.streaminsight as si

    platforms = jax.config.jax_platforms
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    try:
        si._pin_worker_to_cpu()
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", platforms)
    si._reset_pool()
    try:
        worker_env = si._get_pool(1).submit(os.getenv, "JAX_PLATFORMS")
        assert worker_env.result(timeout=120) == "cpu"
    finally:
        si._reset_pool()


def test_auto_switch_runs_cheap_grids_serially(monkeypatch):
    """parallel=True on a cheap grid must not touch the process pool."""
    import repro.core.streaminsight as si

    cells = [StreamExperiment(machine="serverless", partitions=n,
                              n_messages=12, seed=0) for n in (1, 2)]
    assert estimated_cost(cells) < PARALLEL_COST_THRESHOLD

    def boom(workers):
        raise AssertionError("auto-switch leaked a cheap grid into the pool")

    monkeypatch.setattr(si, "_get_pool", boom)
    results = run_cells(cells, parallel=True)
    assert [r.experiment.partitions for r in results] == [1, 2]
    # a grid past the threshold must take the pool branch
    heavy = [dataclasses.replace(c, n_messages=10_000_000) for c in cells]
    assert estimated_cost(heavy) >= PARALLEL_COST_THRESHOLD
    with pytest.raises(AssertionError, match="leaked"):
        run_cells(heavy, parallel=True)


def test_pooled_run_merges_trace_summaries():
    """The compact return channel: pooled cells surface per-(component,
    kind) event summaries in the caller's registry."""
    cells = [StreamExperiment(machine="serverless", partitions=n,
                              n_messages=12, seed=0) for n in (1, 2)]
    reg = MetricRegistry()
    results = run_cells(cells, parallel="force", metrics=reg)
    for res in results:
        summary = reg.trace_summary(res.run_id)
        assert summary, f"no merged summary for {res.run_id}"
        assert summary["engine/complete"][0] == 12
        counts_ok = all(len(v) == 3 and v[1] <= v[2] for v in summary.values())
        assert counts_ok
    assert set(reg.run_ids()) >= {r.run_id for r in results}


def test_result_cache_serves_rerun_without_executing(tmp_path, monkeypatch):
    si = StreamInsight(cache_dir=tmp_path)
    si.run(small_design())
    first = si.records()
    assert len(list(tmp_path.glob("*.json"))) == 2

    # a second sweep over the same design must not execute a single cell
    import repro.core.streaminsight as streaminsight_mod

    def boom(*_a, **_kw):
        raise AssertionError("cache miss: run_experiment was called")

    monkeypatch.setattr(streaminsight_mod, "run_experiment", boom)
    si2 = StreamInsight(cache_dir=tmp_path)
    si2.run(small_design())
    assert si2.records() == first


def test_result_cache_key_covers_all_fields(tmp_path):
    base = StreamExperiment(machine="serverless", partitions=2, n_messages=16)
    cache = ResultCache(tmp_path)
    cache.put(base, run_experiment(base))
    assert cache.get(base) is not None
    for changed in (
            StreamExperiment(machine="serverless", partitions=2, n_messages=17),
            StreamExperiment(machine="serverless", partitions=2, n_messages=16,
                             seed=1),
            StreamExperiment(machine="serverless", partitions=2, n_messages=16,
                             batch_max=4),
            StreamExperiment(machine="serverless", partitions=2, n_messages=16,
                             policy="update_locked"),
    ):
        assert cache.get(changed) is None, changed


def test_result_cache_corrupt_and_stale_entries_fall_through(tmp_path):
    exp = StreamExperiment(machine="serverless", partitions=2, n_messages=12)
    cache = ResultCache(tmp_path)
    res = run_experiment(exp)
    cache.put(exp, res)
    assert cache.get(exp) is not None

    # corrupt JSON → treated as a miss, never an exception
    cache.path(exp).write_text("{not json")
    assert cache.get(exp) is None

    # stale schema (missing result fields) → miss
    cache.path(exp).write_text(json.dumps(
        {"experiment": dataclasses.asdict(exp)}))
    assert cache.get(exp) is None

    # wrong experiment kwargs (e.g. a renamed field) → miss
    doc = {"experiment": {"bogus_field": 1}}
    doc.update({k: getattr(res, k) for k in _RESULT_FIELDS})
    cache.path(exp).write_text(json.dumps(doc))
    assert cache.get(exp) is None

    # a fresh put repairs the entry and serves again
    cache.put(exp, res)
    assert cache.get(exp).throughput == res.throughput


def test_result_cache_put_roundtrips_all_result_fields(tmp_path):
    exp = StreamExperiment(machine="wrangler", partitions=2, n_messages=12)
    cache = ResultCache(tmp_path)
    res = run_experiment(exp)
    cache.put(exp, res)
    got = cache.get(exp)
    assert got is not None
    for field_name in _RESULT_FIELDS:
        assert getattr(got, field_name) == getattr(res, field_name), field_name
    assert got.experiment == exp


def test_run_cells_mixed_cache_hits_preserve_order(tmp_path):
    """Interleaved cache hits and live (pooled) runs land in input order."""
    cells = [StreamExperiment(machine="serverless", partitions=n,
                              n_messages=12, seed=0) for n in (4, 1, 3, 2)]
    cache = ResultCache(tmp_path)
    # pre-warm only the middle two cells
    for exp in cells[1:3]:
        cache.put(exp, run_experiment(exp))
    seen = []
    results = run_cells(cells, parallel="force", cache=cache,
                        on_result=lambda exp, res: seen.append(exp.partitions))
    assert [r.experiment.partitions for r in results] == [4, 1, 3, 2]
    assert sorted(seen) == [1, 2, 3, 4]          # every cell notified once
    # the two misses are now cached too
    assert all(cache.get(exp) is not None for exp in cells)
    # and a rerun is bit-identical
    rerun = run_cells(cells, parallel=False)
    assert [r.throughput for r in rerun] == [r.throughput for r in results]


def test_policy_and_batch_max_are_grid_axes():
    d = ExperimentDesign(machines=["wrangler"], partitions=[1],
                         policy=["full_fit_locked", "lock_free"],
                         batch_max=[1, 4])
    exps = d.experiments()
    assert len(exps) == 4
    assert {(e.policy, e.batch_max) for e in exps} == {
        ("full_fit_locked", 1), ("full_fit_locked", 4),
        ("lock_free", 1), ("lock_free", 4)}
    # scalar (seed-style) values still work unchanged
    d2 = ExperimentDesign(policy="update_locked", batch_max=2)
    assert all(e.policy == "update_locked" and e.batch_max == 2
               for e in d2.experiments())


def _synthetic_records(levels, machine="wrangler", sigma=0.3, kappa=0.004):
    """Records shaped like ExperimentResult.record() without running cells."""
    import numpy as np

    return [{"machine": machine, "points": 16000, "centroids": 1024,
             "memory_mb": 3008, "policy": None, "batch_max": 1,
             "partitions": int(n),
             "throughput": float(n / (1 + sigma * (n - 1) + kappa * n * (n - 1)))}
            for n in levels]


def test_evaluate_multi_sizes_match_single_calls():
    """evaluate([k1, k2, ...]) is one batched fit but must reproduce the
    per-size evaluate(k) results exactly (same RNG stream per size)."""
    recs = _synthetic_records([1, 2, 3, 4, 6, 8, 12, 16])
    si = StreamInsight()
    multi = si.evaluate([2, 3, 4], records=recs, seed=7)
    assert [m["n_train_configs"] for m in multi] == [2, 3, 4]
    for agg in multi:
        single = si.evaluate(agg["n_train_configs"], records=recs, seed=7)
        assert single == agg


def test_evaluate_sparse_grid_skips_instead_of_crashing():
    """A scenario whose partition grid is too sparse for the requested
    training-set size is dropped from the aggregate, never a ValueError."""
    import math

    sparse = _synthetic_records([1, 2, 16])
    rich = _synthetic_records([1, 2, 3, 4, 6, 8], machine="serverless",
                              sigma=0.02, kappa=1e-5)
    si = StreamInsight()
    # n_train=4 > the sparse scenario's 3 levels: only the rich one survives
    agg = si.evaluate(4, records=sparse + rich, seed=0)
    assert {k[0] for k in agg["scenarios"]} == {"serverless"}
    # nothing fits at all -> empty aggregate with NaN means, still no crash
    empty = si.evaluate(5, records=sparse, seed=0)
    assert empty["scenarios"] == {}
    assert math.isnan(empty["mean_rmse"])
    # the sparse scenario still works at a feasible size
    both = si.evaluate(2, records=sparse + rich, seed=0)
    assert {k[0] for k in both["scenarios"]} == {"serverless", "wrangler"}


def test_fit_models_bootstrap_cis_in_report():
    recs = _synthetic_records([1, 2, 3, 4, 6, 8, 12, 16])
    si = StreamInsight()
    models = si.fit_models(records=recs, bootstrap=16, bootstrap_seed=3)
    assert len(models) == 1
    fit = models[0].fit
    assert fit.n_bootstrap == 16
    assert fit.sigma_ci[0] <= fit.sigma <= fit.sigma_ci[1]
    report = si.report()          # plain report still works, no CI text
    assert "CI95" not in report


def test_result_cache_tmp_name_is_writer_unique(tmp_path, monkeypatch):
    """Two processes sharing a cache dir stage to different tmp files, so
    one writer can't clobber the other's in-flight payload."""
    import repro.core.streaminsight as si_mod

    exp = StreamExperiment(machine="serverless", partitions=2, n_messages=12)
    cache = ResultCache(tmp_path)
    mine = cache._tmp_path(exp)
    monkeypatch.setattr(si_mod.os, "getpid", lambda: 424242)
    theirs = cache._tmp_path(exp)
    assert mine != theirs
    assert mine.name.startswith(cache.path(exp).name)
    monkeypatch.undo()
    # a put leaves exactly the final artifact behind — no stray tmp files
    cache.put(exp, run_experiment(exp))
    assert cache.get(exp) is not None
    assert [p.name for p in tmp_path.iterdir()] == [cache.path(exp).name]


def test_scenario_key_separates_policy_levels():
    si = StreamInsight()
    si.run(ExperimentDesign(machines=["wrangler"], partitions=[1, 2],
                            n_messages=16,
                            policy=["full_fit_locked", "update_locked"]),
           parallel=True)
    models = si.fit_models()
    assert len(models) == 2
    assert {m.key[4] for m in models} == {"full_fit_locked", "update_locked"}
    assert all(len(m.n) == 2 for m in models)


def test_pool_survives_worker_kill_mid_grid():
    """SIGKILL a pool worker while a forced-parallel adaptation grid is in
    flight: ``run_cells`` must respawn the pool, re-run only the cells that
    never landed, and return results bit-identical to a serial run."""
    import os
    import signal

    import repro.core.streaminsight as si
    from repro.core.miniapp import AdaptationExperiment, AdaptationPlan

    cells = [AdaptationPlan(fast=False, experiment=AdaptationExperiment(
        machine="serverless", scaling_policy="usl", seed=seed,
        usl_sigma=0.0, usl_kappa=3.0e-4, usl_gamma=1.94,
        horizon_s=90.0, max_partitions=8, slo_lag=32, control_interval_s=2.0,
        stabilization_s=0.0, scale_down_hysteresis=0.08, headroom=0.0,
        catchup_horizon_s=8.0, max_step_up=2,
        drift_t_s=25.0, drift_factor=1.8,
        rate=dict(kind="step", base_hz=2.0, high_hz=8.0, t_step=15.0,
                  t_end=70.0))) for seed in range(8)]
    serial = [r.record() for r in run_cells(cells, parallel=False)]

    # warm the pool so a worker exists, then note the executor object
    run_cells(cells[:2], parallel="force", max_workers=1)
    old_pool = si._pool
    assert old_pool is not None and old_pool._processes

    state = {"killed": False, "landed": 0}

    def kill_on_first_result(_exp, _res):
        # fires in the parent as each chunk completes; with one worker and
        # 4 chunks of 2, later chunks are still in flight at the first call
        state["landed"] += 1
        if not state["killed"]:
            state["killed"] = True
            for pid in list(si._pool._processes):
                os.kill(pid, signal.SIGKILL)

    pooled = run_cells(cells, parallel="force", max_workers=1,
                       on_result=kill_on_first_result)
    assert state["killed"]
    assert state["landed"] == len(cells)      # completed cells not re-notified
    # the broken executor was replaced, not resubmitted to
    assert si._pool is not None and si._pool is not old_pool
    assert [r.record() for r in pooled] == serial
    si._reset_pool()
