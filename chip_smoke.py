#!/usr/bin/env python3
"""Smoke test: the streaming K-Means mini-app runs on a TPU chip.

One chip (the default) drives the mini-app's main path through the public
API at the paper's published sizes (arXiv 1909.06055 §IV): a producer
appends 32 messages of 26,000 nine-dim float32 points to a 4-partition
broker topic; a threaded streaming engine hands each message to a
``jax://`` pilot, where MiniBatch K-Means with 8,192 centroids updates the
shared model under a lock (the paper's ``full_fit_locked`` sharing).  Every
step is checked against an independent float64 numpy MiniBatch K-Means
step, replayed in the order the lock applied the messages.

``--four-chips`` runs only the sharded path: a few full-width
``qwen2-0.5b`` train steps on a (data=2, model=2) mesh over four chips,
against the same steps on one chip from the same seed.

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chips

Every check that misses exits non-zero with the reason.  On success the
last line of stdout is one JSON object naming the device.  A host without
a TPU is refused: nothing here runs on the CPU instead.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.metrics import MetricRegistry, new_run_id  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import kmeans  # noqa: E402
from repro.pilot.api import PilotComputeService, PilotDescription, State  # noqa: E402
from repro.streaming.broker import Broker  # noqa: E402
from repro.streaming.engine import ThreadedStreamingEngine, Workload  # noqa: E402

# the paper's largest mini-app cell (arXiv 1909.06055 §IV)
N_MESSAGES, POINTS, DIM, CENTROIDS, PARTITIONS = 32, 26_000, 9, 8_192, 4
N_BLOBS, DATA_SCALE = 256, 10.0     # clustered stream: blob centres in ±10

LABEL_AGREEMENT_MIN = 0.999
EPS32 = float(np.finfo(np.float32).eps)
# A point is a near tie when its two nearest centroids are closer than the
# rounding of the float32 sum ||x||^2 + ||c||^2 - 2 x.c, whose terms are as
# large as ||x||^2 + ||c||^2: a few ulps per term over nine dimensions.
TIE_ULPS = 32
# Centroids after each step.  float32 keeps 24 bits, and one update rounds
# a coordinate of magnitude <= M a handful of times (sum, mean, rate, two
# products, add): about 8 * 2^-24 * M = 2^-21 * M.  The bound allows 16x.
CENTROID_TOL_REL = 2.0 ** -17
# Sharded vs one-chip losses differ only in the order of sums.  One bf16
# rounding is 2^-9 (about 2e-3) of a value, and a loss averages 1,024 token
# losses, so the two agree well inside one rounding; the bound is one.
LOSS_TOL_REL = 2e-3


class SmokeCheckFailed(RuntimeError):
    """A check of the smoke test missed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


def say(key: str, value) -> None:
    print(f"chip_smoke: {key} = {value}", flush=True)


_compiles = 0   # backend compiles in this process, persistent-cache hits included


def _count_compile(event: str, _secs: float, **_kw) -> None:
    global _compiles
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def make_stream(seed: int, n_messages: int, points: int, dim: int) -> list:
    """Seeded clustered messages: blob centres plus unit Gaussian noise."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(-DATA_SCALE, DATA_SCALE, (N_BLOBS, dim))
    which = rng.integers(0, N_BLOBS, (n_messages, points))
    pts = centres[which] + rng.normal(size=(n_messages, points, dim))
    return list(pts.astype(np.float32))


def _two_nearest(x: np.ndarray, c: np.ndarray, rows: int = 512):
    """float64 (label, gap between the two nearest, ||x||^2 + ||c_label||^2).

    Ranks ||c||^2 - 2 x.c: the ||x||^2 term is the same for every centroid
    of a point, so it changes neither the order nor the gap."""
    cn = (c * c).sum(1)
    minus_2ct = -2.0 * c.T
    label = np.empty(len(x), np.int64)
    gap = np.empty(len(x))
    for lo in range(0, len(x), rows):
        g = x[lo:lo + rows] @ minus_2ct
        g += cn
        idx = np.arange(len(g))
        best = g.argmin(1)
        first = g[idx, best]
        g[idx, best] = np.inf
        label[lo:lo + rows] = best
        gap[lo:lo + rows] = g.min(1) - first
    return label, gap, (x * x).sum(1) + cn[label]


def reference_step(c: np.ndarray, counts: np.ndarray, x: np.ndarray,
                   labels: np.ndarray):
    """sklearn's MiniBatchKMeans update in float64: a centroid moves towards
    its batch mean by its batch count over its cumulative count."""
    k, d = c.shape
    bc = np.bincount(labels, minlength=k).astype(np.float64)
    sums = np.stack([np.bincount(labels, weights=x[:, j], minlength=k)
                     for j in range(d)], axis=1)
    counts = counts + bc
    eta = np.where(counts > 0, bc / np.maximum(counts, 1.0), 0.0)
    means = sums / np.maximum(bc, 1.0)[:, None]
    return (1.0 - eta)[:, None] * c + eta[:, None] * means, counts


def reference_replay(steps: list) -> dict:
    """Replays the device's steps in order, each from the state the device
    held before it.  ``steps`` holds (points, centroids before, counts
    before, device labels, centroids after) per message.

    Each step is anchored to the device's own state: a free-running float64
    run would part from the float32 one at the first near tie, and one point
    on the other side moves a young centroid by a third of its distance
    (counts are ~3 after the first message), which no tolerance separates
    from a wrong result."""
    agreement, n_ties, worst_err = [], 0, 0.0
    for x32, c32, n32, dev, after in steps:
        x, c = x32.astype(np.float64), c32.astype(np.float64)
        ref, gap, scale = _two_nearest(x, c)
        tie = gap <= TIE_ULPS * EPS32 * scale
        same = ref == dev
        n_ties += int(tie.sum())
        agreement.append(float(same[~tie].mean()))
        # centroids fed a point the device put elsewhere (a near tie) move
        # by more than rounding: the centroid check leaves them out
        touched = np.zeros(len(c), bool)
        touched[ref[~same]] = touched[dev[~same]] = True
        want, _ = reference_step(c, n32.astype(np.float64), x, ref)
        err = np.abs(after - want).max(axis=1)[~touched]
        bound = max(1.0, float(np.abs(want).max()))
        worst_err = max(worst_err, float(err.max(initial=0.0)) / bound)
    return {"agreement": agreement, "ties": n_ties, "centroid_err_rel": worst_err}


def _raise_failed_unit(pilot) -> None:
    """A failed compute unit is an error of the smoke, never a count."""
    for cu in pilot.compute_units:
        if cu.state == State.FAILED:
            raise cu.exception


def run_kmeans_phase(*, n_messages: int = N_MESSAGES, points: int = POINTS,
                     dim: int = DIM, centroids: int = CENTROIDS,
                     partitions: int = PARTITIONS, seed: int = 0,
                     timeout_s: float = 600.0) -> dict:
    """Producer -> Broker -> ThreadedStreamingEngine -> jax:// pilot ->
    ``kmeans.minibatch_step``; then the checks against the reference.
    Returns the numbers the caller prints; raises on any miss."""
    device = jax.devices()[0]
    stream = make_stream(seed, n_messages, points, dim)
    state = jax.device_put(
        kmeans.init_state(jax.random.PRNGKey(seed), centroids, dim,
                          scale=DATA_SCALE / 2), device)
    labels_of = jax.jit(lambda p, c: kmeans.assign(p, c)[0])
    pcs = PilotComputeService()
    pilot = pcs.submit_pilot(PilotDescription(
        resource="jax://mesh", attrs={"mesh_shape": (1,), "mesh_axes": ("data",)}))

    # -- warm-up: every program the run calls, before the engine starts
    pts0 = jnp.asarray(stream[0])
    with pilot.mesh:      # units run under the pilot's mesh: warm there
        t0 = time.perf_counter()
        compiled = kmeans.minibatch_step.lower(state, pts0).compile()
        compile_s = time.perf_counter() - t0
        kernel_in_step = "tpu_custom_call" in compiled.as_text()
        warm = jax.tree.map(lambda a: a.copy(), state)
        for _ in range(2):    # a fresh state, then a step's own output
            labels_of(pts0, warm.centroids).block_until_ready()
            warm = jax.block_until_ready(kmeans.minibatch_step(warm, pts0))
        labels_of(pts0, state.centroids).block_until_ready()
    compiles_before = _compiles

    # -- the run: topic, workload, engine, producer
    broker = Broker()
    broker.create_topic("points", partitions)
    metrics = MetricRegistry()
    run_id = new_run_id("chip-smoke")

    model_lock = threading.Lock()
    applied: list[int] = []          # message index, in the lock's order
    seen: dict[int, tuple] = {}      # index -> (centroids, counts, labels) before
    convert_s: list[float] = []
    step_s: list[float] = []

    def process(msgs):
        nonlocal state
        out = []
        for m in msgs:
            t_a = time.perf_counter()
            pts = jnp.asarray(m.value).block_until_ready()
            convert_s.append(time.perf_counter() - t_a)
            i = int(m.msg_id.rsplit("/", 1)[1])
            with model_lock:
                labels = labels_of(pts, state.centroids).block_until_ready()
                # copies: the step donates the state's buffers
                seen[i] = (np.array(state.centroids), np.array(state.counts),
                           labels)
                t_b = time.perf_counter()
                state = jax.block_until_ready(kmeans.minibatch_step(state, pts))
                step_s.append(time.perf_counter() - t_b)
                applied.append(i)
            out.append(labels)
        return out

    # max_retries=0: a unit that fails is re-raised below, never retried
    engine = ThreadedStreamingEngine(broker, "points", pilot,
                                     Workload(fn=process, name="kmeans"),
                                     metrics, run_id, batch_max=1,
                                     max_retries=0)
    engine.start()
    t_run = time.perf_counter()
    for i, pts in enumerate(stream):
        broker.append("points", pts, ts=time.perf_counter(), run_id=run_id,
                      msg_id=f"{run_id}/{i}", size_bytes=pts.nbytes)
    try:
        engine.drain(n_messages, timeout=timeout_s)
    except TimeoutError:
        _raise_failed_unit(pilot)
        raise
    finally:
        engine.stop()
        pcs.close()
    run_s = time.perf_counter() - t_run
    _raise_failed_unit(pilot)
    compiles_in_run = _compiles - compiles_before

    core = engine.core
    check(core.processed == n_messages,
          f"processed {core.processed} of {n_messages} messages")
    check(core.failed_batches == core.abandoned == core.retried == 0,
          f"failed_batches={core.failed_batches} abandoned={core.abandoned} "
          f"retried={core.retried}")
    check(sorted(applied) == list(range(n_messages)),
          "the lock did not apply every message exactly once")
    check(compiles_in_run == 0, f"{compiles_in_run} compiles after warm-up")

    final = np.asarray(state.centroids)
    check(bool(np.isfinite(final).all()), "non-finite centroids")
    afters = [seen[i][0] for i in applied[1:]] + [final]
    t_ref = time.perf_counter()
    ref = reference_replay([(stream[i], seen[i][0], seen[i][1],
                             np.asarray(seen[i][2]), after)
                            for i, after in zip(applied, afters)])
    replay_s = time.perf_counter() - t_ref
    worst = min(ref["agreement"])
    check(worst >= LABEL_AGREEMENT_MIN,
          f"label agreement {worst} < {LABEL_AGREEMENT_MIN} on some message")
    err = ref["centroid_err_rel"]
    check(err <= CENTROID_TOL_REL,
          f"centroid error {err} x M > {CENTROID_TOL_REL} x M after some step")

    stats = device.memory_stats() or {}
    return {
        "device_kind": device.device_kind,
        "kernel_in_step": kernel_in_step,
        "compile_s": compile_s,
        "compiles_in_run": compiles_in_run,
        "processed": core.processed,
        "failed_batches": core.failed_batches,
        "abandoned": core.abandoned,
        "retried": core.retried,
        "run_s": run_s,
        "replay_s": replay_s,
        "step_s": step_s,
        "convert_s": convert_s,
        "label_agreement_min": worst,
        "near_ties": ref["ties"],
        "max_centroid_err_rel": err,
        "centroid_tol_rel": CENTROID_TOL_REL,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def run_sharded_train_phase(*, arch: str = "qwen2-0.5b", reduced: bool = False,
                            steps: int = 3, batch: int = 8, seq: int = 128,
                            seed: int = 0) -> dict:
    """A few train steps on a (data=2, model=2) mesh over four devices, and
    the same steps on one device from the same seed; raises on any miss."""
    from repro.configs.base import ShapeSpec, get_config, pad_for_mesh
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train
    from repro.models import model as M
    from repro.training.optimizer import init_opt_state

    devices = jax.devices()
    check(len(devices) >= 4, f"four devices needed, JAX sees {len(devices)}")
    # pad once for the 2-way model axis, so both runs hold the same params
    cfg = pad_for_mesh(get_config(arch, reduced=reduced), 2)
    shape = ShapeSpec("smoke", seq, batch, "train")
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       global_batch=batch, seed=seed)
    batches = [data.batch_at(s) for s in range(steps)]

    def train(mesh):
        with mesh:
            step_fn, (p_sds, o_sds, b_sds), _ = build_train(cfg, shape, mesh)
            t0 = time.perf_counter()
            compiled = step_fn.lower(p_sds, o_sds, b_sds).compile()
            compile_s = time.perf_counter() - t0
            shard = lambda tree, sds: jax.tree.map(  # noqa: E731
                lambda a, s: jax.device_put(a, s.sharding), tree, sds)
            # fresh from the seed: the step donates, and a device_put
            # onto a device the array is on may alias its buffer
            params = shard(M.init_params(jax.random.PRNGKey(seed), cfg), p_sds)
            opt = shard(init_opt_state(params), o_sds)
            losses = []
            for b in batches:
                params, opt, metrics = compiled(params, opt, shard(b, b_sds))
                losses.append(float(metrics["loss"]))
        return losses, params, compiled.as_text(), compile_s

    sharded, params4, text, compile4 = train(
        make_mesh((2, 2), ("data", "model"), devices=devices[:4]))
    single, _, _, compile1 = train(
        make_mesh((1, 1), ("data", "model"), devices=devices[:1]))

    leaves = jax.tree.leaves(params4)
    spans = {len(a.sharding.device_set) for a in leaves}
    n_split = sum(not a.sharding.is_fully_replicated for a in leaves)
    check(spans == {4}, f"parameter shardings span {spans} devices, not 4")
    check(n_split > 0, "every parameter is replicated: nothing is sharded")
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    check(collectives["all-reduce"] + collectives["reduce-scatter"] > 0,
          f"no gradient reduction across devices in the step: {collectives}")
    check(all(np.isfinite(sharded + single)), "non-finite loss")
    diffs = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    check(max(diffs) <= LOSS_TOL_REL,
          f"sharded losses {sharded} vs one device {single}")
    return {"arch": cfg.name, "losses_4": sharded, "losses_1": single,
            "max_loss_rel_diff": max(diffs), "collectives": collectives,
            "sharded_leaves": n_split, "leaves": len(leaves),
            "compile_s_4": compile4, "compile_s_1": compile1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's devices are {platform}; "
              "this smoke test never runs on the CPU", file=sys.stderr)
        return 2
    say("compile_cache", use_compile_cache())
    say("device", f"{platform} {devices[0].device_kind} x{len(devices)}")
    try:
        if args.four_chips:
            res = run_sharded_train_phase(seed=args.seed)
        else:
            res = run_kmeans_phase(seed=args.seed)
            check(res["kernel_in_step"],
                  "the compiled step holds no Pallas kernel (tpu_custom_call)")
    except SmokeCheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for key in ("step_s", "convert_s"):
        if key in res:
            v = np.asarray(res.pop(key))
            say(key, f"median {np.median(v)} max {v.max()} first {v[0]} (n={len(v)})")
    for key, value in res.items():
        say(key, value)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
