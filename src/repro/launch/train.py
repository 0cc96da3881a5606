"""End-to-end training driver.

CPU-runnable with reduced configs (``--reduced``) — the quickstart path —
and mesh-ready for real hardware: sharding comes from the same
``launch.steps`` assembly the dry-run proves.  Fault tolerance: periodic
async checkpoints + automatic resume from the latest complete step.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeSpec, get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train
from repro.models import model as M
from repro.training.checkpoint import CheckpointManager, latest_step
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train_loop import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2x4' to train on a (data, model) device mesh")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10 + 1),
                              decay_steps=args.steps)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed,
                       n_prefix=cfg.n_prefix if cfg.frontend else 0,
                       d_model=cfg.d_model if cfg.frontend else 0)

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)])
        spec = ShapeSpec("cli", args.seq, args.batch, "train")
        with mesh:
            step_fn, _, _ = build_train(cfg, spec, mesh,
                                        n_microbatches=args.microbatches,
                                        opt_cfg=opt_cfg)
    else:
        mesh = None
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, args.microbatches))

    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = init_opt_state(params)
    start = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and latest_step(args.ckpt_dir) is not None:
        tree, start = mgr.restore_latest({"params": params, "opt": opt_state})
        params, opt_state = tree["params"], tree["opt"]
        print(f"resumed from step {start}")

    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M steps={args.steps}")
    t0 = time.perf_counter()
    losses = []
    for step in range(start, args.steps):
        batch = jax.tree.map(jnp.asarray, data.batch_at(step))
        if mesh:
            with mesh:
                params, opt_state, metrics = step_fn(params, opt_state, batch)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            tok_s = (step - start + 1) * args.batch * args.seq / max(dt, 1e-9)
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} tok/s {tok_s:,.0f}")
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state})
        mgr.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
