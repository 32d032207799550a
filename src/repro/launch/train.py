"""End-to-end training driver.

Trains on the default device under a plain ``jax.jit`` (no mesh, no
sharding); ``--full`` trains the published widths and depth, the default is
the reduced same-family config.  Integrates the substrate: deterministic
data pipeline, microbatched AdamW, async checkpointing with restart,
failure injection + supervisor restore, and straggler tracking.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --reduced \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --reduced \
      --steps 50 --simulate-failure-at 23
"""

from __future__ import annotations

import argparse
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import DataConfig, DataPipeline
from repro.models import build_model
from repro.obs import timer as obs_timer
from repro.optim import AdamWConfig, adamw
from repro.runtime import HeartbeatMonitor, StragglerTracker
from repro.train import make_train_step
from repro.utils.env import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument(
        "--plan-chips", type=int, default=None,
        help="dry-run: print the fleet planner's ranked slice plan for this "
             "arch at the given chip budget, then exit (no model is built)",
    )
    ap.add_argument("--plan-shape", default="train_4k")
    args = ap.parse_args(argv)

    if args.plan_chips is not None:
        from repro.launch.planner import format_table, plan_model

        plan = plan_model(
            args.arch, args.plan_chips, shape=args.plan_shape, simulate_top_k=1
        )
        print(format_table(plan))
        return plan

    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    model = build_model(arch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps, weight_decay=0.01)
    params = model.init(jax.random.key(args.seed))
    opt_state = adamw.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={arch.name} params={n_params/1e6:.2f}M devices={jax.device_count()}")

    step_fn = jax.jit(make_train_step(model, opt_cfg, args.microbatches))

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    if mgr and args.resume and mgr.latest_step() is not None:
        start_step, (params, opt_state) = mgr.restore((params, opt_state))
        print(f"resumed from checkpoint step {start_step}")

    data_cfg = DataConfig(seed=args.seed, global_batch=args.batch, seq_len=args.seq)
    pipeline = DataPipeline(arch, data_cfg, start_step=start_step)
    monitor = HeartbeatMonitor([f"w{i}" for i in range(jax.device_count())], timeout=60.0)
    straggler = StragglerTracker()

    losses = []
    pending_save = None
    try:
        for step, batch in pipeline:
            if step >= args.steps:
                break
            if args.simulate_failure_at is not None and step == args.simulate_failure_at:
                print(f"[fault] simulated worker failure at step {step}; restoring")
                monitor.last_seen["w0"] = -np.inf
                failed = monitor.check()
                assert failed == ["w0"]
                if mgr and mgr.latest_step() is not None:
                    restored_step, (params, opt_state) = mgr.restore((params, opt_state))
                    print(f"[fault] restored checkpoint step {restored_step}")
                monitor.rejoin("w0")
                args.simulate_failure_at = None  # don't loop
            with obs_timer("train.step", step=step) as tm:
                jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt_state, metrics = step_fn(params, opt_state, jbatch)
                loss = float(metrics["loss"])
            dt = tm.elapsed
            straggler.record("w0", dt)
            losses.append(loss)
            if step % args.log_every == 0:
                print(
                    f"step {step:5d} loss {loss:7.4f} "
                    f"gnorm {float(metrics['grad_norm']):8.3f} "
                    f"lr {float(metrics['lr']):.2e} {dt*1e3:6.1f} ms"
                )
            if mgr and (step + 1) % args.ckpt_every == 0:
                if pending_save is not None:
                    pending_save.result()
                pending_save = mgr.save_async(step + 1, (params, opt_state))
    finally:
        pipeline.close()
        if pending_save is not None:
            pending_save.result()

    if mgr:
        mgr.save(args.steps, (params, opt_state))
    window = max(len(losses) // 5, 1)
    first, last = float(np.mean(losses[:window])), float(np.mean(losses[-window:]))
    print(f"done: loss {first:.4f} -> {last:.4f} over {len(losses)} steps")
    return first, last


if __name__ == "__main__":
    main()
