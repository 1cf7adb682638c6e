"""End-to-end driver: train a ~100M-parameter qwen2-family model for a
few hundred steps with the full production stack — ring dataflow, remat,
microbatching, async checkpointing, deterministic restart, straggler
monitoring.

    PYTHONPATH=src python examples/train_llm.py [--steps 300]

(~100M params; on CPU expect a few seconds/step. The same script scales
to the full config on a pod by swapping make_host_mesh for
make_production_mesh.)
"""
import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import AttentionConfig, ParallelConfig, TrainConfig
from repro.data.pipeline import DataSpec, Prefetcher, synthetic_batch
from repro.launch.mesh import make_host_mesh
from repro.runtime.fault import StepGuard, StragglerMonitor
from repro.runtime.train_loop import build_train_program


def model_100m():
    """qwen2-family ~100M: 8L d_model=512 8H(kv 2) d_ff=2048 vocab=32k."""
    base = get_config("qwen2-0.5b")
    return dataclasses.replace(
        base,
        name="qwen2-100m",
        num_layers=8,
        d_model=512,
        d_ff=2048,
        vocab_size=32_768,
        attention=dataclasses.replace(
            base.attention, num_heads=8, num_kv_heads=2, head_dim=64),
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_llm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = model_100m()
    mesh = make_host_mesh()
    pcfg = ParallelConfig(reduction="ring", remat="full", microbatches=2)
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, warmup_steps=20,
                       total_steps=args.steps, moment_dtype="float32")
    prog = build_train_program(cfg, mesh, pcfg, tcfg)
    params, state = prog.init_fn(0)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    mgr = CheckpointManager(args.ckpt, keep=2)
    start = 0
    if args.resume and mgr.latest_step():
        restored, start = mgr.restore({"params": params, "state": state})
        params, state = restored["params"], restored["state"]
        print(f"resumed at step {start}")

    spec = DataSpec(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, seed=0)
    monitor = StragglerMonitor()
    guard = StepGuard(recover=lambda s: None)
    t_start = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v)
                 for k, v in synthetic_batch(spec, step).items()}
        t0 = time.time()
        params, state, m = guard.run(prog.step_fn, step, params, state, batch)
        monitor.observe(step, time.time() - t0)
        if step % 20 == 0 or step == args.steps - 1:
            tok_s = args.batch * args.seq / max(time.time() - t0, 1e-9)
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}  {tok_s/1e3:.1f}k tok/s")
        if (step + 1) % 100 == 0:
            mgr.save(step + 1, {"params": params, "state": state})
            print(f"  checkpoint @ {step+1} (async)")
    mgr.save(args.steps, {"params": params, "state": state}, blocking=True)
    dt = time.time() - t_start
    print(f"done: {args.steps - start} steps in {dt/60:.1f} min; "
          f"flagged stragglers: {monitor.flagged_steps[:5]}")


if __name__ == "__main__":
    main()
