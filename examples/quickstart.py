"""Quickstart: train a tiny Domino-dataflow LM on CPU in ~a minute.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the public API end-to-end: config -> mesh -> train program
(ring computing-on-the-move reductions) -> training loop -> serving.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.data.pipeline import DataSpec, synthetic_batch
from repro.launch.mesh import make_host_mesh
from repro.runtime.serve_loop import build_serve_program, greedy_generate
from repro.runtime.train_loop import build_train_program


def main():
    enable_compile_cache()
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = make_host_mesh()
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    pcfg = ParallelConfig(reduction="ring", remat="full")
    tcfg = TrainConfig(optimizer="adamw", lr=3e-3, warmup_steps=5,
                       total_steps=60)
    prog = build_train_program(cfg, mesh, pcfg, tcfg)
    params, state = prog.init_fn(0)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M ({cfg.name} reduced)")

    spec = DataSpec(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    for step in range(60):
        batch = {k: jnp.asarray(v)
                 for k, v in synthetic_batch(spec, step % 4).items()}
        params, state, m = prog.step_fn(params, state, batch)
        if step % 10 == 0:
            print(f"step {step:3d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}")

    # serve the freshly trained model (greedy, batched)
    sprog = build_serve_program(cfg, mesh, pcfg, batch=4, s_max=48)
    prompt = {"tokens": jnp.asarray(
        synthetic_batch(DataSpec(cfg.vocab_size, 32, 4), 0)["tokens"])}
    tokens = greedy_generate(sprog, params, prompt, steps=8)
    print("generated:", tokens[0].tolist())


if __name__ == "__main__":
    main()
