"""Batched serving with Domino numerics: int8 CIM-resident weights +
int8 KV cache, prefill + greedy decode on a sharded host mesh.

    PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-1b]
"""
import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.runtime.serve_loop import (
    build_serve_program,
    greedy_generate,
    quantize_params_for_serving,
)
from repro.runtime.train_loop import build_train_program


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    mesh = make_host_mesh()
    pcfg = ParallelConfig(reduction="ring")
    s_max = args.prompt_len + args.gen + 1

    prog = build_serve_program(cfg, mesh, pcfg, batch=args.batch,
                               s_max=s_max, kv_dtype="int8",
                               cim_weights=True, quant_min_size=1)
    tprog = build_train_program(cfg, mesh, pcfg, TrainConfig())
    params, _ = tprog.init_fn(0)
    qparams = quantize_params_for_serving(params, min_size=1)

    raw = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    q = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(qparams))
    print(f"{cfg.name}: weights {raw/1e6:.2f}MB -> {q/1e6:.2f}MB int8 "
          f"(CIM-resident, {raw/q:.2f}x)")

    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    t0 = time.time()
    tokens = greedy_generate(prog, qparams, batch, args.gen)
    dt = time.time() - t0
    print(f"prefill({args.prompt_len}) + decode({args.gen}) x batch "
          f"{args.batch}: {dt:.2f}s  ({args.batch*args.gen/dt:.1f} tok/s, "
          "CPU interpret-mode numbers)")
    print("sample:", tokens[0].tolist())


if __name__ == "__main__":
    main()
