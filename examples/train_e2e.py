"""End-to-end driver: train a ~100M-param model for a few hundred steps,
checkpoint it, SAMD-quantize the result, and compare serving quality —
the paper's full train -> freeze -> analyse -> pack -> deploy pipeline.

Run:   PYTHONPATH=src python examples/train_e2e.py [--steps 200]
CPU-sized by default (~8M params); pass --big for the ~100M config if you
have minutes to spare.
"""
import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import RunConfig, get_arch
from repro.configs.base import ShapeConfig
from repro.data import SyntheticLM
from repro.launch import steps as steps_mod
from repro.models import (
    build_template, forward, init_from_spec, quantize_params,
)
from repro.optim.adamw import adamw_init
from repro.quant.config import QuantConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--big", action="store_true",
                    help="~100M params (slower on CPU)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    base = get_arch("qwen1.5-0.5b")
    if args.big:  # ~100M params
        cfg = base.scaled(n_layers=8, d_model=512, d_ff=1408,
                          n_heads=8, n_kv_heads=8, head_dim=64,
                          vocab=32000, scan_layers=False, attn_chunk=128)
    else:        # CPU-friendly ~8M params
        cfg = base.scaled(n_layers=4, d_model=256, d_ff=704,
                          n_heads=4, n_kv_heads=4, head_dim=64,
                          vocab=4096, scan_layers=False, attn_chunk=128)

    run = RunConfig(
        arch=cfg, shape=ShapeConfig("t", args.seq_len, args.batch, "train"),
        learning_rate=6e-4, lr_warmup=20,
    )
    template = build_template(cfg)
    params = init_from_spec(template, jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch {cfg.name}-reduced: {n_params/1e6:.1f}M params, "
          f"{cfg.n_layers}L d={cfg.d_model}")

    opt = adamw_init(params)
    step = jax.jit(steps_mod.make_train_step(cfg, run),
                   donate_argnums=(0, 1))
    data = SyntheticLM(cfg.vocab, args.seq_len, args.batch, seed=0)
    ckdir = os.path.join(tempfile.gettempdir(), "repro_e2e_ckpt")
    mgr = CheckpointManager(ckdir, keep=2)

    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, metrics = step(params, opt, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e}")
        if i and i % 100 == 0:
            mgr.save(i, {"params": params, "opt": opt})
    mgr.save(args.steps, {"params": params, "opt": opt}, blocking=True)
    print(f"checkpointed to {ckdir}")

    # deployment: SAMD-pack the trained weights and measure agreement
    batch = {k: jnp.asarray(v) for k, v in next(data).items()}
    logits_fp, _, _ = forward(params, batch["tokens"], cfg)
    pred_fp = np.asarray(jnp.argmax(logits_fp.astype(jnp.float32), -1))
    print("\nSAMD deployment (weight packing + next-token agreement):")
    for bits in (8, 4, 3, 2):
        q = quantize_params(params, template, QuantConfig(bits=bits))
        logits_q, _, _ = forward(q, batch["tokens"], cfg)
        pred_q = np.asarray(jnp.argmax(logits_q.astype(jnp.float32), -1))
        agree = float(np.mean(pred_fp == pred_q))
        packed_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(q)
        )
        fp_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
        )
        print(f"  {bits}-bit: params {fp_bytes/1e6:.1f}MB -> "
              f"{packed_bytes/1e6:.1f}MB, greedy-token agreement "
              f"{agree*100:.1f}%")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
