"""End-to-end training driver.

Runs real training on this host (reduced or custom configs; the ~100M
quickstart in examples/ uses this).  On a cluster the same entry point runs
the full configs — the step function, sharding rules, checkpointing and
fault-tolerance hooks are identical; only the mesh differs.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --steps 200 \
      --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_config, reduced
from repro.data import DataConfig, SyntheticLMDataset, prefetch
from repro.launch.cache import use_checkout_cache
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.optim import AdamWConfig
from repro.training import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(REGISTRY), default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving reduced config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=0, help="override n_layers")
    ap.add_argument("--d-model", type=int, default=0, help="override d_model")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="1x1", help="AxB data x model")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()
    use_checkout_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    overrides = {}
    if args.layers:
        overrides["n_layers"] = args.layers
    if args.d_model:
        overrides["d_model"] = args.d_model
    if overrides:
        cfg = cfg.with_(**overrides)
    model = Model(cfg)
    print(f"[train] {cfg.name}: {model.n_params()/1e6:.1f}M params "
          f"({model.n_active_params()/1e6:.1f}M active), mesh={args.mesh}")

    dims = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model")[: len(dims)])

    tcfg = TrainConfig(
        microbatches=args.microbatches,
        remat_policy=args.remat,
        optim=AdamWConfig(
            lr=args.lr, warmup_steps=max(args.steps // 20, 5),
            total_steps=args.steps,
        ),
    )
    trainer = Trainer(
        model, mesh, tcfg, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every
    )
    if not trainer.maybe_restore():
        trainer.init_state(jax.random.PRNGKey(0))
        print("[train] fresh init")
    else:
        print(f"[train] restored from step {trainer.step}")

    data = SyntheticLMDataset(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    )
    t0 = time.perf_counter()
    history = trainer.run(
        prefetch(iter(data)), args.steps, log_every=args.log_every
    )
    dt = time.perf_counter() - t0
    if history:
        tokens = args.steps * args.batch * args.seq
        print(
            f"[train] {len(history)} steps in {dt:.1f}s "
            f"({tokens / dt:,.0f} tok/s); "
            f"loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}"
        )
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)


if __name__ == "__main__":
    main()
