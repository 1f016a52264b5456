"""End-to-end training driver: a ~125M-parameter model for a few hundred
steps with checkpointing and an injected failure + restart.

    PYTHONPATH=src python examples/train_e2e.py [--steps 200]

(xlstm-125m at full width but 4 layers trains at a usable pace on CPU; pass
--full for the whole 12-layer stack if you have the patience.)
"""

import argparse
import sys
import tempfile
import time

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, SyntheticLMDataset, prefetch  # noqa: E402
from repro.ft import SimulatedFailure  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.training import TrainConfig, Trainer  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    cfg = get_config("xlstm-125m").with_(vocab=2048, max_seq_len=args.seq)
    if not args.full:
        cfg = cfg.with_(n_layers=4, xlstm_pattern="mmms")
    model = Model(cfg)
    print(f"[e2e] {cfg.name}: {model.n_params()/1e6:.1f}M params")

    mesh = make_mesh((1, 1), ("data", "model"))
    data = SyntheticLMDataset(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    )
    fail_at = {args.steps // 2}

    def inject(step):
        if step in fail_at:
            fail_at.discard(step)
            raise SimulatedFailure(f"chaos-drill failure at step {step}")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(
            model,
            mesh,
            TrainConfig(
                optim=AdamWConfig(
                    lr=3e-3, warmup_steps=20, total_steps=args.steps
                )
            ),
            ckpt_dir=ckpt_dir,
            ckpt_every=25,
            failure_injector=inject,
        )
        trainer.init_state(jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        hist = trainer.run(prefetch(iter(data)), args.steps, log_every=25)
        dt = time.perf_counter() - t0
    tokens = args.steps * args.batch * args.seq
    print(
        f"[e2e] {len(hist)} steps ({tokens / dt:,.0f} tok/s) "
        f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}; "
        f"survived 1 injected failure"
    )
    assert hist[-1]["loss"] < hist[0]["loss"]


if __name__ == "__main__":
    main()
