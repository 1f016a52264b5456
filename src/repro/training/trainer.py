"""Sharded training: pjit train step + fault-tolerant run loop.

``build_train_step`` assembles the full production step:
  - params sharded by logical-axis rules (divisibility fallback),
  - optimizer state ZeRO-1-sharded across the data(+pod) axes,
  - microbatched gradient accumulation (jax.lax.scan over microbatches),
  - remat policy by name,
  - loss in f32, params bf16, fp32 master weights.

``Trainer`` adds the large-scale-runnability story: checkpoint/restart on
(simulated) failures, straggler monitoring, and elastic remesh.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.distributed import (
    DEFAULT_RULES,
    ShardingRules,
    param_shardings,
    zero1_shardings,
)
from repro.distributed.zero import zero1_from_params
from repro.ft import SimulatedFailure, StragglerMonitor
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init, adamw_step

__all__ = ["TrainConfig", "Trainer", "build_train_step"]


@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat_policy: str = "none"       # none | full | dots | dots_no_batch
    moe_loss_weight: float = 0.01
    z_loss_weight: float = 1e-4
    optim: AdamWConfig = AdamWConfig()
    zero1_axes: Tuple[str, ...] = ("data",)
    zero1_model_dim: bool = False   # EXPERIMENTS.md §Perf H4 (superseded)
    zero1_param_aligned: bool = True  # §Perf H5: states follow param layout
    donate_state: bool = True


def _batch_sharding(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0] if axes else None))


def build_train_step(
    model: Model,
    mesh: Mesh,
    tcfg: TrainConfig,
    rules: ShardingRules = DEFAULT_RULES,
):
    """Returns (train_step_jitted, shardings dict, fallback log)."""
    specs = model.param_specs()
    axes_tree = model.param_axes()
    abstract_params = model.abstract_params()
    p_shard, fallbacks = param_shardings(axes_tree, abstract_params, mesh, rules)

    # optimizer state shardings: step replicated; moments/master ZeRO-1
    abstract_state = jax.eval_shape(
        lambda p: adamw_init(p, tcfg.optim), abstract_params
    )
    zero_axes = tuple(a for a in (*tcfg.zero1_axes, "pod") if a in mesh.shape)

    def state_shardings():
        def shard_like(tree):
            if tcfg.zero1_param_aligned:
                return zero1_from_params(p_shard, tree, mesh, zero_axes)
            return zero1_shardings(
                tree, mesh, zero_axes, model_dim=tcfg.zero1_model_dim
            )

        s = {
            "step": NamedSharding(mesh, P()),
            "mu": shard_like(abstract_state["mu"]),
            "nu": shard_like(abstract_state["nu"]),
        }
        if "master" in abstract_state:
            s["master"] = shard_like(abstract_state["master"])
        return s

    s_shard = state_shardings()
    b_shard = _batch_sharding(mesh)

    def loss_for(params, tokens, labels, embeds):
        return model.loss_fn(
            params,
            tokens,
            labels,
            embeds=embeds,
            remat=tcfg.remat_policy != "none",
            remat_policy=tcfg.remat_policy
            if tcfg.remat_policy != "none" else "full",
            moe_loss_weight=tcfg.moe_loss_weight,
            z_loss_weight=tcfg.z_loss_weight,
        )

    def train_step(params, opt_state, tokens, labels, embeds=None):
        mb = tcfg.microbatches
        if mb > 1:
            B = tokens.shape[0] if tokens is not None else embeds.shape[0]
            assert B % mb == 0, "batch must divide microbatches"

            def re(x):
                return (
                    None
                    if x is None
                    else x.reshape(mb, B // mb, *x.shape[1:])
                )

            tks, lbs, ebs = re(tokens), re(labels), re(embeds)

            def micro(carry, xs):
                g_acc, loss_acc = carry
                tk = xs[0]
                lb = xs[1]
                eb = xs[2] if len(xs) > 2 else None
                (l, _), g = jax.value_and_grad(loss_for, has_aux=True)(
                    params, tk, lb, eb
                )
                g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                return (g_acc, loss_acc + l), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            xs = (tks, lbs) if ebs is None else (tks, lbs, ebs)
            (g, loss), _ = jax.lax.scan(micro, (g0, jnp.float32(0.0)), xs)
            g = jax.tree.map(lambda x: x / mb, g)
            loss = loss / mb
            metrics_aux: Dict[str, jax.Array] = {}
        else:
            (loss, metrics_aux), g = jax.value_and_grad(
                loss_for, has_aux=True
            )(params, tokens, labels, embeds)
        new_params, new_state, opt_metrics = adamw_step(
            params, g, opt_state, tcfg.optim
        )
        metrics = {"loss": loss, **opt_metrics}
        for k, v in (metrics_aux or {}).items():
            metrics[k] = v
        return new_params, new_state, metrics

    donate = (0, 1) if tcfg.donate_state else ()
    in_sh = [p_shard, s_shard, b_shard, b_shard]
    if model.cfg.frontend != "none":
        in_sh.append(b_shard)  # stub embeddings are batch-sharded too
    step_fn = jax.jit(
        train_step,
        in_shardings=tuple(in_sh),
        out_shardings=(p_shard, s_shard, None),
        donate_argnums=donate,
    )
    shardings = {"params": p_shard, "state": s_shard, "batch": b_shard}
    return step_fn, shardings, fallbacks


class Trainer:
    """Fault-tolerant training runner (checkpoint/restart + stragglers)."""

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        tcfg: TrainConfig,
        *,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 50,
        rules: ShardingRules = DEFAULT_RULES,
        failure_injector: Optional[Callable[[int], None]] = None,
    ):
        self.model = model
        self.mesh = mesh
        self.tcfg = tcfg
        self.rules = rules
        self.step_fn, self.shardings, self.fallbacks = build_train_step(
            model, mesh, tcfg, rules
        )
        self.ckpt = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.stragglers = StragglerMonitor()
        self.failure_injector = failure_injector
        self.params = None
        self.opt_state = None
        self.step = 0

    def init_state(self, rng: jax.Array) -> None:
        with self.mesh:
            self.params = jax.jit(
                self.model.init, out_shardings=self.shardings["params"]
            )(rng)
            self.opt_state = jax.jit(
                lambda p: adamw_init(p, self.tcfg.optim),
                out_shardings=self.shardings["state"],
            )(self.params)
        self.step = 0

    def maybe_restore(self) -> bool:
        if self.ckpt is None:
            return False
        template = {
            "params": self.model.abstract_params(),
            "state": jax.eval_shape(
                lambda p: adamw_init(p, self.tcfg.optim),
                self.model.abstract_params(),
            ),
        }
        shardings = {
            "params": self.shardings["params"],
            "state": self.shardings["state"],
        }
        step, tree = self.ckpt.restore_latest(template, shardings)
        if step is None:
            return False
        self.params = tree["params"]
        self.opt_state = tree["state"]
        self.step = step
        return True

    def run(self, batches, n_steps: int, *, log_every: int = 10):
        """Run with automatic restart on SimulatedFailure."""
        history = []
        while self.step < n_steps:
            try:
                for _ in range(self.step, n_steps):
                    batch = next(batches)
                    if self.failure_injector is not None:
                        self.failure_injector(self.step)
                    t0 = time.perf_counter()
                    self.params, self.opt_state, metrics = self.step_fn(
                        self.params,
                        self.opt_state,
                        jnp.asarray(batch["tokens"]),
                        jnp.asarray(batch["labels"]),
                    )
                    loss = float(metrics["loss"])
                    dt = time.perf_counter() - t0
                    self.step += 1
                    history.append({"step": self.step, "loss": loss, "dt": dt})
                    if self.ckpt and self.step % self.ckpt_every == 0:
                        self.ckpt.save(
                            self.step,
                            {"params": self.params, "state": self.opt_state},
                        )
                    if log_every and self.step % log_every == 0:
                        print(
                            f"step {self.step:5d} loss {loss:.4f} "
                            f"({dt * 1e3:.0f} ms)"
                        )
            except SimulatedFailure as e:
                print(f"[ft] failure at step {self.step}: {e}; restarting")
                if not self.maybe_restore():
                    raise RuntimeError(
                        "failure before first checkpoint; cannot recover"
                    ) from e
        if self.ckpt:
            self.ckpt.wait()
        return history
