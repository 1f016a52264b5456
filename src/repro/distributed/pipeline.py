"""Pipeline parallelism (GPipe-style) via shard_map collective_permute.

Completes the parallelism matrix (DP/TP/PP/EP/SP).  Layers are split into
``n_stages`` equal groups placed along a ``pipe`` mesh axis; microbatches
stream through the classic GPipe schedule: ``n_micro + n_stages - 1`` ticks,
each tick running one stage-step everywhere (idle ticks compute on zeros and
are masked out) and rotating activations to the next stage with
``collective_permute`` — one-sided neighbour pushes, the paper's xGMI-write
pattern at pipeline granularity.  Eidola models exactly this traffic via
``periodic_stream`` eidolons (see ``repro.core.egpu``).

The forward is numerically identical to the unpipelined stack (tested) and
differentiable (``collective_permute`` transposes to the reverse shift, so
the backward pass is the mirrored pipeline).

Bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1), reported by
``bubble_fraction`` and validated in tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply", "bubble_fraction", "stack_stage_params"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def stack_stage_params(layer_params, n_stages: int):
    """[L, ...] stacked layer params -> [n_stages, L/n_stages, ...]."""

    def re(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(re, layer_params)


def pipeline_apply(
    mesh: Mesh,
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    n_micro: int,
    axis: str = "pipe",
):
    """Builds a pipelined stack applier.

    layer_fn(layer_params, x) -> x applies ONE layer.
    Returns ``apply(stage_params, x)`` where ``stage_params`` is a pytree of
    [n_stages, layers_per_stage, ...] arrays (sharded on dim 0 over ``axis``)
    and ``x`` is [n_micro * mb, ...] (replicated).  Output matches running
    all layers sequentially.
    """
    n_stages = mesh.shape[axis]

    def body(stage_p, x):
        # stage_p: [1, L/S, ...] (this stage's layers); x: [n_micro*mb, ...]
        sidx = jax.lax.axis_index(axis)
        B = x.shape[0]
        mb = B // n_micro
        micros = x.reshape(n_micro, mb, *x.shape[1:])
        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        my_layers = jax.tree.map(lambda a: a[0], stage_p)

        def run_stage(xmb):
            def one(x_c, p_l):
                return layer_fn(p_l, x_c), None

            out, _ = jax.lax.scan(one, xmb, my_layers)
            return out

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if within range); others use buf
            inject = jnp.where(
                t < n_micro,
                micros[jnp.clip(t, 0, n_micro - 1)],
                jnp.zeros_like(buf),
            )
            x_in = jnp.where(sidx == 0, inject, buf)
            y = run_stage(x_in)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(sidx == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: o.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y),
                lambda o: o,
                outs,
            )
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(micros[0])
        outs0 = jnp.zeros_like(micros)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks)
        )
        # every stage holds zeros except the last; share the result
        outs = jax.lax.psum(outs, axis) if n_stages > 1 else outs
        # psum adds the last stage's outputs to zeros from the others
        return outs.reshape(B, *x.shape[1:])

    stage_spec = jax.tree.map(lambda _: P(axis), {"_": 0})  # placeholder
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
