"""Fused RMSNorm Pallas kernel.

Row-blocked: each grid step normalizes ``br`` rows entirely in VMEM (load,
reduce, scale, store in one pass), eliminating the separate
square/mean/rsqrt/mul HBM round-trips of the unfused lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm_pallas"]


def _kernel(x_ref, g_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)            # [br, D]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)            # [D]
    o_ref[...] = (y * (1.0 + g)[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret"))
def rmsnorm_pallas(
    x: jax.Array,       # [..., D]
    gamma: jax.Array,   # [D]
    *,
    br: int = 256,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    R = x2.shape[0]
    br = min(br, R)
    pad = (-R) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=((R + pad) // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda r: (r, 0)),
            pl.BlockSpec((D,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(((R + pad), D), x.dtype),
        interpret=interpret,
    )(x2, gamma)
    return out[:R].reshape(orig_shape)
