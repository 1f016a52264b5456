"""Tiled GEMV Pallas kernel (the fused GEMV+AllReduce's compute hot loop).

TPU-native adaptation of the paper's workgroup tiling: output rows are tiled
``bm`` at a time (MXU-aligned, multiples of 128 at full size); the reduction
dim streams through VMEM in ``bk`` slabs via the grid's second axis with an
f32 accumulator in the output block.  ``N`` (the GEMV's vector width) rides
along as the output block's lane dim padded to the VPU lane width by the
BlockSpec machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gemv_pallas"]


def _gemv_kernel(a_ref, x_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]  # [bm, bk]
    x = x_ref[...]  # [bk, N]
    o_ref[...] += jnp.dot(
        a.astype(jnp.float32), x.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def gemv_pallas(
    a: jax.Array,          # [M, K]
    x: jax.Array,          # [K, N]
    *,
    bm: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = x.shape
    assert K == K2, (a.shape, x.shape)
    bm = min(bm, M)
    bk = min(bk, K)
    assert M % bm == 0 and K % bk == 0, "block sizes must tile the problem"
    grid = (M // bm, K // bk)
    out = pl.pallas_call(
        _gemv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, k: (m, k)),   # A tile in VMEM
            pl.BlockSpec((bk, N), lambda m, k: (k, 0)),    # x slab in VMEM
        ],
        out_specs=pl.BlockSpec((bm, N), lambda m, k: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a, x)
    return out.astype(a.dtype)
