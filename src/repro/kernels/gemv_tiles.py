"""Owner-ordered GEMV partial-tile kernel (fused GEMV+AllReduce schedule).

Implements the paper's Fig. 3 tile ordering on a single device: the grid's
first axis walks output tiles in *owner* order starting at this device's ring
successor — remote-owned partial tiles are produced first (so their xGMI/ICI
pushes can start while local tiles compute), local tiles last.  The tile
permutation arrives via TPU scalar prefetch (``PrefetchScalarGridSpec``), the
idiomatic mechanism for data-dependent BlockSpec index maps.  A progress
output, resident in SMEM for the whole grid, records which owner each grid
step serviced, letting tests assert the remote-first schedule that the Eidola
workload model times.  Values are identical to a plain GEMV.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gemv_tiles_pallas", "remote_first_order"]


def remote_first_order(n_dev: int, my_dev: int, tiles_per_dev: int):
    """Tile visit order: successor owner's tiles first, own tiles last."""
    order = []
    for step in range(1, n_dev + 1):
        owner = (my_dev + step) % n_dev
        for i in range(tiles_per_dev):
            order.append(owner * tiles_per_dev + i)
    return jnp.asarray(order, jnp.int32)


def _kernel(order_ref, a_ref, x_ref, o_ref, prog_ref, *, tiles_per_dev):
    t = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    nk = pl.num_programs(1)

    @pl.when(k == nk - 1)
    def _record():
        # which owner did this grid step service (schedule introspection)
        prog_ref[t] = order_ref[t] // tiles_per_dev


@functools.partial(
    jax.jit, static_argnames=("n_dev", "my_dev", "bm", "bk", "interpret")
)
def gemv_tiles_pallas(
    a: jax.Array,     # [M, K]
    x: jax.Array,     # [K, N]
    *,
    n_dev: int,
    my_dev: int,
    bm: int = 64,
    bk: int = 512,
    interpret: bool = False,
):
    """Returns (y [M,N] in a.dtype, owner_served i32[T]) over T grid tiles."""
    M, K = a.shape
    _, N = x.shape
    bm = min(bm, M // n_dev)
    bk = min(bk, K)
    assert M % (n_dev * bm) == 0 and K % bk == 0
    tiles_per_dev = M // n_dev // bm
    n_tiles = M // bm
    order = remote_first_order(n_dev, my_dev, tiles_per_dev)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda t, k, order: (order[t], k)),
            pl.BlockSpec((bk, N), lambda t, k, order: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, N), lambda t, k, order: (order[t], 0)),
            # a (1,) VMEM block per step breaks Mosaic's rank-1 tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
    )
    y, prog = pl.pallas_call(
        functools.partial(_kernel, tiles_per_dev=tiles_per_dev),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
        ],
        interpret=interpret,
    )(order, a, x)
    return y.astype(a.dtype), prog
