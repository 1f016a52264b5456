"""jit'd public wrappers for the Pallas kernels (+ dispatch helpers).

The kernels compile to Mosaic by default (``interpret=False``), so on a TPU
they always run compiled.  ``interpret=True`` runs the same kernel bodies in
the Pallas interpreter, for checking values on a CPU; it is slow and its
timings say nothing about the compiled kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_pallas
from .gemv import gemv_pallas
from .gemv_tiles import gemv_tiles_pallas, remote_first_order
from .rmsnorm import rmsnorm_pallas

__all__ = [
    "gemv",
    "gemv_tiles",
    "decode_attention",
    "rmsnorm",
    "remote_first_order",
]


def gemv(a, x, **kw):
    """y = A @ x with MXU-aligned tiling."""
    return gemv_pallas(a, x, **kw)


def gemv_tiles(a, x, *, n_dev, my_dev, **kw):
    """(y, owner_schedule): fused GEMV+AllReduce tile order on one device."""
    return gemv_tiles_pallas(a, x, n_dev=n_dev, my_dev=my_dev, **kw)


def decode_attention(q, k, v, length, **kw):
    """Flash-decoding: one token vs. a (long) KV cache."""
    return decode_attention_pallas(q, k, v, length, **kw)


def rmsnorm(x, gamma, **kw):
    """Fused RMSNorm."""
    return rmsnorm_pallas(x, gamma, **kw)
