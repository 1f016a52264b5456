"""Flash-decoding Pallas kernel: one query token vs. a long KV cache.

Grid walks (batch, kv-head, kv-block); VMEM f32 scratch holds the running
(max, sum, output) triple of the kv-head's GQA group, merged across KV
blocks with the standard log-sum-exp rescaling.  Each step sees 2-D tiles
(the group's ``rep`` query rows, and ``bs`` cache rows of one kv-head: the
cache is viewed as ``[B, S, KV*D]`` and a block takes one head's ``D``
lanes), so both products are plain MXU matmuls.  Compiled for the TPU this
needs ``D % 128 == 0`` or a single kv-head (Mosaic's lane tiling); the
interpreter takes any width.  On real TPU the sequence axis is the natural split-K
axis of flash-decoding (parallelized across cores / sequence shards — the
sequence-parallel decode path of long_500k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_pallas"]

_NEG_INF = -2.0e38


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bs):
    b = pl.program_id(0)
    s_blk = pl.program_id(2)

    @pl.when(s_blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)      # [rep, D] (one kv-head's group)
    k = k_ref[...].astype(jnp.float32)      # [bs, D]
    v = v_ref[...].astype(jnp.float32)      # [bs, D]
    rep, D = q.shape
    length = len_ref[b]

    s = jax.lax.dot_general(
        q * (D ** -0.5), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                        # [rep, bs]
    pos = s_blk * bs + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
    s = jnp.where(pos < length, s, _NEG_INF)

    m_prev = m_ref[...]                      # [rep, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                   # [rep, bs]
    l_new = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(s_blk == pl.num_programs(2) - 1)
    def _final():
        o_ref[...] = (acc / jnp.maximum(l_new, 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention_pallas(
    q: jax.Array,       # [B, H, D]
    k: jax.Array,       # [B, S, KV, D]
    v: jax.Array,       # [B, S, KV, D]
    length: jax.Array,  # i32[] valid cache prefix
    *,
    bs: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    bs = min(bs, S)
    assert S % bs == 0, "kv block must tile the cache"
    rep = H // KV
    lens = jnp.broadcast_to(jnp.asarray(length, jnp.int32)[None], (B,))
    qg = q.reshape(B, KV, rep, D)
    kf = k.reshape(B, S, KV * D)
    vf = v.reshape(B, S, KV * D)

    # the per-batch lengths ride in SMEM by scalar prefetch: a (1,) VMEM
    # block per step breaks Mosaic's rank-1 tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, S // bs),
        in_specs=[
            pl.BlockSpec((None, None, rep, D), lambda b, g, s, lens: (b, g, 0, 0)),
            pl.BlockSpec((None, bs, D), lambda b, g, s, lens: (b, s, g)),
            pl.BlockSpec((None, bs, D), lambda b, g, s, lens: (b, s, g)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, rep, D), lambda b, g, s, lens: (b, g, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        interpret=interpret,
    )(lens, qg, kf, vf)
    return out.reshape(B, H, D)
