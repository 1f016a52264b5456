"""Expert-parallel MoE via shard_map all-to-all (the production path).

GSPMD cannot partition ``ragged_dot`` over tokens/experts — it all-gathers
every token to every device and computes densely against local experts
(~500x FLOPs at olmoe scale; measured in EXPERIMENTS.md §Perf).  This module
routes tokens explicitly instead, which is also precisely the paper's MoE
workload ("embedding pooling + All-to-All and GEMM + All-to-All ... can be
evaluated using Eidola without modification"):

scatter path (training / large token counts, ``T_loc % msz == 0``):
  1. each model-axis rank takes its 1/msz slice of the data-shard's tokens,
  2. routes top-k pairs into per-destination capacity buffers (overflow
     drops, counted in aux metrics),
  3. ``all_to_all`` over the model axis delivers pairs to expert owners,
  4. local grouped GEMM (``ragged_dot``) over the rank's E/msz experts,
  5. ``all_to_all`` back + weighted combine + ``all_gather`` of token slices.

gather path (decode / tiny token counts):
  every rank computes only the pairs owned by its local experts on the full
  (small) token set and a ``psum`` over the model axis combines.

Both paths are differentiable (sort/scatter/all_to_all all have transposes)
and validated against the dense local oracle in tests.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .common import ModelConfig

__all__ = ["moe_apply_ep", "ep_applicable"]


def ep_applicable(cfg: ModelConfig, mesh: Optional[Mesh]) -> bool:
    if mesh is None or "model" not in mesh.shape:
        return False
    msz = mesh.shape["model"]
    return msz > 1 and cfg.n_experts % msz == 0


def _act(cfg):
    return jax.nn.gelu if cfg.mlp_act == "gelu" else jax.nn.silu


def _route(cfg: ModelConfig, p, xm):
    """top-k routing on a token slice. xm: [T, d]."""
    logits = xm.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    E = cfg.n_experts
    density = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    density = density / jnp.maximum(density.sum(), 1.0)
    lb = E * jnp.sum(density * probs.mean(axis=0))
    zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return idx, weights, lb, zl


def _grouped_ffn(cfg, p, xs, group_sizes):
    act = _act(cfg)
    g = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    return jax.lax.ragged_dot(
        (act(g) * u).astype(xs.dtype), p["w_down"], group_sizes
    )


def _shared_ffn(cfg, p, x2):
    if not cfg.n_shared_experts:
        return jnp.zeros_like(x2)
    act = _act(cfg)
    return ((act(x2 @ p["sh_gate"]) * (x2 @ p["sh_up"])) @ p["sh_down"]).astype(
        x2.dtype
    )


def _pmean_axes(v, axes):
    for a in axes:
        v = jax.lax.pmean(v, a)
    return v


# ---------------------------------------------------------------------------
# scatter path (training)
# ---------------------------------------------------------------------------


def _ep_scatter_body(cfg: ModelConfig, reduce_axes, ff_axis, p, x_blk):
    """Inside shard_map: x_blk [B_loc, S, d] identical across model ranks."""
    B_loc, S, d = x_blk.shape
    if ff_axis:
        # FSDP-style per-layer gather of the ff-sharded expert weights
        p = dict(p)
        p["w_gate"] = jax.lax.all_gather(p["w_gate"], ff_axis, axis=2, tiled=True)
        p["w_up"] = jax.lax.all_gather(p["w_up"], ff_axis, axis=2, tiled=True)
        p["w_down"] = jax.lax.all_gather(p["w_down"], ff_axis, axis=1, tiled=True)
    msz = jax.lax.axis_size("model")
    midx = jax.lax.axis_index("model")
    E_loc = cfg.n_experts // msz
    k = cfg.experts_per_token
    T = B_loc * S
    Tm = T // msz
    x2 = x_blk.reshape(T, d)
    xm = jax.lax.dynamic_slice_in_dim(x2, midx * Tm, Tm)

    idx, weights, lb, zl = _route(cfg, p, xm)
    flat_e = idx.reshape(-1)                    # [Tm*k] global expert ids
    pair_tok = jnp.arange(Tm * k) // k
    dest = flat_e // E_loc                      # owning model rank
    C = int(math.ceil(Tm * k / msz * cfg.capacity_factor))

    # position of each pair within its destination buffer (sorted by dest)
    order = jnp.argsort(dest)
    sdest = dest[order]
    run_start = jnp.searchsorted(sdest, jnp.arange(msz), side="left")
    pos_sorted = jnp.arange(Tm * k) - run_start[sdest]
    keep = pos_sorted < C
    dropped = (~keep).sum().astype(jnp.float32)
    pos_clamped = jnp.where(keep, pos_sorted, C)  # OOB scatter rows drop

    send_x = jnp.zeros((msz, C, d), x2.dtype)
    send_le = jnp.full((msz, C), E_loc, jnp.int32)   # E_loc = dummy group
    gathered = xm[pair_tok[order]]
    send_x = send_x.at[sdest, pos_clamped].set(
        jnp.where(keep[:, None], gathered, 0.0)
    )
    send_le = send_le.at[sdest, pos_clamped].set(
        jnp.where(keep, flat_e[order] % E_loc, E_loc)
    )

    recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
    recv_le = jax.lax.all_to_all(send_le, "model", 0, 0, tiled=False)
    flat_x = recv_x.reshape(msz * C, d)
    flat_le = recv_le.reshape(msz * C)

    order2 = jnp.argsort(flat_le)
    xs = flat_x[order2]
    gs = jnp.zeros((E_loc + 1,), jnp.int32).at[flat_le].add(1)
    ys = _grouped_ffn(cfg, p, xs, gs[:-1])       # dummy-group rows -> 0
    y_flat = jnp.zeros_like(flat_x).at[order2].set(ys.astype(flat_x.dtype))
    y_buf = y_flat.reshape(msz, C, d)

    ret = jax.lax.all_to_all(y_buf, "model", 0, 0, tiled=False)
    # gather my pairs' results back out of the buffers
    pair_y = ret[sdest, pos_clamped % C]          # clamped rows get weight 0
    pair_w = jnp.where(keep, weights.reshape(-1)[order], 0.0)
    y_m = jnp.zeros((Tm, d), jnp.float32).at[pair_tok[order]].add(
        pair_y.astype(jnp.float32) * pair_w[:, None]
    )
    y_m = y_m.astype(x2.dtype) + _shared_ffn(cfg, p, xm)
    y_full = jax.lax.all_gather(y_m, "model", axis=0, tiled=True)  # [T, d]

    aux = jnp.stack([lb, zl, dropped])
    aux = _pmean_axes(aux, ("model", *reduce_axes))
    return y_full.reshape(B_loc, S, d), aux


# ---------------------------------------------------------------------------
# gather path (decode / tiny T)
# ---------------------------------------------------------------------------


def _ep_gather_body(cfg: ModelConfig, reduce_axes, ff_axis, p, x_blk):
    B_loc, S, d = x_blk.shape
    msz = jax.lax.axis_size("model")
    midx = jax.lax.axis_index("model")
    E_loc = cfg.n_experts // msz
    k = cfg.experts_per_token
    T_loc = B_loc * S
    x_loc = x_blk.reshape(T_loc, d)
    if ff_axis:
        # tokens are few at decode: gather them across the ff-sharding axis
        # and compute PARTIAL expert outputs on the local ff slice
        x2 = jax.lax.all_gather(x_loc, ff_axis, axis=0, tiled=True)
    else:
        x2 = x_loc
    T = x2.shape[0]

    idx, weights, lb, zl = _route(cfg, p, x2)
    flat_e = idx.reshape(-1)
    pair_tok = jnp.arange(T * k) // k
    mine = (flat_e // E_loc) == midx
    le = jnp.where(mine, flat_e % E_loc, E_loc)    # dummy group for others

    order = jnp.argsort(le)
    xs = x2[pair_tok[order]]
    gs = jnp.zeros((E_loc + 1,), jnp.int32).at[le].add(1)
    ys = _grouped_ffn(cfg, p, xs, gs[:-1])          # partial over ff slice
    w_sorted = jnp.where(mine, weights.reshape(-1), 0.0)[order]
    y2 = jnp.zeros((T, d), jnp.float32).at[pair_tok[order]].add(
        ys.astype(jnp.float32) * w_sorted[:, None]
    )
    y2 = jax.lax.psum(y2, "model")
    if ff_axis:
        y2 = jax.lax.psum(y2, ff_axis)              # sum ff-slice partials
        aidx = jax.lax.axis_index(ff_axis)
        y2 = jax.lax.dynamic_slice_in_dim(y2, aidx * T_loc, T_loc)
    y2 = y2.astype(x_loc.dtype) + _shared_ffn(cfg, p, x_loc)
    aux = jnp.stack([lb, zl, jnp.float32(0.0)])
    aux = _pmean_axes(aux, ("model", *reduce_axes))
    return y2.reshape(B_loc, S, d), aux


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_apply_ep(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    mesh: Mesh,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE layer. x: [B, S, d], B sharded on (pod, data)."""
    msz = mesh.shape["model"]
    B, S, d = x.shape
    batch_axes = []
    div = 1
    for a in ("pod", "data"):
        if a in mesh.shape and B % (div * mesh.shape[a]) == 0:
            batch_axes.append(a)
            div *= mesh.shape[a]
    T_loc = (B // div) * S
    use_scatter = T_loc % msz == 0 and (T_loc // msz) >= 8

    x_spec = P(tuple(batch_axes) if batch_axes else None, None, None)
    # expert FFN width shards across data when divisible (FSDP-style storage)
    dsz_m = mesh.shape.get("data", 1)
    ff_axis = "data" if (dsz_m > 1 and cfg.d_ff % dsz_m == 0) else None
    ff_spec = ff_axis
    param_specs = {
        "router": P(None, None),
        "w_gate": P("model", None, ff_spec),
        "w_up": P("model", None, ff_spec),
        "w_down": P("model", ff_spec, None),
    }
    for key in ("sh_gate", "sh_up", "sh_down"):
        if key in p:
            param_specs[key] = P(None, None)
    p_used = {k: p[k] for k in param_specs}

    body = _ep_scatter_body if use_scatter else _ep_gather_body
    fn = jax.shard_map(
        partial(body, cfg, tuple(batch_axes), ff_axis),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P(None)),
        check_vma=False,
    )
    y, aux = fn(p_used, x)
    return y, {
        "moe_load_balance": aux[0],
        "moe_z": aux[1],
        "moe_dropped": aux[2],
    }
