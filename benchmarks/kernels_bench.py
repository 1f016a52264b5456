"""Pallas-kernel correctness sweeps against the ``ref.py`` oracles.

These sweeps check values only and time nothing: on the host the kernels
run under the Pallas interpreter (``interpret=True``), whose speed says
nothing about the compiled kernel, and the D=64 multi-head attention shapes
are ones Mosaic does not compile.  Kernel times come from a profiler trace
on the chip, not from here."""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def gemv_sweep() -> Dict:
    rows: List[Dict] = []
    rng = jax.random.PRNGKey(0)
    for (M, K) in ((256, 2048), (512, 8192), (1024, 8192)):
        a = jax.random.normal(rng, (M, K), jnp.float32)
        x = jax.random.normal(rng, (K, 1), jnp.float32)
        y = ops.gemv(a, x, bm=128, bk=512, interpret=True)
        err = float(jnp.max(jnp.abs(y - ref.gemv_ref(a, x))))
        rows.append({"M": M, "K": K, "max_err": err})
    return {"rows": rows, "pass": all(r["max_err"] < 1e-3 for r in rows)}


def decode_attention_sweep() -> Dict:
    rows: List[Dict] = []
    rng = jax.random.PRNGKey(1)
    for (B, H, KV, D, S) in ((1, 8, 2, 64, 1024), (4, 8, 8, 64, 2048)):
        q = jax.random.normal(rng, (B, H, D), jnp.float32)
        k = jax.random.normal(rng, (B, S, KV, D), jnp.float32)
        v = jax.random.normal(rng, (B, S, KV, D), jnp.float32)
        o = ops.decode_attention(q, k, v, jnp.int32(S - 3), bs=256,
                                 interpret=True)
        err = float(jnp.max(jnp.abs(o - ref.decode_attention_ref(q, k, v, S - 3))))
        rows.append({"B": B, "H": H, "S": S, "max_err": err})
    return {"rows": rows, "pass": all(r["max_err"] < 1e-3 for r in rows)}


def all_benches() -> Dict[str, Dict]:
    return {"gemv": gemv_sweep(), "decode_attention": decode_attention_sweep()}
