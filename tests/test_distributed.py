"""Multi-device distribution tests.

These need ``--xla_force_host_platform_device_count`` set BEFORE jax
initializes, so each test runs an inline script in a subprocess with the
flag in its environment (the same mechanism dryrun.py uses in-process).
"""

import os
import subprocess
import sys

import pytest

# model-forward-dominated: runs in the separate slow CI job, not the fast
# simulator suite
pytestmark = pytest.mark.slow

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_devices(script: str, n_devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_fused_gemv_allreduce_equals_psum():
    run_devices(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.collectives import psum_matmul, fused_gemv_allreduce
mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32) * 0.05
y1 = jax.jit(psum_matmul(mesh))(x, w)
y2 = jax.jit(fused_gemv_allreduce(mesh))(x, w)
np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
print("OK")
"""
    )


def test_ep_moe_matches_local_oracle_and_grads():
    run_devices(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig, materialize
from repro.models.moe import moe_apply, moe_specs
from repro.models.moe_ep import moe_apply_ep

cfg = ModelConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=48,
                  vocab=64, n_experts=8, experts_per_token=2,
                  n_shared_experts=1, capacity_factor=4.0,
                  param_dtype=jnp.float32)
p = materialize(moe_specs(cfg), jax.random.PRNGKey(0))
mesh = make_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32) * 0.5
y_local, _ = moe_apply(cfg, p, x)
y_ep, _ = jax.jit(lambda p, x: moe_apply_ep(cfg, p, x, mesh))(p, x)
np.testing.assert_allclose(y_ep, y_local, rtol=1e-4, atol=1e-4)
# decode-sized input exercises the gather path
x1 = x[:, :1, :]
y1_l, _ = moe_apply(cfg, p, x1)
y1_e, _ = jax.jit(lambda p, x: moe_apply_ep(cfg, p, x, mesh))(p, x1)
np.testing.assert_allclose(y1_e, y1_l, rtol=1e-4, atol=1e-4)
g_ep = jax.grad(lambda p: jnp.sum(moe_apply_ep(cfg, p, x, mesh)[0]**2))(p)
g_lo = jax.grad(lambda p: jnp.sum(moe_apply(cfg, p, x)[0]**2))(p)
for k in g_ep:
    np.testing.assert_allclose(g_ep[k], g_lo[k], rtol=1e-3, atol=1e-4)
print("OK")
"""
    )


def test_sharded_train_step_matches_single_device():
    """The same init + batch must give the same loss on (1,1) and (2,4)."""
    out = run_devices(
        """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models import Model, ModelConfig
from repro.training import TrainConfig, build_train_step
from repro.optim import AdamWConfig, adamw_init
import numpy as np

cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                  vocab=128, param_dtype=jnp.float32)
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 128)
lab = jnp.roll(tok, -1, axis=1)
losses = []
for dims in ((1, 1), (2, 4)):
    mesh = make_mesh(dims, ("data", "model"))
    model = Model(cfg, mesh=mesh)
    tcfg = TrainConfig(optim=AdamWConfig(lr=1e-3), donate_state=False)
    step, sh, fb = build_train_step(model, mesh, tcfg)
    with mesh:
        params = jax.jit(model.init, out_shardings=sh["params"])(
            jax.random.PRNGKey(0))
        state = jax.jit(lambda p: adamw_init(p, tcfg.optim),
                        out_shardings=sh["state"])(params)
        p2, s2, metrics = step(params, state, tok, lab)
    losses.append(float(metrics["loss"]))
print("losses", losses)
assert abs(losses[0] - losses[1]) < 1e-3, losses
print("OK")
"""
    )
    assert "OK" in out


def test_sharded_train_step_bf16_across_mesh_shapes():
    """The fp32 cross-mesh determinism above, in bf16: the same init + batch
    must give matching losses on (1,1), (2,4), and (4,2) meshes with bf16
    params (ROADMAP open item — the partitionable-threefry fix was only
    exercised at fp32).  bf16 accumulates rounding differently per sharding,
    so the tolerance is bf16-sized rather than exact."""
    out = run_devices(
        """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models import Model, ModelConfig
from repro.training import TrainConfig, build_train_step
from repro.optim import AdamWConfig, adamw_init

cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                  vocab=128, param_dtype=jnp.bfloat16)
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 128)
lab = jnp.roll(tok, -1, axis=1)
losses = []
for dims in ((1, 1), (2, 4), (4, 2)):
    mesh = make_mesh(dims, ("data", "model"))
    model = Model(cfg, mesh=mesh)
    tcfg = TrainConfig(optim=AdamWConfig(lr=1e-3), donate_state=False)
    step, sh, fb = build_train_step(model, mesh, tcfg)
    with mesh:
        params = jax.jit(model.init, out_shardings=sh["params"])(
            jax.random.PRNGKey(0))
        assert all(p.dtype == jnp.bfloat16 for p in jax.tree.leaves(params))
        state = jax.jit(lambda p: adamw_init(p, tcfg.optim),
                        out_shardings=sh["state"])(params)
        p2, s2, metrics = step(params, state, tok, lab)
    losses.append(float(metrics["loss"]))
print("losses", losses)
spread = max(losses) - min(losses)
assert spread < 0.05, (losses, spread)
print("OK")
"""
    )
    assert "OK" in out


def test_indivisible_dims_fall_back_to_replication():
    """minicpm3's vocab (73448) is not divisible by a 16-way model axis:
    those tensors must fall back to replication (recorded), not crash —
    and a reduced model still runs under resolved shardings."""
    run_devices(
        """
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import get_config, reduced
from repro.models import Model
from repro.distributed import param_shardings, DEFAULT_RULES

# FULL config, abstract only (no allocation): vocab 73448 % 16 != 0
cfg = get_config("minicpm3-4b")
m = Model(cfg)
mesh16 = make_mesh((1, 16), ("data", "model"))
sh, fallbacks = param_shardings(m.param_axes(), m.abstract_params(), mesh16,
                                DEFAULT_RULES)
assert any("replicated" in f for f in fallbacks), fallbacks

# and a reduced model actually runs under resolved shardings
cfg_r = reduced(get_config("gemma3-1b"))
mr = Model(cfg_r)
mesh = make_mesh((2, 4), ("data", "model"))
sh_r, _ = param_shardings(mr.param_axes(), mr.abstract_params(), mesh,
                          DEFAULT_RULES)
params = jax.jit(mr.init, out_shardings=sh_r)(jax.random.PRNGKey(0))
tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg_r.vocab)
logits, _ = jax.jit(lambda p, t: mr.forward(p, t))(params, tok)
assert logits.shape == (4, 16, cfg_r.vocab)
print("OK")
""",
        n_devices=16,
    )


def test_dryrun_single_cell_tiny_mesh():
    """run_cell end-to-end on a 2x2 mesh inside the subprocess."""
    out = run_devices(
        """
import os
os.environ.setdefault("XLA_FLAGS", "")
from repro.launch.dryrun import run_cell
rec = run_cell("xlstm-125m", "train_4k", "2x2", {"remat": "full"},
               verbose=False)
assert rec["status"] == "ok", rec.get("error")
assert rec["flops_per_device"] > 0
assert rec["collective_bytes_per_device"] > 0
assert rec["max_scan_trip"] >= 1
print("OK")
""",
        n_devices=4,
    )
    assert "OK" in out


def test_compressed_psum_accuracy():
    run_devices(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum
mesh = make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
fn = jax.shard_map(lambda x: compressed_psum(x, "data"), mesh=mesh,
               in_specs=P("data"), out_specs=P("data"), check_vma=False)
out = jax.jit(fn)(g)
exact = np.broadcast_to(np.asarray(g).sum(0, keepdims=True), (8, 64))
# int8 quantization bound: n_shards * max|g| / 127 (elementwise absolute)
bound = 8 * float(np.abs(np.asarray(g)).max()) / 127.0
err = np.abs(np.asarray(out) - exact).max()
assert err < bound, (err, bound)
print("OK")
"""
    )


def test_pipeline_parallel_matches_sequential():
    run_devices(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import pipeline_apply, stack_stage_params
mesh = make_mesh((4,), ("pipe",))
L, d = 8, 16
W = jax.random.normal(jax.random.PRNGKey(0), (L, d, d), jnp.float32) * 0.25
b = jax.random.normal(jax.random.PRNGKey(1), (L, d), jnp.float32) * 0.1
layers = {"w": W, "b": b}
def layer_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])
x = jax.random.normal(jax.random.PRNGKey(2), (8, d), jnp.float32)
ref = x
for i in range(L):
    ref = layer_fn(jax.tree.map(lambda a: a[i], layers), ref)
apply = pipeline_apply(mesh, layer_fn, n_micro=4)
out = jax.jit(apply)(stack_stage_params(layers, 4), x)
np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
# grads flow through collective_permute's transpose (reverse pipeline)
g = jax.grad(lambda sp: jnp.sum(apply(sp, x)**2))(stack_stage_params(layers, 4))
assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
print("OK")
""",
        n_devices=4,
    )
