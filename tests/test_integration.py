"""Integration tests: training loop, checkpoint/restart, elastic remesh,
stragglers, serving, data pipeline, EP MoE equivalence, distributed
collectives (these run on a 1-device mesh; multi-device paths are covered by
tests/test_distributed.py under forced host devices)."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree
from repro.data import DataConfig, SyntheticLMDataset, prefetch
from repro.launch.mesh import make_mesh
from repro.ft import (
    ElasticMeshManager,
    HeartbeatMonitor,
    SimulatedFailure,
    StragglerMonitor,
)
from repro.models import Model, ModelConfig
from repro.optim import AdamWConfig
from repro.serving import ServeConfig, ServeEngine
from repro.training import TrainConfig, Trainer

# model-forward-dominated: runs in the separate slow CI job, not the fast
# simulator suite
pytestmark = pytest.mark.slow


def tiny_model():
    return Model(
        ModelConfig(
            name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            d_ff=128, vocab=128, max_seq_len=128,
        )
    )


def test_loss_decreases_and_failure_recovery():
    mesh = make_mesh((1, 1), ("data", "model"))
    model = tiny_model()
    data = SyntheticLMDataset(
        DataConfig(vocab=128, seq_len=64, global_batch=8, seed=1)
    )
    fails = {12}

    def inject(step):
        if step in fails:
            fails.discard(step)
            raise SimulatedFailure(f"injected at {step}")

    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(
            model, mesh,
            TrainConfig(optim=AdamWConfig(lr=1e-2, warmup_steps=5,
                                          total_steps=40)),
            ckpt_dir=d, ckpt_every=10, failure_injector=inject,
        )
        tr.init_state(jax.random.PRNGKey(0))
        hist = tr.run(prefetch(iter(data)), 30, log_every=0)
        losses = [h["loss"] for h in hist]
        assert losses[-1] < losses[0] - 0.3
        # failure at step 12 forced a restart from the step-10 checkpoint:
        # steps 11/12 run twice
        assert len(hist) > 30


def test_checkpoint_roundtrip_and_crash_safety(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((5,), jnp.bfloat16)},
    }
    path = str(tmp_path / "ck")
    save_pytree(tree, path)
    back = load_pytree(jax.eval_shape(lambda: tree), path)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == jnp.bfloat16
    # torn checkpoint (no COMMIT) must be invisible
    os.remove(os.path.join(path, "COMMIT"))
    with pytest.raises(FileNotFoundError):
        load_pytree(jax.eval_shape(lambda: tree), path)


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (10, 20, 30):
        mgr.save(s, {"x": jnp.full((4,), s, jnp.float32)})
    assert mgr.steps() == [20, 30]
    step, tree = mgr.restore_latest({"x": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert step == 30 and float(tree["x"][0]) == 30.0


def test_elastic_mesh_shrinks_on_failure():
    devs = list(range(8))  # device ids stand in for jax devices
    mgr = ElasticMeshManager(devs, model_parallel=2)
    assert mgr.current_mesh().shape["data"] == 4
    mgr.fail_devices([3])
    m = mgr.current_mesh()
    assert m.shape["data"] == 3  # one model-parallel replica lost
    mgr.fail_devices([0, 1, 2, 4, 5])
    assert mgr.current_mesh().shape["data"] == 1  # one replica left
    mgr.fail_devices([6])
    with pytest.raises(SimulatedFailure):
        mgr.current_mesh()  # 1 device < model_parallel=2: no replica fits


def test_elastic_mesh_uses_real_devices():
    devs = jax.devices()
    mgr = ElasticMeshManager(devs, model_parallel=1)
    mesh = mgr.current_mesh()
    assert mesh.shape["data"] == len(devs)


def test_straggler_monitor_flags_slow_host():
    mon = StragglerMonitor(threshold=1.5, window=4)
    for _ in range(4):
        rep = mon.record_step({0: 1.0, 1: 1.02, 2: 0.98, 3: 2.5})
    assert rep.stragglers == [3]
    assert rep.worst_ratio > 2.0


def test_heartbeat_monitor_detects_dead_host():
    t = [0.0]
    mon = HeartbeatMonitor([0, 1, 2], timeout_s=5.0, clock=lambda: t[0])
    t[0] = 4.0
    mon.beat(0)
    mon.beat(1)
    t[0] = 7.0
    assert mon.dead_hosts() == [2]
    assert mon.alive_hosts() == [0, 1]


def test_serving_generates_and_batches():
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, ServeConfig(max_batch=2))
    outs = eng.generate([[5, 6, 7], [9, 10], [1, 2, 3, 4]], max_new_tokens=4)
    assert [len(o) for o in outs] == [7, 6, 8]
    assert eng.stats["requests"] == 3
    # greedy decoding is deterministic
    outs2 = eng.generate([[5, 6, 7]], max_new_tokens=4)
    assert outs2[0] == outs[0]


def test_data_pipeline_determinism_and_host_sharding():
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=8, n_hosts=2, seed=3)
    ds = SyntheticLMDataset(cfg)
    a1 = ds.batch(5, host=0)
    a2 = ds.batch(5, host=0)
    b = ds.batch(5, host=1)
    np.testing.assert_array_equal(a1["tokens"], a2["tokens"])
    assert not np.array_equal(a1["tokens"], b["tokens"])
    assert a1["tokens"].shape == (4, 16)
    # labels are next-token shifted
    np.testing.assert_array_equal(a1["labels"][:, :-1], a1["tokens"][:, 1:])


def test_prefetch_preserves_order():
    vals = list(range(20))
    out = list(prefetch(iter(vals), depth=3))
    assert out == vals


def test_remesh_preserves_values():
    from repro.ft import remesh_pytree
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mesh1 = make_mesh((1,), ("data",))

    def sh_fn(mesh):
        return {"w": NamedSharding(mesh, P())}

    out = remesh_pytree(tree, sh_fn, mesh1)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
