"""Compile the Pallas kernels and the lane replay for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology it is only told about.  What Mosaic refuses here (block shapes
that break its tiling rules, too much VMEM) it would refuse on the chip, so
these compiles guard the kernels at their real widths on every CPU run.
Nothing executes, so nothing here is a time or a result.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.cohort_timeline import replay_lane_jax
from repro.kernels import ops

GEMMA = get_config("gemma3-1b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_gemv_tiles_compiles_for_v5e(one_chip):
    M = K = 8192
    fn = functools.partial(ops.gemv_tiles, n_dev=4, my_dev=1)
    hlo = _compile(
        fn,
        _spec(one_chip, (M, K), jnp.bfloat16),
        _spec(one_chip, (K, 1), jnp.bfloat16),
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_gemv_compiles_for_v5e(one_chip):
    M = K = 8192
    hlo = _compile(
        ops.gemv,
        _spec(one_chip, (M, K), jnp.bfloat16),
        _spec(one_chip, (K, 1), jnp.bfloat16),
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_for_v5e(one_chip):
    B, S = 1, 4096
    H, KV, D = GEMMA.n_heads, GEMMA.n_kv_heads, GEMMA.head_dim
    hlo = _compile(
        ops.decode_attention,
        _spec(one_chip, (B, H, D), jnp.bfloat16),
        _spec(one_chip, (B, S, KV, D), jnp.bfloat16),
        _spec(one_chip, (B, S, KV, D), jnp.bfloat16),
        _spec(one_chip, (), jnp.int32),
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles_for_v5e(one_chip):
    hlo = _compile(
        ops.rmsnorm,
        _spec(one_chip, (4096, GEMMA.d_model), jnp.bfloat16),
        _spec(one_chip, (GEMMA.d_model,), jnp.float32),
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_replay_lane_jax_compiles_for_v5e(one_chip):
    # a 256-device ring lane: 16 cohorts x 1021 steps, vmapped over devices
    fn = jax.vmap(functools.partial(replay_lane_jax, poll=64, check=4))
    hlo = _compile(
        fn,
        _spec(one_chip, (256, 16), jnp.int32),
        _spec(one_chip, (256, 1021), jnp.bool_),
        _spec(one_chip, (256, 1021), jnp.int32),
    ).as_text()
    # int32 all the way: reads and end cycles, no 64-bit emulation
    root = re.findall(r"ROOT %\S+ = \((.*)\) tuple", hlo)[-1]  # entry's
    assert re.findall(r"\w+\[[\d,]*\]", root) == ["s32[256,16]"] * 2
    assert "s64" not in hlo
