"""Additional substrate coverage: optimizer behaviour, HLO capture parsing,
timeline exports, MoE capacity drops, predictor math, topology algebra."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo_capture import (
    CollectiveOp,
    collective_bytes,
    parse_collectives,
    schedule_to_trace,
)
from repro.core.predictor import predict_step, roofline
from repro.core.topology import Topology, V5E
from repro.core.trace_render import ascii_timeline, phase_totals, to_chrome_trace, to_csv
from repro.core import SimConfig, SyncPolicy, EngineKind, run_gemv_allreduce
from repro.optim import AdamWConfig, adamw_init, adamw_step, cosine_lr

# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=200, min_lr_ratio=1.0, master_fp32=True)
    params = {"w": jnp.array([5.0, -3.0, 2.0])}
    state = adamw_init(params, cfg)
    for _ in range(150):
        g = {"w": 2.0 * params["w"]}  # d/dw ||w||^2
        params, state, metrics = adamw_step(params, g, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.1
    assert float(metrics["grad_norm"]) < 1.0


def test_cosine_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(cosine_lr(cfg, jnp.int32(s))) for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5, abs=0.01)
    assert lrs[2] == pytest.approx(1.0, abs=0.01)
    assert lrs[2] > lrs[3] > lrs[4]
    assert lrs[4] == pytest.approx(0.1, abs=0.01)


def test_adamw_bf16_params_fp32_master_roundtrip():
    cfg = AdamWConfig(lr=1e-3, master_fp32=True)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = adamw_init(params, cfg)
    assert state["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((4,), 0.5, jnp.bfloat16)}
    p2, s2, _ = adamw_step(params, g, state, cfg)
    assert p2["w"].dtype == jnp.bfloat16
    # master tracks higher-precision value
    assert float(s2["master"]["w"][0]) != 1.0


# ---------------------------------------------------------------------------
# HLO capture parsing
# ---------------------------------------------------------------------------

HLO_SNIPPET = """
  %all-reduce.2 = f32[8,128]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  %ag = bf16[4096,512]{1,0} all-gather(%p0), channel_id=2, replica_groups=[16,32]<=[512], dimensions={0}
  %rs = f32[64]{0} reduce-scatter(%big), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = bf16[256]{0} collective-permute(%x), channel_id=4, source_target_pairs={{0,1},{1,0}}
"""


def test_parse_collectives_kinds_and_groups():
    ops = parse_collectives(HLO_SNIPPET)
    kinds = {o.kind: o for o in ops}
    assert kinds["all-reduce"].group_size == 4
    assert kinds["all-reduce"].result_bytes == 8 * 128 * 4
    assert kinds["all-gather"].group_size == 32
    # all-gather operand is the shard
    assert kinds["all-gather"].operand_bytes == 4096 * 512 * 2 // 32
    assert kinds["reduce-scatter"].group_size == 4
    assert kinds["reduce-scatter"].operand_bytes == 64 * 4 * 4
    assert collective_bytes(ops) > 0


# the shape of a TPU compile of a ring step: layouts on every type, async
# -start/-done pairs, and the ppermute inside a 3-trip scan loop
TPU_HLO = """
%body (p: (s32[], f32[8,2048])) -> (s32[], f32[8,2048]) {
  %p = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)S(1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %x = f32[8,2048]{1,0:T(8,128)S(1)} get-tuple-element(%p), index=1
  %collective-permute-start = (f32[8,2048]{1,0:T(8,128)S(1)}, f32[8,2048]{1,0:T(8,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %collective-permute-done = f32[8,2048]{1,0:T(8,128)S(1)} collective-permute-done(%collective-permute-start)
  %one = s32[]{:T(128)} constant(1)
  %ni = s32[]{:T(128)} add(%i, %one)
  ROOT %t = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)S(1)}) tuple(%ni, %collective-permute-done)
}

%cond (p: (s32[], f32[8,2048])) -> pred[] {
  %p = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)S(1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %n = s32[]{:T(128)} constant(3)
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,2048]) -> f32[8,8192] {
  %a = f32[8,2048]{1,0:T(8,128)S(1)} parameter(0)
  %z = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)S(1)}) tuple(%z, %a)
  %w = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)S(1)}) while(%init), condition=%cond, body=%body
  %y = f32[8,2048]{1,0:T(8,128)S(1)} get-tuple-element(%w), index=1
  ROOT %all-gather.5 = f32[8,8192]{1,0:T(8,128)S(1)} all-gather(%y), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={1}, use_global_device_ids=true
}
"""


def test_parse_collectives_reads_tpu_layouts_and_permute_pairs():
    ops = parse_collectives(TPU_HLO)
    # one entry per op (the -done half carries no traffic); the permute's
    # group is the four devices its source/target pairs name
    assert [(o.kind, o.group_size, o.result_bytes) for o in ops] == [
        ("collective-permute", 4, 8 * 2048 * 4),
        ("all-gather", 4, 8 * 8192 * 4),
    ]
    assert [o.group_size for o in parse_collectives(HLO_SNIPPET)
            if o.kind == "collective-permute"] == [2]


def test_analyzer_counts_permute_executions_in_scan_loop():
    from repro.core.hlo_analyzer import analyze_hlo

    by_kind = analyze_hlo(TPU_HLO).collectives_by_kind()
    # trip-count aware: 3 executions of one 64 KiB buffer, not the start
    # tuple's summed buffers
    assert by_kind["collective-permute"] == (3.0, 3.0 * 8 * 2048 * 4)
    assert by_kind["all-gather"] == (1.0, 8 * 2048 * 4)


def test_schedule_to_trace_replayable():
    ops = [CollectiveOp("all-reduce", 2**20, 2**20, 16),
           CollectiveOp("all-gather", 2**18, 2**14, 16)]
    topo = Topology((16, 16), ("data", "model"))
    tr = schedule_to_trace(ops, topo, compute_gap_ns=100.0)
    assert len(tr) > 3
    from repro.core import Eidola

    r = Eidola(SimConfig(engine=EngineKind.EVENT, sync=SyncPolicy.SYNCMON), tr).run()
    assert r.flag_reads > 0 and r.kernel_span_ns > 0


# ---------------------------------------------------------------------------
# topology / predictor algebra
# ---------------------------------------------------------------------------


def test_ring_allreduce_cost_algebra():
    topo = Topology((16, 16), ("data", "model"))
    c = topo.collective("all-reduce", 100 * 2**20, "model")
    assert c.steps == 30  # 2(k-1)
    # 2B(k-1)/k on the link
    assert c.link_bytes == 2 * 100 * 2**20 * 15 // 16
    c2 = topo.collective("collective-permute", 2**20, "data")
    assert c2.steps == 1 and c2.link_bytes == 2**20


def test_pod_axis_uses_dci_bandwidth():
    topo = Topology((2, 16, 16), ("pod", "data", "model"))
    # same bytes over one hop: the inter-pod fabric is slower per link
    t_ici = topo.collective("collective-permute", 2**26, "model").time_s
    t_dci = topo.collective("collective-permute", 2**26, "pod").time_s
    assert t_dci > t_ici


def test_roofline_dominant_term():
    topo = Topology((16, 16), ("data", "model"))
    t = roofline(
        arch="x", shape="y", mesh="single", topo=topo,
        hlo_flops_per_device=1e12, hlo_bytes_per_device=1e12,
        collective_bytes_per_device=10**9, model_flops_total=1e12 * 256 * 0.5,
    )
    assert t.dominant == "memory"  # 1e12/819e9 > 1e12/197e12, 1e9/50e9
    assert 0 < t.roofline_fraction() < 1
    p = predict_step(t, topo)
    assert p.no_overlap_s >= p.full_overlap_s


# ---------------------------------------------------------------------------
# timeline exports
# ---------------------------------------------------------------------------


def test_timeline_exports():
    r = run_gemv_allreduce(SimConfig(engine=EngineKind.EVENT), 2_000.0)
    tr = to_chrome_trace(r.segments)
    obj = json.loads(tr)
    assert len(obj["traceEvents"]) > 100
    csv = to_csv(r.segments)
    assert csv.splitlines()[0] == "wg,phase,start_ns,end_ns"
    art = ascii_timeline(r.segments, max_rows=4)
    assert "wg" in art
    totals = phase_totals(r.segments)
    assert totals.get("remote_tiles", 0) > 0


# ---------------------------------------------------------------------------
# MoE capacity drops
# ---------------------------------------------------------------------------


def test_moe_ep_capacity_drops_counted():
    import os
    import subprocess
    import sys

    script = """
import jax, jax.numpy as jnp
from repro.models.common import ModelConfig, materialize
from repro.models.moe import moe_specs
from repro.models.moe_ep import moe_apply_ep
cfg = ModelConfig(n_layers=1, d_model=16, n_heads=2, n_kv_heads=2, d_ff=32,
                  vocab=64, n_experts=8, experts_per_token=4,
                  capacity_factor=0.25, param_dtype=jnp.float32)
p = materialize(moe_specs(cfg), jax.random.PRNGKey(0))
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 4), ("data", "model"))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16), jnp.float32)
y, aux = jax.jit(lambda p, x: moe_apply_ep(cfg, p, x, mesh))(p, x)
assert float(aux["moe_dropped"]) > 0, "tiny capacity must drop tokens"
assert bool(jnp.isfinite(y).all())
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
