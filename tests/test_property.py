"""Hypothesis property tests on system invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (
    AddressMap,
    DirectoryMemory,
    MonitorLog,
    RegisteredWrite,
    SimConfig,
    SyncPolicy,
    EngineKind,
    WriteTrackingTable,
    run_gemv_allreduce,
)
from repro.core.hlo_analyzer import analyze_hlo
from repro.distributed.sharding import DEFAULT_RULES, resolve_spec
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# WTT invariants
# ---------------------------------------------------------------------------


@given(
    times=st.lists(
        st.floats(min_value=0, max_value=1e6, allow_nan=False), min_size=1,
        max_size=64,
    ),
    clock=st.sampled_from([0.94, 1.0, 1.5, 2.0]),
)
@settings(max_examples=60, deadline=None)
def test_wtt_pops_are_chronological(times, clock):
    wtt = WriteTrackingTable(clock_ghz=clock)
    for i, t in enumerate(times):
        wtt.register(RegisteredWrite(wakeup_ns=t, addr=64 * i, data=i, seq=i))
    popped = []
    while not wtt.empty:
        c, group = wtt.pop_next_group()
        assert group, "pop of nonempty WTT must return writes"
        popped.append((c, [w.seq for w in group]))
    cycles = [c for c, _ in popped]
    assert cycles == sorted(cycles)
    assert sorted(s for _, seqs in popped for s in seqs) == sorted(
        range(len(times))
    )


@given(
    times=st.lists(
        st.integers(min_value=0, max_value=10_000), min_size=1, max_size=64
    ),
    poll_at=st.integers(min_value=0, max_value=12_000),
)
@settings(max_examples=60, deadline=None)
def test_wtt_poll_returns_exactly_due_writes(times, poll_at):
    wtt = WriteTrackingTable(clock_ghz=1.0)
    for i, t in enumerate(times):
        wtt.register(RegisteredWrite(wakeup_ns=float(t), addr=0, data=i, seq=i))
    due = wtt.poll(poll_at)
    assert {w.seq for w in due} == {
        i for i, t in enumerate(times) if t <= poll_at
    }
    assert len(wtt) == sum(1 for t in times if t > poll_at)


# ---------------------------------------------------------------------------
# Monitor Log: a wake fires iff the masked compare matches (hoare)
# ---------------------------------------------------------------------------


@given(
    wake_value=st.integers(min_value=0, max_value=2**32 - 1),
    written=st.integers(min_value=0, max_value=2**32 - 1),
    size=st.sampled_from([1, 2, 4, 8]),
)
@settings(max_examples=60, deadline=None)
def test_monitor_hoare_masked_compare(wake_value, written, size):
    mem = DirectoryMemory(AddressMap(n_devices=4))
    log = MonitorLog(mem, semantics="hoare", wake_latency_cycles=1)
    addr = mem.amap.flag_addr(1)
    e = log.monitor(addr, size, wake_value)
    immediate = log.mwait(e, wf_id=0, now_cycle=0)
    if immediate:
        # condition already held (memory zero-initialized, wake value 0):
        # the wavefront never descheduled, so no wake can fire
        assert mem.peek(addr, size) == (wake_value & ((1 << (8 * size)) - 1))
        e.waiting_wfs.add(0)  # arm anyway to exercise the wake path below
    mem.enact_xgmi_write(
        RegisteredWrite(wakeup_ns=0, addr=addr, data=written, size=size), 10
    )
    wakes = log.pop_wakes_until(10_000)
    should_wake = (written & ((1 << (8 * size)) - 1)) == (
        wake_value & ((1 << (8 * size)) - 1)
    )
    assert bool(wakes) == should_wake


# ---------------------------------------------------------------------------
# engine equivalence as a property over delays
# ---------------------------------------------------------------------------


@given(
    delays=st.lists(
        st.floats(min_value=0, max_value=30_000, allow_nan=False),
        min_size=3, max_size=3,
    ),
    sync=st.sampled_from([SyncPolicy.SPIN, SyncPolicy.SYNCMON]),
)
@settings(max_examples=12, deadline=None)
def test_event_and_vector_engines_agree(delays, sync):
    out = {}
    for eng in (EngineKind.EVENT, EngineKind.VECTOR):
        cfg = SimConfig(sync=sync, engine=eng, workgroups=32, M=32, K=512)
        r = run_gemv_allreduce(cfg, delays, collect_segments=False)
        out[eng] = (r.flag_reads, r.nonflag_reads, r.kernel_span_ns)
    assert out[EngineKind.EVENT] == out[EngineKind.VECTOR]


# ---------------------------------------------------------------------------
# sharding rules: resolved specs always divide the dims they shard
# ---------------------------------------------------------------------------


@given(
    dims=st.lists(st.integers(min_value=1, max_value=4096), min_size=1,
                  max_size=4),
    axes=st.lists(
        st.sampled_from(["embed", "heads", "kv", "mlp", "vocab", "experts",
                         None]),
        min_size=1, max_size=4,
    ),
)
@settings(max_examples=80, deadline=None)
def test_resolve_spec_divisibility(dims, axes):
    import os

    n = min(len(dims), len(axes))
    dims, axes = dims[:n], axes[:n]
    mesh = make_mesh((1, 1), ("data", "model"))

    # a fake 4x4 mesh is enough to test the table logic; use real mesh sizes
    spec = resolve_spec(dims, axes, DEFAULT_RULES, mesh, path="t")
    # every sharded dim must divide by its mesh axis size
    for d, part in zip(dims, tuple(spec)):
        if part is not None:
            assert d % mesh.shape[part] == 0


# ---------------------------------------------------------------------------
# HLO analyzer: while-loop multipliers on synthetic modules
# ---------------------------------------------------------------------------


@given(trip=st.integers(min_value=2, max_value=500))
@settings(max_examples=20, deadline=None)
def test_analyzer_scales_with_trip_count(trip):
    hlo = f"""
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {{
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(f32[8,8] %x, f32[8,8] %x), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}}

%cond (p: (s32[], f32[8,8])) -> pred[] {{
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant({trip})
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {{
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}}
"""
    mod = analyze_hlo(hlo)
    assert mod.max_while_trip() == trip
    assert mod.dot_flops() == trip * 2 * 8 * 8 * 8


# ---------------------------------------------------------------------------
# tiered lockstep: group-uniform solving over multi-tier fabrics
# ---------------------------------------------------------------------------

from repro.core.scenario import get_scenario, simulate  # noqa: E402

_TIERED_KEYS = (
    "flag_reads", "nonflag_reads", "local_writes", "xgmi_writes_in",
    "xgmi_writes_out", "xgmi_bytes_in", "xgmi_bytes_out", "read_bytes",
    "write_bytes",
)


def _tiered_sig(r):
    return (
        tuple(r.traffic.get(k) for k in _TIERED_KEYS),
        r.sim_cycles,
        tuple(sorted((d, tuple(sorted(t.items()))) for d, t in
                     r.per_device.items())),
        (r.wtt_registered, r.wtt_enacted),
        tuple(sorted((k, v) for k, v in r.meta["fabric"].items()
                     if isinstance(v, int))),
    )


@given(
    name=st.sampled_from([
        "ring_allreduce", "all_to_all", "hierarchical_allreduce",
        "pipeline_p2p",
    ]),
    fabric=st.sampled_from(["two_tier", "fat_tree", "rail_optimized"]),
    dpn=st.sampled_from([2, 3, 4]),
    nodes=st.integers(min_value=2, max_value=8),
)
@settings(max_examples=14, deadline=None)
def test_tiered_lockstep_matches_timeline(name, fabric, dpn, nodes):
    n = dpn * nodes
    if not 4 <= n <= 33:
        return
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(n)
    kw = dict(devices=n, closed_loop=True, collect_segments=False,
              devices_per_node=dpn, fabric=fabric)
    fast = simulate(name, cfg, **kw)  # lockstep auto-selects
    slow = simulate(name, cfg, lockstep=False, **kw)
    if name == "pipeline_p2p":
        # cross-rank pipelined chains fall back with a group-level blame
        assert "group" in fast.meta["lockstep_reason"]
        assert fast.meta["program_stats"]["lockstep"] is False
    else:
        assert fast.meta["lockstep_reason"] == "engaged", (
            name, fabric, n, dpn, fast.meta["lockstep_reason"],
        )
        assert fast.meta["program_stats"]["lockstep"] is True
    assert _tiered_sig(fast) == _tiered_sig(slow), (name, fabric, n, dpn)
