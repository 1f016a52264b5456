"""The expert-parallel MoE scenario (``moe_ep``) at a small size: 16 ranks
on 2 nodes, 32 experts in 4 groups, the best 2 groups, top-4, 8 tokens a
rank.  The tiered lockstep solve engages and equals the event engine on
every counter; the program equals the plain reference
(``bench/refs/moe_ep.py``); zero-pair messages carry only their flag, odd
source ranks use the replicas; the spans and counters the benchmark reads
are recorded where they belong."""

import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import EngineKind, SimConfig
from repro.core.cluster import Cluster
from repro.core.lockstep_tiered import compile_tiered
from repro.core.scenario import EmitRun, get_scenario, simulate
from repro.core.scenarios.moe_ep import route_pairs

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "bench" / "refs")]

from benchlib import answers, harness  # noqa: E402
from benchlib.harness import TIME_GAP_LIMIT  # noqa: E402
from benchlib.spec import load_cell, load_reference  # noqa: E402

N, DPN = 16, 8
SMALL = dict(experts=32, redundant_experts=8, expert_groups=4, topk_groups=2,
             top_k=4, tokens_per_device=8)
_INT_KEYS = ("traffic", "per_device", "sim_cycles", "wtt_registered",
             "wtt_enacted", "flag_reads", "nonflag_reads")


def _kw(fabric, layer=3):
    return dict(devices=N, devices_per_node=DPN, closed_loop=True,
                collect_segments=False, fabric=fabric, layer=layer, **SMALL)


def _cfg():
    return SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(N)


def _counters(r):
    """Every counter of a Report, floats of the fabric's queues apart."""
    fab = r.meta["fabric"]
    return (
        tuple(getattr(r, k) for k in _INT_KEYS),
        r.meta["device_spans_ns"],
        {k: v for k, v in fab.items() if not k.endswith("queued_ns")},
    )


def _small_call(layer):
    """The benchmark cell's call with the deployment cut to the small
    shape (the H800 widths and constants otherwise as configured)."""
    cell = load_cell("deepseek_v3_ep144.decode_zipf")
    call = next(harness.traffic.CallStream(cell.config, cell.mix, 0))
    call["sim_config"] = {**call["sim_config"], "n_egpus": N - 1}
    call["params"] = {**call["params"], "devices": N, "devices_per_node": DPN,
                      "layer": layer, **SMALL}
    return call


@pytest.mark.parametrize("fabric", ["rail_optimized", "two_tier"])
@pytest.mark.parametrize("layer", [3, 41])
def test_lockstep_engages_and_equals_the_event_engine(fabric, layer):
    fast = simulate("moe_ep", _cfg(), **_kw(fabric, layer))
    event = simulate("moe_ep", _cfg(), timeline=False, **_kw(fabric, layer))
    assert fast.meta["lockstep_reason"] == "engaged"
    assert event.meta["engine_impl"] == "event"
    assert _counters(fast) == _counters(event)
    for key, want in event.meta["fabric"].items():
        if key.endswith("queued_ns"):  # summed in another order
            assert fast.meta["fabric"][key] == pytest.approx(want, rel=1e-12)


def test_expert_phase_keeps_a_duration_per_rank():
    sc = get_scenario("moe_ep")(_cfg(), **{
        k: v for k, v in _kw("rail_optimized").items()
        if k not in ("devices", "collect_segments")})
    plan = compile_tiered(Cluster(_cfg(), sc, collect_segments=False))
    durs = [ins[2] for ins in plan.instrs if ins[0] == "p"]
    per_rank = [d for d in durs if isinstance(d, np.ndarray)]
    assert per_rank, durs
    cycles = [sc.expert_cycles(r) for r in range(N)]
    assert len(set(cycles)) > 1
    assert any(np.array_equal(d.ravel(), cycles) for d in per_rank)


@pytest.mark.parametrize("layer", [3, 17, 60])
@pytest.mark.parametrize("engine", [{}, {"lockstep": False, "timeline": False}],
                         ids=["default", "event"])
def test_program_equals_the_reference(layer, engine):
    from repro.core import simulate as sim

    call = _small_call(layer)
    got = answers.answer_of(harness.call_simulate(
        lambda scenario, cfg, **kw: sim(scenario, cfg, **kw, **engine), call))
    want = load_reference("moe_ep").answer(call)
    off, gap, where = answers.compare(got, want)
    assert off == 0, where
    assert gap <= TIME_GAP_LIMIT, where
    if engine:  # the event engine adds the queue times in the reference's order
        assert gap == 0.0, where


@pytest.mark.parametrize("layer", [3, 4, 30, 59, 60])
def test_pair_matrix_equals_the_reference_router(layer):
    import moe_routing

    params = dict(SMALL, zipf_s=0.3, layer=layer)
    got, slots = route_pairs(N, SMALL["tokens_per_device"], **{
        k: v for k, v in params.items() if k != "tokens_per_device"})
    want, want_slots = moe_routing.pairs_of(params, N)
    assert np.array_equal(got, want)
    assert np.array_equal(slots, want_slots)
    assert got.sum() == N * SMALL["tokens_per_device"] * SMALL["top_k"]


def test_zero_pair_messages_carry_only_the_flag():
    sc = get_scenario("moe_ep")(_cfg(), closed_loop=True,
                                devices_per_node=DPN, fabric="rail_optimized",
                                **SMALL)
    pairs = sc.pairs
    off = ~np.eye(N, dtype=bool)
    assert (pairs[off] == 0).any() and (pairs[off] > 0).any()
    db = sc.dispatch_bytes
    for r in range(N):
        dispatch = sc.programs_for(r)[0].phases.segments[0]
        sent = [p for e in dispatch.emits for p in e.payload_bytes]
        assert sent == [int(pairs[r, d]) * db for d in range(N) if d != r]
    r = simulate(sc, collect_segments=False)
    msgs = 2 * N * (N - 1)
    fab = r.meta["fabric"]
    assert fab["messages"] == msgs
    want = (pairs[off].sum() * (db + sc.combine_bytes) + 8 * msgs)
    assert fab["bytes"] == want


def test_odd_sources_use_the_replicas():
    # 40 slots on 16 ranks: ranks 13-15 hold replicas only, so only odd
    # source ranks send them pairs
    pairs, slot_rank = route_pairs(N, 64, zipf_s=0.3, layer=5, **{
        k: v for k, v in SMALL.items() if k != "tokens_per_device"})
    assert set(slot_rank[32:]) >= {13, 14, 15}
    assert not set(slot_rank[:32]) & {13, 14, 15}
    assert pairs[0::2, 13:].sum() == 0
    assert pairs[1::2, 13:].sum() > 0


def test_deployment_placement_at_144_ranks():
    # expert e on rank e // 2, two replicas on each of ranks 128-143
    _, slot_rank = route_pairs(144, 1, experts=256, redundant_experts=32,
                               top_k=8, expert_groups=8, topk_groups=4,
                               zipf_s=0.3, layer=3)
    assert np.array_equal(slot_rank[:256], np.arange(256) // 2)
    assert np.array_equal(slot_rank[256:], 128 + np.arange(32) // 2)


def test_spans_nest_and_counters_are_recorded():
    r = simulate("moe_ep", _cfg(), **_kw("rail_optimized"))
    spans = r.meta["spans"]
    names = [s[0] for s in spans]

    def parents(name):
        return {spans[s[1]][0] for s in spans if s[0] == name}

    assert parents("moe.route") == {"entry.scenario"}
    assert parents("lockstep.ordered") == {"lockstep.solve"}
    assert names.count("lockstep.ordered") == 2  # dispatch and combine
    c = r.meta["counters"]
    sc = get_scenario("moe_ep")(_cfg(), closed_loop=True, **SMALL)
    off = sc.pairs.sum() - np.trace(sc.pairs)
    assert c["lockstep.ordered_msgs"] == 2 * N * (N - 1)
    assert c["moe.fabric_pairs"] == off
    assert c["moe.hot_rank_pairs"] == sc.pairs.sum(axis=0).max()


def test_hierarchical_call_adds_one_ordered_span():
    # the broadcast fan-out is the hierarchical all-reduce's one ordered
    # family: one span and its messages, no more
    n, dpn = 12, 4
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(n)
    r = simulate("hierarchical_allreduce", cfg, devices=n, closed_loop=True,
                 collect_segments=False, devices_per_node=dpn,
                 fabric="rail_optimized")
    assert r.meta["lockstep_reason"] == "engaged"
    names = [s[0] for s in r.meta["spans"]]
    assert names.count("lockstep.ordered") == 1
    assert r.meta["counters"]["lockstep.ordered_msgs"] == (n // dpn) * (dpn - 1)


def test_uniform_fanout_keeps_one_payload():
    # all_to_all's fan-out stays one int: its plan and floats are unchanged
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(12)
    sc = get_scenario("all_to_all")(cfg, closed_loop=True, devices_per_node=4,
                                    fabric="rail_optimized")
    plan = compile_tiered(Cluster(cfg, sc, collect_segments=False))
    fams = {ins[4] for ins in plan.instrs if ins[0] == "p" and ins[4]}
    assert [type(f.nb) for f in fams] == [int]


def test_emit_run_checks_its_payloads():
    run = EmitRun(3, dst0=1, payload_bytes=(0, 5, 9))
    assert [op.payload_bytes for op in run.expand()] == [0, 5, 9]
    with pytest.raises(ValueError, match="one size per member"):
        EmitRun(2, dst0=0, payload_bytes=(1,))
    with pytest.raises(ValueError, match="non-negative"):
        EmitRun(1, dst0=0, payload_bytes=(-1,))


def test_layout_proven():
    from repro.analysis import prove_layout

    proof = prove_layout("moe_ep", devices_per_node=4, max_devices=256)
    assert proof.ok, proof.render()
