"""Tiered lockstep: group-uniform bulk solving over multi-tier fabrics.

Seeded-random cross-engine identity and group-IR round-trips that must run
unconditionally (the hypothesis-driven shape sweep lives in
``test_property.py`` and is skipped when hypothesis is absent), the
solver's float results pinned exactly, and its step and slot counters.
"""

import math
import random

import numpy as np
import pytest

from repro.core import EngineKind, SimConfig
from repro.core.cluster import Cluster
from repro.core.lockstep_tiered import _as_slice, compile_tiered
from repro.core.scenario import get_scenario, simulate

_KEYS = (
    "flag_reads", "nonflag_reads", "local_writes", "xgmi_writes_in",
    "xgmi_writes_out", "xgmi_bytes_in", "xgmi_bytes_out", "read_bytes",
    "write_bytes",
)


def _sig(r):
    return (
        tuple(r.traffic.get(k) for k in _KEYS),
        r.sim_cycles,
        tuple(sorted(
            (d, tuple(sorted(t.items()))) for d, t in r.per_device.items()
        )),
        (r.wtt_registered, r.wtt_enacted),
        tuple(sorted(
            (k, v) for k, v in r.meta["fabric"].items()
            if isinstance(v, int)
        )),
    )


def test_three_engine_bit_identity_seeded():
    # seeded random shapes through all three implementations: the per-WG
    # event interpreter, the cohort timeline, and the tiered bulk solver
    rng = random.Random(0x51D07A)
    names = ["ring_allreduce", "all_to_all", "hierarchical_allreduce"]
    fabrics = ["two_tier", "fat_tree", "rail_optimized"]
    for _ in range(3):
        name = rng.choice(names)
        fabric = rng.choice(fabrics)
        dpn = rng.choice([2, 3, 4])
        n = dpn * rng.randint(2, 5)
        cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(n)
        kw = dict(devices=n, closed_loop=True, collect_segments=False,
                  devices_per_node=dpn, fabric=fabric)
        event = simulate(name, cfg, timeline=False, **kw)
        timeline = simulate(name, cfg, lockstep=False, **kw)
        lockstep = simulate(name, cfg, lockstep=True, **kw)
        assert timeline.meta["engine_impl"] == "timeline"
        assert lockstep.meta["lockstep_reason"] == "engaged"
        s_event = _sig(event)
        assert s_event == _sig(timeline), (name, fabric, n, dpn)
        assert s_event == _sig(lockstep), (name, fabric, n, dpn)


def test_tiered_identity_all_scenarios_all_fabrics():
    # every closed-loop scenario x every tiered preset at one odd shape;
    # pipeline falls back (identity then holds trivially, but the recorded
    # reason must carry the group blame)
    for name in ("ring_allreduce", "all_to_all", "hierarchical_allreduce",
                 "pipeline_p2p"):
        for fabric in ("two_tier", "fat_tree", "rail_optimized"):
            n, dpn = 12, 4
            cfg = SimConfig(
                engine=EngineKind.EVENT, workgroups=4,
            ).with_devices(n)
            kw = dict(devices=n, closed_loop=True, collect_segments=False,
                      devices_per_node=dpn, fabric=fabric)
            fast = simulate(name, cfg, **kw)  # lockstep auto-selects
            slow = simulate(name, cfg, lockstep=False, **kw)
            if name == "pipeline_p2p":
                assert "group" in fast.meta["lockstep_reason"]
                assert fast.meta["program_stats"]["lockstep"] is False
            else:
                assert fast.meta["lockstep_reason"] == "engaged", (
                    name, fabric, fast.meta["lockstep_reason"],
                )
            assert _sig(fast) == _sig(slow), (name, fabric)


def test_ring_flag_pool_clears_partial_region():
    # per-step flag slots would overrun the default flag/partial gap beyond
    # ~256 devices; the scenario's map must keep the regions disjoint so
    # data-marker writes can never alias (and stale-satisfy) ring-step flags
    ring = get_scenario("ring_allreduce")
    for n in (8, 256, 512, 4096):
        amap = ring.default_amap(SimConfig().with_devices(n))
        assert amap.flag_region()[1] <= amap.partial_base, n
    small = ring.default_amap(SimConfig().with_devices(8))
    from repro.core.memory import AddressMap

    assert small.partial_base == AddressMap.partial_base  # no-op below scale


def test_marker_alias_declines_with_blame():
    # hierarchical_allreduce's *legacy* layout (no partial clearance) lets
    # data-marker writes reach high flag slots at 256 nodes; the solver must
    # refuse (the engines resolve waits by value, so a stale marker satisfies
    # them early) and name the rank and flag.  The shipped default_amap
    # re-bases partial_base above the pool, so the legacy map is rebuilt here
    # explicitly: 512 devices, dpn=2 -> bcast_slot 512, a 16.8 MB pool that
    # overruns the default 16.7 MB flag/partial gap
    import pytest

    from repro.core.memory import AddressMap

    legacy = AddressMap(n_devices=512, flag_slots=513)
    assert legacy.flag_region()[1] > legacy.partial_base  # still aliases
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(512)
    with pytest.raises(ValueError, match=r"data-marker writes on rank \d+"
                                         r" reach flag \(writer \d+, slot"):
        simulate(
            "hierarchical_allreduce", cfg, devices=512, closed_loop=True,
            collect_segments=False, devices_per_node=2, fabric="two_tier",
            lockstep=True, amap=legacy,
        )


def test_hierarchical_pod_lockstep_engages():
    # the clearance re-base is the whole point: the same 512-device shape
    # that declines under the legacy map now engages the tiered solver and
    # stays bit-identical to the cohort timeline
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(512)
    kw = dict(devices=512, closed_loop=True, collect_segments=False,
              devices_per_node=2, fabric="two_tier")
    fast = simulate("hierarchical_allreduce", cfg, lockstep=True, **kw)
    slow = simulate("hierarchical_allreduce", cfg, lockstep=False, **kw)
    assert fast.meta["lockstep_reason"] == "engaged"
    assert _sig(fast) == _sig(slow)


def test_group_classification_roundtrips_expand():
    # the tiered plan's per-group schedule must replay each member rank's
    # SymbolicProgram.expand() phase-for-phase (names and order)
    from repro.core.cluster import Cluster
    from repro.core.lockstep_tiered import compile_tiered
    from repro.core.scenario import as_symbolic

    for name, n, dpn in (
        ("ring_allreduce", 12, 4),
        ("all_to_all", 12, 4),
        ("hierarchical_allreduce", 12, 4),
        ("hierarchical_allreduce", 33, 3),
    ):
        cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(n)
        sc = get_scenario(name)(
            cfg, closed_loop=True, devices_per_node=dpn, fabric="two_tier",
        )
        plan = compile_tiered(Cluster(cfg, sc, collect_segments=False))
        seen = set()
        for grp in plan.groups:
            sched = [
                ph.name
                for seg in grp.segs
                for _ in range(seg.count)
                for ph in seg.body
            ]
            for dev in grp.devs:
                dev = int(dev)
                seen.add(dev)
                sp = as_symbolic(sc.programs_for(dev)[0].phases)
                assert sp is not None
                expanded = [p.name for p in sp.expand()]
                assert sched == expanded, (name, n, dpn, dev)
        assert seen == set(range(n)), (name, n, dpn)


# The solver's float results, recorded before its per-step loop was
# restructured (deferred integer tallies, hoisted leg constants, skipped
# all-zero queue adds): (scenario, fabric, devices, devices_per_node) ->
# sim_cycles, every ``*queued_ns`` of the fabric's stats, and the exact sum
# (``math.fsum``) of the per-port queue times.  The last case is one in which
# no message ever waits for its port.
_PINNED = {
    ("ring_allreduce", "two_tier", 12, 4): (
        298741,
        {"dci_queued_ns": 48614.39999999991, "ici_queued_ns": 0.0,
         "queued_ns": 48614.3999999999},
        48614.3999999999,
    ),
    ("ring_allreduce", "fat_tree", 12, 4): (
        673333,
        {"dci_queued_ns": 48506.88, "ici_queued_ns": 0.0,
         "queued_ns": 193273.54666666663,
         "spine_queued_ns": 144766.66666666663},
        193273.54666666663,
    ),
    ("ring_allreduce", "rail_optimized", 12, 4): (
        298741,
        {"ici_queued_ns": 0.0, "queued_ns": 48614.3999999999,
         "rail_queued_ns": 48614.39999999991},
        48614.3999999999,
    ),
    ("all_to_all", "two_tier", 12, 4): (
        405868,
        {"dci_queued_ns": 8628518.400000002, "ici_queued_ns": 6519499.2,
         "queued_ns": 15148017.600000001},
        15148017.600000001,
    ),
    ("all_to_all", "fat_tree", 12, 4): (
        1555628,
        {"dci_queued_ns": 22298081.59999999, "ici_queued_ns": 5611851.599999999,
         "queued_ns": 42674502.79999998, "spine_queued_ns": 14764569.6},
        42674502.79999999,
    ),
    ("all_to_all", "rail_optimized", 12, 4): (
        222380,
        {"ici_queued_ns": 755006.3999999999, "queued_ns": 5173191.999999999,
         "rail_queued_ns": 4418185.6},
        5173192.0,
    ),
    ("hierarchical_allreduce", "two_tier", 12, 4): (
        355600,
        {"dci_queued_ns": 0.0, "ici_queued_ns": 62915.03999999998,
         "queued_ns": 62915.03999999998},
        62915.03999999998,
    ),
    ("hierarchical_allreduce", "fat_tree", 12, 4): (
        925200,
        {"dci_queued_ns": 0.0, "ici_queued_ns": 62915.040000000095,
         "queued_ns": 62915.040000000095, "spine_queued_ns": 0.0},
        62915.040000000095,
    ),
    ("hierarchical_allreduce", "rail_optimized", 12, 4): (
        355600,
        {"ici_queued_ns": 62915.03999999998, "queued_ns": 62915.03999999998,
         "rail_queued_ns": 0.0},
        62915.03999999998,
    ),
    ("ring_allreduce", "rail_optimized", 64, 8): (
        813168,
        {"ici_queued_ns": 0.0, "queued_ns": 0.0, "rail_queued_ns": 0.0},
        0.0,
    ),
}


def _pod_scenario(name, fabric, n, dpn):
    cfg = SimConfig(engine=EngineKind.EVENT, workgroups=4).with_devices(n)
    return cfg, get_scenario(name)(
        cfg, closed_loop=True, devices_per_node=dpn, fabric=fabric,
    )


@pytest.mark.parametrize("case", list(_PINNED),
                         ids=lambda c: "-".join(map(str, c)))
def test_solver_floats_pinned(case):
    # the float recurrence keeps every add in its order, so the queue sums
    # and the cycle count stay bit-for-bit what they were
    cycles, queued, port_q = _PINNED[case]
    cfg, sc = _pod_scenario(*case)
    cluster = Cluster(cfg, sc, collect_segments=False, lockstep=True)
    r = cluster.run()
    assert r.meta["lockstep_reason"] == "engaged"
    assert r.sim_cycles == cycles
    assert {k: v for k, v in r.meta["fabric"].items()
            if k.endswith("queued_ns")} == queued
    assert math.fsum(
        ps[2] for ps in cluster.fabric.port_stats.values()
    ) == port_q


@pytest.mark.parametrize("fabric, n, dpn, quiet", [
    ("rail_optimized", 64, 8, True),  # no message waits for its port
    ("two_tier", 12, 4, False),       # the DCI uplinks queue
])
def test_solver_counters(fabric, n, dpn, quiet):
    # lockstep.steps counts the plan's instructions, lockstep.slots every
    # elementwise leg-slot pricing, lockstep.quiet_slots those with no queue
    cfg, sc = _pod_scenario("ring_allreduce", fabric, n, dpn)
    plan = compile_tiered(Cluster(cfg, sc, collect_segments=False))
    slots = sum(
        len(ins[4].leg_slots) for ins in plan.instrs
        if ins[0] == "p" and ins[4] is not None and ins[4].pricing == "elem"
    )
    assert slots > 0
    cfg, sc = _pod_scenario("ring_allreduce", fabric, n, dpn)
    r = simulate(sc, lockstep=True, collect_segments=False)
    c = r.meta["counters"]
    assert c["lockstep.steps"] == len(plan.instrs)
    assert c["lockstep.slots"] == slots
    if quiet:
        assert c["lockstep.quiet_slots"] == slots
    else:
        assert 0 <= c["lockstep.quiet_slots"] < slots


@pytest.mark.parametrize("idx, as_slice", [
    ([0, 2, 4, 6], True),
    ([7, 15, 23], True),
    ([3, 4], True),
    ([2, 4, 6, 10], False),   # the step changes
    ([1015, 0, 1, 2], False),  # descends, then ascends
    ([5, 5, 5], False),        # repeats: a scatter must not collapse them
    ([4], False),
    ([], False),
])
def test_index_progressions_become_slices(idx, as_slice):
    a = np.array(idx, np.int64)
    got = _as_slice(a)
    assert isinstance(got, slice) is as_slice
    src = np.arange(2000) * 3
    assert np.array_equal(src[got], src[a])
