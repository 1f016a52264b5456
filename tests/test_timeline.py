"""Timeline engine (pod-scale closed loop) tests.

Covers the four contracts ``repro.core.cohort_timeline`` makes:

* **bit-identity** — counters, sim_cycles, and segments match the event
  engine exactly, across scenarios, shapes, and fabrics (incl. sanitized
  runs and emit coalescing modes);
* **lazy write runs** — a :class:`LazyWriteRun` descriptor synthesizes, pops,
  and interleaves exactly like the ``count`` materialized registrations it
  stands for, including same-cycle heap tie-breaks and mid-run registration
  (property-tested: seeded-random always, hypothesis when installed);
* **eligibility** — ``timeline=True`` errors loudly when the lockstep-lane
  invariant does not hold (and auto mode falls back silently), and deadlock
  diagnostics are engine-independent;
* **lane replay** — the dense closed form (numpy reference and
  ``jax.lax.scan`` variant) reproduces a real cluster run's flag reads and
  kernel end cycle.
"""

import random
import re

import numpy as np
import pytest

from repro.core import (
    Cluster,
    EidolaDeadlock,
    EmitOp,
    EngineKind,
    PhaseSpec,
    Scenario,
    SimConfig,
    SyncPolicy,
    TraceBundle,
    TrafficOp,
    WGProgram,
    simulate,
)
from repro.core.cohort_timeline import (
    lane_step_arrays,
    replay_lane_numpy,
    timeline_support,
)
from repro.core.events import RegisteredWrite, register_phase
from repro.core.scenarios.ring_allreduce import RingAllReduceScenario
from repro.core.wtt import LazyWriteRun, WriteTrackingTable

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property test falls back to the seeded-random sweep
    HAVE_HYPOTHESIS = False

FAST = SimConfig(workgroups=12, n_cus=4)

CLOSED_LOOP = (
    "ring_allreduce",
    "all_to_all",
    "pipeline_p2p",
    "hierarchical_allreduce",
)

COUNTERS = (
    "flag_reads",
    "nonflag_reads",
    "local_writes",
    "xgmi_writes_in",
    "xgmi_writes_out",
    "xgmi_bytes_in",
    "xgmi_bytes_out",
    "read_bytes",
    "write_bytes",
)


def _segments_key(report):
    return sorted(
        (s.device, s.wg, s.phase, round(s.start_ns, 6), round(s.end_ns, 6))
        for s in report.segments
    )


def _run_pair(name, **kw):
    a = simulate(name, FAST, closed_loop=True, timeline=False, **kw)
    b = simulate(name, FAST, closed_loop=True, timeline=True, **kw)
    assert a.meta["engine_impl"] == "event"
    assert b.meta["engine_impl"] == "timeline"
    assert b.engine == "event"  # same semantics: bench row keys comparable
    return a, b


def _assert_reports_equal(a, b):
    for k in COUNTERS:
        assert a.traffic.get(k) == b.traffic.get(k), k
    assert a.sim_cycles == b.sim_cycles
    assert a.kernel_span_ns == b.kernel_span_ns
    assert a.wtt_enacted == b.wtt_enacted
    assert _segments_key(a) == _segments_key(b)


# ---------------------------------------------------------------------------
# bit-identity against the event engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", CLOSED_LOOP)
def test_timeline_bit_identical_flat(name):
    a, b = _run_pair(name, devices=4, sanitize=True)
    _assert_reports_equal(a, b)


@pytest.mark.parametrize("name", CLOSED_LOOP)
@pytest.mark.parametrize("fabric", ["fat_tree", "rail_optimized"])
def test_timeline_bit_identical_tiered(name, fabric):
    a, b = _run_pair(
        name, devices=8, devices_per_node=4, fabric=fabric, sanitize=True
    )
    _assert_reports_equal(a, b)


def test_timeline_breakdown_reported():
    r = simulate(
        "ring_allreduce", FAST, devices=4, closed_loop=True, timeline=True,
        lockstep=False, collect_segments=False,
    )
    bd = r.meta["wall_breakdown"]
    assert set(bd) == {"interpreter_s", "fabric_s", "wtt_s", "other_s"}
    assert all(isinstance(v, float) and v >= 0.0 for v in bd.values())
    assert sum(bd.values()) <= r.wall_time_s + 1e-6


def test_lockstep_breakdown_reported():
    r = simulate(
        "ring_allreduce", FAST, devices=4, closed_loop=True, lockstep=True,
        collect_segments=False,
    )
    assert r.meta["program_stats"]["lockstep"] is True
    bd = r.meta["wall_breakdown"]
    assert set(bd) == {"compile_s", "solve_s", "writeback_s"}
    assert all(isinstance(v, float) and v >= 0.0 for v in bd.values())
    assert sum(bd.values()) <= r.wall_time_s + 1e-6


class _ProgramScenario(Scenario):
    """Closed-loop scenario whose per-rank phases come from a callback."""

    name = "_timeline_program_scenario"
    closed_loop = True

    def __init__(self, cfg, phases_fn, amap=None):
        super().__init__(cfg, amap)
        self._phases_fn = phases_fn

    def programs_for(self, device):
        shared = tuple(self._phases_fn(self, device))
        return [
            WGProgram(wg=w, cu=w % self.cfg.n_cus, dispatch_cycle=0,
                      phases=shared)
            for w in range(self.cfg.workgroups)
        ]

    def programs(self):
        return self.programs_for(0)

    def traces(self):
        return TraceBundle()


for _name in ("tl_burst", "tl_settle", "tl_wait", "tl_drain", "tl_stuck",
              "tl_busy"):
    register_phase(_name)


def _mixed_emit_phases(sc, device):
    """Rank 0 emits both per-workgroup ('each') and coalesced ('last')
    bursts with marker data writes (the LazyWriteRun path); rank 1 waits."""
    if device == 0:
        return [
            PhaseSpec(
                "tl_burst", 40,
                traffic=(TrafficOp("reads", 2, 64),),
                emits=(
                    EmitOp(dst=1, slot=0, payload_bytes=4096,
                           data_writes=5, coalesce="each"),
                ),
            ),
            PhaseSpec(
                "tl_settle", 60,
                traffic=(TrafficOp("local_writes", 1, 64),),
                emits=(
                    EmitOp(dst=1, slot=1, payload_bytes=256,
                           data_writes=3, coalesce="last"),
                ),
            ),
        ]
    return [
        PhaseSpec("tl_wait", wait_addrs=(sc.amap.flag_addr(0, slot=0),)),
        PhaseSpec("tl_wait", wait_addrs=(sc.amap.flag_addr(0, slot=1),)),
        PhaseSpec("tl_drain", 25, traffic=(TrafficOp("reads", 3, 64),)),
    ]


def test_timeline_bit_identical_mixed_emits():
    from repro.core import AddressMap

    cfg = FAST.with_(n_egpus=1)  # 2 devices
    reports = {}
    for tl in (False, True):
        sc = _ProgramScenario(
            cfg, _mixed_emit_phases,
            amap=AddressMap(n_devices=2, flag_slots=2),
        )
        r = Cluster(cfg, sc, timeline=tl, sanitize=True).run()
        assert r.meta["engine_impl"] == ("timeline" if tl else "event")
        reports[tl] = r
    _assert_reports_equal(reports[False], reports[True])


# ---------------------------------------------------------------------------
# lazy write runs: descriptor == materialized registrations
# ---------------------------------------------------------------------------


def _eager_writes(run):
    """The count materialized writes a LazyWriteRun stands for, built with
    the eager path's exact float expression (cycle rounding must agree)."""
    out = []
    for k in range(run.count):
        t = run.base_ns + run.span_ns * (k + 1) / (run.count + 1)
        if t < run.min_ns:
            t = run.min_ns
        out.append(
            RegisteredWrite(
                wakeup_ns=t,
                addr=run.addr_base + k * run.addr_stride,
                data=run.data,
                size=run.size,
                src=run.src,
                seq=run.seq0 + k,
            )
        )
    return out


def _drain(wtt):
    """Pop every (cycle, write-key) pair in enactment order."""
    out = []
    while True:
        cyc, group = wtt.pop_next_group()
        if cyc is None:
            return out
        for w in group:
            out.append((cyc, w.addr, w.data, w.size, w.src, w.seq))


def _check_run_equivalence(run, extra_writes=(), pops_before_extra=0):
    """Lazy table (descriptor) and eager table (materialized writes) see the
    same registration/pop sequence; their pop streams must be identical."""
    lazy = WriteTrackingTable()
    eager = WriteTrackingTable()
    lazy.register_many([run])
    eager.register_many(_eager_writes(run))
    assert len(lazy) == len(eager) == run.count
    got, want = [], []
    for _ in range(pops_before_extra):
        ca, ga = lazy.pop_next_group()
        cb, gb = eager.pop_next_group()
        got.append((ca, [(w.addr, w.seq) for w in ga]))
        want.append((cb, [(w.addr, w.seq) for w in gb]))
    if extra_writes:
        lazy.register_many(list(extra_writes))
        eager.register_many(list(extra_writes))
    got.extend(_drain(lazy))
    want.extend(_drain(eager))
    assert got == want
    assert len(lazy) == len(eager) == 0


def test_lazy_run_matches_eager_seeded_random():
    rng = random.Random(0xE1D01A)
    for _ in range(120):
        count = rng.randint(1, 40)
        base = rng.choice([0.0, rng.uniform(0, 5000)])
        span = rng.choice([0.0, rng.uniform(0, 3000)])
        run = LazyWriteRun(
            count=count,
            base_ns=base,
            span_ns=span,
            addr_base=0x1000,
            addr_stride=rng.choice([0, 8, 64]),
            data=rng.randint(0, 2**31),
            size=rng.choice([4, 8]),
            src=rng.randint(0, 7),
            seq0=rng.randint(0, 100),
            min_ns=rng.choice([0.0, base + span * rng.uniform(0, 1.2)]),
        )
        # a mid-run registration landing inside the run's cycle range (often
        # exactly on a member's cycle: the reg_no tie-break must agree too)
        member_ns = run.wakeup_ns(rng.randrange(count))
        extra = [
            RegisteredWrite(
                wakeup_ns=member_ns, addr=0x9000, data=1, size=8, src=9
            ),
            RegisteredWrite(
                wakeup_ns=member_ns + rng.uniform(0, 100),
                addr=0x9040, data=2, size=8, src=9,
            ),
        ]
        _check_run_equivalence(
            run, extra_writes=extra,
            pops_before_extra=rng.randint(0, min(3, count)),
        )


def test_lazy_run_same_cycle_tie_breaks():
    # span 0: every member lands on the same cycle; pop order must be the
    # registration order (contiguous reg_no block), before later same-cycle
    # registrations from other producers
    run = LazyWriteRun(count=8, base_ns=100.0, span_ns=0.0,
                       addr_base=0x2000, addr_stride=8, data=7, size=8)
    tied = RegisteredWrite(wakeup_ns=100.0, addr=0x8000, data=3, size=8)
    _check_run_equivalence(run, extra_writes=[tied])


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        count=st.integers(min_value=1, max_value=64),
        base=st.floats(0, 1e5, allow_nan=False, allow_infinity=False),
        span=st.floats(0, 1e4, allow_nan=False, allow_infinity=False),
        stride=st.sampled_from([0, 8, 64]),
        min_frac=st.floats(0, 1.5),
        pops=st.integers(min_value=0, max_value=3),
    )
    def test_lazy_run_matches_eager_hypothesis(
        count, base, span, stride, min_frac, pops
    ):
        run = LazyWriteRun(
            count=count, base_ns=base, span_ns=span,
            addr_base=0x1000, addr_stride=stride, data=11, size=8,
            min_ns=(base + span) * min_frac,
        )
        extra = [
            RegisteredWrite(wakeup_ns=run.wakeup_ns(count // 2),
                            addr=0x9000, data=1, size=8)
        ]
        _check_run_equivalence(
            run, extra_writes=extra, pops_before_extra=min(pops, count)
        )

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_lazy_run_matches_eager_hypothesis():
        pass


def test_pop_due_run_is_prefix_of_pop_next_group():
    def build():
        w = WriteTrackingTable()
        w.register_many([
            LazyWriteRun(count=10, base_ns=100.0, span_ns=900.0,
                         addr_base=0x1000, addr_stride=8, data=5, size=8),
            RegisteredWrite(wakeup_ns=550.0, addr=0x9000, data=1, size=8),
        ])
        return w

    ref = build()
    want = _drain(ref)

    wtt = build()
    got = []
    res = wtt.pop_due_run(None)
    assert res is not None
    cycles, addrs, data, size = res
    assert len(cycles) == len(addrs)
    # the bulk pop must stop before the interleaved plain write's key
    assert all(c <= wtt.ns_to_cycles(550.0) for c in cycles)
    got.extend((c, a, data, size, 5, i)
               for i, (c, a) in enumerate(zip(cycles, addrs)))
    # fix up src/seq fields for comparison: members carry src=-1 here
    got = [(c, a, d, s) for c, a, d, s, _, _ in got]
    rest = [(c, a, d, s) for c, a, d, s, _, _ in _drain(wtt)]
    assert got + rest == [(c, a, d, s) for c, a, d, s, _, _ in want]
    assert len(wtt) == 0


def test_pop_due_run_respects_stop_cycle():
    wtt = WriteTrackingTable()
    run = LazyWriteRun(count=10, base_ns=100.0, span_ns=900.0,
                       addr_base=0x1000, addr_stride=8, data=5, size=8)
    wtt.register_many([run])
    stop = wtt.ns_to_cycles(run.wakeup_ns(4))
    cycles, addrs, _, _ = wtt.pop_due_run(stop)
    assert all(c <= stop for c in cycles)
    assert len(wtt) == run.count - len(cycles)
    # the remainder still pops in order
    rest = _drain(wtt)
    assert len(rest) == run.count - len(cycles)
    assert [a for _, a, *_ in rest] == [
        0x1000 + 8 * k for k in range(len(cycles), run.count)
    ]


def test_pop_due_run_returns_none_on_plain_head():
    wtt = WriteTrackingTable()
    wtt.register(RegisteredWrite(wakeup_ns=10.0, addr=0x10, data=1, size=8))
    assert wtt.pop_due_run(None) is None
    assert len(wtt) == 1  # untouched


# ---------------------------------------------------------------------------
# eligibility and fallback
# ---------------------------------------------------------------------------


def test_timeline_true_rejects_cohorts_off():
    sc = RingAllReduceScenario(FAST)
    sc.closed_loop = True
    with pytest.raises(ValueError, match="cohorts=False"):
        Cluster(FAST, sc, cohorts=False, timeline=True).run()


def test_timeline_true_rejects_cycle_engine():
    cfg = FAST.with_(engine=EngineKind.CYCLE)
    sc = RingAllReduceScenario(cfg)
    sc.closed_loop = True
    with pytest.raises(ValueError, match="EngineKind.EVENT"):
        Cluster(cfg, sc, timeline=True).run()


def test_timeline_true_rejects_syncmon():
    cfg = FAST.with_(sync=SyncPolicy.SYNCMON)
    sc = RingAllReduceScenario(cfg)
    sc.closed_loop = True
    with pytest.raises(ValueError, match="SPIN"):
        Cluster(cfg, sc, timeline=True).run()


class _SlowReduce:
    def scale_phase(self, wg, name, cycles):
        return cycles * 3 if name == "ring_reduce" else cycles

    def jitter_write(self, w):
        return w


def test_timeline_auto_falls_back_on_perturbation():
    r = simulate(
        "ring_allreduce", FAST, devices=4, closed_loop=True,
        perturb={1: _SlowReduce()},
    )
    assert r.meta["engine_impl"] == "event"
    with pytest.raises(ValueError, match="perturbation"):
        simulate(
            "ring_allreduce", FAST, devices=4, closed_loop=True,
            perturb={1: _SlowReduce()}, timeline=True,
        )


def test_timeline_opt_out_is_respected_and_named():
    class _OptOut(RingAllReduceScenario):
        timeline_opt_out = "exercises per-member wake interleaving"

    sc = _OptOut(FAST)
    sc.closed_loop = True
    cl = Cluster(FAST, sc)
    assert "exercises per-member wake interleaving" in timeline_support(cl)
    r = cl.run()
    assert r.meta["engine_impl"] == "event"
    sc2 = _OptOut(FAST)
    sc2.closed_loop = True
    with pytest.raises(ValueError, match="opts out"):
        Cluster(FAST, sc2, timeline=True).run()


def test_timeline_requires_closed_loop():
    with pytest.raises(ValueError, match="closed-loop"):
        simulate("gemv_allreduce", FAST, timeline=True)


def test_timeline_deadlock_parity():
    def phases(sc, device):
        if device == 0:
            # waits on a flag no peer ever emits
            return [PhaseSpec("tl_stuck",
                              wait_addrs=(sc.amap.flag_addr(1, slot=0),))]
        return [PhaseSpec("tl_busy", 50, traffic=(TrafficOp("reads", 1, 64),))]

    cfg = FAST.with_(n_egpus=1)  # 2 devices
    msgs = {}
    for tl in (False, True):
        sc = _ProgramScenario(cfg, phases)
        with pytest.raises(EidolaDeadlock) as ei:
            Cluster(cfg, sc, timeline=tl).run()
        # the detection cycle is engine bookkeeping (when the queue was
        # noticed empty), not part of the diagnosis — normalize it
        msgs[tl] = re.sub(r"at cycle \d+", "at cycle N", str(ei.value))
    assert msgs[False] == msgs[True]
    assert "device 0" in msgs[True]
    assert "wg 0-11" in msgs[True]


# ---------------------------------------------------------------------------
# dense lane replay (numpy reference, jax variant)
# ---------------------------------------------------------------------------


def _lane_inputs(cluster):
    """Per-device (dispatch vector, member counts, step arrays) after a run."""
    out = {}
    for node in cluster.nodes:
        tgt = node.target
        dispatch = np.array(
            [c.program.dispatch_cycle for c in tgt.cohorts], np.int64
        )
        counts = np.array([c.count for c in tgt.cohorts], np.int64)
        is_wait, val = lane_step_arrays(
            tgt.cohorts[0].phases, tgt.flag_set_cycle
        )
        out[node.device_id] = (dispatch, counts, is_wait, val)
    return out


def test_replay_numpy_matches_real_run():
    cfg = FAST
    sc = RingAllReduceScenario(cfg)
    sc.closed_loop = True
    cl = Cluster(cfg, sc, timeline=True)
    cl.run()
    for dev, (dispatch, counts, is_wait, val) in _lane_inputs(cl).items():
        reads, end = replay_lane_numpy(
            dispatch, is_wait, val,
            poll=cfg.poll_interval_cycles, check=cfg.flag_check_cycles,
        )
        node = cl.nodes[dev]
        assert int((reads * counts).sum()) == node.memory.traffic.flag_reads
        assert int(end.max()) == node.target.kernel_end_cycle


def test_replay_jax_matches_numpy():
    jax = pytest.importorskip("jax")
    from repro.core.cohort_timeline import lane_int32, replay_lane_jax

    replay = jax.jit(replay_lane_jax, static_argnames=("poll", "check"))
    rng = np.random.default_rng(7)
    for _ in range(10):
        n_steps = rng.integers(1, 30)
        n_cohorts = rng.integers(1, 12)
        is_wait = rng.random(n_steps) < 0.5
        val = np.where(
            is_wait,
            rng.integers(0, 5000, n_steps),
            rng.integers(1, 400, n_steps),
        ).astype(np.int64)
        dispatch = rng.integers(0, 300, n_cohorts).astype(np.int64)
        r_np, t_np = replay_lane_numpy(dispatch, is_wait, val, poll=64,
                                       check=4)
        r_jx, t_jx = replay(
            *lane_int32(dispatch, is_wait, val, poll=64, check=4),
            poll=64, check=4,
        )
        np.testing.assert_array_equal(r_np, np.asarray(r_jx, np.int64))
        np.testing.assert_array_equal(t_np, np.asarray(t_jx, np.int64))


def test_replay_jax_refuses_what_int32_cannot_hold():
    """The int32 device replay never wraps: the host boundary proves the
    range or raises, and unchecked 64-bit inputs are refused."""
    from repro.core.cohort_timeline import lane_int32, replay_lane_jax

    is_wait = np.array([False, True, False])
    dispatch = np.array([0, 5], np.int64)
    top = np.iinfo(np.int32).max
    # just inside: the bound is dispatch + durations + waits * check
    ok = np.array([top - 3000, 1000, 1000], np.int64)
    lane_int32(dispatch, is_wait, ok, poll=64, check=4)
    # a long timed step past 2**31 would wrap a plain int32 scan
    big = np.array([top, 1000, 1000], np.int64)
    with pytest.raises(OverflowError, match="int32"):
        lane_int32(dispatch, is_wait, big, poll=64, check=4)
    # a flag that only becomes visible past 2**31 - poll
    late = np.array([10, top - 10, 10], np.int64)
    with pytest.raises(OverflowError):
        lane_int32(dispatch, is_wait, late, poll=64, check=4)
    with pytest.raises(ValueError):
        lane_int32(-dispatch - 1, is_wait, ok, poll=64, check=4)
    with pytest.raises(TypeError, match="lane_int32"):
        replay_lane_jax(dispatch, is_wait, ok, poll=64, check=4)
