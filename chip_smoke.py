#!/usr/bin/env python3
"""Chip smoke test: drive Eidola's main paths once on a TPU and check them.

    python chip_smoke.py              # one chip: phases device .. train
    python chip_smoke.py --chips 4    # four chips: the chips4 phase only

Phases, one line each (``[phase] ...``); each checks its own result and any
failure exits non-zero before the result line is printed:

  device    JAX sees a TPU; any other platform is an error naming it.
  simulate  ``simulate()`` (the users' main path, a host solve) on two
            pod-scale cells of ``BENCH_multi_device.json``; counters equal
            the committed rows bit for bit and the lockstep solver engaged.
  lane      a 256-device ring run on the timeline engine, then every
            device's lane replayed by the jitted int32 ``replay_lane_jax`` on
            the TPU; reads and end cycles equal the int64 numpy reference and
            the run's own counters.
  kernels   the four Pallas kernels, compiled (``tpu_custom_call``), at real
            widths, against ``repro.kernels.ref``.
  train     gemma3-1b at full width through ``repro.training.Trainer``, as
            ``repro.launch.train`` builds it, depth and batch cut to fit one
            chip; loss finite and falling.
  chips4    ``fused_gemv_allreduce`` on a 4-chip ``model`` mesh against
            ``psum_matmul`` and a one-device reference, then its compiled HLO
            through ``parse_collectives`` -> ``schedule_to_trace`` -> an
            Eidola replay; the ring's collective-permute runs n-1 times.

Times printed are host-clock walls (compile included where it says so),
not device times.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` where set, else
``.jax_cache/`` in this checkout.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import use_checkout_cache  # noqa: E402

SEED = 0

# gemma3-1b's 26 layers with fp32 master weights and AdamW moments need
# 18.77 GB of the v5e's 15.75 GB HBM (v5e compile, memory analysis); 8
# layers at 8 x 128 tokens need 9.56 GB (args 6.74 + temps 2.83)
TRAIN_LAYERS = 8
TRAIN_BATCH = 8
TRAIN_SEQ = 128
TRAIN_STEPS = 10


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"error: chip_smoke needs a TPU, but JAX found platform "
            f"{platform!r} ({len(devs)} device(s)); it never runs elsewhere"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"error: --chips {chips} needs {chips} TPU devices, found {len(devs)}"
        )
    dev = {"platform": platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", f"{dev['kind']} x{dev['count']}, jax {jax.__version__}")
    return dev


def phase_simulate(cells=(("ring_allreduce", 1024, 4),
                         ("hierarchical_allreduce", 1024, 32))) -> None:
    from benchmarks.multi_device_bench import COUNTER_KEYS
    from repro.core import EngineKind, SimConfig, simulate

    rows = json.loads((ROOT / "BENCH_multi_device.json").read_text())["rows"]
    cfg = SimConfig(workgroups=64, engine=EngineKind.EVENT)
    # (scenario, devices, devices_per_node, fabric preset): two_tier cells
    for name, nd, dpn in cells:
        want = [r for r in rows
                if (r["scenario"], r["devices"], r["devices_per_node"],
                    r["fabric"], r["sync"], r["workgroups"])
                == (name, nd, dpn, None, "spin", cfg.workgroups)]
        check(len(want) == 1, f"{name}@{nd}: {len(want)} BENCH rows")
        want = want[0]
        t0 = time.perf_counter()
        r = simulate(name, cfg, devices=nd, closed_loop=True,
                     devices_per_node=dpn, collect_segments=False)
        wall = time.perf_counter() - t0
        got = {"flag_reads": r.flag_reads, "nonflag_reads": r.nonflag_reads,
               "xgmi_writes_in": r.traffic.get("xgmi_writes_in", 0),
               "wtt_enacted": r.wtt_enacted, "sim_cycles": r.sim_cycles,
               "kernel_span_ns": r.kernel_span_ns}
        drift = {k: (want[k], got[k]) for k in COUNTER_KEYS
                 if got[k] != want[k]}
        check(not drift, f"{name}@{nd} dpn={dpn}: counters drifted {drift}")
        reason = r.meta.get("lockstep_reason")
        check(reason == "engaged", f"{name}@{nd}: lockstep {reason!r}")
        say("simulate",
            f"{name} devices={nd} dpn={dpn} two_tier: {len(COUNTER_KEYS)} "
            f"counters equal BENCH (flag_reads {got['flag_reads']}), "
            f"lockstep engaged, host wall {wall} s")


def phase_lane(nd: int = 256) -> None:
    from repro.core import Cluster, EngineKind, SimConfig
    from repro.core.cohort_timeline import (
        lane_int32, lane_step_arrays, replay_lane_jax, replay_lane_numpy)
    from repro.core.scenarios.ring_allreduce import RingAllReduceScenario

    cfg = SimConfig(workgroups=64, engine=EngineKind.EVENT, n_egpus=nd - 1)
    sc = RingAllReduceScenario(cfg)
    sc.closed_loop = True
    t0 = time.perf_counter()
    cl = Cluster(cfg, sc, timeline=True, lockstep=False,
                 collect_segments=False)
    rep = cl.run()
    run_wall = time.perf_counter() - t0
    check(rep.meta.get("engine_impl") == "timeline",
          f"engine {rep.meta.get('engine_impl')!r}, wanted the timeline")

    poll, chk = cfg.poll_interval_cycles, cfg.flag_check_cycles
    lanes, counts, ref = [], [], []
    for node in cl.nodes:
        tgt = node.target
        dispatch = np.array(
            [c.program.dispatch_cycle for c in tgt.cohorts], np.int64)
        is_wait, val = lane_step_arrays(tgt.cohorts[0].phases,
                                        tgt.flag_set_cycle)
        lanes.append(lane_int32(dispatch, is_wait, val, poll=poll, check=chk))
        counts.append([c.count for c in tgt.cohorts])
        ref.append(replay_lane_numpy(dispatch, is_wait, val,
                                     poll=poll, check=chk))
    shapes = {tuple(a.shape for a in lane) for lane in lanes}
    check(len(shapes) == 1, f"lanes differ in shape: {shapes}")
    dispatch, is_wait, val = (jnp.asarray(np.stack(a)) for a in zip(*lanes))

    replay = jax.jit(jax.vmap(
        functools.partial(replay_lane_jax, poll=poll, check=chk)))
    t0 = time.perf_counter()
    reads, end = jax.block_until_ready(replay(dispatch, is_wait, val))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(replay(dispatch, is_wait, val))
    second = time.perf_counter() - t0
    placed = {d.platform for d in reads.devices()}
    check(placed == {"tpu"}, f"replay ran on {placed}")
    check(reads.dtype == jnp.int32, f"replay dtype {reads.dtype}")

    reads, end = np.asarray(reads, np.int64), np.asarray(end, np.int64)
    counts = np.asarray(counts, np.int64)
    np.testing.assert_array_equal(reads, np.stack([r for r, _ in ref]))
    np.testing.assert_array_equal(end, np.stack([t for _, t in ref]))
    for node, r, t, c in zip(cl.nodes, reads, end, counts):
        check(int((r * c).sum()) == node.memory.traffic.flag_reads,
              f"device {node.device_id}: replayed reads != run's flag_reads")
        check(int(t.max()) == node.target.kernel_end_cycle,
              f"device {node.device_id}: replayed end != kernel_end_cycle")
    say("lane",
        f"ring_allreduce devices={nd}: {nd} lanes x {dispatch.shape[1]} "
        f"cohorts x {val.shape[1]} steps replayed on {'/'.join(placed)} "
        f"(int32) = numpy "
        f"int64 reference = run's flag_reads ({rep.flag_reads}) and kernel "
        f"end cycles; host wall: timeline run {run_wall} s, replay first "
        f"call {first} s (compile included), second {second} s")


def phase_kernels(M: int = 8192, B: int = 4, S: int = 4096,
                  rows: int = 4096) -> None:
    from repro.configs import get_config
    from repro.kernels import ops, ref

    gemma = get_config("gemma3-1b")
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)

    def compiled(name, fn, *args):
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        check("tpu_custom_call" in c.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        return c, dt

    # gemv_tiles and gemv: the fused GEMV+AllReduce compute, 8192 x 8192
    K = M
    n_dev, my_dev = 4, 1
    a = jax.random.normal(keys[0], (M, K), jnp.bfloat16)
    x = jax.random.normal(keys[1], (K, 1), jnp.bfloat16)
    y_ref = ref.gemv_ref(a, x)

    fn = functools.partial(ops.gemv_tiles, n_dev=n_dev, my_dev=my_dev)
    c, dt = compiled("gemv_tiles", fn, a, x)
    y, served = c(a, x)
    err = rel_err(y, ref.gemv_tiles_ref(a, x, n_dev, my_dev))
    check(err < 2e-2, f"gemv_tiles rel err {err}")
    served = np.asarray(served)
    tiles_per_dev = len(served) // n_dev
    order = np.asarray(ops.remote_first_order(n_dev, my_dev, tiles_per_dev))
    check(np.array_equal(served, order // tiles_per_dev),
          "gemv_tiles owner schedule != remote_first_order")
    say("kernels", f"gemv_tiles {M}x{K} bf16 n_dev={n_dev}: rel err {err}, "
        f"{len(served)} tiles in remote-first owner order; compile {dt} s")

    c, dt = compiled("gemv", ops.gemv, a, x)
    err = rel_err(c(a, x), y_ref)
    check(err < 2e-2, f"gemv rel err {err}")
    say("kernels", f"gemv {M}x{K} bf16: rel err {err}; compile {dt} s")

    # decode attention and rmsnorm at gemma3-1b widths
    H, KV, D = gemma.n_heads, gemma.n_kv_heads, gemma.head_dim
    q = jax.random.normal(keys[2], (B, H, D), jnp.bfloat16)
    k = jax.random.normal(keys[3], (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(keys[4], (B, S, KV, D), jnp.bfloat16)
    length = S - 7
    c, dt = compiled("decode_attention", ops.decode_attention, q, k, v,
                     jnp.int32(length))
    err = rel_err(c(q, k, v, jnp.int32(length)),
                  ref.decode_attention_ref(q, k, v, length))
    check(err < 3e-2, f"decode_attention rel err {err}")
    say("kernels", f"decode_attention B={B} H={H} KV={KV} D={D} S={S} bf16: "
        f"rel err {err}; compile {dt} s")

    xs = jax.random.normal(keys[5], (rows, gemma.d_model), jnp.bfloat16)
    g = jax.random.normal(keys[6], (gemma.d_model,), jnp.float32) * 0.2
    c, dt = compiled("rmsnorm", ops.rmsnorm, xs, g)
    err = rel_err(c(xs, g), ref.rmsnorm_ref(xs, g))
    check(err < 2e-2, f"rmsnorm rel err {err}")
    say("kernels", f"rmsnorm {rows}x{gemma.d_model} bf16: rel err {err}; "
        f"compile {dt} s")


def phase_train(full=None) -> None:
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLMDataset, prefetch
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.optim import AdamWConfig
    from repro.training import TrainConfig, Trainer

    full = full or get_config("gemma3-1b")
    cfg = full.with_(n_layers=TRAIN_LAYERS)
    say("train", f"cut: n_layers {full.n_layers} -> {cfg.n_layers}, batch "
        f"{TRAIN_BATCH} x seq {TRAIN_SEQ}; widths kept: d_model "
        f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    model = Model(cfg)
    # as repro.launch.train builds it (its defaults: lr 3e-3, 1x1 mesh)
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = TrainConfig(optim=AdamWConfig(
        lr=3e-3, warmup_steps=max(TRAIN_STEPS // 20, 5),
        total_steps=TRAIN_STEPS))
    trainer = Trainer(model, mesh, tcfg)
    trainer.init_state(jax.random.PRNGKey(SEED))
    data = SyntheticLMDataset(DataConfig(
        vocab=cfg.vocab, seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH,
        seed=SEED))

    tok = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
    t0 = time.perf_counter()
    mem = trainer.step_fn.lower(
        trainer.params, trainer.opt_state, tok, tok).compile().memory_analysis()
    compile_s = time.perf_counter() - t0
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    check(limit is None or need < limit,
          f"train step needs {need} B, device holds {limit} B")
    say("train", f"{model.n_params()} params; step memory {need / 2**30} GiB "
        f"of {limit / 2**30 if limit else 'unknown'} GiB; compile {compile_s} s")

    t0 = time.perf_counter()
    hist = trainer.run(prefetch(iter(data)), TRAIN_STEPS, log_every=0)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} steps ran")
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    say("train", f"{TRAIN_STEPS} steps, loss {losses[0]} -> {losses[-1]} "
        f"(all: {losses}); host wall {wall} s, first step "
        f"{hist[0]['dt']} s, last {hist[-1]['dt']} s")


def phase_chips4(n: int = 4, B: int = 8, K: int = 8192,
                 N: int = 8192) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import EngineKind, Eidola, SimConfig
    from repro.core.hlo_analyzer import analyze_hlo
    from repro.core.hlo_capture import parse_collectives, schedule_to_trace
    from repro.core.topology import Topology
    from repro.distributed.collectives import (
        fused_gemv_allreduce, psum_matmul)
    from repro.launch.mesh import make_mesh

    devs = jax.devices()[:n]
    mesh = make_mesh((n,), ("model",), devices=devs)
    k1, k2 = jax.random.split(jax.random.PRNGKey(SEED))
    # bf16-exact values: the TPU's default f32 matmul pass is then exact
    # per product, so the three results differ only in f32 summation order
    x_h = np.asarray(jax.random.normal(k1, (B, K), jnp.bfloat16), np.float32)
    w_h = np.asarray(jax.random.normal(k2, (K, N), jnp.bfloat16), np.float32)
    x = jax.device_put(x_h, NamedSharding(mesh, P(None, "model")))
    w = jax.device_put(w_h, NamedSharding(mesh, P("model", None)))
    for name, arr, dim in (("x", x, 1), ("w", w, 0)):
        shard_devs = [s.device for s in arr.addressable_shards]
        check(len(set(shard_devs)) == n, f"{name} shards on {shard_devs}")
        for s in arr.addressable_shards:
            lo = s.index[dim].start
            check(s.data.shape[dim] == K // n and s.device in devs,
                  f"{name} shard {s.index} on {s.device}")
            check(lo is not None and lo % (K // n) == 0,
                  f"{name} shard index {s.index}")

    fused = jax.jit(fused_gemv_allreduce(mesh))
    t0 = time.perf_counter()
    comp = fused.lower(x, w).compile()
    compile_s = time.perf_counter() - t0
    y = comp(x, w)
    y_psum = jax.jit(psum_matmul(mesh))(x, w)
    check(len(y.sharding.device_set) == n,
          f"result on {len(y.sharding.device_set)} devices")
    y_ref = x_h.astype(np.float64) @ w_h.astype(np.float64)
    e_ref, e_psum = rel_err(y, y_ref), rel_err(y, y_psum)
    check(e_ref < 1e-5 and e_psum < 1e-5,
          f"fused vs reference {e_ref}, vs psum {e_psum}")
    say("chips4", f"fused_gemv_allreduce x[{B},{K}] @ w[{K},{N}] f32 on "
        f"{n} chips ({', '.join(str(d.id) for d in devs)}): one shard per "
        f"chip, rel err vs one-device f64 reference {e_ref}, vs psum_matmul "
        f"{e_psum}; compile {compile_s} s")

    hlo = comp.as_text()
    ops_ = parse_collectives(hlo)
    static = sum(o.kind == "collective-permute" for o in ops_)
    by_kind = analyze_hlo(hlo).collectives_by_kind()
    runs = by_kind.get("collective-permute", (0, 0))[0]
    async_form = "collective-permute-start" in hlo
    say("chips4", f"HLO: parse_collectives lists {static} collective-permute "
        f"op(s) ({[(o.kind, o.group_size) for o in ops_]}); trip-count-aware "
        f"analyze_hlo counts {runs} executions (n-1 = {n - 1}); async "
        f"-start/-done form: {async_form}")
    check(runs == n - 1, f"collective-permute executions {runs} != {n - 1}")

    trace = schedule_to_trace(ops_, Topology((n,), ("model",)),
                              compute_gap_ns=2000.0)
    r = Eidola(SimConfig(engine=EngineKind.EVENT), trace).run()
    check(len(trace) > 0 and r.flag_reads > 0 and r.kernel_span_ns > 0,
          f"replay: {len(trace)} writes, flag_reads {r.flag_reads}")
    say("chips4", f"Eidola replay of the captured schedule: {len(trace)} "
        f"registered writes, flag_reads {r.flag_reads}, kernel span "
        f"{r.kernel_span_ns} ns (simulated)")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip collective phase")
    args = ap.parse_args()

    cache_dir = use_checkout_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_chips4()
    else:
        phase_simulate()
        phase_lane()
        phase_kernels()
        phase_train()
    print(f"[done] host wall {time.perf_counter() - t0} s; compile cache "
          f"{cache_dir}: {cache['hits']} hits, {cache['misses']} misses",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
