import os
# A CPU placeholder tool: 512 virtual host devices stand in for the pod, on
# every machine (a TPU host would otherwise hand jax its real chips).  MUST
# precede every other import (jax locks platform and device count at init).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Runs on 512 virtual CPU devices, never on an accelerator: what it gives is
the partitioned program (bytes, FLOPs, collectives), not a device time.

For each cell this produces, with ZERO device allocation:
  - ``compiled.memory_analysis()``  -> bytes/device (does it fit HBM?)
  - ``compiled.cost_analysis()``    -> per-device HLO FLOPs + bytes accessed
  - parsed collective schedule      -> per-device collective bytes by kind
and writes one JSON record per cell to ``results/dryrun/``.  EXPERIMENTS.md
§Dry-run/§Roofline and the Eidola pod-scale replay all read these records.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--out DIR]
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import META, REGISTRY, SHAPES, get_config
from repro.configs.shapes import cells_for
from repro.core.hlo_analyzer import analyze_hlo
from repro.distributed import DEFAULT_RULES
from repro.launch.mesh import make_mesh_by_name
from repro.launch.specs import batch_shardings, cache_shardings, input_specs
from repro.models import Model
from repro.optim import AdamWConfig
from repro.training import TrainConfig, build_train_step

DEFAULT_OUT = "results/dryrun"


# ---------------------------------------------------------------------------
# step builders per mode
# ---------------------------------------------------------------------------


def _lower_train(model: Model, mesh, shape, opts) -> Any:
    tcfg = TrainConfig(
        remat_policy=opts.get("remat", "none"),
        optim=AdamWConfig(master_fp32=not opts.get("no_master", False)),
        microbatches=opts.get("microbatches", 1),
        zero1_model_dim=opts.get("zero1_model_dim",
                                 model.n_params() > 100e9),
        donate_state=True,
    )
    step_fn, shardings, fallbacks = build_train_step(model, mesh, tcfg)
    ins = input_specs(model, shape)
    from repro.optim import adamw_init

    abstract_params = model.abstract_params()
    abstract_state = jax.eval_shape(lambda p: adamw_init(p, tcfg.optim), abstract_params)
    args = [abstract_params, abstract_state, ins["tokens"], ins["labels"]]
    if "embeds" in ins:
        args.append(ins["embeds"])
    with mesh:
        lowered = step_fn.lower(*args)
    return lowered, fallbacks


def _param_shardings(model: Model, mesh):
    from repro.distributed import param_shardings

    return param_shardings(
        model.param_axes(), model.abstract_params(), mesh, DEFAULT_RULES
    )


def _lower_prefill(model: Model, mesh, shape, opts):
    p_shard, fallbacks = _param_shardings(model, mesh)
    b_shard = batch_shardings(mesh)
    ins = input_specs(model, shape)
    kwargs = {}
    if "embeds" in ins:
        fn = lambda p, e: model.prefill(p, None, embeds=e)  # noqa: E731
        in_sh = (p_shard, b_shard)
        args = (model.abstract_params(), ins["embeds"])
    else:
        fn = lambda p, t: model.prefill(p, t)  # noqa: E731
        in_sh = (p_shard, b_shard)
        args = (model.abstract_params(), ins["tokens"])
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
    return lowered, fallbacks


def _lower_decode(model: Model, mesh, shape, opts):
    p_shard, fallbacks = _param_shardings(model, mesh)
    ins = input_specs(model, shape)
    B, S = shape.global_batch, shape.seq_len
    c_shard = cache_shardings(model, mesh, B, S)
    tok_shard = batch_shardings(mesh) if B % mesh.shape.get("data", 1) == 0 and B > 1 else None
    if "embeds" in ins:
        fn = lambda p, c, t, pos, e: model.decode_step(  # noqa: E731
            p, c, t, pos, embeds=e
        )
        in_sh = (p_shard, c_shard, tok_shard, None, None)
        args = (model.abstract_params(), ins["caches"], ins["tokens"], ins["pos"],
                ins["embeds"])
    else:
        fn = lambda p, c, t, pos: model.decode_step(p, c, t, pos)  # noqa: E731
        in_sh = (p_shard, c_shard, tok_shard, None)
        args = (model.abstract_params(), ins["caches"], ins["tokens"], ins["pos"])
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=None).lower(*args)
    return lowered, fallbacks


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(
    arch: str,
    shape_name: str,
    mesh_name: str,
    opts: Optional[Dict[str, Any]] = None,
    *,
    verbose: bool = True,
) -> Dict[str, Any]:
    opts = opts or {}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mode": shape.mode,
        "options": opts,
        "meta": META.get(arch, {}),
        "status": "ok",
    }
    if shape_name == "long_500k" and not cfg.supports_500k:
        rec["status"] = "skipped"
        rec["skip_reason"] = (
            "pure full-attention arch; long_500k skipped per assignment"
        )
        return rec
    if opts.get("attn_constraints"):
        cfg = cfg.with_(attn_sharding_constraints=True)
    if opts.get("mla_absorbed"):
        cfg = cfg.with_(mla_absorbed_decode=True)
    mesh = make_mesh_by_name(mesh_name)
    model = Model(cfg, mesh=mesh)
    rec["n_params"] = model.n_params()
    rec["n_active_params"] = model.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    if shape.mode == "train":
        rec["model_flops"] = 6.0 * model.n_active_params() * tokens
    else:
        rec["model_flops"] = 2.0 * model.n_active_params() * tokens
    try:
        t0 = time.perf_counter()
        if shape.mode == "train":
            lowered, fallbacks = _lower_train(model, mesh, shape, opts)
        elif shape.mode == "prefill":
            lowered, fallbacks = _lower_prefill(model, mesh, shape, opts)
        else:
            lowered, fallbacks = _lower_decode(model, mesh, shape, opts)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        ca0 = ca[0] if isinstance(ca, list) else ca
        hlo = compiled.as_text()
        # trip-count-aware analysis (XLA's cost_analysis counts while bodies
        # once; see core/hlo_analyzer.py) — the primary §Roofline source
        mod = analyze_hlo(hlo)
        colls = mod.collectives_by_kind()
        rec.update(
            {
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "fallbacks": fallbacks,
                "flops_per_device": float(mod.total_flops()),
                "dot_flops_per_device": float(mod.dot_flops()),
                "bytes_per_device": float(mod.total_bytes()),
                "xla_flops_raw": float(ca0.get("flops", 0.0)),
                "xla_bytes_raw": float(ca0.get("bytes accessed", 0.0)),
                "max_scan_trip": mod.max_while_trip(),
                "memory": {
                    "argument_bytes": mem.argument_size_in_bytes,
                    "output_bytes": mem.output_size_in_bytes,
                    "temp_bytes": mem.temp_size_in_bytes,
                    "alias_bytes": mem.alias_size_in_bytes,
                    "code_bytes": mem.generated_code_size_in_bytes,
                },
                "collectives": {
                    k: {"count": c, "bytes": b} for k, (c, b) in colls.items()
                },
                "collective_bytes_per_device": float(mod.collective_bytes()),
                "n_collective_ops": int(sum(c for c, _ in colls.values())),
            }
        )
        # live bytes per device (arguments alias in-place via donation)
        rec["hbm_bytes_per_device"] = (
            mem.argument_size_in_bytes
            + mem.temp_size_in_bytes
            + max(mem.output_size_in_bytes - mem.alias_size_in_bytes, 0)
        )
        if verbose:
            print(
                f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
                f"compile={t_compile:.1f}s "
                f"flops/dev={rec['flops_per_device']:.3e} "
                f"coll_bytes/dev={rec['collective_bytes_per_device']:,} "
                f"hbm/dev={rec['hbm_bytes_per_device'] / 2**30:.2f} GiB"
            )
    except Exception as e:  # noqa: BLE001 - recorded, rerun fails loudly
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: ERROR {e}")
    return rec


def cell_path(out_dir: str, arch: str, shape: str, mesh: str, tag: str = "") -> str:
    suffix = f"__{tag}" if tag else ""
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(REGISTRY), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", default="both", help="single|multi|both|AxB[xC]")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--tag", default="", help="variant tag for perf iterations")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--attn-constraints", action="store_true")
    ap.add_argument("--mla-absorbed", action="store_true")
    ap.add_argument("--no-master", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    opts = {"remat": args.remat, "microbatches": args.microbatches,
            "attn_constraints": args.attn_constraints,
            "mla_absorbed": args.mla_absorbed,
            "no_master": args.no_master}
    # note: `v not in (1, False)` would drop True since True == 1 in Python
    opts = {
        k: v for k, v in opts.items()
        if not (v is False or v == "none" or (k == "microbatches" and v == 1))
    }

    if args.all:
        cells = []
        for arch in REGISTRY:
            if META.get(arch, {}).get("tier") == "variant":
                continue  # beyond-pool variants run individually, not in --all
            for shape_name, skip in cells_for(get_config(arch)):
                cells.append((arch, shape_name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for mesh_name in meshes:
        for arch, shape_name in cells:
            path = cell_path(args.out, arch, shape_name, mesh_name, args.tag)
            if args.skip_existing and os.path.exists(path):
                continue
            rec = run_cell(arch, shape_name, mesh_name, opts)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            n_ok += rec["status"] == "ok"
            n_skip += rec["status"] == "skipped"
            n_err += rec["status"] == "error"
    print(f"[dryrun] done: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
