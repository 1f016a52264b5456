"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus a pass/fail comparison
against the paper's claims, and saves the full results to
``results/benchmarks.json``.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip slow sweeps")
    ap.add_argument("--out", default="results/benchmarks.json")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks import kernels_bench, paper_figs

    results = {}
    csv_rows = ["name,us_per_call,derived"]

    def record(name, fn):
        t0 = time.perf_counter()
        out = fn()
        dt = (time.perf_counter() - t0) * 1e6
        results[name] = out
        passes = {k: v for k, v in out.items() if k.startswith("pass")}
        derived = ";".join(f"{k}={v}" for k, v in passes.items())
        csv_rows.append(f"{name},{dt:.0f},{derived}")
        status = "PASS" if all(passes.values()) else "FAIL"
        print(f"[bench] {name:24s} {status}  {derived}")
        if "paper_claim" in out:
            print(f"        paper: {out['paper_claim']}")
        return out

    print("=" * 72)
    print("Eidola paper-figure reproductions")
    print("=" * 72)
    f6 = record("fig6_wakeup_sweep", paper_figs.fig6_wakeup_sweep)
    print(f"        slope={f6['slope_per_us']:.0f} reads/us r2={f6['r2']:.6f} "
          f"nonflag={f6['nonflag_reads']:,}")
    f9 = record("fig9_syncmon", paper_figs.fig9_syncmon)
    print(f"        band=[{f9['min_reads']}, {f9['max_reads']}] "
          f"(paper: [728, 788]) nonflag={f9['nonflag_reads']:,}")
    if not args.quick:
        f10 = record("fig10_scaling_m", paper_figs.fig10_scaling_m)
        print(f"        r2={f10['r2']:.3f} over M=256..4096")
        f11 = record("fig11_scaling_egpus", paper_figs.fig11_scaling_egpus)
        print(
            f"        normalized t(255 eGPUs)={f11['normalized_at_max']:.1f}x "
            f"(paper: 7.3x-35.9x; linear would be 256x)"
        )
        f11m = record(
            "fig11_scaling_egpus_mwait",
            lambda: paper_figs.fig11_scaling_egpus(syncmon=True),
        )
        print(f"        mwait-instrumented: {f11m['normalized_at_max']:.1f}x")
    f12 = record("fig12_variability", paper_figs.fig12_variability)
    print(f"        wait inflation {f12['wait_inflation']:.1f}x; "
          f"kernel {f12['ideal_kernel_ns']:.0f} -> "
          f"{f12['contended_kernel_ns']:.0f} ns")
    print(f12["ascii_contended"])
    eng = record("engine_comparison", paper_figs.engine_comparison)
    print(
        f"        event {eng['speedup_event_vs_cycle']:.1f}x / vector "
        f"{eng['speedup_vector_vs_cycle']:.1f}x vs per-cycle polling"
    )

    print("-" * 72)
    print("Pallas kernel correctness vs. ref.py (interpret mode; no timings)")
    for name, out in kernels_bench.all_benches().items():
        results[f"kernel_{name}"] = out
        print(f"[bench] kernel_{name:17s} "
              f"{'PASS' if out['pass'] else 'FAIL'} rows={len(out['rows'])}")
        csv_rows.append(f"kernel_{name},0,pass={out['pass']}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("-" * 72)
    print("\n".join(csv_rows))
    failures = [
        n for n, out in results.items()
        if not all(v for k, v in out.items() if k.startswith("pass"))
    ]
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print(f"all benchmarks pass; results -> {args.out}")


if __name__ == "__main__":
    main()
