#!/usr/bin/env python3
"""Where the program's SyncMon path parts from the plain reference.

    python3 bench/tools/syncmon_witness.py --seeds 5,6 --calls 300
    python3 bench/tools/syncmon_witness.py \
        --delays 9017.413455468839,9024.103996625785,9043.69562914493

The first form draws ``--calls`` calls of the ``fig6_sweep`` mix per seed
on the ``eidola_table1`` configuration, runs each under SyncMon on the
program's timed path (the event engine and its interpreter) and on its
closed-form engine (``engine="vector"``), works each out on the plain
reference (``bench/refs/gemv_allreduce.py``), and prints per seed how many
calls each engine answers differently from the reference, and on which
fields.  The second form does the same for one call with the given
per-peer flag delays and prints each side's differing fields.  Runs on the
CPU; needs no chip.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import answers  # noqa: E402
from benchlib.harness import call_simulate  # noqa: E402
from benchlib.spec import BENCH, load_reference  # noqa: E402
from benchlib.traffic import CallStream  # noqa: E402

ENGINES = ("event", "vector")


def sides(call):
    """The reference's answer and each engine's, under SyncMon."""
    from repro.core import simulate

    call = {**call, "sim_config": {**call["sim_config"], "sync": "syncmon"}}
    want = load_reference(call["scenario"]).answer(call)
    got = {}
    for engine in ENGINES:
        c = {**call, "sim_config": {**call["sim_config"], "engine": engine}}
        got[engine] = answers.answer_of(call_simulate(simulate, c))
    return want, got


def differing(got, want):
    return {"/".join(map(str, k)): [got.get(k), want.get(k)]
            for k in sorted(want.keys() | got.keys(), key=str)
            if got.get(k) != want.get(k)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", default="5,6")
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--delays", default=None)
    args = ap.parse_args(argv)
    config = json.loads((BENCH / "configs" / "eidola_table1.json").read_text())
    mix = json.loads((BENCH / "mixes" / "fig6_sweep.json").read_text())
    if args.delays:
        call = next(CallStream(config, mix, 0))
        call["params"] = {"flag_delays_ns":
                          [float(x) for x in args.delays.split(",")]}
        want, got = sides(call)
        print(json.dumps({"delays_ns": call["params"]["flag_delays_ns"],
                          **{e: differing(got[e], want) for e in ENGINES}}))
        return 0
    for seed in args.seeds.split(","):
        fields = {e: Counter() for e in ENGINES}
        calls = Counter()
        for call in itertools.islice(CallStream(config, mix, int(seed)),
                                     args.calls):
            want, got = sides(call)
            for e in ENGINES:
                diff = differing(got[e], want)
                calls[e] += bool(diff)
                fields[e].update(diff.keys())
        print(json.dumps({"seed": int(seed), "calls": args.calls,
                          "calls_differing": dict(calls),
                          "fields_differing": {e: dict(c)
                                               for e, c in fields.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
