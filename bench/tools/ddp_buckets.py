#!/usr/bin/env python3
"""Compute the gradient buckets of one data-parallel training step of
Zamba2-2.7B and write them into the DDP mixes.

    python3 bench/tools/ddp_buckets.py            # rewrite the mix files
    python3 bench/tools/ddp_buckets.py --check    # exit 1 if they differ

The rule is PyTorch DistributedDataParallel's bucket assignment
(``torch.distributed`` ``_compute_bucket_assignment_by_size``, as DDP calls
it at construction): gradients in float32; parameters taken in the reverse
of their registration order; a bucket closes as soon as the bytes in it
reach its limit, the tensor that crossed the limit included; the first
bucket's limit is 1 MiB (``_DEFAULT_FIRST_BUCKET_BYTES``) and every later
one's is ``bucket_cap_mb`` = 25 MiB.  A tensor above the cap that arrives at
an empty bucket therefore fills it alone.

Registration order is the order of the repository model's parameter tree
(``Model.param_specs()``): the embedding, the final norm, the Mamba2 stages
in plan order, the shared attention block, the output head; within a
block, the keys in the order the tree holds them.  A stage that the model
scans over layers is unstacked into one tensor per layer, layer by layer,
as ``nn.ModuleList`` registers them.  The shapes come from the repository's
``configs/zamba2_2_7b.py``; the list is written into the mix files as data,
so later changes to the model code cannot move the benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

ROOT = Path(__file__).resolve().parents[2]
MIXES = ("ddp_buckets.ring", "ddp_buckets.hier")
FIRST_BUCKET_BYTES = 1 << 20
BUCKET_CAP_BYTES = 25 << 20
GRAD_BYTES = 4  # float32 gradients


def registered_tensors() -> List[Tuple[str, int]]:
    """``(name, elements)`` of every parameter tensor, in registration
    order, scanned layers unstacked."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models.common import ParamSpec
    from repro.models.model import Model

    model = Model(get_config("zamba2-2.7b"))

    def walk(tree, path: str, stacked: bool) -> Iterator[Tuple[str, Tuple]]:
        if isinstance(tree, ParamSpec):
            yield path, tree.shape
        elif isinstance(tree, dict):
            for k, v in tree.items():
                yield from walk(v, f"{path}.{k}" if path else k, stacked)
        else:
            for i, v in enumerate(tree):
                yield from walk(v, f"{path}.{i}", stacked)

    out: List[Tuple[str, int]] = []
    specs = model.param_specs()
    for key, tree in specs.items():
        if key != "stages":
            out += [(p, math.prod(s)) for p, s in walk(tree, key, False)]
            continue
        for i, (stage, st_tree) in enumerate(zip(model.plan, tree)):
            leaves = list(walk(st_tree, f"stages.{i}", True))
            if stage.kind != "scan":
                out += [(p, math.prod(s)) for p, s in leaves]
                continue
            for layer in range(stage.n):
                out += [(f"{p}[{layer}]", math.prod(s[1:])) for p, s in leaves]
    return out


def buckets(tensors: List[Tuple[str, int]]) -> List[int]:
    """Bytes of each bucket, in the order DDP launches their all-reduces."""
    out: List[int] = []
    limit, size = FIRST_BUCKET_BYTES, 0
    for _, elems in reversed(tensors):
        size += elems * GRAD_BYTES
        if size >= limit:
            out.append(size)
            size, limit = 0, BUCKET_CAP_BYTES
    if size:
        out.append(size)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    tensors = registered_tensors()
    sizes = buckets(tensors)
    params = sum(e for _, e in tensors)
    print(f"{len(tensors)} tensors, {params} parameters, {len(sizes)} "
          f"buckets, {sum(sizes)} bytes", file=sys.stderr)
    stale = []
    for name in MIXES:
        path = ROOT / "bench" / "mixes" / f"{name}.json"
        mix: Dict = json.loads(path.read_text())
        if mix["walk"]["payload_bytes"] != sizes or mix["parameters"] != params:
            stale.append(name)
            mix["walk"]["payload_bytes"] = sizes
            mix["parameters"] = params
            if not args.check:
                path.write_text(json.dumps(mix, indent=1) + "\n")
    if args.check and stale:
        print(f"stale: {stale}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
