"""Compiled-HLO capture: the bridge from the JAX framework to Eidola.

The paper's workflow (Fig. 4) starts from *profiles of real applications*.
Our framework's analogue of a profile is the compiled artifact of the
multi-pod dry-run: the post-SPMD HLO text contains every collective the step
will execute, with exact per-device operand shapes.  This module parses those
collectives, computes the roofline collective bytes, and lowers the schedule
into an Eidola :class:`TraceBundle` — each collective's ring steps become
timestamped semaphore (flag) writes that eidolon peers replay, exactly like
the paper's ``register_write`` setup kernel.

Parsing is deliberately tolerant: it supports post-SPMD HLO text (what
``compiled.as_text()`` emits, e.g. ``%all-reduce.2 = f32[8,128]{1,0}
all-reduce(%dot), replica_groups=[2,4]<=[8]``), including async
``-start/-done`` forms, and StableHLO MLIR from ``lowered.as_text()``.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .events import TraceBundle
from .memory import AddressMap
from .topology import Topology

__all__ = [
    "CollectiveOp",
    "parse_collectives",
    "collective_bytes",
    "schedule_to_trace",
    "summarize",
]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  f32[8,128]{1,0}   bf16[]   s32[4]{0}
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
# e.g.  replica_groups=[2,4]<=[8]   replica_groups={{0,1},{2,3}}
_IOTA_RG_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_BRACE_RG_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
# collective-permute names its peers instead of groups:
#   source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
# HLO op line:  %name = TYPE kind(...)  or  %name = (T1, T2) kind-start(...)
# TYPE may carry TPU layouts, e.g. f32[8,2048]{1,0:T(8,128)S(1)}
_HLO_OP_RE = re.compile(
    r"=\s+(\(?[^=]+?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
# StableHLO MLIR:  stablehlo.all_reduce ... : tensor<16x64xbf16>
_MLIR_OP_RE = re.compile(
    r"(?:stablehlo|mhlo)\.(all_reduce|all_gather|reduce_scatter|all_to_all|"
    r"collective_permute)"
)
_MLIR_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z]+[0-9]*)>")


@dataclass(frozen=True)
class CollectiveOp:
    kind: str                 # one of _KINDS
    result_bytes: int         # per-device result size
    operand_bytes: int        # per-device operand size (roofline numerator)
    group_size: int           # participants per replica group (1 if unknown)
    dtype: str = ""
    line: str = ""

    @property
    def is_cross_device(self) -> bool:
        return self.group_size != 1


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * nb


def _first_tensor_bytes(type_str: str) -> Tuple[int, str]:
    """Bytes of the first (largest, for tuples) tensor in an HLO type string."""
    best, dt = 0, ""
    for m in _SHAPE_RE.finditer(type_str):
        b = _shape_bytes(m.group(1), m.group(2))
        if b > best:
            best, dt = b, m.group(1)
    return best, dt


def parse_collectives(text: str) -> List[CollectiveOp]:
    """Extract collective ops (with per-device sizes) from HLO/StableHLO text."""
    ops: List[CollectiveOp] = []
    for raw in text.splitlines():
        line = raw.strip()
        m = _HLO_OP_RE.search(line)
        if m:
            kind = m.group(2)
            is_start = bool(m.group(3))
            if line.find(f"{kind}-done") != -1 and not is_start:
                continue  # -done carries no new traffic
            rbytes, dtype = _first_tensor_bytes(m.group(1))
            gsize = group_size(line)
            ops.append(
                CollectiveOp(
                    kind=kind,
                    result_bytes=rbytes,
                    operand_bytes=_operand_bytes(kind, rbytes, gsize),
                    group_size=gsize,
                    dtype=dtype,
                    line=line[:240],
                )
            )
            continue
        m = _MLIR_OP_RE.search(line)
        if m:
            kind = m.group(1).replace("_", "-")
            tensors = _MLIR_TENSOR_RE.findall(line)
            rbytes, dtype = 0, ""
            if tensors:
                dims, dt = tensors[-1]
                n = 1
                for d in dims.split("x"):
                    if d:
                        n *= int(d)
                rbytes = n * _DTYPE_BYTES.get(dt, 0)
                dtype = dt
            ops.append(
                CollectiveOp(
                    kind=kind,
                    result_bytes=rbytes,
                    operand_bytes=rbytes,
                    group_size=0,  # unknown at StableHLO level
                    dtype=dtype,
                    line=line[:240],
                )
            )
    return ops


def group_size(line: str) -> int:
    """Participants per replica group of one HLO collective (1 if unknown);
    for collective-permute, the devices its source/target pairs touch."""
    gm = _IOTA_RG_RE.search(line)
    if gm:
        return int(gm.group(2))
    bm = _BRACE_RG_RE.search(line)
    if bm:
        return len([x for x in bm.group(1).split(",") if x.strip()])
    pm = _PAIRS_RE.search(line)
    if pm:
        return len(set(re.findall(r"\d+", pm.group(1))))
    return 1


def _operand_bytes(kind: str, result_bytes: int, group_size: int) -> int:
    """Per-device operand size implied by the result size."""
    g = max(1, group_size)
    if kind == "all-gather":
        return result_bytes // g
    if kind == "reduce-scatter":
        return result_bytes * g
    return result_bytes


def collective_bytes(ops: Sequence[CollectiveOp]) -> int:
    """Roofline numerator: sum of per-device operand sizes of cross-device
    collectives (group_size 1 ops move no bytes)."""
    return sum(o.operand_bytes for o in ops if o.group_size != 1)


def by_kind(ops: Sequence[CollectiveOp]) -> Dict[str, Tuple[int, int]]:
    out: Dict[str, Tuple[int, int]] = {}
    for o in ops:
        c, b = out.get(o.kind, (0, 0))
        out[o.kind] = (c + 1, b + o.operand_bytes)
    return out


def summarize(ops: Sequence[CollectiveOp]) -> str:
    rows = [f"{k}: n={c} bytes={b:,}" for k, (c, b) in sorted(by_kind(ops).items())]
    rows.append(f"TOTAL collective bytes (operand sum): {collective_bytes(ops):,}")
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# schedule -> Eidola trace
# ---------------------------------------------------------------------------


def schedule_to_trace(
    ops: Sequence[CollectiveOp],
    topo: Topology,
    *,
    axis_for_group: Optional[Dict[int, str]] = None,
    compute_gap_ns: float = 0.0,
    n_egpu_peers: int = 3,
) -> TraceBundle:
    """Lower a collective schedule into eidolon semaphore-write traces.

    Each collective contributes its ring-step completion times; step ``i``'s
    completion is one 8-byte flag write from peer ``1 + i % n_egpu_peers``.
    ``compute_gap_ns`` inserts the compute time between consecutive
    collectives (from cost_analysis FLOPs / peak, supplied by the caller).
    The result replays at cycle fidelity in the standard Eidola engines,
    closing the loop between the production framework and the simulator.
    """
    amap = AddressMap(n_devices=n_egpu_peers + 1)
    bundle = TraceBundle(
        meta={
            "pattern": "hlo_capture",
            "n_collectives": len(ops),
            "topology": topo.describe(),
        }
    )
    t_ns = 0.0
    axis_for_group = axis_for_group or {}
    default_axis = topo.axis_names[-1]
    for i, op in enumerate(ops):
        if op.group_size == 1:
            continue
        axis = axis_for_group.get(op.group_size, default_axis)
        # fall back to the axis whose size matches the replica group
        for name, size in zip(topo.axis_names, topo.axis_sizes):
            if size == op.group_size:
                axis = name
                break
        cost = topo.collective(op.kind, op.operand_bytes, axis)
        t_ns += compute_gap_ns
        for j, arr_s in enumerate(cost.arrival_times_s(t_ns * 1e-9)):
            src = 1 + (j % n_egpu_peers)
            bundle.add(
                wakeup_ns=arr_s * 1e9,
                addr=amap.partial_base + 64 * ((i * 64 + j) % 65536),
                data=j,
                size=8,
                src=src,
            )
        t_ns = cost.arrival_times_s(t_ns * 1e-9)[-1] * 1e9
        # final completion: the collective's semaphore flag
        bundle.add(
            wakeup_ns=t_ns,
            addr=amap.flag_addr(1 + (i % n_egpu_peers)),
            data=1,
            size=8,
            src=1 + (i % n_egpu_peers),
        )
    # end-of-step barrier: every peer signals its flag so any waiting
    # workload (the GEMV+AllReduce wait loop included) can terminate
    for g in range(1, n_egpu_peers + 1):
        bundle.add(
            wakeup_ns=t_ns, addr=amap.flag_addr(g), data=1, size=8, src=g
        )
    return bundle
