"""Tiered lockstep: group-uniform bulk solving over multi-tier fabrics.

The flat solver (:mod:`repro.core.lockstep`) requires one globally
rank-uniform program on the single-tier ring.  This module generalizes both
axes at once:

* **groups** — ranks partition by ``SymbolicProgram.group`` (leaders vs.
  workers in ``hierarchical_allreduce``, the single ``ring``/``all`` group of
  the uniform collectives).  Structural uniformity — segment kinds, loop
  bounds, phase names/durations/traffic, emit parameters — is required only
  *within* a group; rank-varying peers and flag addresses stay per-group
  vectors.  Cross-group dependencies (worker handoff -> leader barrier,
  leader broadcast -> worker wait) are stitched by a compile-time worklist
  that orders every group's stage instances so each wait follows the
  emission(s) that write its flags, and fails loudly (naming the blocked
  group, rank, phase, and flag) when no such order exists — which is exactly
  the pipelined cross-rank chain the timeline engine keeps handling.

* **multi-leg route families** — emissions are priced over the fabric's real
  leg sequences (intra-node ICI, DCI uplinks, fat-tree spine, rails) by a
  vectorized replica of the routing policy, spot-checked against
  ``fab.legs`` at compile time.  Two pricers cover every supported family:

  - *elementwise*: when no two messages of a stage share an egress port
    (ring steps, hierarchical stages on all presets), each leg is one
    ``max``/``add`` pass over per-port busy vectors — identical IEEE-754 ops
    to the event engine's sequential ``_leg`` calls, which factor into
    independent per-port chains because every port has a single producer
    rank whose issue cycles are monotone in program order.

  - *ordered*: when messages share ports (the all-to-all incast's single
    dispatch stage, the broadcast fan-out), messages are priced in the event
    engine's global order — ``(cycle, device, dst-run position)`` — by a
    port-wavefront: each sweep extends every port's priced prefix with the
    touches whose upstream legs resolved, using restart-segment ``cumsum``
    chains that reproduce the scalar ``start = max(ready, busy)``;
    ``busy = start + ser`` sequence bit-exactly.  The supported topologies
    route leg ``i`` classes strictly before leg ``i+1`` classes, so the
    sweep count is bounded by the leg depth, not the message count.

Divergences from the event engine match the flat solver's documented set
(no ``_mem``/``flag_set_cycle`` mirrors, aggregate float ``queued_ns`` in
stage order, ``wtt_head_polls`` 0); per-port busy chains, set cycles, and
every integer counter stay bit-identical.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .engine import EngineResult
from .scenario import (
    Affine,
    AffineRun,
    EmitOp,
    EmitRun,
    LoopEmit,
    LoopSpec,
    as_symbolic,
)
from .spans import count, span

__all__ = ["compile_tiered", "run_tiered"]

_SUPPORTED = {
    "ring": "_RingRouting",
    "two_tier": "_TwoTierRouting",
    "fat_tree": "_FatTreeRouting",
    "rail_optimized": "_RailRouting",
}


def _unsupported(msg):
    from .lockstep import UnsupportedProgram

    return UnsupportedProgram(msg)


def _uniform(values, what, ids=None):
    """First value, or raise naming the first divergent rank."""
    vals = list(values)
    first = vals[0]
    for i, v in enumerate(vals[1:], 1):
        if v != first:
            who = ids[i] if ids is not None else i
            who0 = ids[0] if ids is not None else 0
            raise _unsupported(
                f"{what} varies across ranks (rank {who} differs from "
                f"rank {who0})"
            )
    return first


# ---------------------------------------------------------------------------
# port space + vectorized routing replicas
# ---------------------------------------------------------------------------


class _Ports:
    """Dense integer port ids + per-port link-class tables for one fabric.

    Encodings (id -> tuple is materialized in ``tuples`` for write-back):

    * ici ``(dev, +-1)``   -> ``dev*2 + (0 if +1 else 1)``
    * two_tier ``("dci", node, +-1)`` -> ``2n + node*2 + (0 if +1 else 1)``
    * fat_tree ``("up", node)`` / ``("down", node)`` / ``("spine", leaf)``
    * rail ``("rail", node, r)``
    """

    def __init__(self, fab):
        spec = fab.spec
        self.kind = spec.name
        n = self.n = spec.n_devices
        self.dpn = spec.devices_per_node
        self.n_nodes = n // self.dpn
        self.params = dict(getattr(spec, "params", {}) or {})
        tuples: List[tuple] = []
        cls: List[str] = []
        for dev in range(n):
            tuples.append((dev, 1))
            tuples.append((dev, -1))
            cls.extend(("ici", "ici"))
        nn = self.n_nodes
        if self.kind == "two_tier":
            for node in range(nn):
                tuples.append(("dci", node, 1))
                tuples.append(("dci", node, -1))
                cls.extend(("dci", "dci"))
        elif self.kind == "fat_tree":
            self.npl = int(self.params["nodes_per_leaf"])
            self.n_leaves = int(self.params["n_leaves"])
            for node in range(nn):
                tuples.append(("up", node))
                cls.append("dci")
            for node in range(nn):
                tuples.append(("down", node))
                cls.append("dci")
            for leaf in range(self.n_leaves):
                tuples.append(("spine", leaf))
                cls.append("spine")
        elif self.kind == "rail_optimized":
            self.rails = int(spec.nics_per_node)
            for node in range(nn):
                for r in range(self.rails):
                    tuples.append(("rail", node, r))
                    cls.append("rail")
        self.tuples = tuples
        self.P = len(tuples)
        names = sorted(set(cls))
        self.cls_names = names
        cid = {c: i for i, c in enumerate(names)}
        self.port_cls = np.array([cid[c] for c in cls], np.int64)
        missing = [c for c in names if c not in fab._cls]
        if missing:
            raise _unsupported(
                f"fabric lacks link class(es) {missing} the solver prices"
            )
        self.cls_bw = np.array([fab._cls[c][0] for c in names])
        self.cls_lat = np.array([fab._cls[c][1] for c in names])

    # -- vectorized port encoders ---------------------------------------
    def ici(self, dev, d):
        return dev * 2 + (d != 1)

    def dci(self, node, nd):
        return 2 * self.n + node * 2 + (nd != 1)

    def up(self, node):
        return 2 * self.n + node

    def down(self, node):
        return 2 * self.n + self.n_nodes + node

    def spine(self, leaf):
        return 2 * self.n + 2 * self.n_nodes + leaf

    def rail(self, node, r):
        return 2 * self.n + node * self.rails + r


def _ring_vec(src, dst, n):
    """(hops, dir) arrays of the shortest ring path — ``_ring_route``."""
    fwd = (dst - src) % n
    bwd = (src - dst) % n
    take_fwd = fwd <= bwd
    hops = np.where(take_fwd, fwd, bwd)
    d = np.where(take_fwd, 1, -1)
    return hops, d


def _legs_csr(ports: _Ports, src, dst):
    """Vectorized leg expansion: CSR of (port, hops, cls) per message, legs
    in traversal order.  Replicates the routing policies of the supported
    presets; ``_spot_check`` verifies samples against the real ``fab.legs``.
    """
    n = ports.n
    dpn = ports.dpn
    m = len(src)
    # candidate leg sets in traversal order (append order IS the per-message
    # leg order: a message matches either the same-node set or the cross-node
    # sets, and the cross sets are appended rank-ascending)
    cand: List[tuple] = []  # (mask, port_all, hops_all, cls_id)
    cid = {c: i for i, c in enumerate(ports.cls_names)}
    ici_c = cid["ici"]

    def add_sel(mask, rank, port_all, hops_all, cls_id):
        """port/hops given over all m; select by mask (rank is implied by
        append order and kept only for readability at call sites)."""
        cand.append((mask, port_all, hops_all, cls_id))

    if ports.kind == "ring":
        hops, d = _ring_vec(src, dst, n)
        full = np.ones(m, bool)
        add_sel(full, 0, ports.ici(src, d), hops, ici_c)
    else:
        idt = src.dtype
        sn, sl = np.divmod(src, dpn)
        dn, dl = np.divmod(dst, dpn)
        same = sn == dn
        lhops, ld = _ring_vec(sl, dl, dpn)
        add_sel(same, 0, ports.ici(src, ld), lhops, ici_c)
        cross = ~same
        if ports.kind == "two_tier":
            dci_c = cid["dci"]
            h1, d1 = _ring_vec(sl, np.zeros(m, idt), dpn)
            add_sel(cross & (sl != 0), 0, ports.ici(src, d1), h1, ici_c)
            nhops, nd = _ring_vec(sn, dn, ports.n_nodes)
            add_sel(cross, 1, ports.dci(sn, nd), nhops, dci_c)
            gw = dn * dpn
            h3, d3 = _ring_vec(np.zeros(m, idt), dl, dpn)
            add_sel(cross & (dl != 0), 2, ports.ici(gw, d3), h3, ici_c)
        elif ports.kind == "fat_tree":
            dci_c = cid["dci"]
            spine_c = cid["spine"]
            npl = ports.npl
            s_leaf = sn // npl
            d_leaf = dn // npl
            h1, d1 = _ring_vec(sl, np.zeros(m, idt), dpn)
            add_sel(cross & (sl != 0), 0, ports.ici(src, d1), h1, ici_c)
            ones = np.ones(m, idt)
            add_sel(cross, 1, ports.up(sn), ones, dci_c)
            add_sel(
                cross & (s_leaf != d_leaf), 2, ports.spine(s_leaf),
                2 * ones, spine_c,
            )
            add_sel(cross, 3, ports.down(dn), ones, dci_c)
            gw = dn * dpn
            h5, d5 = _ring_vec(np.zeros(m, idt), dl, dpn)
            add_sel(cross & (dl != 0), 4, ports.ici(gw, d5), h5, ici_c)
        elif ports.kind == "rail_optimized":
            rail_c = cid["rail"]
            rails = ports.rails
            r = dl % rails
            h1, d1 = _ring_vec(sl, r, dpn)
            add_sel(cross & (sl != r), 0, ports.ici(src, d1), h1, ici_c)
            add_sel(
                cross, 1, ports.rail(sn, r), np.ones(m, idt), rail_c
            )
            nic = dn * dpn + r
            h3, d3 = _ring_vec(r, dl, dpn)
            add_sel(cross & (dl != r), 2, ports.ici(nic, d3), h3, ici_c)
        else:  # pragma: no cover - gated by _SUPPORTED
            raise _unsupported(f"unsupported fabric kind {ports.kind!r}")

    # direct CSR construction: leg (msg i, set r) lands at
    # offs[i] + (earlier sets present for i) — no sort over the leg table
    # int32 throughout: the leg table reaches ~66M rows at 4096 devices on
    # fat_tree, and every downstream pass (sorts, gathers, chains) is
    # memory-bandwidth bound; all values fit comfortably in 31 bits
    counts = np.zeros(m, np.int32)
    for mask, _p, _h, _c in cand:
        counts += mask
    offs = np.zeros(m + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    L = int(offs[m])
    msg = np.repeat(np.arange(m, dtype=np.int32), counts)
    port = np.empty(L, np.int32)
    hops = np.empty(L, np.int32)
    cls = np.empty(L, np.int32)
    prior = np.zeros(m, np.int32)
    for mask, port_all, hops_all, cls_id in cand:
        idx = np.flatnonzero(mask)
        if not idx.size:
            continue
        pos = offs[idx] + prior[idx]
        port[pos] = port_all[idx]
        hops[pos] = hops_all[idx]
        cls[pos] = cls_id
        prior += mask
    return {
        "msg": msg, "port": port, "hops": hops, "cls": cls, "offs": offs,
    }


def _spot_check(ports: _Ports, fab, src, dst, legs) -> None:
    """Verify sampled messages' replicated legs against ``fab.legs``."""
    m = len(src)
    if m == 0:
        return
    samples = sorted({0, m // 3, m // 2, (2 * m) // 3, m - 1})
    offs = legs["offs"]
    for i in samples:
        got = fab.legs(int(src[i]), int(dst[i]))
        lo, hi = int(offs[i]), int(offs[i + 1])
        if len(got) != hi - lo:
            raise _unsupported(
                "fabric routes diverge from the solver's replicated router"
            )
        for j, leg in enumerate(got):
            t = lo + j
            ok = (
                leg.cls == ports.cls_names[int(legs["cls"][t])]
                and leg.port == ports.tuples[int(legs["port"][t])]
                and leg.hops == int(legs["hops"][t])
            )
            if not ok:
                raise _unsupported(
                    "fabric routes diverge from the solver's replicated "
                    "router"
                )


# ---------------------------------------------------------------------------
# group-aligned program
# ---------------------------------------------------------------------------


class _GEmit:
    """One group's emission family at one aligned phase position.

    kind: "single" (one message per rank, k-invariant dst), "run" (a
    contiguous per-rank dst run sharing one flag address), or "fanout_all"
    (the all-peers incast, group == all ranks).
    """

    __slots__ = (
        "kind", "payload", "size", "dw", "dst", "addr_base", "addr_step",
        "cnt",
    )

    def __init__(self, kind, payload, size, dw, dst, addr_base, addr_step,
                 cnt=1):
        self.kind = kind
        self.payload = payload
        self.size = size
        self.dw = dw
        self.dst = dst              # int64[g] dst device (base for "run")
        self.addr_base = addr_base  # int64[g] flag addr at k=0
        self.addr_step = addr_step  # int, addr advance per k
        self.cnt = cnt              # messages per rank ("run")


class _GPhase:
    __slots__ = ("name", "is_wait", "dur", "tdelta", "wait", "emit")

    def __init__(self, name, is_wait, dur, tdelta, wait, emit):
        self.name = name
        self.is_wait = is_wait
        self.dur = dur
        self.tdelta = tdelta
        # wait: None | ("cols", [(base_vec, kstep), ...])
        #            | ("allpeers", alpha, beta)
        self.wait = wait
        self.emit = emit


class _GSeg:
    __slots__ = ("count", "k0", "body")

    def __init__(self, count, k0, body):
        self.count = count
        self.k0 = k0
        self.body = body


class _Group:
    __slots__ = ("name", "devs", "segs", "counts", "dispatch", "total",
                 "tdf")

    def __init__(self, name, devs):
        self.name = name
        self.devs = devs  # int64[g], ascending device ids
        self.segs: List[_GSeg] = []
        self.counts = None
        self.dispatch = None
        self.total = 0
        self.tdf = None


def _wait_cols(specs, devs, k0, count, gname, phname):
    """Classify one aligned wait position into ordered address columns.

    Each rank's ``wait_addrs`` entries normalize to (base, kstep) columns:
    ints and ``AffineRun`` members are k-invariant, an ``Affine`` advances
    by its step per loop iteration.  Column structure must match across the
    group; bases become per-rank vectors.
    """
    g = len(specs)
    per_rank: List[List[Tuple[int, int]]] = []
    for i, sp in enumerate(specs):
        cols: List[Tuple[int, int]] = []
        for e in sp.wait_addrs:
            if isinstance(e, AffineRun):
                for p in range(e.count):
                    cols.append((e.start + e.stride * p, 0))
            elif isinstance(e, Affine):
                if count > 1:
                    cols.append((e.base, e.step))
                else:
                    cols.append((e.at(k0), 0))
            elif isinstance(e, (int, np.integer)):
                cols.append((int(e), 0))
            else:
                raise _unsupported(
                    f"unsupported wait entry {type(e).__name__} in phase "
                    f"{phname!r} of group {gname!r}"
                )
        per_rank.append(cols)
    ncols = _uniform(
        (len(c) for c in per_rank), f"wait width of phase {phname!r}",
        ids=devs,
    )
    out = []
    for c in range(ncols):
        kstep = _uniform(
            (per_rank[i][c][1] for i in range(g)),
            f"wait address step of phase {phname!r}", ids=devs,
        )
        base = np.array([per_rank[i][c][0] for i in range(g)], np.int64)
        out.append((base, kstep))
    return ("cols", out)


def _try_allpeers_wait(specs, devs, k0, count, n):
    """("allpeers", alpha, beta) when the group is all ranks and the wait is
    the all-peers barrier; None otherwise."""
    if len(devs) != n or devs[0] != 0 or devs[-1] != n - 1:
        return None
    total = 0
    for e in specs[0].wait_addrs:
        total += e.count if isinstance(e, AffineRun) else 1
    if total != n - 1 or n - 1 <= 1:
        return None
    from .lockstep import UnsupportedProgram, _classify_wait

    try:
        w = _classify_wait(specs, k0, count, n)
    except UnsupportedProgram:
        return None
    return w if w[0] == "allpeers" else None


def _classify_emit_group(amap, specs, devs, k0, count, n, gname, phname):
    """None, or a :class:`_GEmit` for the aligned emission position."""
    if not specs[0].emits:
        for i, sp in enumerate(specs):
            if sp.emits:
                raise _unsupported(
                    f"emit presence of phase {phname!r} varies across ranks "
                    f"(rank {devs[i]} differs from rank {devs[0]})"
                )
        return None
    g = len(specs)
    blame = f"phase {phname!r} of group {gname!r}"
    all_single = all(
        len(sp.emits) == 1 and isinstance(sp.emits[0], (LoopEmit, EmitOp))
        for sp in specs
    )
    all_run = all(
        len(sp.emits) == 1 and isinstance(sp.emits[0], EmitRun)
        for sp in specs
    )
    if all_single:
        dst = np.empty(g, np.int64)
        slots: List[Tuple[int, int]] = []
        payloads, sizes, dws = set(), set(), set()
        for i, sp in enumerate(specs):
            e = sp.emits[0]
            if isinstance(e, LoopEmit):
                if e.coalesce != "last":
                    raise _unsupported(
                        f"per-workgroup ('each') emission in {blame}"
                    )
                if e.dst.step != 0 and count > 1:
                    raise _unsupported(
                        f"k-varying emission destination in {blame} on a "
                        "multi-tier fabric"
                    )
                dst[i] = e.dst.at(k0)
                slots.append(
                    (e.slot.base, e.slot.step) if count > 1
                    else (e.slot.at(k0), 0)
                )
            elif isinstance(e, EmitOp):
                if e.coalesce != "last":
                    raise _unsupported(
                        f"per-workgroup ('each') emission in {blame}"
                    )
                if e.addr is not None:
                    raise _unsupported(
                        f"explicit EmitOp.addr override in {blame}"
                    )
                dst[i] = e.dst
                slots.append((e.slot, 0))
            else:
                raise _unsupported(
                    f"unsupported emit entry {type(e).__name__} in {blame}"
                )
            payloads.add(e.payload_bytes)
            sizes.add(e.size)
            dws.add(e.data_writes)
        if len(payloads) != 1 or len(sizes) != 1 or len(dws) != 1:
            raise _unsupported(f"emit parameters of {blame} vary across ranks")
        addr_base = np.empty(g, np.int64)
        addr_steps = set()
        for i, (sb, ss) in enumerate(slots):
            src_dev = int(devs[i])
            a0 = amap.flag_addr(src_dev, sb + ss * k0)
            if count > 1:
                a1 = amap.flag_addr(src_dev, sb + ss * (k0 + 1))
                step = a1 - a0
                klast = k0 + count - 1
                if amap.flag_addr(src_dev, sb + ss * klast) != a0 + step * (
                    count - 1
                ):
                    raise _unsupported(
                        f"flag address of {blame} is not affine over the "
                        "loop range"
                    )
            else:
                step = 0
            addr_steps.add(step)
            addr_base[i] = a0 - step * k0
        if len(addr_steps) != 1:
            raise _unsupported(
                f"flag address step of {blame} varies across ranks"
            )
        if dst.min() < 0 or dst.max() >= n:
            raise _unsupported(f"emit destination out of range in {blame}")
        if np.any(dst == devs):
            bad = int(devs[np.flatnonzero(dst == devs)[0]])
            raise _unsupported(
                f"self-directed emission in {blame} (rank {bad})"
            )
        return _GEmit(
            "single", payloads.pop(), sizes.pop(), dws.pop(), dst,
            addr_base, addr_steps.pop(),
        )
    # ---- contiguous per-rank dst run sharing one flag address ----------
    if all_run:
        if count > 1:
            raise _unsupported(
                f"EmitRun fan-out inside a k-loop in {blame} rewrites the "
                "same flags every iteration"
            )
        dst0 = np.empty(g, np.int64)
        cnts, slot0s, payloads, sizes, dws = set(), set(), set(), set(), set()
        for i, sp in enumerate(specs):
            e = sp.emits[0]
            if e.coalesce != "last":
                raise _unsupported(
                    f"per-workgroup ('each') emission in {blame}"
                )
            if e.count > 1 and e.dst_stride != 1 or e.slot_stride != 0:
                raise _unsupported(
                    f"non-contiguous EmitRun fan-out in {blame}"
                )
            dst0[i] = e.dst0
            cnts.add(e.count)
            slot0s.add(e.slot0)
            payloads.add(e.payload_bytes)
            sizes.add(e.size)
            dws.add(e.data_writes)
        if len(cnts) != 1 or len(slot0s) != 1 or len(payloads) != 1 \
                or len(sizes) != 1 or len(dws) != 1:
            raise _unsupported(f"fan-out parameters of {blame} vary across ranks")
        cnt = cnts.pop()
        if cnt < 1:
            return None
        slot0 = slot0s.pop()
        if dst0.min() < 0 or int(dst0.max()) + cnt - 1 >= n:
            raise _unsupported(f"emit destination out of range in {blame}")
        for i in range(g):
            if dst0[i] <= devs[i] < dst0[i] + cnt:
                raise _unsupported(
                    f"self-directed emission in {blame} (rank {int(devs[i])})"
                )
        addr_base = np.array(
            [amap.flag_addr(int(d), slot0) for d in devs], np.int64
        )
        return _GEmit(
            "run", payloads.pop(), sizes.pop(), dws.pop(), dst0,
            addr_base, 0, cnt=cnt,
        )
    # ---- all-peers fan-out (group must cover every rank) ---------------
    if len(devs) == n and devs[0] == 0:
        from .lockstep import UnsupportedProgram, _classify_emit

        try:
            e = _classify_emit(amap, specs, k0, count, n)
        except UnsupportedProgram as exc:
            raise _unsupported(f"{exc} ({blame})")
        if type(e).__name__ == "_FanoutEmit":
            if count > 1:
                raise _unsupported(
                    f"all-peers fan-out inside a k-loop in {blame}"
                )
            return _GEmit(
                "fanout_all", e.payload, e.size, e.dw, None, e.addr_vec, 0,
            )
    raise _unsupported(f"unsupported emission pattern in {blame}")


def _align_group(amap, n, group: _Group, progs) -> None:
    """Fill ``group.segs`` with the aligned per-phase classification."""
    devs = group.devs
    gname = group.name
    nsegs = _uniform(
        (len(p.segments) for p in progs),
        f"segment count of group {gname!r}", ids=devs,
    )
    tdf = group.tdf
    for j in range(nsegs):
        col = [p.segments[j] for p in progs]
        s0 = col[0]
        if isinstance(s0, LoopSpec):
            for i, s in enumerate(col):
                if not isinstance(s, LoopSpec) or s.count != s0.count \
                        or s.k0 != s0.k0 or len(s.body) != len(s0.body):
                    raise _unsupported(
                        f"loop structure of group {gname!r} varies across "
                        f"ranks (rank {devs[i]} differs from rank {devs[0]})"
                    )
            body = [
                _gphase(
                    amap, n, tdf, [s.body[b] for s in col], devs, gname,
                    s0.k0, s0.count,
                )
                for b in range(len(s0.body))
            ]
            group.segs.append(_GSeg(s0.count, s0.k0, body))
        else:
            for i, s in enumerate(col):
                if isinstance(s, LoopSpec):
                    raise _unsupported(
                        f"segment kinds of group {gname!r} vary across "
                        f"ranks (rank {devs[i]} differs from rank {devs[0]})"
                    )
            group.segs.append(
                _GSeg(1, 0, [_gphase(amap, n, tdf, col, devs, gname, 0, 1)])
            )


def _gphase(amap, n, tdf, specs, devs, gname, k0, count) -> _GPhase:
    s0 = specs[0]
    name = s0.name
    is_wait = s0.wait_addrs is not None
    for i, sp in enumerate(specs):
        if sp.name != name or (sp.wait_addrs is not None) != is_wait:
            raise _unsupported(
                f"phase structure of group {gname!r} varies across ranks "
                f"(rank {devs[i]} differs from rank {devs[0]})"
            )
    dur = 0 if is_wait else _uniform(
        (sp.duration_cycles for sp in specs),
        f"duration of phase {name!r} in group {gname!r}", ids=devs,
    )
    _uniform(
        (sp.traffic for sp in specs),
        f"traffic of phase {name!r} in group {gname!r}", ids=devs,
    )
    tdelta = tdf(s0) if tdf is not None else None
    wait = emit = None
    if is_wait:
        for i, sp in enumerate(specs):
            if sp.emits:
                raise _unsupported(
                    f"wait phase {name!r} of group {gname!r} has emissions "
                    f"(rank {devs[i]})"
                )
        wait = _try_allpeers_wait(specs, devs, k0, count, n)
        if wait is None:
            wait = _wait_cols(specs, devs, k0, count, gname, name)
    else:
        emit = _classify_emit_group(
            amap, specs, devs, k0, count, n, gname, name
        )
    return _GPhase(name, is_wait, dur, tdelta, wait, emit)


# ---------------------------------------------------------------------------
# emission families + compiled plan
# ---------------------------------------------------------------------------


class _Fam:
    """One aligned emission position's route family, shared by its k
    instances.  Messages are enumerated source-major (group row order, dst
    ascending within a rank's run) — the event engine's per-firing op order.
    """

    __slots__ = (
        "gi", "fid", "kind", "pricing", "payload", "size", "dw", "nb",
        "m", "cnt", "src_row", "src_dev", "dst", "addr_rel", "addr_step",
        "legs", "leg_slots", "keys_sorted", "keys_order", "dst_unique",
        "addr_vec", "cls_legs",
    )


class _Rec:
    """One emission instance awaiting its consumer wait(s)."""

    __slots__ = ("uid", "fam", "k", "consumed", "live")

    def __init__(self, uid, fam, k):
        self.uid = uid
        self.fam = fam
        self.k = k
        self.consumed = np.zeros(fam.m, bool)
        self.live = fam.m


class _TieredPlan:
    __slots__ = ("ports", "groups", "instrs", "refs")

    def __init__(self, ports, groups, instrs, refs):
        self.ports = ports
        self.groups = groups
        # ("p", gi, dur, tdelta, fam|None, uid, k)  non-wait phase
        # ("w", gi, cols, tdelta)  cols: [[(uid, idx, rows), ...], ...]
        # ("aw", gi, uid, tdelta)  all-peers barrier on a fanout record
        self.instrs = instrs
        self.refs = refs  # int64[n_uids]: runtime gathers per record


def _build_fam(ports, fab, grp, gi, fid, e: _GEmit, n) -> _Fam:
    fam = _Fam()
    fam.gi = gi
    fam.fid = fid
    fam.kind = e.kind
    fam.payload = e.payload
    fam.size = e.size
    fam.dw = e.dw
    fam.nb = e.payload + e.size
    fam.addr_step = e.addr_step
    fam.leg_slots = None
    fam.keys_sorted = None
    fam.addr_vec = None
    g = len(grp.devs)
    if e.kind == "fanout_all":
        fam.pricing = "ordered"
        fam.m = n * (n - 1)
        fam.cnt = n - 1
        fam.addr_vec = e.addr_base
        fam.legs = None  # built lazily at the (single) run instance
        fam.src_row = fam.src_dev = fam.dst = fam.addr_rel = None
        fam.dst_unique = False
        fam.cls_legs = None
        return fam
    if e.kind == "single":
        fam.cnt = 1
        fam.src_row = np.arange(g, dtype=np.int64)
        fam.src_dev = grp.devs
        fam.dst = e.dst
        fam.addr_rel = e.addr_base
    else:  # run
        fam.cnt = e.cnt
        fam.src_row = np.repeat(np.arange(g, dtype=np.int64), e.cnt)
        fam.src_dev = grp.devs[fam.src_row]
        fam.dst = (
            e.dst[:, None] + np.arange(e.cnt, dtype=np.int64)
        ).ravel()
        fam.addr_rel = np.repeat(e.addr_base, e.cnt)
    fam.m = len(fam.dst)
    fam.legs = _legs_csr(ports, fam.src_dev, fam.dst)
    _spot_check(ports, fab, fam.src_dev, fam.dst, fam.legs)
    # matching keys: (flag addr at k=0, dst) must identify each message
    keys = fam.addr_rel * np.int64(n) + fam.dst
    order = np.argsort(keys, kind="stable")
    skeys = keys[order]
    if fam.m > 1 and np.any(skeys[1:] == skeys[:-1]):
        raise _unsupported(
            f"duplicate (flag, destination) pair in an emission of group "
            f"{grp.name!r}"
        )
    fam.keys_sorted = skeys
    fam.keys_order = order
    fam.dst_unique = np.unique(fam.dst).size == fam.m
    # pricing: elementwise when no two messages of the instance share a
    # port; ordered per-port chains otherwise
    prt = fam.legs["port"]
    if np.unique(prt).size == prt.size:
        fam.pricing = "elem"
        offs = fam.legs["offs"]
        local = np.arange(len(prt), dtype=np.int64) - offs[fam.legs["msg"]]
        slots = []
        for s in range(int(local.max()) + 1 if len(prt) else 0):
            sel = np.flatnonzero(local == s)
            slots.append((
                fam.legs["msg"][sel], prt[sel],
                fam.legs["hops"][sel], fam.legs["cls"][sel],
            ))
        fam.leg_slots = slots
    else:
        fam.pricing = "ordered"
    fam.cls_legs = np.bincount(
        fam.legs["cls"], minlength=len(ports.cls_names)
    )
    return fam


def _register_ports(own, fam, gname):
    """Record port ownership; every port must have a single producer rank
    unless all its touches are priced in-order within one instance."""
    if fam.legs is None:
        return
    prt = fam.legs["port"]
    src = fam.src_dev[fam.legs["msg"]]
    pairs = np.unique(np.stack((prt, src)), axis=1)
    seen_ports, first = np.unique(pairs[0], return_index=True)
    if fam.pricing == "elem" and seen_ports.size != pairs.shape[1]:
        raise _unsupported(
            f"link port shared across source ranks in an emission of "
            f"group {gname!r}"
        )
    for p, s in zip(pairs[0], pairs[1]):
        p = int(p)
        s = int(s)
        prev = own.get(p)
        if prev is not None and prev != s:
            raise _unsupported(
                f"link port shared across source ranks {prev} and {s} "
                f"(group {gname!r}); cross-rank port interleaving stays on "
                "the timeline engine"
            )
        own[p] = s


class _Cursor:
    """Unrolled (segment, iteration, body position) walker for one group."""

    __slots__ = ("grp", "si", "kk", "bi", "done")

    def __init__(self, grp):
        self.grp = grp
        self.si = 0
        self.kk = 0
        self.bi = 0
        self.done = not grp.segs
        self._skip_empty()

    def _skip_empty(self):
        while not self.done and self.grp.segs[self.si].count <= 0:
            self.si += 1
            if self.si >= len(self.grp.segs):
                self.done = True

    def phase(self):
        seg = self.grp.segs[self.si]
        return seg.body[self.bi], seg.k0 + self.kk

    def advance(self):
        seg = self.grp.segs[self.si]
        self.bi += 1
        if self.bi >= len(seg.body):
            self.bi = 0
            self.kk += 1
            if self.kk >= seg.count:
                self.kk = 0
                self.si += 1
                if self.si >= len(self.grp.segs):
                    self.done = True
                    return
                self._skip_empty()


def _decode_flag(amap, n, addr):
    """Best-effort (writer, slot) of a flag address, for blame text."""
    try:
        base = amap.flag_addr(0, 0)
        dstride = amap.flag_addr(1, 0) - base
        idx, rem = divmod(int(addr) - base, dstride)
        if rem == 0 and idx >= 0:
            return idx % n, idx // n
    except Exception:
        pass
    return None, None


def _check_flag_reuse(progs, amap, cfg):
    """Decline programs where a flag address the solver stitches to an
    emission can also be set by an *earlier, unrelated* write.

    The event and timeline engines resolve waits by *value*: once a flag
    address holds data, every later wait on it completes at the next poll.
    The solver instead stitches each wait to its affine-matched emission, so
    any second writer of a stitched address makes the two disagree — either
    a *flag rewrite* (two emission instances targeting one (rank, flag)) or
    *marker aliasing* (``EmitOp.data_writes`` markers growing up from
    ``partial_base`` into a flag pool that overran the gap).

    The actual analysis lives in the parametric layout prover
    (:func:`repro.analysis.layout.check_programs`) — one implementation,
    shared with ``verify_scenario``/``prove_layout`` — and this gate cites
    the prover's finding verbatim.  Declined shapes stay on the timeline
    engine, which reproduces the engines' stale-flag timing exactly.
    """
    # analysis builds on core; import lazily to keep core import-light and
    # cycle-free
    from repro.analysis.layout import check_programs

    findings = check_programs(progs, amap, cfg)
    for f in findings:
        if f.severity != "error":
            continue
        tail = (
            "; stale-flag waits stay on the timeline engine"
            if f.kind == "flag-reuse"
            else "; stale-flag visibility stays on the timeline engine"
        )
        raise _unsupported(f.message + tail)


def _match_col(open_recs, want_addr, want_dst, n, cache):
    """Resolve one wait column against open emission records, latest first.

    Returns (segments, pend) — segments are (uid, idx, rows) gathers, pend
    the deferred consumption marks — or (None, blocked_row) when some rank's
    flag has no unconsumed earlier emission.
    """
    g = len(want_addr)
    remaining = np.ones(g, bool)
    segments = []
    pend = []
    for rec in reversed(open_recs):
        fam = rec.fam
        if fam.keys_sorted is None:
            continue
        rel = want_addr - fam.addr_step * rec.k
        ck = (fam.fid, rel.tobytes(), want_dst.tobytes())
        rows = cache.get(ck)
        if rows is None:
            keys = rel * np.int64(n) + want_dst
            pos = np.searchsorted(fam.keys_sorted, keys)
            pos_c = np.minimum(pos, fam.m - 1)
            hit = fam.keys_sorted[pos_c] == keys
            rows = np.where(hit, fam.keys_order[pos_c], -1)
            cache[ck] = rows
        valid = remaining & (rows >= 0)
        vi = np.flatnonzero(valid)
        if not vi.size:
            continue
        rr = rows[vi]
        free = ~rec.consumed[rr]
        vi = vi[free]
        if not vi.size:
            continue
        segments.append((rec.uid, vi, rows[vi]))
        pend.append((rec, rows[vi]))
        remaining[vi] = False
        if not remaining.any():
            return segments, pend
    return None, int(np.flatnonzero(remaining)[0])


def compile_tiered(cluster) -> _TieredPlan:
    """Group-align, classify, and schedule the pod's symbolic programs over
    a multi-tier fabric.  Raises :class:`UnsupportedProgram` with the
    offending group/rank/phase when the shape doesn't fit."""
    cfg = cluster.cfg
    n = cfg.n_devices
    amap = cluster.amap
    fab = cluster.fabric
    rcls = type(fab.spec.routing).__name__
    if _SUPPORTED.get(fab.spec.name) != rcls:
        raise _unsupported(
            f"fabric {fab.spec.name!r} (routing {rcls}) is outside the "
            "tiered solver's presets"
        )
    if amap.flag_addr(0, 0) >= (1 << 62) // max(2, n):
        raise _unsupported("flag address space too large for match keys")
    ports = _Ports(fab)
    progs = [
        as_symbolic(node.target.cohorts[0].phases) for node in cluster.nodes
    ]
    gorder: List[str] = []
    gmap: Dict[str, List[int]] = {}
    for dev, p in enumerate(progs):
        gname = p.group if p.group is not None else "ranks"
        if gname not in gmap:
            gmap[gname] = []
            gorder.append(gname)
        gmap[gname].append(dev)
    groups: List[_Group] = []
    for gname in gorder:
        devs = np.array(gmap[gname], np.int64)
        grp = _Group(gname, devs)
        tgt0 = cluster.nodes[int(devs[0])].target
        c0 = tgt0.cohorts
        grp.counts = np.array([c.count for c in c0], np.int64)
        grp.dispatch = np.array(
            [c.program.dispatch_cycle for c in c0], np.int64
        )
        grp.total = int(grp.counts.sum())
        grp.tdf = tgt0._tdelta_for
        for d in devs[1:]:
            cs = cluster.nodes[int(d)].target.cohorts
            if len(cs) != len(c0) or any(
                a.count != b.count
                or a.program.dispatch_cycle != b.program.dispatch_cycle
                for a, b in zip(cs, c0)
            ):
                raise _unsupported(
                    f"cohort shapes vary across ranks of group {gname!r} "
                    f"(rank {int(d)})"
                )
        _align_group(amap, n, grp, [progs[int(d)] for d in devs])
        groups.append(grp)

    # ---- worklist: order every group's phase instances -----------------
    fams: Dict[tuple, _Fam] = {}
    own: Dict[int, int] = {}
    recs: List[_Rec] = []
    open_recs: List[_Rec] = []
    instrs: List[tuple] = []
    refs: List[int] = []
    cursors = [_Cursor(grp) for grp in groups]
    cache: Dict[tuple, np.ndarray] = {}
    arrc: Dict[bytes, np.ndarray] = {}
    blocked: List[Optional[tuple]] = [None] * len(groups)

    def share(a):
        b = arrc.get(a.tobytes())
        if b is None:
            arrc[a.tobytes()] = a
            return a
        return b

    ar = np.arange(n, dtype=np.int64)
    while True:
        progress = False
        alldone = True
        for gi, (grp, cur) in enumerate(zip(groups, cursors)):
            while not cur.done:
                ph, k = cur.phase()
                if not ph.is_wait:
                    fam = uid = None
                    if ph.emit is not None:
                        fkey = (gi, cur.si, cur.bi)
                        fam = fams.get(fkey)
                        if fam is None:
                            fam = _build_fam(
                                ports, fab, grp, gi, len(fams), ph.emit, n
                            )
                            _register_ports(own, fam, grp.name)
                            fams[fkey] = fam
                        uid = len(recs)
                        rec = _Rec(uid, fam, k)
                        recs.append(rec)
                        open_recs.append(rec)
                        refs.append(0)
                    instrs.append(("p", gi, ph.dur, ph.tdelta, fam, uid, k))
                    cur.advance()
                    progress = True
                    continue
                if ph.wait[0] == "allpeers":
                    alpha, beta = ph.wait[1], ph.wait[2]
                    want = alpha + beta * ar
                    hit = None
                    for rec in reversed(open_recs):
                        if rec.fam.addr_vec is not None and rec.live and \
                                np.array_equal(rec.fam.addr_vec, want):
                            hit = rec
                            break
                    if hit is None:
                        blocked[gi] = (ph.name, k, int(grp.devs[0]), None)
                        break
                    hit.live = 0
                    refs[hit.uid] += 1
                    instrs.append(("aw", gi, hit.uid, ph.tdelta))
                else:
                    cols = []
                    fail = None
                    done_pend = []
                    for base, kstep in ph.wait[1]:
                        want_addr = base + kstep * k
                        segs, pend = _match_col(
                            open_recs, want_addr, grp.devs, n, cache
                        )
                        if segs is None:
                            fail = (want_addr, pend)
                            break
                        cols.append([
                            (u, share(i), share(r)) for u, i, r in segs
                        ])
                        done_pend.extend(pend)
                    if fail is not None:
                        addr = int(fail[0][fail[1]])
                        blocked[gi] = (
                            ph.name, k, int(grp.devs[fail[1]]), addr
                        )
                        break
                    for rec, rr in done_pend:
                        rec.consumed[rr] = True
                        rec.live -= len(rr)
                    for col in cols:
                        for u, _i, _r in col:
                            refs[u] += 1
                    instrs.append(("w", gi, cols, ph.tdelta))
                open_recs = [r for r in open_recs if r.live]
                cur.advance()
                progress = True
            if not cur.done:
                alldone = False
        if alldone:
            break
        if not progress:
            for gi, b in enumerate(blocked):
                if b is not None and not cursors[gi].done:
                    name, k, dev, addr = b
                    if addr is None:
                        raise _unsupported(
                            f"all-peers wait phase {name!r} (k={k}) of "
                            f"group {groups[gi].name!r} has no matching "
                            "earlier fan-out emission"
                        )
                    w, s = _decode_flag(amap, n, addr)
                    flag = (
                        f"flag (writer {w}, slot {s})" if w is not None
                        else f"flag 0x{addr:x}"
                    )
                    raise _unsupported(
                        f"wait phase {name!r} (k={k}) of group "
                        f"{groups[gi].name!r}: rank {dev} observes {flag} "
                        "with no earlier emission; cross-rank pipelined "
                        "chains stay on the timeline engine"
                    )
            raise _unsupported(
                "no group can advance (cyclic cross-group dependency)"
            )  # pragma: no cover

    if any(f.kind == "fanout_all" for f in fams.values()) and len(fams) > 1:
        raise _unsupported(
            "all-peers fan-out cannot share link ports with other "
            "emission stages"
        )
    _check_flag_reuse(progs, amap, cfg)
    return _TieredPlan(
        ports, groups, instrs, np.array(refs, np.int64)
    )


# ---------------------------------------------------------------------------
# the solver runtime
# ---------------------------------------------------------------------------


def _chain(b0, rdy, ser):
    """Price one port's resolved touch prefix: the scalar
    ``start = max(ready, busy); busy = start + ser`` sequence, vectorized as
    restart-segment cumsums (``np.cumsum`` accumulates left-to-right, so each
    segment's floats equal the event engine's sequential adds exactly).

    Two regimes, both bit-exact:

    - ready-dominant (the port drains between touches): a restarting
      element's busy is a single add ``rdy + ser``, so the run-continues
      test ``rdy[t+1] > rdy[t] + ser`` is elementwise and the whole run
      vectorizes (the intermediate busies never accumulate).
    - busy-dominant: cumsum a bounded chunk seeded with the
      exactly-carried busy value — crossing a chunk boundary reproduces
      the sequential float adds bit-for-bit, so chunking changes cost
      (quadratic -> amortized linear), never values.  The chunk doubles
      while segments run long and snaps back small on a restart."""
    mlen = rdy.size
    starts = np.empty(mlen)
    # iso[t]: element t+1 restarts given element t restarted
    # (rdy[t+1] > busy_t = rdy[t] + ser, a single exact add)
    iso = np.empty(mlen, bool)
    if mlen > 1:
        np.greater(rdy[1:], rdy[:-1] + ser, out=iso[: mlen - 1])
    iso[mlen - 1] = False
    # nf[t]: first index >= t with iso False (run terminator)
    idx = np.arange(mlen, dtype=np.int64)
    nf = np.where(iso, mlen, idx)
    nf = np.minimum.accumulate(nf[::-1])[::-1]
    i = 0
    b = float(b0)
    chunk = 32
    while i < mlen:
        r0 = rdy[i]
        if r0 > b:
            # maximal restart run: every element's start is its own ready
            t = int(nf[i]) - i + 1
            starts[i: i + t] = rdy[i: i + t]
            b = float(rdy[i + t - 1]) + ser
            i += t
            continue
        rem = mlen - i
        c = chunk if chunk < rem else rem
        ch = np.empty(c + 1)
        ch[0] = b
        ch[1:] = ser
        bs = np.cumsum(ch)
        viol = np.flatnonzero(rdy[i + 1: i + c] > bs[1:c])
        if viol.size:
            t = int(viol[0]) + 1
            chunk = 32
        else:
            t = c
            if chunk < (1 << 20):
                chunk *= 2
        starts[i: i + t] = bs[:t]
        b = float(bs[t])
        i += t
    return starts, b


def _as_slice(idx):
    """``idx`` as a slice selecting the same elements when it is an
    ascending arithmetic progression (a slice indexes by view, with no
    gather or scatter), else ``idx`` itself."""
    if len(idx) < 2:
        return idx
    step = int(idx[1] - idx[0])
    if step <= 0 or not np.array_equal(
        np.diff(idx), np.full(len(idx) - 1, step)
    ):
        return idx
    return slice(int(idx[0]), int(idx[-1]) + 1, step)


def run_tiered(cluster, plan: _TieredPlan, breakdown: Dict[str, float]):
    """Solve the compiled tiered plan; mutates cluster state only in the
    final write-back (a mid-solve failure falls back to the timeline engine
    cleanly).

    The per-step loop carries only the float recurrence (port busy chains,
    arrivals, wakes) and the spin's cursor update.  Integer accounting is
    order-free, so it is tallied per family and per group (emissions,
    ``tdelta`` applications, spin polls) and applied once at the write-back.
    A leg slot in which no message waits for its port (every start equals
    its ready time) would add ``+0.0`` to each queue sum, which leaves a
    non-negative float unchanged, so it skips those adds; the float adds
    that remain keep their order.
    """
    with span("lockstep.solve") as solve:
        cfg = cluster.cfg
        n = cfg.n_devices
        clock = cfg.clock_ghz
        poll = cfg.poll_interval_cycles
        check = cfg.flag_check_cycles
        xgmi_lat = cfg.xgmi_enact_latency_ns
        include_dw = cfg.include_data_writes
        fab = cluster.fabric
        ports = plan.ports
        groups = plan.groups
        ar_n = np.arange(n, dtype=np.int64)

        P = ports.P
        port_busy = np.array(
            [fab._busy_until_ns.get(t, 0.0) for t in ports.tuples]
        )
        port_used = np.zeros(P, bool)
        port_cnt = np.zeros(P, np.int64)
        port_byt = np.zeros(P, np.int64)
        port_qd = np.zeros(P)
        port_bw = ports.cls_bw[ports.port_cls]
        port_lat = ports.cls_lat[ports.port_cls]
        C = len(ports.cls_names)
        cls_msgs = np.zeros(C, np.int64)
        cls_bytes = np.zeros(C, np.int64)
        cls_q = np.zeros(C)
        g_q = 0.0
        max_set = 0

        T = [np.tile(g.dispatch, (len(g.devs), 1)) for g in groups]
        sc_store: Dict[int, np.ndarray] = {}
        refs = plan.refs.tolist()

        # order-free integer tallies, applied at the write-back
        emitted: Dict[_Fam, int] = {}  # family -> instances emitted
        td_cnt: List[Dict[tuple, int]] = [{} for _ in groups]  # tdelta -> n
        polls = [np.zeros_like(t) for t in T]  # summed spin poll counts
        spins = [0] * len(groups)
        # per-call constants of each elementwise family's leg slots:
        # (messages, ports, serialization ns, hop latency ns, link classes)
        elem_slots: Dict[_Fam, list] = {}
        n_slots = 0
        n_quiet = 0

        def spin(gi, V):
            """The interpreter's unified spin closed form over one group's
            cursor matrix (one wait address per rank)."""
            nt = V[:, None] - T[gi]
            nt += poll - 1
            nt //= poll
            np.maximum(nt, 0, out=nt)
            polls[gi] += nt
            spins[gi] += 1
            nt *= poll
            nt += check
            T[gi] += nt

        def tally(gi, d):
            if d is not None:
                c = td_cnt[gi]
                c[d] = c.get(d, 0) + 1

        def slots_of(fam):
            s = elem_slots.get(fam)
            if s is None:
                nb = fam.nb
                s = elem_slots[fam] = [
                    (_as_slice(mi), _as_slice(prt), nb / port_bw[prt],
                     hops * port_lat[prt], cls)
                    for mi, prt, hops, cls in fam.leg_slots
                ]
            return s

        def price_elem(fam, arr):
            """Leg-by-leg elementwise pricing of ``arr`` (issue times, priced
            in place into arrivals); valid because no two messages of the
            instance share a port (checked at compile)."""
            nonlocal g_q, n_slots, n_quiet
            slots = slots_of(fam)
            n_slots += len(slots)
            for mi, prt, ser, lat, cls in slots:
                # ``rdy`` is a view of ``arr`` where ``mi`` is a slice, so
                # it is read before ``arr[mi]`` is written
                rdy = arr[mi]
                st = np.maximum(rdy, port_busy[prt])
                fin = st + ser
                port_busy[prt] = fin
                if np.count_nonzero(st != rdy):
                    q = st - rdy
                    port_qd[prt] += q
                    g_q += float(q.sum())
                    np.add.at(cls_q, cls, q)
                else:
                    n_quiet += 1
                arr[mi] = fin + lat
            return arr

        def price_ordered(fam, issue, E_msg, legs):
            """Port-wavefront pricing in the event engine's global message
            order; each sweep extends every port's priced prefix with the
            touches whose upstream legs have resolved arrivals."""
            nonlocal g_q
            nb = fam.nb
            m = len(issue)
            msg = legs["msg"]
            L = len(msg)
            if np.all(E_msg == E_msg[0]):
                tmsg = msg
                tprt = legs["port"]
                thops = legs["hops"]
            else:
                morder = np.argsort(E_msg, kind="stable")
                inv = np.empty(m, np.int64)
                inv[morder] = np.arange(m, dtype=np.int64)
                tord = np.lexsort((np.arange(L), inv[msg]))
                tmsg = msg[tord]
                tprt = legs["port"][tord]
                thops = legs["hops"][tord]
            first = np.ones(L, bool)
            first[1:] = tmsg[1:] != tmsg[:-1]
            ready = np.full(L, np.nan)
            ready[first] = issue[tmsg[first]]
            nxt = np.full(L, -1, np.int32)
            cont = np.flatnonzero(~first[1:])
            nxt[cont] = cont + 1
            last = np.ones(L, bool)
            last[:-1] = first[1:]
            tsort = np.argsort(tprt, kind="stable")
            # tsort groups legs by ascending port id; per-port extents come from
            # a bincount (no gather of the sorted keys, no diff pass)
            pcnt = np.bincount(tprt, minlength=ports.P)
            plist = np.flatnonzero(pcnt)
            pend = np.cumsum(pcnt[plist])
            pstart = pend - pcnt[plist]
            cursor = np.zeros(len(plist), np.int64)
            arr_out = np.empty(m)
            done = 0
            while done < L:
                moved = False
                for pi in range(len(plist)):
                    s = int(pstart[pi] + cursor[pi])
                    e = int(pend[pi])
                    if s >= e:
                        continue
                    tl = tsort[s:e]
                    rdy = ready[tl]
                    isn = np.isnan(rdy)
                    cnt = int(isn.argmax())
                    if cnt == 0:
                        if isn[0]:
                            continue
                        cnt = len(tl)
                    tl = tl[:cnt]
                    rdy = rdy[:cnt]
                    p = int(plist[pi])
                    ser = nb / port_bw[p]
                    sts, bfin = _chain(port_busy[p], rdy, ser)
                    port_busy[p] = bfin
                    port_used[p] = True
                    fin = sts + ser
                    q = sts - rdy
                    port_cnt[p] += cnt
                    port_byt[p] += cnt * nb
                    port_qd[p] = float(
                        np.cumsum(np.concatenate(([port_qd[p]], q)))[-1]
                    )
                    qs = float(q.sum())
                    g_q += qs
                    cls_q[ports.port_cls[p]] += qs
                    a = fin + thops[tl] * port_lat[p]
                    nx = nxt[tl]
                    has = nx >= 0
                    ready[nx[has]] = a[has]
                    lm = last[tl]
                    arr_out[tmsg[tl[lm]]] = a[lm]
                    cursor[pi] += cnt
                    done += cnt
                    moved = True
                if not moved:  # pragma: no cover - leg classes form a DAG
                    raise _unsupported(
                        "link-port pricing stalled (non-DAG port order)"
                    )
            return arr_out

        def wake_cycles(arr, minns):
            """Set cycles of priced arrivals ``arr`` (consumed in place)."""
            nonlocal max_set
            arr += xgmi_lat
            np.maximum(arr, minns, out=arr)
            arr *= clock
            sc = np.rint(arr, out=arr).astype(np.int64)
            ms = int(sc.max())
            if ms > max_set:
                max_set = ms
            return sc

        def emit_family(fam, uid):
            Tg = T[fam.gi]
            # a single cohort's column is its own maximum
            E = Tg[:, 0] if Tg.shape[1] == 1 else Tg.max(axis=1)
            issue = E / clock
            minns = (E + 1) / clock
            if fam.kind != "single":  # "single": src_row is the identity
                issue = issue[fam.src_row]
                minns = minns[fam.src_row]
            if fam.pricing == "elem":
                arr = price_elem(fam, issue)
            else:
                arr = price_ordered(fam, issue, E[fam.src_row], fam.legs)
            sc = wake_cycles(arr, minns)
            if refs[uid] > 0:
                sc_store[uid] = sc
            emitted[fam] = emitted.get(fam, 0) + 1

        def emit_fanout(fam, uid):
            E = T[fam.gi].max(axis=1)
            src = np.repeat(np.arange(n, dtype=np.int32), n - 1)
            dstm = np.tile(np.arange(n - 1, dtype=np.int32), (n, 1))
            dstm += dstm >= ar_n[:, None]
            dst = dstm.ravel()
            legs = _legs_csr(ports, src, dst)
            _spot_check(ports, fab, src, dst, legs)
            issue = (E / clock)[src]
            arr = price_ordered(fam, issue, E[src], legs)
            sc = wake_cycles(arr, ((E + 1) / clock)[src])
            if refs[uid] > 0:
                M = np.zeros((n, n), np.int64)
                M[src, dst] = sc
                sc_store[uid] = M
            emitted[fam] = emitted.get(fam, 0) + 1
            legs_cls = np.bincount(legs["cls"], minlength=C)
            cls_msgs[:] += legs_cls
            cls_bytes[:] += legs_cls * fam.nb

        for ins in plan.instrs:
            tag = ins[0]
            if tag == "p":
                _, gi, dur, td, fam, uid, _k = ins
                if dur:
                    T[gi] += dur
                if fam is not None:
                    if fam.kind == "fanout_all":
                        emit_fanout(fam, uid)
                    else:
                        emit_family(fam, uid)
                tally(gi, td)
            elif tag == "w":
                _, gi, cols, td = ins
                g = len(groups[gi].devs)
                for col in cols:
                    V = np.empty(g, np.int64)
                    for uid, idx, rows in col:
                        V[idx] = sc_store[uid][rows]
                        refs[uid] -= 1
                        if refs[uid] == 0:
                            del sc_store[uid]
                    spin(gi, V)
                tally(gi, td)
            else:  # "aw"
                _, gi, uid, td = ins
                M = sc_store[uid]
                for j in range(n - 1):
                    gidx = np.where(ar_n > j, j, j + 1)
                    spin(gi, M[gidx, ar_n])
                refs[uid] -= 1
                if refs[uid] == 0:
                    del sc_store[uid]
                tally(gi, td)
        count("lockstep.steps", len(plan.instrs))
        count("lockstep.slots", n_slots)
        count("lockstep.quiet_slots", n_quiet)

    # ---- write-back -----------------------------------------------------
    with span("lockstep.writeback") as writeback:
        a_fr = np.zeros(n, np.int64)
        a_rb = np.zeros(n, np.int64)
        a_nfr = np.zeros(n, np.int64)
        a_lw = np.zeros(n, np.int64)
        a_wb = np.zeros(n, np.int64)
        a_xo = np.zeros(n, np.int64)
        a_xob = np.zeros(n, np.int64)
        a_xi = np.zeros(n, np.int64)
        a_xib = np.zeros(n, np.int64)
        a_reg = np.zeros(n, np.int64)
        a_marks = np.zeros(n, np.int64)
        seq_add = 0
        g_msgs = 0
        g_bytes = 0
        for gi, grp in enumerate(groups):
            devs = grp.devs
            tot = grp.total
            m = polls[gi] @ grp.counts + spins[gi] * tot
            a_fr[devs] += m
            a_rb[devs] += 8 * m
            d = [0] * 6
            for td, c in td_cnt[gi].items():
                for j in range(6):
                    d[j] += c * td[j]
            a_nfr[devs] += d[0] * tot
            a_rb[devs] += d[1] * tot
            a_lw[devs] += d[2] * tot
            a_wb[devs] += d[3] * tot
            a_xo[devs] += d[4] * tot
            a_xob[devs] += d[5] * tot
        for fam, c in emitted.items():
            dw = fam.dw if include_dw and fam.dw > 0 else 0
            regs = 1 + dw
            per_rank = c * fam.cnt
            devs = groups[fam.gi].devs
            a_xo[devs] += per_rank
            a_xob[devs] += per_rank * fam.size
            if fam.kind == "fanout_all":
                a_xi += per_rank * regs
                a_xib += per_rank * (fam.size + 8 * dw)
                a_reg += per_rank * regs
                if dw:
                    a_marks += per_rank * dw
            elif fam.dst_unique:
                a_xi[fam.dst] += c * regs
                a_xib[fam.dst] += c * (fam.size + 8 * dw)
                a_reg[fam.dst] += c * regs
                if dw:
                    a_marks[fam.dst] += c * dw
            else:
                np.add.at(a_xi, fam.dst, c * regs)
                np.add.at(a_xib, fam.dst, c * (fam.size + 8 * dw))
                np.add.at(a_reg, fam.dst, c * regs)
                if dw:
                    np.add.at(a_marks, fam.dst, c * dw)
            seq_add += c * fam.m * regs
            g_msgs += c * fam.m
            g_bytes += c * fam.m * fam.nb
            if fam.cls_legs is not None:
                cls_msgs += c * fam.cls_legs
                cls_bytes += c * fam.cls_legs * fam.nb
            if fam.pricing == "elem":
                prt = fam.legs["port"]
                port_used[prt] = True
                port_cnt[prt] += c
                port_byt[prt] += c * fam.nb
        kend = np.zeros(n, np.int64)
        for gi, grp in enumerate(groups):
            kend[grp.devs] = T[gi].max(axis=1)
        sim_cycles = max(int(kend.max()), max_set)
        for r, node in enumerate(cluster.nodes):
            t = node.memory.traffic
            t.flag_reads += int(a_fr[r])
            t.nonflag_reads += int(a_nfr[r])
            t.read_bytes += int(a_rb[r])
            t.local_writes += int(a_lw[r])
            t.write_bytes += int(a_wb[r])
            t.xgmi_writes_out += int(a_xo[r])
            t.xgmi_bytes_out += int(a_xob[r])
            t.xgmi_writes_in += int(a_xi[r])
            t.xgmi_bytes_in += int(a_xib[r])
            tgt = node.target
            tgt.done_count = tgt.n_wgs
            tgt.kernel_end_cycle = int(kend[r])
            ws = node.wtt.stats
            ws.registered += int(a_reg[r])
            ws.enacted += int(a_reg[r])
            if a_marks[r]:
                cluster._data_marks[r] = (
                    cluster._data_marks.get(r, 0) + int(a_marks[r])
                )
        cluster._seq += seq_add
        st = fab.stats
        st["messages"] += g_msgs
        st["bytes"] += g_bytes
        st["queued_ns"] += g_q
        for ci, cname in enumerate(ports.cls_names):
            if cls_msgs[ci]:
                st[f"{cname}_messages"] = (
                    st.get(f"{cname}_messages", 0) + int(cls_msgs[ci])
                )
                st[f"{cname}_bytes"] = (
                    st.get(f"{cname}_bytes", 0) + int(cls_bytes[ci])
                )
                st[f"{cname}_queued_ns"] = (
                    st.get(f"{cname}_queued_ns", 0.0) + float(cls_q[ci])
                )
        for p in np.flatnonzero(port_used):
            p = int(p)
            port = ports.tuples[p]
            fab._busy_until_ns[port] = float(port_busy[p])
            ps = fab.port_stats.get(port)
            if ps is None:
                ps = fab.port_stats[port] = [0, 0, 0.0]
            ps[0] += int(port_cnt[p])
            ps[1] += int(port_byt[p])
            ps[2] += float(port_qd[p])
    breakdown.update(solve_s=solve.dur, writeback_s=writeback.dur)
    return EngineResult(
        sim_cycles=sim_cycles,
        wall_time_s=solve.dur + writeback.dur + breakdown.get("compile_s", 0.0),
        head_polls=0,
        breakdown=breakdown,
    )
