"""The call stream: the same seed gives the same calls, the DDP walk
follows the step's buckets in order, and the sweep's draws stay in range."""

import itertools

from benchlib.spec import load_cell
from benchlib.traffic import CallStream


def calls(name, seed, n):
    cell = load_cell(name)
    return list(itertools.islice(CallStream(cell.config, cell.mix, seed), n))


def test_same_seed_same_calls():
    for name in ("dgx_h100_4su.ring_ddp", "eidola_table1.fig6_sweep"):
        assert calls(name, 2**33 + 5, 40) == calls(name, 2**33 + 5, 40)
        assert calls(name, 1, 40) != calls(name, 2, 40)


def test_ddp_walk_follows_the_step_and_wraps():
    cell = load_cell("dgx_h100_4su.hier_ddp")
    buckets = cell.mix["walk"]["payload_bytes"]
    stream = CallStream(cell.config, cell.mix, 9)
    start = stream.pos
    got = [c["params"]["payload_bytes"]
           for c in itertools.islice(stream, 2 * len(buckets))]
    assert got == (buckets[start:] + buckets[:start]) * 2
    starts = {CallStream(cell.config, cell.mix, s).pos for s in range(40)}
    assert len(starts) > 20  # the seed picks where the window starts


def test_sweep_draws_cover_the_range_without_repeats():
    got = [c["params"]["flag_delays_ns"] for c in calls("eidola_table1.fig6_sweep", 3, 64)]
    assert len(set(got)) == 64
    assert all(0.0 <= x < 40000.0 for x in got)
    for block in range(4):  # one call per 2.5 us stratum in each block of 16
        strata = sorted(int(x // 2500) for x in got[16 * block:16 * block + 16])
        assert strata == list(range(16))
