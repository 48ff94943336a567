"""The trace reduction against values counted by hand on a trimmed
recorded v5e trace (``data/v5e_barrier_trace.json``): one barrier
campaign's host spans, its three XLA programs, and twelve of its device
ops, among them a ``while`` op with ops nested inside it."""

import json
import types
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.metrics import device_idle_share, host_gap_ms, program_ms

DATA = Path(__file__).with_name("data") / "v5e_barrier_trace.json"

# init ops: [..021, ..215] touching -> 194, [..217, ..304] 87, [..306, ..633]
# touching -> 327: 608 ns. _fused_cycles: custom-call 1 + convert 15718 +
# while.46 64366278 (covers iota, while.47 and dynamic_slice) + copy-done 3
FUSED_BUSY = 1 + 15718 + 64366278 + 3
BUSY = 608 + FUSED_BUSY
WINDOW = 590612942 - 49331148
CAMPAIGN = 590612942 - 49470648


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.from_json(json.loads(DATA.read_text()))


def test_busy_union(trace):
    assert tr.busy(trace, *tr.window(trace)) == BUSY
    assert tr.covered(tr.union(trace.ops[0]), 50382100, 50382300) == (215 - 100) + (300 - 217)


def test_module_device_time(trace):
    assert tr.module_busy(trace, "_fused_cycles") == FUSED_BUSY
    assert tr.module_busy(trace, "init") == 608
    assert tr.module_busy(trace, "convert_element_type") == 0
    assert tr.module_busy(trace, "fused_cycles") == 0    # names match whole


def test_idle_gaps_by_host_span(trace):
    b = tr.breakdown(trace, top=3)
    # 50382633 -> 493607075 overlaps build by 1507695 ns and run by 441714227
    # 558718159 -> 590612942 overlaps run by 2393274 and read by 29497999
    # 49331148 -> 50382021 lies in the window, and in build from 49470648
    assert [n for n, _ in b["idle_gaps"]] == ["campaign.run", "campaign.read",
                                             "campaign.build"]
    assert [round(v * 1e9) for _, v in b["idle_gaps"]] == [443224442, 31894783,
                                                          1050873]


def test_self_time_of_nested_ops(trace):
    ops = dict(tr.breakdown(trace, top=20)["device_ops"])
    # while.46 minus iota (2) and while.47 (6391571); while.47 minus 318
    assert ops["%while.46"] == pytest.approx(57974705e-9, abs=1e-15)
    assert ops["%while.47"] == pytest.approx(6391253e-9, abs=1e-15)
    assert sum(ops.values()) == pytest.approx(BUSY * 1e-9, abs=1e-15)


def test_metric_readers(trace):
    ctx = types.SimpleNamespace(trace=trace, program="_fused_cycles",
                                campaigns=[object()])
    assert program_ms.read(ctx) == pytest.approx(FUSED_BUSY * 1e-6)
    assert host_gap_ms.read(ctx) == pytest.approx((CAMPAIGN - BUSY) * 1e-6)
    assert device_idle_share.read(ctx) == pytest.approx(100 * (1 - BUSY / WINDOW))
    assert tr.campaigns(trace) == [[49470648.0, 590612942.0]]
