"""The readers of the program's own spans and scopes
(``bench/program_spans.py``): the protobuf reader of op-name paths on a
hand-encoded trace, and every new metric against values counted by hand
on a trimmed recorded v5e trace (``data/v5e_barrier_spans_trace.json``)."""

import json
import types
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.metrics import (aggregate_ms, h2d_ms, local_train_ms,
                           policy_solve_ms, schedule_ms, stage_ms, staged_mb,
                           unattributed_gap_ms)

DATA = Path(__file__).with_name("data")


def _varint(n: int) -> bytes:
    out = b""
    while True:
        low, n = n & 0x7F, n >> 7
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def test_op_paths_read_from_the_wire_format(tmp_path):
    # stat metadata: 1 = tf_op, 2 = flops, 3 = an interned path string
    stat_meta = [_msg((1, i), (2, _msg((1, i), (2, name))))
                 for i, name in ((1, "tf_op"), (2, "flops"),
                                 (3, "jit(g)/aggregate/mul:"))]
    fusion = _msg((1, 7), (2, "%fusion.1 = f32[3] fusion()"),
                  (5, _msg((1, 2), (3, 440))),
                  (5, _msg((1, 1), (5, "jit(f)/local_train/dot_general:"))))
    copy = _msg((1, 8), (2, "%copy.2 = f32[3] copy()"),
                (5, _msg((1, 1), (7, 3))))
    bare = _msg((1, 9), (2, "%while.3 = f32[3] while()"))
    device = _msg((1, 2), (2, "/device:TPU:0"),
                  (3, _msg((2, "XLA Ops"), (4, _msg((1, 7), (2, 1000))))),
                  *((4, _msg((1, k), (2, m))) for k, m in
                    ((7, fusion), (8, copy), (9, bare))),
                  *((5, s) for s in stat_meta))
    host = _msg((1, 1), (2, "/host:CPU"),
                (4, _msg((1, 1), (2, _msg((1, 1), (2, "fed.stage"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device), (4, "host0")))
    assert ps.op_paths(str(path)) == {0: {
        "%fusion.1 = f32[3] fusion()": "jit(f)/local_train/dot_general:",
        "%copy.2 = f32[3] copy()": "jit(g)/aggregate/mul:"}}


@pytest.fixture(scope="module")
def ctx():
    obj = json.loads((DATA / "v5e_barrier_spans_trace.json").read_text())
    return types.SimpleNamespace(trace=tr.Trace.from_json(obj),
                                 program_spans=ps.Spans.from_json(obj),
                                 program="_fused_cycles", campaigns=[object()])


# the campaign's eleven fed.stage spans, end - start: the zeroed blocks
# (264450), then one per cycle with that cycle's fed.draw nested inside
# (41460411 + 37235740 + 37088041 + 39581420 + 39434351 + 38505120
# + 39237281 + 37494320 + 37392251 + 38327970)
DRAW_STAGE = 386021355
# (10 cycles x 20 learners x 501 rows) x (784 f32 + y int32 + mask f32)
STAGED = 10 * 20 * 501 * (784 * 4 + 4 + 4)


def test_host_span_metrics(ctx):
    assert stage_ms.read(ctx) == pytest.approx(DRAW_STAGE * 1e-6)
    assert h2d_ms.read(ctx) == pytest.approx((432508376 - 429721066) * 1e-6)
    assert staged_mb.read(ctx) == pytest.approx(STAGED * 1e-6) == 315.0288
    assert schedule_ms.read(ctx) is None              # a barrier campaign


def test_unattributed_gap(ctx):
    # device idle in the campaign [41450271, 561085069]: before the init
    # op, all of [41450271, 42348077] (897806: no span yet); between the
    # init op and the program, [42358317, 468550720] (426192403) less the
    # spans fed.allocate (854870), the fed.stage spans (386021355),
    # fed.h2d (2787310), fed.dispatch (893510) and fed.wait up to the
    # program (468550720 - 433465416); [468566586, 469293446] and
    # [533624668, 533624702] lie in fed.wait; after the program,
    # [533626463, 561085069] (27458606) less the rest of fed.wait
    # (1565015) and fed.history (1079770)
    idle = (897806
            + 426192403 - 854870 - 386021355 - 2787310 - 893510
            - (468550720 - 433465416)
            + 27458606 - 1565015 - 1079770)
    assert idle == 26261681
    assert unattributed_gap_ms.read(ctx) == pytest.approx(idle * 1e-6)


def test_device_scope_metrics(ctx):
    # each scope's ops are leaves (inside %while.46 and %while.47, whose
    # own paths are empty), so their self time is their duration
    assert local_train_ms.read(ctx) == pytest.approx(
        ((469354438 - 469322452) + (469418337 - 469385370)) * 1e-6)
    assert aggregate_ms.read(ctx) == pytest.approx((475714898 - 475711285) * 1e-6)
    assert ps.scope_ns(ctx.trace, ctx.program_spans, "eval") == 475724451 - 475715541
    assert policy_solve_ms.read(ctx) is None           # no realloc scan here
    # the scope is a whole path component: jit(local_train_stacked) alone
    # does not count
    assert ps.scope_ns(ctx.trace, ctx.program_spans, "local_train_stacked") is None


def test_a_trace_without_program_spans_reads_nothing():
    """The parent commit's program writes no spans or scopes: every new
    reader returns None on its recorded trace, which lacks the keys."""
    obj = json.loads((DATA / "v5e_barrier_trace.json").read_text())
    ctx = types.SimpleNamespace(trace=tr.Trace.from_json(obj),
                                program_spans=ps.Spans.from_json(obj),
                                program="_fused_cycles", campaigns=[object()])
    for metric in (stage_ms, h2d_ms, schedule_ms, unattributed_gap_ms,
                   staged_mb, local_train_ms, aggregate_ms, policy_solve_ms):
        assert metric.read(ctx) is None
