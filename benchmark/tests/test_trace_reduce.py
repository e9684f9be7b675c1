"""trace_reduce's arithmetic on a small synthetic event list."""

import pytest

from benchmark.trace_reduce import (NO_SPAN, SEAMS, Op, Span, mentions_shape,
                                    reduce, self_times, union)


def test_union_merges_overlaps():
    assert union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]


def test_busy_idle_and_gap_attribution():
    # window 0..10 s; device busy 1..3, 3.000005..5 (a seam), 7..9
    table = "f32[1001,300]{1,0} fusion(f32[1001,300], s32[64])"
    ops = {"/device:TPU:0": [
        Op("fusion.1", 1.0, 2.0, table),
        Op("copy.2", 3.000005, 1.999995, "f32[64,300]{1,0} copy"),
        Op("while.3", 7.0, 2.0, "(f32[1001,300], s32[]) while"),
        Op("fusion.4", 7.5, 1.0, "f32[64,300]{1,0} fusion(f32[64,300])"),
    ]}
    spans = [Span("bench.window", 0.0, 10.0),
             Span("bench.call", 0.0, 6.5), Span("bench.feed", 0.2, 0.6),
             Span("bench.call", 6.5, 3.5), Span("other", 0.0, 10.0)]
    r = reduce(ops, spans, table_shapes=[(1001, 300)])
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(2.0 + 1.999995 + 2.0)
    assert r["idle_share"] == pytest.approx(1 - 5.999995 / 10.0)
    gaps = dict(r["idle_gaps"])
    # 0..1 midpoint 0.5 lies in bench.feed (innermost); 5..7 midpoint 6.0
    # in the first bench.call; 9..10 in the second
    assert gaps["bench.feed"] == pytest.approx(1.0)
    assert gaps["bench.call"] == pytest.approx(2.0 + 1.0)
    assert gaps[SEAMS] == pytest.approx(5e-6)
    top = dict(r["device_ops"])
    assert top["fusion.1 f32[1001,300]"] == pytest.approx(2.0)
    assert top["while.3 f32[1001,300]"] == pytest.approx(1.0)  # self: 2 - 1
    assert top["fusion.4 f32[64,300]"] == pytest.approx(1.0)
    # table-shaped: fusion.1 (2.0) alone. The while carries the table but
    # is a container, and its body's fusion is batch-shaped
    assert r["table_s"] == pytest.approx(2.0)
    assert r["chips"] == 1 and r["n_ops"] == 4


def test_ops_outside_the_window_are_clipped_and_chips_averaged():
    ops = {"/device:TPU:0": [Op("a", -1.0, 2.0, ""), Op("a", 3.0, 5.0, "")],
           "/device:TPU:1": [Op("a", 0.0, 4.0, "")],
           "/device:TPU:2": []}
    r = reduce(ops, [Span("bench.window", 0.0, 4.0)])
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((1.0 + 1.0 + 4.0) / 2)
    assert dict(r["idle_gaps"])[NO_SPAN] == pytest.approx(2.0 / 2)


def test_no_device_ops_reads_all_idle():
    r = reduce({}, [Span("bench.window", 0.0, 2.0)])
    assert r["busy_s"] == 0.0 and r["idle_share"] == 1.0
    assert r["window_s"] == pytest.approx(2.0)


def test_self_time_of_nested_ops():
    ops = [Op("outer", 0.0, 10.0, ""), Op("mid", 1.0, 5.0, ""),
           Op("leaf", 2.0, 1.0, ""), Op("late", 7.0, 2.0, "")]
    got = {o.name: t for o, t in self_times(ops)}
    assert got == {"outer": 3.0, "mid": 4.0, "leaf": 1.0, "late": 2.0}


def test_table_time_is_a_union_and_never_above_busy():
    t = "f32[1001,300]{1,0} fusion(f32[1001,300])"
    ops = {"/device:TPU:0": [Op("a", 0.0, 2.0, t), Op("b", 1.0, 2.0, t)]}
    r = reduce(ops, [Span("bench.window", 0.0, 4.0)], [(1001, 300)])
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["table_s"] == pytest.approx(3.0)


def test_shape_match_is_exact():
    op = Op("x", 0, 1, "f32[21001,300]{1,0} fusion(f32[1001,300])")
    assert mentions_shape(op, [(1001, 300)])
    assert not mentions_shape(op, [(1001, 30)])
