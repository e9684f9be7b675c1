"""``layers/counts`` on a synthetic list of the program's span
records: each ratio from the window's counts alone, ``None`` where a
count is absent."""

import pytest

from benchmark.layers import counts


def span(name, prof=True, **args):
    return {"name": name, "ts": 0.0, "dur": 1.0, "id": 1, "parent": None,
            "request": None, "prof": prof, "args": args}


def fused(prof=True, scale=1, shards=4):
    """A call's counts as PR 36's ring has them in ``we-fused-x4``."""
    walk = [898_816, 898_816, 899_072, 899_072]
    if shards == 1:
        walk = [sum(walk)]
    return span("we.fused", prof=prof, update_rows=7_488_000 * scale,
                unique_rows=4_897_712 * scale, head_rows=1_884_160 * scale,
                walk_slots_by_shard=[n * scale for n in walk], shards=shards)


def events():
    return ([fused(prof=False, scale=7)]                    # set-up
            + [fused(), fused(scale=2), fused()]
            + [span("lm.step", tokens=16_384),              # queued: no
               span("lm.step", tokens=16_384, overflow_rows=0),  # read-back
               span("lm.step", tokens=16_384, overflow_rows=3),
               span("lm.step", prof=False, overflow_rows=1000)]
            + [fused(prof=False, scale=11)])                # check's call


WANT = {
    "counts.unique_share.we": 100 * 4_897_712 / 7_488_000,
    "counts.head_share.we": 100 * 1_884_160 / 4_897_712,
    "counts.walk_fill_share.we": 100 * 3_013_552 / 3_595_776,
    "counts.overflow_rows.lm": 3,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_each_value_from_the_windows_counts(name):
    assert counts.read_events(name, events()) == pytest.approx(WANT[name])
    if name.endswith(".we"):          # one shard: one entry in the list
        one = [fused(shards=1), fused(shards=1, scale=3)]
        assert counts.read_events(name, one) == pytest.approx(WANT[name])


def test_the_hand_readings_of_the_issue():
    assert WANT["counts.unique_share.we"] == pytest.approx(65.4, abs=0.05)
    assert WANT["counts.head_share.we"] == pytest.approx(38.5, abs=0.05)
    assert WANT["counts.walk_fill_share.we"] == pytest.approx(83.8, abs=0.05)


@pytest.mark.parametrize("name", sorted(WANT) + ["counts.unknown.we"])
def test_absent_counts_read_none(name):
    assert counts.read_events(name, []) is None
    # a program from before the counts; a window with no such span
    older = [span("we.fused", words=600_000, pairs=3_744_000),
             span("lm.step", tokens=16_384), fused(prof=False),
             span("lm.step", prof=False, overflow_rows=0)]
    assert counts.read_events(name, older) is None


def test_a_window_with_part_of_the_counts_reads_what_it_can():
    # PR 28's program: unique_rows, but no head and no walk slots
    evs = [span("we.fused", update_rows=100, unique_rows=60)]
    assert counts.read_events("counts.unique_share.we", evs) == 60.0
    assert counts.read_events("counts.head_share.we", evs) is None
    assert counts.read_events("counts.walk_fill_share.we", evs) is None


def test_read_goes_through_the_programs_ring():
    from multiverso_tpu.telemetry import trace

    saved = trace.TRACER.events()
    try:
        trace.TRACER.reset()
        assert counts.read("counts.overflow_rows.lm", {}) is None
        with trace.TRACER._lock:
            trace.TRACER._events.extend(events())
        assert counts.read("counts.overflow_rows.lm", {}) == 3
    finally:
        trace.TRACER.reset()
        with trace.TRACER._lock:
            trace.TRACER._events.extend(saved)
