"""``layers/prog.py`` on a synthetic list of the program's span records:
the seven values, ``None`` where a span is absent, and the window told
from set-up and from ``check`` by ``prof`` alone."""

import itertools

import pytest

from benchmark.layers import prog

MS = 1e3         # a span's ts and dur are microseconds
_ids = itertools.count(1)


def span(name, ts_ms, dur_ms, prof=False, parent=None, **args):
    return {"name": name, "ts": ts_ms * MS, "dur": dur_ms * MS,
            "id": next(_ids), "parent": parent, "request": None,
            "prof": prof, "args": args}


def events():
    out = []
    # set-up: two tables (children must not count twice), two compiles,
    # a pilot call whose blocks are no part of the window
    for t0, dur in ((0, 3000), (3000, 1500)):
        init = span("table.init", t0, dur, rows=10)
        out += [init, span("table.init.host", t0, dur / 2, parent=init["id"]),
                span("table.init.put", t0 + dur / 2, dur / 2,
                     parent=init["id"])]
    out += [span("xla.compile", 5000, 700, seconds=0.7, event="compile"),
            span("xla.compile", 5800, 50, seconds=0.05, event="cache_load")]
    pilot = span("we.blocks", 6000, 900)
    out += [pilot, span("we.block.device", 6000, 300),
            span("we.prepare", 6000, 40, rows_touched=1, rows_bucket=1000),
            span("we.block.wait_prepared", 6000, 77)]
    fused_setup = span("we.fused", 7000, 500)
    out += [fused_setup,
            span("we.fused.wait", 7100, 100, parent=fused_setup["id"])]
    # the window (prof true): three fused calls with 4, 6 and 50 ms of
    # host time, and one blocks call of four blocks
    for k, host in enumerate((4, 6, 50)):
        call = span("we.fused", 10_000 + 1000 * k, 900 + host, prof=True)
        out += [call, span("we.fused.wait", 10_000 + 1000 * k + host, 900,
                           prof=True, parent=call["id"]),
                span("we.fused.dispatch", 10_000 + 1000 * k, host / 2,
                     prof=True, parent=call["id"])]
    call = span("we.blocks", 20_000, 2000, prof=True)
    out.append(call)
    # dispatched at 20_000, 20_001, ...; done at 20_400, 20_810, 21_200,
    # 21_900: gaps 400 (from its dispatch), 410, 390, 700
    for k, end in enumerate((20_400, 20_810, 21_200, 21_900)):
        out += [span("we.block.device", 20_000 + k, end - 20_000 - k,
                     prof=True),
                span("we.block.wait_prepared", 20_000 + k, (30, 0.5, 0.1,
                                                            0.3)[k],
                     prof=True, parent=call["id"]),
                span("we.prepare", 19_990 + k, 45, prof=True,
                     rows_touched=100_000 + k, rows_bucket=524_288)]
    # after the window, check's calls: prof false again
    late = span("we.fused", 30_000, 5000)
    out += [late, span("we.fused.wait", 30_000, 100, parent=late["id"]),
            span("we.blocks", 36_000, 3000),
            span("we.block.device", 36_000, 2500),
            span("we.prepare", 36_000, 45, rows_touched=5, rows_bucket=10),
            span("table.init", 40_000, 9000, rows=1),
            span("xla.compile", 41_000, 2000, seconds=2.0, event="compile")]
    return out


WANT = {
    "prog.fused_host_ms.we": 6.0,
    "prog.block_dev_ms.we": 405.0,              # median of 400 410 390 700
    "prog.prepare_wait_ms.we": 0.4,             # median of 30 .5 .1 .3
    "prog.pull_fill_share.we": 100.0 * (400_000 + 6) / (4 * 524_288),
    "prog.table_init_s.setup": 4.5,
    "prog.compile_s.setup": 0.75,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_each_value_from_the_windows_spans(name):
    assert prog.read_events(name, events()) == pytest.approx(WANT[name])


def test_p95_needs_twenty_blocks_and_reads_the_tail():
    assert prog.read_events("prog.block_dev_p95_ms.we", events()) is None
    call = span("we.blocks", 0, 50_000, prof=True)
    ends, t = [], 0.0
    for k in range(40):
        t += 900.0 if k == 17 else 400.0
        ends.append(t)
    evs = [call] + [span("we.block.device", 0, end, prof=True)
                    for end in ends]
    gaps = prog.block_gaps_ms(evs)
    assert sorted(gaps)[-1] == pytest.approx(900.0) and len(gaps) == 40
    assert prog.read_events("prog.block_dev_ms.we", evs) == pytest.approx(400)
    p95 = prog.read_events("prog.block_dev_p95_ms.we", evs)
    assert 400.0 <= p95 <= 900.0


def test_gaps_do_not_cross_calls():
    evs = []
    for c in range(2):
        base = 10_000 * c
        evs.append(span("we.blocks", base, 1000, prof=True))
        evs += [span("we.block.device", base + 1, 299, prof=True),
                span("we.block.device", base + 2, 698, prof=True)]
    # the first block of each call from its own dispatch (1 ms in)
    assert prog.block_gaps_ms(evs) == pytest.approx([299, 400, 299, 400])


@pytest.mark.parametrize("name", sorted(WANT) + ["prog.block_dev_p95_ms.we",
                                                 "prog.unknown_ms.we"])
def test_absent_spans_read_none(name):
    assert prog.read_events(name, []) is None
    other = [span("other.span", 0, 10, prof=True), span("other.setup", 0, 5)]
    assert prog.read_events(name, other) is None


def test_untraced_run_has_no_window_and_counts_everything_as_setup():
    evs = [e for e in events() if not e["prof"]]
    assert prog.read_events("prog.fused_host_ms.we", evs) is None
    assert prog.read_events("prog.block_dev_ms.we", evs) is None
    assert prog.read_events("prog.pull_fill_share.we", evs) is None
    # the DLRM window runs no span of the program: all of it is set-up
    assert prog.read_events("prog.table_init_s.setup",
                            evs) == pytest.approx(13.5)
    assert prog.read_events("prog.compile_s.setup", evs) == pytest.approx(2.75)


def test_read_goes_through_the_programs_ring():
    from multiverso_tpu.telemetry import trace

    saved = trace.TRACER.events()
    try:
        trace.TRACER.reset()
        assert prog.read("prog.table_init_s.setup", {}) is None
        t0 = 1_000_000_000
        trace.record("table.init", t0, t0 + 2_000_000_000, rows=3)
        assert prog.program_events()[-1]["name"] == "table.init"
        assert prog.read("prog.table_init_s.setup", {}) == pytest.approx(2.0)
    finally:
        trace.TRACER.reset()
        for e in saved:
            trace.TRACER._events.append(e)
