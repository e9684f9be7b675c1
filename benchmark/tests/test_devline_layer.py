"""``layers/devline.py`` on a synthetic list of the program's span
records: the three values of a group, ``None`` without device spans, the
window told by ``prof`` alone, and the file's own arithmetic held equal
to the program's ``trace.device_timeline`` on the same list."""

import itertools
import random

import pytest

from benchmark.layers import devline

MS = 1e3         # a span's ts and dur are microseconds
_ids = itertools.count(1)


def span(name, ts_ms, dur_ms, prof=True, cat="prog", cause=None, **args):
    return {"name": name, "cat": cat, "ts": ts_ms * MS, "dur": dur_ms * MS,
            "id": next(_ids), "parent": None, "cause": cause, "tid": 1,
            "request": None, "prof": prof, "args": args}


def device(name, ts_ms, dispatched_ms, end_ms, prof=True, cause=None):
    """A device span as the watcher records it: ``dispatched`` in ns."""
    return dict(span(name, ts_ms, end_ms - ts_ms, prof=prof, cat="device",
                     cause=cause, dispatched=int(dispatched_ms * 1e6)),
                tid=2)


def fused_events():
    """Set-up's call (prof false), then a window of four calls: the host
    takes 2 ms from a call's start to its dispatch's return and 1 ms from
    ready to the call's end, the driver 0.5 ms between calls; the device
    takes 500 ms a call, 800 for the third."""
    out = [span("we.fused", 0, 600, prof=False),
           device("we.fused.device", 0, 2, 599, prof=False)]
    t = 10_000.0
    for k in range(4):
        took = 800 if k == 2 else 500
        out += [span("we.fused", t, 2 + took + 1),
                device("we.fused.device", t + 1, t + 2, t + 2 + took)]
        t += 2 + took + 1 + 0.5
    # check's call after the window: prof false again
    out += [span("we.fused", t + 5000, 100, prof=False),
            device("we.fused.device", t + 5000, t + 5001, t + 5099,
                   prof=False)]
    return out


# from the first call's start to the last device end: 4 calls, 3 seams
EXTENT_MS = 4 * 2 + 3 * 500 + 800 + 3 * 1.5
STARVED_MS = 4 * 2 + 3 * 1.5
TRACE = {"window_s": 2.4, "idle_share": 0.01}


def test_fused_calls_read_starved_unfiled_and_the_long_run():
    evs = fused_events()
    assert devline.timeline(evs, "we")["starved_s"] == pytest.approx(
        STARVED_MS * 1e-3)
    assert devline.timeline(evs, "we")["runs_ms"] == pytest.approx(
        [500, 500, 800, 500])
    starved = devline.read_events("devline.starved_share.we", evs, TRACE)
    assert starved == pytest.approx(100 * STARVED_MS * 1e-3 / 2.4)
    assert devline.read_events("devline.unfiled_idle_share.we", evs,
                               TRACE) == pytest.approx(1.0 - starved)
    assert devline.read_events("devline.run_max_over_p50.we", evs,
                               TRACE) == pytest.approx(1.6)


def test_steps_queued_one_ahead_starve_only_in_the_lead_in():
    """``step_ahead``: step k + 1 is dispatched while step k runs, so a
    run counts from the end before it and only the first dispatch (3 ms
    after the window's first span began) leaves the device with nothing."""
    evs, t = [], 1000.0
    for k in range(5):
        evs += [span("lm.step", t, 1, request=k + 1),
                device("lm.step.device", t + 0.5, t + 3 if k == 0 else t + 1,
                       1003 + 400 * (k + 1))]
        t = 1003 + 400 * k + 2          # read back step k, queue the next
    line = devline.timeline(evs, "lm")
    assert line["starved_s"] == pytest.approx(3e-3)
    assert line["runs_ms"] == pytest.approx([400] * 5)
    assert devline.read_events("devline.run_max_over_p50.lm", evs,
                               TRACE) == pytest.approx(1.0)
    assert devline.timeline(evs, "we") is None       # another group's


def test_without_a_traced_window_the_share_is_of_the_spans_extent():
    evs = fused_events()
    none = {"window_s": 0.0, "idle_share": 1.0}
    last_call_end = EXTENT_MS + 1
    assert devline.read_events("devline.starved_share.we", evs,
                               none) == pytest.approx(
        100 * STARVED_MS / last_call_end)
    assert devline.read_events("devline.unfiled_idle_share.we", evs,
                               none) is None


@pytest.mark.parametrize("name", [
    "devline.starved_share.we", "devline.unfiled_idle_share.we",
    "devline.run_max_over_p50.we", "devline.starved_share.lm",
    "devline.unfiled_idle_share.lm", "devline.run_max_over_p50.lm",
    "devline.unknown.we"])
def test_no_device_span_in_the_window_reads_none(name):
    assert devline.read_events(name, [], TRACE) is None
    # dlrm-step: a ring that is all set-up; a program from before the
    # device spans: host spans only; set-up's device spans are no window's
    other = [span("we.fused", 0, 10), span("lm.step", 20, 10),
             device("we.fused.device", 0, 1, 9, prof=False),
             device("lm.step.device", 20, 21, 29, prof=False)]
    assert devline.read_events(name, other, TRACE) is None


def test_a_record_without_the_count_is_in_flight_from_its_start():
    evs = [span("we.blocks", 0, 1000),
           dict(device("we.block.device", 100, 0, 500), args={}),
           dict(device("we.block.device", 101, 0, 900), args={})]
    line = devline.timeline(evs, "we")
    assert line["starved_s"] == pytest.approx(0.1)
    assert line["runs_ms"] == pytest.approx([400, 400])


@pytest.mark.parametrize("seed", range(8))
def test_the_arithmetic_is_the_programs(seed):
    """Random programs, some overlapping, some apart, and a window that
    begins before the first dispatch: this file's starved seconds and
    runs are ``trace.device_timeline``'s."""
    from multiverso_tpu.telemetry import trace

    rng = random.Random(seed)
    t, evs = 50.0, [span("call", 0, 10)]
    for _ in range(rng.randint(1, 30)):
        start = t + rng.choice([-1, 1]) * rng.uniform(0, 40)
        start = max(start, 1.0)
        dispatched = start + rng.uniform(0, 3)
        end = max(t, dispatched) + rng.uniform(1, 60)
        evs.append(device("we.fused.device", start, dispatched, end))
        t = end
    ours = devline.timeline(evs, "we")
    theirs = trace.device_timeline(evs, since=0.0)
    assert ours["starved_s"] == pytest.approx(theirs["starved_s"], abs=1e-12)
    assert ours["runs_ms"] == pytest.approx(
        [r["run_ms"] for r in theirs["runs"]])
    assert sum(theirs["by_owner"].values()) == pytest.approx(
        ours["starved_s"], abs=1e-12)


def test_read_goes_through_the_programs_ring():
    from multiverso_tpu.telemetry import trace

    saved = trace.TRACER.events()
    try:
        trace.TRACER.reset()
        ctx = {"trace": TRACE}
        assert devline.read("devline.starved_share.we", ctx) is None
        with trace.TRACER._lock:
            trace.TRACER._events.extend(fused_events())
        assert devline.read("devline.run_max_over_p50.we",
                            ctx) == pytest.approx(1.6)
    finally:
        trace.TRACER.reset()
        with trace.TRACER._lock:
            trace.TRACER._events.extend(saved)
