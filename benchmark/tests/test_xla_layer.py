"""``layers/xla.py`` on a synthetic list of the program's span records
(the four values, ``None`` without the record or its counts, set-up told
from the window and from ``check`` as ``layers/prog.py`` tells them), and
the traced line of one language-model cell at ``--cpu-tiny`` sizes."""

import itertools
import json
import os

import pytest

from benchmark.layers import xla
from conftest import ROOT, run_cell

MS = 1e3
_ids = itertools.count(1)
CELL = "nemotron3n-train-16k"
NAMES = ("xla.scoped_ops_share.lm", "xla.program_memory_gb.lm",
         "xla.temp_memory_gb.lm", "xla.lower_s.setup")


def span(name, ts_ms, dur_ms, prof=False, **args):
    return {"name": name, "ts": ts_ms * MS, "dur": dur_ms * MS,
            "id": next(_ids), "parent": None, "request": None,
            "prof": prof, "args": args}


def program(ts_ms, name="lm.step", scoped=900, **over):
    counts = dict(program=name, module="jit_step", instructions=1200,
                  scoped=scoped, scopes={}, recompiled=0,
                  argument_bytes=8_000_000_000, output_bytes=8_100_000_000,
                  alias_bytes=7_900_000_000, temp_bytes=2_500_000_000,
                  code_bytes=40_000_000)
    return span("xla.program", ts_ms, 300, **dict(counts, **over))


def events():
    out = [span("xla.compile", 100, 700, seconds=0.7, event="compile",
                trace_s=1.5, lower_s=0.25),
           span("xla.compile", 900, 50, seconds=0.05, event="cache_load",
                trace_s=0.125, lower_s=0.0625),
           # a calibration's program, then the step's, twice: the last counts
           program(1000, name="lm.forward", scoped=10),
           program(2000, scoped=600, temp_bytes=9_000_000_000),
           program(3000)]
    # the window (prof true) and, after it, what a check compiles
    out += [span("lm.step", 10_000 + 900 * k, 850, prof=True)
            for k in range(3)]
    out += [span("xla.compile", 20_000, 4000, seconds=4.0, event="compile",
                 trace_s=30.0, lower_s=9.0),
            program(21_000, scoped=1)]
    return out


WANT = {"xla.scoped_ops_share.lm": 75.0,
        "xla.program_memory_gb.lm": 8.0 + 8.1 - 7.9 + 2.5 + 0.04,
        "xla.temp_memory_gb.lm": 2.5,
        "xla.lower_s.setup": 1.5 + 0.25 + 0.125 + 0.0625}


@pytest.mark.parametrize("name", NAMES)
def test_reads_each_value_from_the_set_ups_records(name):
    assert xla.read_events(name, events()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES + ("xla.unknown.lm",))
def test_a_program_without_the_record_reads_none(name):
    assert xla.read_events(name, []) is None
    # the parent's ring: compiles without the two counts, no xla.program
    old = [span("xla.compile", 0, 700, seconds=0.7, event="compile"),
           span("lm.step", 10_000, 850, prof=True)]
    assert xla.read_events(name, old) is None
    # another program's record is not the step's
    other = [program(0, name="we.blocks")]
    assert xla.read_events(name, other) is None


def test_an_untraced_run_counts_everything_as_set_up():
    evs = [e for e in events() if not e["prof"]]
    assert xla.read_events("xla.lower_s.setup", evs) == pytest.approx(
        WANT["xla.lower_s.setup"] + 39.0)
    assert xla.read_events("xla.scoped_ops_share.lm", evs) == pytest.approx(
        100.0 / 1200)


def test_read_goes_through_the_programs_ring():
    from multiverso_tpu.telemetry import trace

    saved = trace.TRACER.events()
    try:
        trace.TRACER.reset()
        assert xla.read("xla.temp_memory_gb.lm", {}) is None
        t0 = 1_000_000_000
        trace.record("xla.program", t0, t0 + 1000, program="lm.step",
                     instructions=4, scoped=3, temp_bytes=5_000_000_000)
        assert xla.read("xla.temp_memory_gb.lm", {}) == pytest.approx(5.0)
        assert xla.read("xla.scoped_ops_share.lm", {}) == pytest.approx(75.0)
    finally:
        trace.TRACER.reset()
        for e in saved:
            trace.TRACER._events.append(e)


def test_the_four_entries_list_their_cells_and_nothing_else_changed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"]: m for m in spec["per_layer"]
            if m["name"].startswith("xla.")}
    assert set(mine) == set(NAMES)
    assert [m["name"] for m in spec["per_layer"][-4:]] == list(NAMES)
    lm = [w["name"] for w in spec["workloads"]
          if w["traffic"].startswith("lm-train")]
    assert len(lm) == 5
    for name in NAMES[:3]:
        assert mine[name]["workloads"] == lm
        assert mine[name]["moves"] == "words_per_s"
    assert mine["xla.lower_s.setup"]["workloads"] == [
        w["name"] for w in spec["workloads"]]
    assert mine["xla.lower_s.setup"]["moves"] == "setup_s"
    layers = {m["layer"] for m in spec["per_layer"]
              if not m["name"].startswith("xla.")}
    assert {m["layer"] for m in mine.values()} <= layers


def test_a_traced_line_of_a_language_model_cell_reports_the_four():
    result, lines = run_cell(ROOT, CELL, trace=1, seed=2147483019)
    got = result["metrics"]
    assert set(NAMES) <= set(got)
    assert 50.0 < got["xla.scoped_ops_share.lm"]["value"] <= 100.0
    assert (got["xla.program_memory_gb.lm"]["value"]
            > got["xla.temp_memory_gb.lm"]["value"] > 0)
    assert got["xla.lower_s.setup"]["value"] > 0
    assert got["xla.program_memory_gb.lm"]["unit"] == "GB"
    detail = next(json.loads(line)["detail"] for line in lines
                  if line.startswith('{"detail"'))
    assert detail["compiles_in_window"] == 0
