"""The one span primitive (telemetry/trace.py) and its sites on the
measured paths: parents and self time against a brute-force oracle, the
two classes and their gates, ``prof`` and the profiler's own trace, the
spans and counts ``train_fused``, the device-plane ``train_ps_blocks``
and a table build leave, the device-completion watcher, and the scope
names the traced regions carry."""

import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.ops import row_combine
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard


def _ev(i, ts, dur, parent=None, name="s"):
    return {"name": name, "id": i, "parent": parent, "ts": float(ts),
            "dur": float(dur)}


def _oracle_self_us(events, span_id):
    """Microseconds of the span no child covers, one at a time."""
    e = next(x for x in events if x["id"] == span_id)
    kids = [x for x in events if x["parent"] == span_id]
    return sum(1 for t in range(int(e["ts"]), int(e["ts"] + e["dur"]))
               if not any(k["ts"] <= t < k["ts"] + k["dur"] for k in kids))


SELF_CASES = {
    "leaf": [_ev(1, 0, 100)],
    "one_child": [_ev(1, 0, 100), _ev(2, 10, 30, 1)],
    "disjoint_children": [_ev(1, 0, 100), _ev(2, 10, 20, 1),
                          _ev(3, 50, 25, 1)],
    "overlapping_children": [_ev(1, 0, 100), _ev(2, 10, 40, 1),
                             _ev(3, 30, 40, 1)],
    "grandchild_counts_once": [_ev(1, 0, 100), _ev(2, 10, 50, 1),
                               _ev(3, 20, 10, 2)],
    "child_overruns_parent": [_ev(1, 0, 100), _ev(2, 80, 50, 1)],
    # another thread's span of the same interval has its own parent chain
    "cross_thread_is_no_child": [_ev(1, 0, 100), _ev(2, 10, 30, 1),
                                 _ev(3, 0, 100), _ev(4, 5, 90, 3)],
}


@pytest.mark.parametrize("case", sorted(SELF_CASES))
def test_self_ms_matches_brute_force(case):
    events = SELF_CASES[case]
    got = ttrace.self_ms(events)
    assert set(got) == {e["id"] for e in events}
    for e in events:
        assert got[e["id"]] == pytest.approx(
            _oracle_self_us(events, e["id"]) * 1e-3)


def test_parent_comes_from_the_threads_own_stack():
    tr = ttrace.Tracer()
    seen = {}

    def other():
        with tr.span("b.outer") as o:
            with tr.span("b.inner", cause=seen["a"]) as i:
                seen["b"] = (o.id, i.id, i.parent, o.parent)

    with tr.span("a.outer", request=7) as a:
        seen["a"] = a.id
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tr.span("a.inner") as inner:
            assert inner.parent == a.id
        tr.add_span("fine", 0.0, 1.0)        # trace_ids off: nothing
        t_late = time.time_ns()
        time.sleep(0.001)
        tr.record("a.late", t_late, time.time_ns())   # after the fact
    o_id, i_id, i_parent, o_parent = seen["b"]
    assert o_parent is None and i_parent == o_id    # not a.outer
    by = {e["name"]: e for e in tr.events()}
    assert set(by) == {"a.outer", "a.inner", "a.late", "b.outer", "b.inner"}
    assert by["b.inner"]["cause"] == a.id
    assert by["a.late"]["parent"] == a.id
    assert by["a.outer"]["request"] == 7 and by["a.outer"]["parent"] is None
    assert by["a.outer"]["tid"] != by["b.outer"]["tid"]
    live = ttrace.self_ms(tr.events())
    assert live[a.id] <= by["a.outer"]["dur"] * 1e-3
    assert live[a.id] == pytest.approx(
        (by["a.outer"]["dur"] - by["a.inner"]["dur"]
         - by["a.late"]["dur"]) * 1e-3, abs=1e-3)   # ts is a float of us


@pytest.mark.parametrize("site,recorded", [
    ("span", True), ("record", True), ("add_span", False),
    ("add_span_with_trace_ids", True)])
def test_coarse_always_fine_only_with_trace_ids(site, recorded):
    mv.init()            # every flag at its default
    before = len(ttrace.events())
    if site == "add_span_with_trace_ids":
        config.set_flag("trace_ids", True)
        ttrace.configure()
    assert ttrace.enabled() is (site == "add_span_with_trace_ids")
    if site == "span":
        with ttrace.span("t.site", request=3, rows=5) as s:
            s.set(more=1)
    elif site == "record":
        ttrace.record("t.site", 10, 20, request=3, rows=5)
    else:
        ttrace.add_span("t.site", 1.0, 2.0, trace=3, args={"rows": 5})
    new = [e for e in ttrace.events()[before:] if e["name"] == "t.site"]
    assert len(new) == (1 if recorded else 0)
    if recorded:
        e = new[0]
        assert e["request"] == 3 and e["args"]["rows"] == 5
        assert e["ph"] == "X" and e["prof"] is False and e["id"] > 0


def test_enabled_is_one_attribute_read():
    assert ttrace.enabled() is ttrace.TRACER.enabled
    ttrace.TRACER.enabled = True
    assert ttrace.enabled() is True


def test_span_feeds_the_monitor_of_its_name():
    with ttrace.span("t.mon"):
        time.sleep(0.002)
    snap = Dashboard.snapshot()["t.mon"]
    [e] = [e for e in ttrace.events() if e["name"] == "t.mon"]
    assert snap.count == 1
    assert snap.total_ms == pytest.approx(e["dur"] * 1e-3, rel=1e-6)


def test_span_is_recorded_when_its_body_raises():
    with pytest.raises(KeyError):
        with ttrace.span("t.raises"):
            raise KeyError("x")
    assert [e["name"] for e in ttrace.events()] == ["t.raises"]
    with ttrace.span("t.after") as s:
        assert s.parent is None              # the stack was unwound


def test_phase_and_step_are_counts_on_the_record():
    with ttrace.span("t.step", step=1):
        with ttrace.span("t.push", phase="push"):
            time.sleep(0.002)
    by = {e["name"]: e for e in ttrace.events()}
    assert by["t.push"]["args"] == {"phase": "push"}
    assert by["t.step"]["args"] == {"step": 1}
    [rec] = ttrace.step_report(ttrace.events())
    assert rec["phases"]["push"]["count"] == 1


def test_ring_stays_bounded(monkeypatch):
    monkeypatch.setattr(ttrace, "_MAX_EVENTS", 50)
    tr = ttrace.Tracer()
    for i in range(500):
        with tr.span("t.many", request=i):
            pass
    events = tr.events()
    assert len(events) == 50
    assert events[-1]["request"] == 499      # the newest are kept


def test_no_annotation_and_no_watcher_at_defaults(monkeypatch):
    class Refuse:
        is_enabled = staticmethod(lambda: False)

        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation opened with no profiler")

    monkeypatch.setattr(ttrace, "TraceAnnotation", Refuse)
    threads = threading.active_count()
    with ttrace.DeviceWatcher() as w:
        with ttrace.span("t.quiet", request=1):
            w.watch("t.device", jnp.ones(3), time.time_ns(), request=1)
        assert w._thread is None
        assert threading.active_count() == threads
    assert [e["name"] for e in ttrace.events()
            if e["name"].startswith("t.")] == ["t.quiet"]


def test_watcher_closes_spans_in_order_when_trace_ids_is_on():
    ttrace.TRACER.enabled = True
    t0 = time.time_ns()
    with ttrace.DeviceWatcher() as w:
        for i in range(5):
            w.watch("t.device", jnp.full((4,), i) * 2, t0, request=i,
                    cause=100 + i)
        assert w._thread is not None and w._thread.is_alive()
        thread = w._thread
    assert not thread.is_alive() and w._thread is None
    done = [e for e in ttrace.events() if e["name"] == "t.device"]
    assert [e["request"] for e in done] == list(range(5))
    assert [e["cause"] for e in done] == [100 + i for i in range(5)]
    ends = [e["ts"] + e["dur"] for e in done]
    assert ends == sorted(ends) and all(e["parent"] is None for e in done)
    # when watch() was called: the dispatch had returned, the device was
    # not done (stamps are ns, ts and dur floats of us)
    for e in done:
        assert e["cat"] == "device" and e["prof"] is False
        assert e["ts"] <= e["args"]["dispatched"] * 1e-3 <= (
            e["ts"] + e["dur"] + 1e-3)
        assert e["args"]["dispatched"] > t0


# ---------------------------------------------------------------------- #
# the device's timeline, from span records alone
# ---------------------------------------------------------------------- #
def _prog(i, ts, dur, tid=1, name="p", parent=None):
    return {"name": name, "cat": "prog", "id": i, "parent": parent,
            "cause": None, "request": None, "tid": tid, "ts": float(ts),
            "dur": float(dur), "args": {}}


def _dev(i, ts, dispatched, end, cause=None, request=None, name="d"):
    """A device span as the watcher records it: ``dispatched`` in ns."""
    return {"name": name, "cat": "device", "id": i, "parent": None,
            "cause": cause, "request": request, "tid": 99, "ts": float(ts),
            "dur": float(end - ts), "args": {"dispatched": dispatched * 1000}}


def _oracle_starved_us(timeline_events, lo, hi):
    """Whole microseconds of [lo, hi) in which no device span is in
    flight, one at a time."""
    devs = [e for e in timeline_events if e["cat"] == "device"]
    return sum(1 for t in range(int(lo), int(hi)) if not any(
        e["args"]["dispatched"] / 1000 <= t < e["ts"] + e["dur"]
        for e in devs))


TIMELINES = {
    # two programs in flight at once, back to back on the device: the
    # second's run counts from the first's end, and nothing is starved
    "overlap": dict(
        events=[_prog(1, 0, 1000, name="call"),
                _prog(2, 0, 20, name="dispatch.a", parent=1),
                _prog(3, 100, 20, name="dispatch.b", parent=1),
                _dev(10, 0, 20, 500, cause=2, request=1),
                _dev(11, 100, 120, 900, cause=3, request=2)],
        starved=[], runs=[0.480, 0.400]),
    # a gap between two programs, filed under the innermost span open on
    # the dispatching thread (tid 1), not under another thread's (tid 2)
    "gap_innermost": dict(
        events=[_prog(1, 0, 1000, name="call"),
                _prog(2, 0, 20, name="dispatch", parent=1),
                _prog(3, 500, 300, name="call.count", parent=1),
                _prog(4, 550, 100, name="call.count.inner", parent=3),
                _prog(5, 400, 500, tid=2, name="other.thread"),
                _prog(6, 805, 10, name="dispatch", parent=1),
                _dev(10, 0, 20, 500, cause=2, request=1),
                _dev(11, 805, 815, 990, cause=6, request=2)],
        starved=[(500, 815, "call.count")], runs=[0.480, 0.175]),
    # between two calls no span of the program is open
    "gap_between_calls": dict(
        events=[_prog(1, 0, 510, name="call"),
                _prog(2, 0, 20, name="dispatch", parent=1),
                _prog(3, 700, 300, name="call"),
                _prog(4, 700, 20, name="dispatch", parent=3),
                _dev(10, 0, 20, 500, cause=2, request=1),
                _dev(11, 700, 720, 990, cause=4, request=2)],
        starved=[(500, 720, ttrace.NO_SPAN)], runs=[0.480, 0.270]),
    # with a lower bound before the first dispatch, the lead-in counts
    "lead_in": dict(
        since=-50.0,
        events=[_prog(1, -50, 1000, name="call"),
                _prog(2, -10, 30, name="dispatch", parent=1),
                _dev(10, -10, 20, 500, cause=2, request=1)],
        starved=[(-50, 20, "call")], runs=[0.480]),
    # a record without the count (an older file) is in flight from its
    # start; a cause that is not in the list names no thread
    "no_count_no_cause": dict(
        events=[_prog(1, 0, 1000, name="call"),
                dict(_dev(10, 0, 0, 400), args={}),
                _dev(11, 600, 610, 900, cause=12345)],
        starved=[(400, 610, ttrace.NO_SPAN)], runs=[0.400, 0.290]),
}


@pytest.mark.parametrize("case", sorted(TIMELINES))
def test_device_timeline_on_a_synthetic_list(case):
    c = TIMELINES[case]
    got = ttrace.device_timeline(c["events"], since=c.get("since"))
    assert got["starved"] == [tuple(map(float, s[:2])) + (s[2],)
                              for s in c["starved"]]
    assert [r["run_ms"] for r in got["runs"]] == pytest.approx(c["runs"])
    assert [r["request"] for r in got["runs"]] == [
        e["request"] for e in c["events"] if e["cat"] == "device"]
    by_owner = {}
    for a, b, owner in c["starved"]:
        by_owner[owner] = by_owner.get(owner, 0.0) + (b - a) * 1e-6
    assert got["by_owner"] == pytest.approx(by_owner)
    assert got["starved_s"] == pytest.approx(sum(by_owner.values()))
    if all("dispatched" in e["args"] for e in c["events"]
           if e["cat"] == "device"):
        assert got["starved_s"] * 1e6 == pytest.approx(
            _oracle_starved_us(c["events"], got["lo"], got["hi"]))
    # in flight and starved tile [lo, hi]
    covered = sum(b - a for a, b in got["in_flight"]) + sum(
        b - a for a, b, _ in got["starved"])
    assert covered == pytest.approx(got["hi"] - got["lo"])


@pytest.mark.parametrize("case", ["gap_innermost", "no_device_span"])
def test_dump_metrics_timeline_prints_the_summary(case, tmp_path, capsys):
    """The operator's reading of a ``trace-rank<r>.jsonl``."""
    import json

    from tools import dump_metrics

    events = (TIMELINES[case]["events"] if case in TIMELINES
              else [_prog(1, 0, 10)])
    path = tmp_path / "trace-rank0.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    assert dump_metrics.main(["timeline", str(path)]) == 0
    text = capsys.readouterr().out
    if case == "no_device_span":
        assert "no device span" in text and "trace_ids" in text
        return
    # 315 us starved of 970 (first dispatch to last ready), all of it
    # under call.count; the longer run first, with the request that names it
    assert "2 programs" in text and "32.474%" in text
    lines = text.splitlines()
    [owner] = [ln for ln in lines if ln.endswith("call.count")]
    assert float(owner.split()[0]) == pytest.approx(315e-6)
    runs = [ln for ln in lines if "request=" in ln]
    assert [ln.split()[-1] for ln in runs] == ["request=1", "request=2"]
    assert float(runs[0].split()[0]) == pytest.approx(0.480)
    assert "table writes" not in text       # no call with the counts


def test_dump_metrics_timeline_prints_the_calls_table_writes(tmp_path,
                                                             capsys):
    """ISSUE 40: the counts on ``we.blocks`` (and ``we.fused``) have an
    operator's reader."""
    import json

    from tools import dump_metrics

    call = {**_prog(7, 0, 900), "name": "we.blocks", "request": 3,
            "args": {"plane": "device", "blocks": 1, "words": 50,
                     "update_rows": 2000, "unique_rows": 1480,
                     "head_rows": 400, "walk_slots": 1280,
                     "kernel_rows": 1080}}
    # a trace from before ISSUE 45 carries no kernel_rows: none walked
    fused = {**_prog(8, 0, 900), "name": "we.fused", "request": 4,
             "args": {"update_rows": 100, "unique_rows": 50,
                      "head_rows": 20, "walk_slots_by_shard": [16, 16]}}
    path = tmp_path / "trace-rank0.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in (
        TIMELINES["gap_innermost"]["events"] + [call, fused])))
    assert dump_metrics.main(["timeline", str(path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    [blocks] = [ln for ln in lines if "we.blocks request=3" in ln]
    # ISSUE 45: kernel_rows over the rows past the heads, 1.00 where the
    # tile kernel walked them all
    assert blocks.split()[2:] == ["2000", "1480", "(74.0%)", "400", "1280",
                                  "1.00"]
    [fused_line] = [ln for ln in lines if "we.fused request=4" in ln]
    assert fused_line.split()[2:] == ["100", "50", "(50.0%)", "20", "32",
                                      "0.00"]


def test_device_timeline_without_device_spans_is_none():
    assert ttrace.device_timeline([_prog(1, 0, 10)]) is None
    assert ttrace.device_timeline([]) is None


def _xplane_names(trace_dir):
    from jax.profiler import ProfileData
    [path] = glob.glob(str(trace_dir / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("t.", "mv.trace")):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return found


def test_prof_is_false_at_rest_and_true_inside_a_profiler_trace(tmp_path):
    assert ttrace.profiling() is False
    with ttrace.span("t.rest"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert ttrace.profiling() is True
        with ttrace.span("t.traced", request=41, rows=2):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    with ttrace.span("t.after"):
        pass
    by = {e["name"]: e for e in ttrace.events()}
    assert by["t.rest"]["prof"] is False and by["t.after"]["prof"] is False
    assert by["t.traced"]["prof"] is True
    # the same span lies in the profiler's own trace, with its request,
    # and the anchor ties the two clocks to within a few hundred us here
    names = _xplane_names(tmp_path)
    assert "t.rest" not in names and "t.after" not in names
    [(start_ns, stats)] = names["t.traced"]
    assert stats["request"] == 41
    anchor = by[ttrace.ANCHOR]
    [(a_ns, a_stats)] = [x for x in names[ttrace.ANCHOR]
                         if "time_ns" in x[1]]
    assert int(a_stats["time_ns"]) == anchor["args"]["time_ns"]
    offset = a_ns - int(a_stats["time_ns"])
    assert abs(start_ns - (by["t.traced"]["ts"] * 1e3 + offset)) < 5e5


# ---------------------------------------------------------------------- #
# the sites
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shape,dtype", [((37, 8), "float32"),
                                         ((12, 3), "int32"),
                                         ((100,), "float32")])
def test_table_init_carries_rows_width_bytes(shape, dtype):
    mv.init()
    if len(shape) == 2:
        t = mv.MatrixTable(*shape, dtype=dtype, name="spanned")
    else:
        t = mv.ArrayTable(shape[0], dtype=dtype, name="spanned")
    events = ttrace.events()
    [init] = [e for e in events if e["name"] == "table.init"]
    a = init["args"]
    rows, width = t.padded_shape[0], int(np.prod(t.padded_shape[1:]))
    assert (a["table"], a["rows"], a["width"]) == ("spanned", rows, width)
    assert a["bytes"] == rows * width * np.dtype(dtype).itemsize
    assert a["bytes"] == t.raw().nbytes
    # a table of zeros is filled on the devices: no host work, no shard
    kids = {e["name"] for e in events if e["parent"] == init["id"]}
    assert kids == {"table.init.zeros"}
    assert (a["shards"], a["host_bytes"]) == (t._num_shards, 0)
    assert init["parent"] is None and init["prof"] is False


def test_compile_leaves_an_xla_compile_span():
    mv.init()
    before = len(ttrace.events())
    with ttrace.span("t.caller") as s:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    new = [e for e in ttrace.events()[before:] if e["name"] == "xla.compile"]
    assert new, "the devstats listener recorded no compile"
    e = new[-1]
    assert e["args"]["event"] in ("compile", "cache_load")
    assert e["args"]["seconds"] > 0 and "mv" in e["args"]["mesh"]
    assert e["dur"] == pytest.approx(e["args"]["seconds"] * 1e6, rel=1e-3)
    assert e["parent"] == s.id


def _tiny_we(**kw):
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    mv.init()
    tokens = synthetic_corpus(6_000, vocab=60, seed=0)
    cfg = WEConfig(**{**dict(size=8, min_count=1, batch_size=64, negative=2,
                             window=2, epoch=1, sample=0), **kw})
    we = WordEmbedding(cfg, Dictionary.build(tokens, 1))
    return we, we.prepare_ids(tokens)


# every name the measured paths left in the ring at PR 57 (the parent of
# ISSUE 58, which rewired the readers' ring): benchmark/layers read these,
# and a new name on one of these paths is a new record in every window
FUSED_SPANS = {"we.fused", "we.fused.pairs", "we.pairs.generate",
               "we.pairs.upload", "we.fused.dispatch", "we.fused.wait",
               "we.fused.count", "xla.compile", "xla.program"}
LM_SPANS = {"lm.step", "lm.step.wait", "xla.compile", "xla.program"}
HOST_PLANE_SPANS = {"we.blocks", "we.prepare", "we.block", "we.push",
                    "we.blocks.drain", "xla.compile"}
# ... and what ISSUE 58 made spans of on the host plane, which no cell
# runs: the step round a block and the phases the step profiler marked
HOST_PLANE_STEP_SPANS = {"we.step", "we.block.wait_prepared", "we.pipeline",
                         "we.block.ps_wait", "we.block.compute"}


def _children(events, parent):
    # what the compiler's listener and the program's map leave under a
    # call (xla.compile, xla.program) is no part of the call's own shape
    return [e["name"] for e in sorted(events, key=lambda e: e["ts"])
            if e["parent"] == parent["id"]
            and e["name"] not in ("xla.compile", "xla.program")]


@pytest.mark.parametrize("mode", ["sg_shared", "sg", "cbow", "hs"])
def test_train_fused_leaves_its_spans_and_counts(mode):
    kw = {"sg_shared": {}, "sg": {"shared_negatives": 0},
          "cbow": {"cbow": 1}, "hs": {"hs": 1, "negative": 0}}[mode]
    we, ids = _tiny_we(**kw)
    start = len(ttrace.events())
    out1 = we.train_fused(ids, epochs=2)
    first = ttrace.events()[start:]
    mid = len(ttrace.events())
    out2 = we.train_fused(ids, epochs=2)
    second = ttrace.events()[mid:]
    assert np.isfinite(out1["loss"]) and np.isfinite(out2["loss"])
    # no "we.fused.copy": the epochs run on the tables' own buffers
    want = ["we.fused.pairs", "we.fused.dispatch", "we.fused.wait",
            "we.fused.count"]
    for events, hit in ((first, 0), (second, 0 if mode == "cbow" else 1)):
        assert {e["name"] for e in events} <= FUSED_SPANS
        [call] = [e for e in events if e["name"] == "we.fused"]
        assert _children(events, call) == want
        a = call["args"]
        assert a["words"] == 2 * ids.size and a["epochs"] == 2
        assert a["pairs"] == out1["pairs"] > 0
        assert a["batches"] == a["pairs"] // we.cfg.batch_size
        # only the shared-negatives epoch combines its update rows
        # (ISSUE 28), and only it says how far
        assert ("unique_rows" in a) == ("update_rows" in a) == (
            "head_rows" in a) == (mode == "sg_shared")
        if mode == "sg_shared":
            assert a["update_rows"] == 2 * 2 * a["pairs"]
            assert 0 < a["unique_rows"] < a["update_rows"]
            # a table of 61 rows is all head (ISSUE 31)
            assert a["head_rows"] == a["unique_rows"]
            assert a["kernel_rows"] == 0        # ISSUE 45: XLA's walk
            # the count before combining is what it was: pool rows too
            assert sum(a["update_rows_by_shard"]) == (
                a["update_rows"] + 2 * a["batches"] * we.cfg.shared_negatives)
        assert call["parent"] is None and call["request"] > 0
        [pairs] = [e for e in events if e["name"] == "we.fused.pairs"]
        assert pairs["args"]["cache_hit"] == hit
        assert pairs["args"]["pairs"] == a["pairs"]
        made = _children(events, pairs)
        if hit:
            assert made == [] and "h2d_bytes" not in pairs["args"]
        else:
            assert made == ["we.pairs.generate", "we.pairs.upload"]
            assert pairs["args"]["h2d_bytes"] > 0
        [disp] = [e for e in events if e["name"] == "we.fused.dispatch"]
        assert disp["args"]["programs"] == 2
        # the parts lie inside the call and leave little of it unnamed
        assert ttrace.self_ms(events)[call["id"]] <= call["dur"] * 1e-3
    assert second[-1]["request"] == first[-1]["request"] + 1


class _NoThread:
    """In the watcher's place at defaults: no thread and no queue may be
    made, so nothing can be held for it."""

    def __init__(self, *a, **k):
        raise AssertionError("the watcher woke with nothing to read it")


def _quiet_watcher(monkeypatch):
    monkeypatch.setattr(ttrace.threading, "Thread", _NoThread)
    monkeypatch.setattr(ttrace.queue, "SimpleQueue", _NoThread)


def _watcher_threads():
    return [t for t in threading.enumerate() if t.name == "mv-trace-watcher"]


@pytest.mark.parametrize("mode", ["defaults", "trace_ids"])
def test_train_fused_records_one_device_span_a_call(mode, monkeypatch):
    we, ids = _tiny_we()
    if mode == "trace_ids":
        config.set_flag("trace_ids", True)
        ttrace.configure()
    else:
        _quiet_watcher(monkeypatch)
    start = len(ttrace.events())
    for _ in range(2):
        we.train_fused(ids, epochs=2)
    # the app's watcher outlives a call (no thread start and join a
    # call); closing it waits for what it was handed
    assert len(_watcher_threads()) == (mode == "trace_ids")
    we._watcher.close()
    assert not _watcher_threads() and we._watcher._queue is None
    events = ttrace.events()[start:]
    done = [e for e in events if e["cat"] == "device"]
    if mode == "defaults":
        assert done == []
        return
    calls = [e for e in events if e["name"] == "we.fused"]
    disps = [e for e in events if e["name"] == "we.fused.dispatch"]
    assert [e["name"] for e in done] == ["we.fused.device"] * 2
    for dev, call, disp in zip(done, calls, disps):
        assert dev["request"] == call["request"] and dev["parent"] is None
        assert dev["cause"] == disp["id"] and disp["parent"] == call["id"]
        # from before the dispatch until ready (the call waits for the
        # same loss); in flight from the dispatch's return
        assert call["ts"] <= dev["ts"] <= disp["ts"]
        assert dev["args"]["dispatched"] * 1e-3 >= (
            disp["ts"] + disp["dur"] - 1e-3)
    line = ttrace.device_timeline(events)
    assert [r["request"] for r in line["runs"]] == [
        c["request"] for c in calls]
    # whatever was starved (the seam between the calls, where the
    # watcher's stamp was not late) is the main thread's: the caller's
    # own code, or a span of a call
    assert {owner for _, _, owner in line["starved"]} <= {
        ttrace.NO_SPAN, "we.fused", "we.fused.dispatch", "we.fused.pairs",
        "we.fused.wait", "we.fused.count"}


BLOCK_SPANS = {"we.blocks", "we.prepare", "we.prepare.arrays",
               "we.prepare.pack", "we.prepare.put", "we.block.wait_prepared",
               "we.block.dispatch", "we.blocks.drain"}


@pytest.mark.parametrize("mode", ["defaults", "profiler", "trace_ids"])
def test_device_plane_blocks_leave_their_spans_and_counts(mode, tmp_path):
    we, ids = _tiny_we(use_ps=1, data_block_size=1500)
    assert we._use_device_plane(1)
    we.train_ps_blocks(ids, epochs=1)           # compile outside the case
    n_blocks = -(-ids.size // 1500)
    if mode == "trace_ids":
        config.set_flag("trace_ids", True)
        ttrace.configure()
    if mode == "profiler":
        jax.profiler.start_trace(str(tmp_path))
    threads = {t.name for t in threading.enumerate()}
    start = len(ttrace.events())
    try:
        out = we.train_ps_blocks(ids, epochs=1)
    finally:
        if mode == "profiler":
            jax.profiler.stop_trace()
    events = [e for e in ttrace.events()[start:]
              if e["name"] not in ("xla.compile", ttrace.ANCHOR)]
    assert np.isfinite(out["loss"])
    assert not any(t.name == "mv-trace-watcher"
                   for t in threading.enumerate())
    watched = mode != "defaults"
    names = {e["name"] for e in events}
    assert names == BLOCK_SPANS | ({"we.block.device"} if watched else set())
    # a call and its drain, and six spans a block (seven when watched)
    assert len(events) == 2 + n_blocks * (7 if watched else 6)
    assert all(e["prof"] is (mode == "profiler") for e in events)
    [call] = [e for e in events if e["name"] == "we.blocks"]
    # ISSUE 40: what the scans' table writes were handed, as we.fused says
    # it: a pair's centre, context and negatives, and what combining left
    rows = {k: call["args"].pop(k) for k in (
        "update_rows", "unique_rows", "head_rows", "walk_slots",
        "kernel_rows")}
    assert call["args"] == {"plane": "device", "blocks": n_blocks,
                            "words": int(ids.size)}
    by = {n: sorted((e for e in events if e["name"] == n),
                    key=lambda e: e["request"]) for n in names}
    for n in ("we.prepare", "we.block.wait_prepared", "we.block.dispatch"):
        assert [e["request"] for e in by[n]] == list(range(n_blocks)), n
    assert rows["update_rows"] == (2 + we.cfg.negative) * sum(
        e["args"]["pairs"] for e in by["we.prepare"])
    assert 0 < rows["head_rows"] <= rows["unique_rows"] < rows["update_rows"]
    assert rows["walk_slots"] % min(row_combine.CHUNK,
                                    we.cfg.batch_size) == 0
    # ISSUE 45: off the chip XLA's scatter walks the lane-wide bucket
    assert rows["kernel_rows"] == 0
    for prep, disp in zip(by["we.prepare"], by["we.block.dispatch"]):
        a = prep["args"]
        assert 0 < a["rows_touched"] <= a["rows_bucket"]
        assert a["rows_bucket"] <= we.table_in.padded_shape[0]
        assert a["pairs"] > 0 and a["h2d_bytes"] > 0
        assert a["minibatches"] * we.cfg.batch_size >= a["pairs"]
        assert _children(events, prep) == [
            "we.prepare.arrays", "we.prepare.pack", "we.prepare.put"]
        assert prep["tid"] != call["tid"] and prep["parent"] is None
        assert disp["cause"] == prep["id"] and disp["parent"] == call["id"]
    assert all("queue_depth" in e["args"] and e["parent"] == call["id"]
               for e in by["we.block.wait_prepared"])
    [drain] = by["we.blocks.drain"]
    assert drain["args"]["blocks"] == n_blocks
    if watched:
        done = by["we.block.device"]
        assert [e["request"] for e in done] == list(range(n_blocks))
        for dev, disp in zip(done, by["we.block.dispatch"]):
            assert dev["cause"] == disp["id"]
            assert dev["ts"] <= disp["ts"] + 1.0      # from the dispatch
            assert dev["ts"] + dev["dur"] <= call["ts"] + call["dur"]
    # the dispatch is its own monitor now; a block is we.block.device
    snap = Dashboard.snapshot()
    assert "we.block" not in snap
    assert snap["we.block.dispatch"].count >= n_blocks
    assert snap["we.prepare"].count >= n_blocks


def test_blocks_count_the_rows_the_tile_kernel_walked(monkeypatch):
    """ISSUE 45: where the block's lane-wide bucket takes the tile kernel
    (here in the interpreter, with a head of 8 rows so that a walk is
    left), ``kernel_rows`` is every distinct row past the heads; the
    fused epoch's tables are 8 wide and keep XLA's walk."""
    monkeypatch.setattr(row_combine, "HEAD", 8)
    monkeypatch.setattr(row_combine, "_kernel_interpret", lambda: True)
    we, ids = _tiny_we(use_ps=1, data_block_size=3000)
    start = len(ttrace.events())
    assert np.isfinite(we.train_ps_blocks(ids, epochs=1)["loss"])
    assert np.isfinite(we.train_fused(ids, epochs=1)["loss"])
    args = {e["name"]: e["args"] for e in ttrace.events()[start:]
            if e["name"] in ("we.blocks", "we.fused")}
    blocks, fused = args["we.blocks"], args["we.fused"]
    assert blocks["kernel_rows"] == (
        blocks["unique_rows"] - blocks["head_rows"]) > 0
    assert fused["kernel_rows"] == 0 < fused["unique_rows"]


def test_host_plane_keeps_its_block_monitors():
    we, ids = _tiny_we(use_ps=1, data_block_size=1500, ps_device_plane="0")
    Dashboard.reset()
    start = len(ttrace.events())
    we.train_ps_blocks(ids, epochs=1)
    snap = Dashboard.snapshot()
    n_blocks = -(-ids.size // 1500)
    for name in ("we.prepare", "we.block", "we.push"):
        assert snap[name].count == n_blocks, name
    [call] = [e for e in ttrace.events() if e["name"] == "we.blocks"]
    assert call["args"]["plane"] == "host"
    names = {e["name"] for e in ttrace.events()[start:]}
    assert HOST_PLANE_SPANS - {"xla.compile"} <= names
    assert names <= HOST_PLANE_SPANS | HOST_PLANE_STEP_SPANS
    steps = ttrace.step_report(ttrace.events())
    assert [r["name"] for r in steps] == ["we.step"] * n_blocks
    # the consumer's phases (the producers prepare on their own threads)
    assert {"io_wait", "ps_wait", "compute", "push"} <= set().union(
        *(r["phases"] for r in steps))
    # the call the steps run inside is not their work: a step is covered
    # by its own spans and the producers' (where one still prepares when
    # the steps begin), and the rest of it is stall
    assert set().union(*(r["async"] for r in steps)) <= {"we.prepare"}
    assert all(r["attributed_ms"] <= r["wall_ms"] for r in steps)
    assert sum(r["stall_ms"] for r in steps) > 0
    block = ttrace.step_summary()
    assert block["steps"] == n_blocks and 0 < block["stall_fraction"] < 1


# ---------------------------------------------------------------------- #
# names on the device (metadata of the compiled programs)
# ---------------------------------------------------------------------- #
def _scopes(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r"mv\.[a-z_.]+", text))


def test_block_program_carries_pull_scan_push_scopes():
    we, ids = _tiny_we(use_ps=1, data_block_size=1500)
    we.train_ps_blocks(ids[:1500], epochs=1)    # builds what a block needs
    prep, _ = we._prepare_block_device(ids[:1500],
                                       np.random.default_rng(0), 0)
    si, ss = we.table_in.state, we.table_out.state
    # the negatives and the plans, made ahead in a program of their own
    batch, plans, _ = we._block_ahead_fn()(
        prep["batch"], prep["valid"], prep["ids_in"].shape[0] + 1,
        prep["remap"], prep["neg_seed"], we._neg_dev)
    text = we._fused_block_fn().lower(
        si["data"], si["ustate"], ss["data"], ss["ustate"], prep["ids_in"],
        prep["ids_sec"], prep["valid"], batch, plans).compile().as_text()
    found = set(re.findall(r"mv\.[a-z_.]+", text))
    assert {"mv.pull", "mv.scan", "mv.push", "mv.scan.gather",
            "mv.scan.grad", "mv.scan.scatter", "mv.rowapply.gather",
            "mv.rowapply.rule", "mv.rowapply.scatter"} <= found


@pytest.mark.parametrize("shared", [True, False])
def test_fused_epoch_carries_fused_scopes(shared):
    from multiverso_tpu.models import word2vec as w2v
    cfg = w2v.W2VConfig(40, 8, 2, 2, 0.025, False, False, 8 if shared else 0)
    unigram = np.full(40, 1 / 40)
    tables = (jnp.zeros((41, 8)), jnp.zeros((41, 8)))
    batches = (jnp.zeros((3, 16), jnp.int32), jnp.ones((3, 16), jnp.int32))
    if shared:
        fn = w2v.make_fused_shared_epoch(cfg, unigram, jnp.float32)
        last = jnp.asarray(w2v.init_lcg_state(8, 0))
    else:
        fn, last = w2v.make_fused_epoch(cfg, unigram), jax.random.key(0)
    text = fn.lower(*tables, *batches, last).compile().as_text()
    found = set(re.findall(r"mv\.[a-z_.]+", text))
    assert {"mv.fused", "mv.fused.gather", "mv.fused.grad",
            "mv.fused.scatter"} <= found
    assert not any(s.startswith("mv.scan") for s in found)


def test_dlrm_step_carries_dlrm_and_rule_scopes():
    from multiverso_tpu.models import dlrm
    mv.init()
    cfg = dlrm.DLRMConfig(vocab_sizes=(11, 7), embed_dim=4, dense_dim=3,
                          bottom_mlp=(8, 4), top_mlp=(8, 1))
    emb = mv.MatrixTable(dlrm.total_rows(cfg), 4, updater="adagrad")
    flat, meta = dlrm.flatten_mlp(dlrm.init_mlp_params(cfg, 0))
    mlp = mv.ArrayTable(flat.size, updater="adagrad", init=flat)
    cat, dense, labels = dlrm.synthetic_ctr(cfg, 16, 0)
    found = _scopes(dlrm.make_train_step(cfg, emb, mlp, meta), emb.state,
                    mlp.state, jnp.asarray(cat), jnp.asarray(dense),
                    jnp.asarray(labels))
    assert {"mv.dlrm.gather", "mv.dlrm.mlp", "mv.dlrm.delta",
            "mv.rowapply.rule"} <= found


def _tiny_lm():
    from multiverso_tpu.models import mla_moe
    mv.init()
    cfg = mla_moe.MLAMoEConfig(vocab=64, n_moe_layers=1, attn="xla",
                               loss_chunk=32, compute_dtype=jnp.float32)
    tables = mla_moe.make_tables(cfg, 0, 0.1, updater="adam")
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, cfg.vocab)
    return mla_moe, cfg, tables, tokens


def test_lm_step_carries_its_counts():
    mla_moe, cfg, tables, tokens = _tiny_lm()
    trainer = mla_moe.Trainer(cfg, tables)
    before = len(ttrace.events())
    _, counts = trainer.step(tokens)
    trainer.adopt()
    events = ttrace.events()[before:]
    assert {e["name"] for e in events} <= LM_SPANS
    step = next(e for e in events if e["name"] == "lm.step")
    wait = next(e for e in events if e["name"] == "lm.step.wait")
    assert wait["parent"] == step["id"] and step["request"] == 1
    layers = len(mla_moe.expert_layers(cfg))
    want = mla_moe.routing_counts(counts, cfg)
    # ... and, static, the losses' products of positions x vocabulary
    # (main and module, three each), the chunks a loss walks, and what the
    # two expert blocks keep for their backward pass: two float32
    # products' results each over a buffer of 256 rows (48 wide), the
    # buffer's int32 order and the route's int32 choice
    assert step["args"] == dict(want, tokens=64, head_products=6,
                                loss_chunks=2, kept_names=4,
                                expert_products_kept=4,
                                kept_bytes=2 * (256 * (2 * 48 * 4 + 4)
                                                + 64 * cfg.top_k * 4))
    assert want["routed_rows"] == layers * 64 * cfg.top_k
    assert 0 <= want["held_rows"] <= want["routed_rows"]
    # the rows the sorted buffers' passes walk (a layer's head of 64, the
    # even load, and where more are routed here the 192 past it as one
    # chunk) beside the rows the buffers hold
    assert step["args"]["buffer_rows"] == layers * 256
    assert step["args"]["buffer_rows_walked"] in range(
        layers * 64, layers * 256 + 1, 192)
    from tools import dump_metrics
    assert dump_metrics._buffer_lines(events)[1].split()[-3:] == [
        str(step["args"]["buffer_rows_walked"]), str(layers * 256),
        f"{step['args']['buffer_rows_walked'] / (layers * 256):.3f}"]
    assert dump_metrics._buffer_lines([{"name": "lm.step", "args": {}}]) == []
    assert want["overflow_rows"] == 0 and want["load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("mode", ["defaults", "trace_ids"])
def test_lm_steps_record_one_device_span_a_program(mode, monkeypatch):
    mla_moe, cfg, tables, tokens = _tiny_lm()
    if mode == "trace_ids":
        config.set_flag("trace_ids", True)
        ttrace.configure()
    else:
        _quiet_watcher(monkeypatch)
    trainer = mla_moe.Trainer(cfg, tables)
    before = len(ttrace.events())
    trainer.step(tokens)
    assert trainer.step_ahead(tokens) is None
    assert trainer.step_ahead(tokens) is not None
    w = trainer._watcher
    assert (w._thread is not None) == (mode == "trace_ids")
    trainer.adopt()
    assert w._thread is None and w._queue is None and not _watcher_threads()
    events = ttrace.events()[before:]
    steps = [e for e in events if e["name"] == "lm.step"]
    done = [e for e in events if e["cat"] == "device"]
    assert [e["request"] for e in steps] == [1, 2, 3, 3]   # and the drain
    if mode == "defaults":
        assert done == []
        return
    assert [e["name"] for e in done] == ["lm.step.device"] * 3
    for dev, step in zip(done, steps):
        assert dev["request"] == step["request"]
        assert dev["cause"] == step["id"] and dev["parent"] is None
        assert step["ts"] <= dev["ts"] <= dev["args"]["dispatched"] * 1e-3
    # the third step was queued while the second ran: its program was in
    # flight before the second's read-back returned
    waits = [e for e in events if e["name"] == "lm.step.wait"]
    assert done[2]["args"]["dispatched"] * 1e-3 <= waits[1]["ts"] + 1e-3
    line = ttrace.device_timeline(events)
    assert [r["request"] for r in line["runs"]] == [1, 2, 3]
    assert all(r["run_ms"] > 0 for r in line["runs"])


def test_lm_step_carries_the_flash_kernels_grid_counts():
    """With the flash kernel as the core, every ``lm.step`` span says what
    one kernel call walks a (batch x head): 32 positions in 16 x 16
    blocks (a head of 8 doubles the q block with the k block) are three
    pairs, two of them on the diagonal."""
    mla_moe, cfg, tables, tokens = _tiny_lm()
    cfg = cfg._replace(attn="flash", attn_block=8)
    assert mla_moe.attn_blocks(cfg, 32) == (16, 16)
    assert mla_moe.attn_blocks(cfg._replace(v_head_dim=256), 32) == (8, 16)
    trainer = mla_moe.Trainer(cfg, tables)
    before = len(ttrace.events())
    assert trainer.step_ahead(tokens) is None
    trainer.adopt()
    steps = [e for e in ttrace.events()[before:] if e["name"] == "lm.step"]
    assert len(steps) == 2          # the step queued, and the drain
    # blocks this small stay whole tiles: three pairs of 16 x 16 computed
    # for the 32 * 33 / 2 positions under the diagonal
    grid = {"attn_grid_steps": 3, "attn_pairs_live": 3,
            "attn_pairs_masked": 2, "attn_positions_computed": 768,
            "attn_positions_needed": 528}
    for e in steps:
        assert {k: e["args"][k] for k in grid} == grid
    assert "attn_positions_needed_window" not in steps[0]["args"]
    from tools import dump_metrics
    lines = dump_metrics._attention_lines(steps)
    assert lines[1] == "    causal  768  528  1.455"
    # what lies between the projections and the core (PR 63): every latent
    # layer's q takes the pass, float32 in and the compute dtype (float32
    # here) out, forward and made again, the core's cotangent in and the
    # product's out backward; off the chip the pass is the plain form
    heads = {"heads_layers": len(cfg.layers()), "heads_kernel_layers": 0,
             "heads_turned_bytes": len(cfg.layers()) * tokens.size
             * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
             * (2 * (4 + 4) + 2 * 4)}
    assert {k: steps[0]["args"][k] for k in heads} == heads
    assert lines[2:] == [
        f"  heads into the core: {heads['heads_layers']} layer(s), the "
        "pass's kernels in 0 (0: the plain form), "
        f"{heads['heads_turned_bytes'] / 1e6:.0f} MB a step through the "
        "pass"]
    assert dump_metrics._attention_lines([{"name": "lm.step", "args": {}}]
                                         ) == []
    assert "routed_rows" not in steps[0]["args"]     # nothing read back yet
    assert mla_moe.attn_grid(cfg._replace(attn="xla"), 32) == {}


def test_lm_step_carries_lm_and_rule_scopes():
    mla_moe, cfg, tables, tokens = _tiny_lm()
    states = {n: t.state for n, t in tables.items()}
    found = _scopes(mla_moe.make_train_step(cfg, tables), states,
                    mla_moe.init_bias(cfg), tokens)
    assert {"mv.lm.attn", "mv.lm.dense", "mv.lm.moe.route",
            "mv.lm.moe.experts", "mv.lm.moe.shared", "mv.lm.head",
            "mv.lm.mtp", "mv.rowapply.rule"} <= found


@pytest.mark.parametrize("updater", ["default", "sgd", "momentum_sgd",
                                     "adagrad", "adam", "ftrl"])
def test_every_updater_rule_is_scoped_and_unchanged(updater):
    from multiverso_tpu import updaters
    u = updaters.get_updater(updater)
    data = jnp.linspace(-1, 1, 24).reshape(6, 4)
    delta = jnp.full((6, 4), 0.25)
    state = u.init_state((6, 4), jnp.float32)
    opt = updaters.AddOption(learning_rate=0.1, rho=0.1, momentum=0.5)
    assert "mv.rowapply.rule" in _scopes(
        lambda d, s, g: u.apply(d, s, g, opt), data, state, delta)
    # the scope is metadata: the rule's own function gives the same values
    plain, _ = type(u).apply.__wrapped__(u, data, state, delta, opt)
    scoped, _ = u.apply(data, state, delta, opt)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(scoped))
