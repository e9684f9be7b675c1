"""The language-model cell's readers (``layers/lm.py``, ``layers/moe.py``,
``layers/attn.py``), the driver's own reading of the attention kernels
and ``lm_shapes.py``, on made-up span records and reductions: the values,
``None`` where the program or the trace has nothing to read, and never a
share over 100 from a sum that missed kernels."""

import itertools

import numpy as np
import pytest

from benchmark import lm_shapes, trace_reduce
from benchmark.drivers import lm_train
from benchmark.layers import attn, lm, moe
from benchmark.trace_reduce import Op, Span

MS = 1e3
_ids = itertools.count(1)


def span(name, ts_ms, dur_ms, prof=False, parent=None, **args):
    return {"name": name, "ts": ts_ms * MS, "dur": dur_ms * MS,
            "id": next(_ids), "parent": parent, "request": None,
            "prof": prof, "args": args}


def events():
    out = []
    warm = span("lm.step", 0, 60000, tokens=16384, routed_rows=100,
                held_rows=90, overflow_rows=7, load_max_over_mean=9.0,
                expert_rows=[[100, 0, 0, 0]])
    out += [warm, span("lm.step.wait", 59000, 950, parent=warm["id"])]
    for k, (whole, wait, held, worst, rows) in enumerate(
            ((953.0, 950.0, 40900, 1.6, [[40, 20, 20, 20], [25, 25, 25, 25]]),
             (955.0, 950.5, 41000, 1.6, [[20, 40, 20, 20], [25, 25, 25, 25]]),
             (960.0, 951.0, 41020, 1.6, [[20, 20, 30, 30], [25, 25, 25, 25]]))):
        step = span("lm.step", 70000 + 1000 * k, whole, prof=True,
                    tokens=16384, routed_rows=327680, held_rows=held,
                    overflow_rows=0, load_max_over_mean=worst,
                    expert_rows=rows)
        out += [step, span("lm.step.wait", 70002 + 1000 * k, wait, prof=True,
                           parent=step["id"])]
    return out


def test_step_host_ms_is_the_median_step_less_its_wait():
    assert lm.read_events("lm.step_host_ms.lm", events()) == pytest.approx(4.5)
    assert lm.read_events("lm.step_host_ms.lm", []) is None
    assert lm.read_events("lm.other.lm", events()) is None


def test_held_share_and_load_read_the_windows_steps_only():
    ev = events()
    assert moe.read_events("moe.held_share.lm", ev) == pytest.approx(
        100.0 * (40900 + 41000 + 41020) / (3 * 327680))
    # the steps added up: [80, 80, 70, 70] of mean 75, not a step's 1.6
    assert moe.read_events("moe.load_max_over_mean.lm", ev) == pytest.approx(
        80 / 75)
    assert moe.read_events("moe.held_share.lm", []) is None
    # a program from before the spans' counts
    bare = [span("lm.step", 0, 10, prof=True)]
    assert moe.read_events("moe.load_max_over_mean.lm", bare) is None


def _ctx(table_s=2.0, busy_s=20.0, **run):
    return {"trace": {"table_s": table_s, "busy_s": busy_s},
            "run": run, "device_kind": "TPU v5 lite"}


def test_expert_shares_come_from_table_s_and_the_drivers_flops():
    flops = lm_shapes.expert_products_flops(40000, 2048, 1536)
    assert flops == 9 * 2 * 2048 * 1536 * 40000
    ctx = _ctx(expert_flops=22 * 5 * flops)
    assert moe.read("moe.expert_device_share.lm", ctx) == pytest.approx(10.0)
    share = moe.read("moe.expert_mxu_share.lm", ctx)
    assert share == pytest.approx(100 * 22 * 5 * flops / 2.0 / 197e12)
    assert 0 < share < 100
    assert moe.read("moe.expert_mxu_share.lm", _ctx()) is None
    assert moe.read("moe.expert_device_share.lm", _ctx(table_s=0.0)) is None


def test_attention_share_answers_only_when_every_kernel_was_seen():
    seen = {"seconds": 9.4, "kernels": 528}
    ctx = _ctx(attention_s=seen, attention_kernels=528)
    assert attn.read("attn.device_share.lm", ctx) == pytest.approx(47.0)
    assert attn.read("attn.device_share.lm",
                     _ctx(attention_s=seen, attention_kernels=552)) is None
    assert attn.read("attn.device_share.lm", _ctx()) is None
    assert attn.read("attn.device_share.lm",
                     _ctx(attention_s={}, attention_kernels=528)) is None
    assert attn.read("attn.mxu_share.lm", ctx) is None


def test_attention_seconds_sums_the_scopes_kernels_inside_the_window():
    call = "(bf16[40,8192,256]) custom-call(bf16[40,8192,256] %x)"
    ops = {"/device:TPU:0": [
        Op("mv.lm.attn.3", 0.5, 0.2, call),              # before the window
        Op("mv.lm.attn.3", 1.0, 0.2, call),
        Op("jvp_mv.lm.attn_.1", 1.3, 0.4, call),
        Op("transpose_jvp_mv.lm.attn__.7", 1.8, 0.1, call),
        Op("convert.9", 2.0, 0.3, "f32[40,8192,256] convert(%mv.lm.attn.3)"),
        Op("gmm.4", 2.4, 0.3, "bf16[9728,1536] custom-call(s32[26] %g)"),
    ]}
    spans = [Span(trace_reduce.WINDOW_SPAN, 0.9, 2.0)]
    got = attn.kernels_in(ops, spans)
    assert got["kernels"] == 3 and got["seconds"] == pytest.approx(0.7)
    assert attn.kernels_in(ops, []) == {}
    assert attn.kernels_in({}, spans) == {}


def test_lm_batches_are_full_seeded_and_inside_the_slice():
    a = lm_train.lm_batches(19360, 2, 8192, 3, 1.1, [64, 2048], 0, 3000000019)
    b = lm_train.lm_batches(19360, 2, 8192, 3, 1.1, [64, 2048], 0, 3000000019)
    c = lm_train.lm_batches(19360, 2, 8192, 3, 1.1, [64, 2048], 0, 7)
    assert a.shape == c.shape == (3, 2, 8192) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() == 0 and a.max() < 19360
    ends = np.flatnonzero(a.reshape(-1) == 0)
    gaps = np.diff(ends)
    assert 64 <= gaps.min() and gaps.max() <= 2048      # document lengths
    # a bounded Zipf: the commonest id is about a seventh of the tokens
    top = np.bincount(a.reshape(-1)).max() / a.size
    assert 0.10 < top < 0.20
