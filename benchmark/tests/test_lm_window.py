"""The second language-model cell (``mellum2-train-8k``): its reader
(``layers/attnmix.py``) on made-up traces and span records,
``attn_shapes.py`` against hand counts, the comparison's control at
``--cpu-tiny`` sizes (``lm_window_control.py``), and the cell itself found
by name and run through ``run.py --cpu-tiny``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import attn_shapes, trace_reduce
from benchmark.layers import attnmix
from benchmark.trace_reduce import Op, Span
from conftest import ROOT, run_cell
from test_lm_layers import span

CELL = "mellum2-train-8k"


def test_live_positions_are_the_triangle_and_the_band_by_hand():
    assert attn_shapes.live_positions(4) == 10
    # window 2 over 4 positions: rows see 1, 2, 2, 2 keys
    assert attn_shapes.live_positions(4, 2) == 7
    assert attn_shapes.live_positions(4, 4) == attn_shapes.live_positions(
        4, 9) == 10
    assert attn_shapes.live_positions(8192) == 33_558_528
    assert attn_shapes.live_positions(8192, 1024) == 7_864_832
    brute = sum(1 for i in range(300) for j in range(300) if 0 <= i - j < 37)
    assert attn_shapes.live_positions(300, 37) == brute


def test_core_flops_are_six_products_over_the_live_positions():
    # one head, 4 positions, head size 2: 10 pairs x 6 products x 2 x 2
    assert attn_shapes.core_flops(1, 1, 4, 2) == 10 * 6 * 2 * 2
    full = attn_shapes.core_flops(2, 32, 8192, 128)
    band = attn_shapes.core_flops(2, 32, 8192, 128, 1024)
    assert full == 64 * 12 * 128 * 33_558_528
    assert band == 64 * 12 * 128 * 7_864_832
    assert 0.23 < band / full < 0.24


def _ctx(busy_s=20.0, **run):
    return {"trace": {"busy_s": busy_s}, "run": run,
            "device_kind": "TPU v5 lite"}


def test_shares_answer_only_when_every_kernel_of_the_kind_was_seen():
    seen = {"window": {"seconds": 3.0, "kernels": 480},
            "full": {"seconds": 4.0, "kernels": 160}}
    want = {"window": 480, "full": 160}
    flops = {"window": 40 * 3 * attn_shapes.core_flops(2, 32, 8192, 128, 1024),
             "full": 40 * attn_shapes.core_flops(2, 32, 8192, 128)}
    ctx = _ctx(attnmix_s=seen, attnmix_kernels=want, attnmix_flops=flops)
    assert attnmix.read("attnmix.window_device_share.lm", ctx) == \
        pytest.approx(15.0)
    assert attnmix.read("attnmix.full_device_share.lm", ctx) == \
        pytest.approx(20.0)
    share = attnmix.read("attnmix.window_mxu_share.lm", ctx)
    assert share == pytest.approx(100 * flops["window"] / 3.0 / 197e12)
    assert 0 < share < 100
    assert 0 < attnmix.read("attnmix.full_mxu_share.lm", ctx) < 100
    # a kernel missed, no sums at all, no operations, an unknown quantity
    short = _ctx(attnmix_s=seen, attnmix_kernels=dict(want, full=164),
                 attnmix_flops=flops)
    assert attnmix.read("attnmix.full_device_share.lm", short) is None
    assert attnmix.read("attnmix.window_device_share.lm", short) == \
        pytest.approx(15.0)
    assert attnmix.read("attnmix.window_device_share.lm", _ctx()) is None
    assert attnmix.read("attnmix.window_mxu_share.lm",
                        _ctx(attnmix_s=seen, attnmix_kernels=want)) is None
    assert attnmix.read("attnmix.window_hbm_share.lm", ctx) is None
    assert attnmix.read("attnmix.other_device_share.lm", ctx) is None


def test_kernels_are_summed_by_scope_inside_the_window():
    call = "(bf16[64,8192,128]) custom-call(bf16[64,8192,128] %x)"
    ops = {"/device:TPU:0": [
        Op("mv.lm.attn.window.3", 0.5, 0.2, call),       # before the window
        Op("mv.lm.attn.window.3", 1.0, 0.2, call),
        Op("mv.lm.attn.window.5", 1.3, 0.4, call),
        Op("mv.lm.attn.full.7", 1.8, 0.1, call),
        Op("mv.lm.attn.9", 1.9, 0.1, call),              # the other model's
        Op("convert.9", 2.0, 0.3,
           "f32[64,8192,128] convert(%mv.lm.attn.window.3)"),
    ]}
    spans = [Span(trace_reduce.WINDOW_SPAN, 0.9, 2.0)]
    got = attnmix.kernels_in(ops, spans)
    assert got["window"]["kernels"] == 2
    assert got["window"]["seconds"] == pytest.approx(0.6)
    assert got["full"] == {"seconds": pytest.approx(0.1), "kernels": 1}
    assert attnmix.kernels_in(ops, []) == {}
    assert attnmix.kernels_in({}, spans) == {}
    # a program without the scopes: nothing seen, and the reader says None
    bare = {"/device:TPU:0": [Op("mv.lm.attn.3", 1.0, 0.2, call)]}
    got = attnmix.kernels_in(bare, spans)
    assert got["window"]["kernels"] == got["full"]["kernels"] == 0
    ctx = _ctx(attnmix_s=got, attnmix_kernels={"window": 12, "full": 4})
    assert attnmix.read("attnmix.window_device_share.lm", ctx) is None


def test_band_pairs_share_reads_the_windows_steps_and_nothing_else():
    counts = dict(attn_pairs_live_window=45, attn_pairs_masked_window=30,
                  attn_pairs_causal_window=136)
    events = [span("lm.step", 0, 900, **dict(counts,
                                             attn_pairs_live_window=136)),
              span("lm.step", 1000, 500, prof=True, **counts),
              span("lm.step", 2000, 500, prof=True, **counts)]
    assert attnmix.read_events("attnmix.band_pairs_share.lm", events) == \
        pytest.approx(100 * 45 / 136)
    # the parent's spans carry no such counts; nor does an XLA core's
    bare = [span("lm.step", 0, 10, prof=True, attn_pairs_live=136)]
    assert attnmix.read_events("attnmix.band_pairs_share.lm", bare) is None
    assert attnmix.read_events("attnmix.band_pairs_share.lm", []) is None
    assert attnmix.read_events("attnmix.window_device_share.lm",
                               events) is None


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mellum2-12b-a2.5b-ep4", "lm-train-8k-window", 1)
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {m for m in mine if m.startswith("attnmix.")} == {
        "attnmix.window_device_share.lm", "attnmix.full_device_share.lm",
        "attnmix.window_mxu_share.lm", "attnmix.full_mxu_share.lm",
        "attnmix.band_pairs_share.lm"}
    assert {"attn.device_share.lm", "moe.expert_device_share.lm",
            "moe.expert_mxu_share.lm", "moe.held_share.lm",
            "moe.load_max_over_mean.lm", "device.idle_share.lm",
            "lm.step_host_ms.lm", "prog.table_init_s.setup",
            "prog.compile_s.setup"} <= mine
    # the load is lm-train-8k's, letter for letter
    with open(os.path.join(ROOT, "benchmark/traffic/lm-train-8k.json")) as f:
        old = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic",
                           entry["traffic"] + ".json")) as f:
        new = json.load(f)
    for key in ("sequences", "positions", "batch_pool", "zipf_a",
                "document_tokens", "end_of_document_id", "reports"):
        assert new[key] == old[key], key
    assert new["driver"] == "lm_train_window"


def test_the_cell_runs_at_cpu_tiny_sizes_and_is_correct():
    result, lines = run_cell(ROOT, CELL, seconds=1.0)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    assert detail["check"]["step_agrees"] and detail["check"]["tables"] == 23
    assert detail["facts"]["overflow_rows"] == 0


def test_the_float8_step_in_the_programs_place_does_not_agree():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_window_control.py"),
         "--seed", "3000000019", "--cpu-tiny"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    said = json.loads(out.stdout.strip().splitlines()[-1])
    program, control = said["program"], said["control"]
    assert program["step_agrees"] and not control["agrees"]
    assert control["count_identities"]
    # the control fails by the gradients' limits, which is what they are for
    assert max(control["grad_norm_err_over_tol"],
               control["grad_elem_err_over_tol"]) > 1.0
    for key in ("loss_err_over_tol", "grad_norm_err_over_tol",
                "grad_elem_err_over_tol", "count_err_over_tol",
                "move_err_over_tol", "balance_err_over_tol"):
        assert program[key] <= 1.0, (key, program[key])
