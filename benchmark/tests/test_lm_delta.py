"""The seventh language-model cell (``qwen3next-train-16k``): the cell found
by name with every metric it reports, the configuration as the published
one but for three keys, the traffic as ``lm-train-16k``'s load letter for
letter, what its delta rule must compute against hand counts
(``delta_shapes``) and against the program's own count, the readers of
``layers/delta`` on made-up sums, and the comparison's controls at
``--cpu-tiny`` sizes (``lm_delta_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import delta_shapes
from benchmark.drivers import lm_train_delta
from benchmark.layers import delta
from conftest import ROOT, run_cell

CELL = "qwen3next-train-16k"
CONFIG = "qwen3-next-80b-a3b-ep16"
OWN = {"delta.mixer_flops_share.lm", "delta.mixer_device_share.lm",
       "delta.rule_mxu_share.lm"}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    spec = _spec()
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm-train-16k-delta", 1)
    assert "sixteenth" in entry["why"] and len(entry["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    # every language-model metric the three first cells share, this one
    # has; the one causal core's two; and its own
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k", "trinity-train-16k"}
              <= set(m.get("workloads", []))}
    assert shared < mine
    assert mine - shared == OWN | {"attnmix.full_device_share.lm",
                                   "attnmix.full_mxu_share.lm"}
    for m in spec["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "words_per_s"
            assert m["layer"].startswith("delta-rule mixer")
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "words_per_s")["workloads"]
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert (os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
            or os.path.isdir(os.path.join(ROOT, "benchmark", "layers",
                                          family)))
    # a quarter of the cells, rounded down, may ask for four chips
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)


def test_the_configuration_is_the_published_one_but_for_three_keys():
    c = _config()
    assert set(c["reduced"]) == set(c["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 32, 18992)
    # no width differs from the source
    assert (c["hidden_size"], c["linear_num_key_heads"],
            c["linear_num_value_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"], c["linear_conv_kernel_dim"],
            c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["partial_rotary_factor"], c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"], c["num_experts_per_tok"],
            c["intermediate_size"], c["full_attention_interval"]) == (
                2048, 16, 32, 128, 128, 4, 16, 2, 256, 0.25, 512, 512, 10,
                5120, 4)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == c["source"]
        differ = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differ == set(c["reduced"])
    assert c["parameters"] == 625_667_136
    for key in ("source", "assumed", "deployment", "tiny"):
        assert c[key]
    for key in ("chunk_size", "stored_gain", "left_out", "kept_unused",
                "document_mask", "compute_precision"):
        assert c["assumed"][key]
    assert "sixteen chips share each layer" in c["deployment"]
    assert not set(c["tiny"]) - {"num_hidden_layers", "num_experts",
                                 "vocab_size", "published"}


def test_the_traffic_is_lm_train_16ks_load_under_another_driver():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-16k.json"), load("lm-train-16k-delta.json")
    assert {k for k in base if base[k] != mine[k]} == {
        "driver", "why", "tiny", "calibration"}
    assert set(base) == set(mine)
    assert mine["driver"] == "lm_train_delta"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"]) == (
                1, 16384, 16, 1.1, [64, 2048])
    # the routers' calibration is lm-train-8k-window's schedule
    window = load("lm-train-8k-window.json")["calibration"]
    assert {k for k in window if window[k] != mine["calibration"][k]} <= {
        "max_passes", "load_max_over_mean"}
    assert mine["calibration"]["max_passes"] == 200


def test_the_rule_must_compute_what_the_hand_count_says():
    # one chunk of 2 positions, one key head of 1 read by one value head
    # of 1: K K^T one pair, q K^T three; T Vb, T Kb and the masked product
    # three pairs each; W S, q S and K~^T V' 2 positions each
    c = dict(linear_num_key_heads=1, linear_num_value_heads=1,
             linear_key_head_dim=1, linear_value_head_dim=1)
    assert delta_shapes.rule_flops_chunk(c, 2) == (
        2 * (1 + 3) + 2 * 3 * 2 + 2 * 3 + 3 * 2 * 2)
    assert delta_shapes.rule_flops(c, 1, 4, 2) == 3 * 2 * 38
    # the cell: 256 chunks of 64; 2,080 live pairs a chunk
    c = _config()
    low = 2080
    a_chunk = (16 * 2 * 128 * (low - 64 + low)
               + 32 * (2 * low * 256 + 2 * low * 128 + 6 * 64 * 128 * 128))
    assert delta_shapes.rule_flops_chunk(c, 64) == a_chunk
    assert delta_shapes.rule_flops(c, 1, 16384, 64) == 3 * 256 * a_chunk
    # q and k 16,384 x 2,048, v and o 16,384 x 4,096 in bfloat16, the two
    # gates 16,384 x 32 in float32; once forward, twice backward
    one_pass = 16384 * (2 * 2048 + 2 * 4096) * 2 + 16384 * 2 * 32 * 4
    assert delta_shapes.rule_bytes(c, 1, 16384) == 3 * one_pass


def test_the_programs_count_is_the_shapes_count():
    """``Qwen3NextConfig.delta_grid`` (what ``lm.step`` carries) and
    ``delta_shapes`` (from the configuration's file) count the same."""
    c = _config()

    class _Cell:
        config = c

    cfg = lm_train_delta._model_config(_Cell)
    for positions in (16384, 4096):
        grid = cfg.delta_grid(positions)
        assert grid["delta_flops_token"] == 3 * delta_shapes.mixer_flops(
            c, cfg.delta_chunk)
        assert grid["step_flops_token"] == delta_shapes.step_flops_token(
            c, positions, cfg.delta_chunk)
    events = [{"name": "lm.step", "prof": True, "args": cfg.delta_grid(16384)}]
    share = delta.read_events("delta.mixer_flops_share.lm", events)
    assert share == pytest.approx(
        100.0 * 3 * delta_shapes.mixer_flops(c, 64)
        / delta_shapes.step_flops_token(c, 16384, 64))
    assert 40.0 < share < 90.0
    # a program without the counts (the parent's) answers nothing
    assert delta.read_events("delta.mixer_flops_share.lm", [
        {"name": "lm.step", "prof": True, "args": {"tokens": 1}}]) is None
    assert delta.read_events("delta.mixer_flops_share.lm", []) is None


@pytest.mark.parametrize("filed, rule_s, want", [
    (19.9, 4.0, (30.0, 12.5)),     # the join filed 99.5% of busy
    (19.0, 4.0, (None, None)),     # under the floor: neither is reported
    (19.9, 0.0, (None, None)),     # no operation under the rule's scope
    (None, None, (None, None))])   # no trace, or a program without the join
def test_the_device_readers_answer_only_over_a_whole_join(filed, rule_s,
                                                          want):
    seen = {} if filed is None else {
        "seconds": {}, "filed_s": filed, "busy_s": 20.0, "mixer_s": 6.0,
        "rule_s": rule_s}
    peak = 197e12
    ctx = {"run": {"delta_s": seen, "delta_flops": 0.125 * 4.0 * peak},
           "device_kind": "TPU v5 lite", "trace": {"busy_s": 20.0}}
    got = (delta.read("delta.mixer_device_share.lm", ctx),
           delta.read("delta.rule_mxu_share.lm", ctx))
    assert got == pytest.approx(want) if want[0] else got == want
    assert delta.scope_seconds("no-such-cell") == {}


def test_the_join_files_the_mixers_scopes_by_pass():
    """``scopes_in`` on a made-up trace and record: the operations under
    ``mv.lm.delta`` and its children, clipped to the window."""
    from benchmark import trace_reduce

    class Op:
        def __init__(self, name, start, dur):
            self.name, self.text, self.start, self.dur = (
                name, f"%{name} = f32[4]{{0}} fusion()", start, dur)

    class Span:
        name, start, dur = trace_reduce.WINDOW_SPAN, 1.0, 10.0

    ops = {"chip0": [Op("fusion.1", 0.5, 1.0), Op("fusion.2", 2.0, 3.0),
                     Op("fusion.3", 6.0, 2.0), Op("fusion.4", 9.0, 1.0)]}
    record = {"name": "xla.program", "args": {"scopes": {
        "mv.lm.delta.rule": {"fwd": [["fusion.1", "f32[4]"]],
                             "bwd": [["fusion.2", "f32[4]"]]},
        "mv.lm.delta": {"fwd": [["fusion.3", "f32[4]"]]},
        "mv.lm.attn.full": {"fwd": [["fusion.4", "f32[4]"]]}}}}
    got = delta.scopes_in(ops, [Span()], [record])
    assert got["seconds"] == {"mv.lm.delta.rule": {"fwd": 0.5, "bwd": 3.0},
                              "mv.lm.delta": {"fwd": 2.0}}
    assert got["every_scope"]["mv.lm.attn.full"] == {"fwd": 1.0}
    assert (got["rule_s"], got["mixer_s"]) == (3.5, 5.5)
    assert got["filed_s"] == got["busy_s"] == 6.5
    assert delta.scopes_in(ops, [Span()], []) == {}
    assert delta.scopes_in(ops, [], [record]) == {}


def test_the_cell_runs_at_tiny_sizes_and_reports_its_metrics():
    result, lines = run_cell(ROOT, CELL, seed=2147483019)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    assert detail["facts"]["overflow_rows"] == 0
    assert detail["check"]["count_identities"]
    assert detail["check"]["router_flips"] <= detail["check"][
        "router_flips_allowed"]
    assert set(detail["check"]["by_class"]) == {"plain", "experts", "router",
                                                "decay"}


def test_a_traced_tiny_run_reports_the_static_share():
    """The per-layer line of a traced run: the cell's own static share is
    there; the two device shares are the chip's to give (on the CPU the
    trace has no device line and they are left out, as the parent's would
    be)."""
    result, _ = run_cell(ROOT, CELL, trace=1, seed=2147483021)
    assert result["correct"] and result["failed"] == 0
    assert "delta.mixer_flops_share.lm" in result["metrics"]
    assert 0.0 < result["metrics"]["delta.mixer_flops_share.lm"][
        "value"] < 100.0
    assert "counts.overflow_rows.lm" in result["metrics"]


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_delta_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == set(lm_train_delta.CONTROLS)
    assert not any(v["agrees"] for v in said["controls"].values())
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
