"""The eighth language-model cell (``xing4-train-4k``): the cell found by
name with every metric it reports, the configuration as the published one
but for five keys, the traffic as ``lm-train-8k``'s load at half the
positions and one sequence, what the stream maps must move against hand
counts (``hc_shapes``), the readers of ``layers/hc`` on made-up sums, and
the comparison's controls at ``--cpu-tiny`` sizes (``lm_hc_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import hc_shapes
from benchmark.drivers import lm_train_hc
from benchmark.layers import hc
from conftest import ROOT, run_cell

CELL = "xing4-train-4k"
CONFIG = "xing4.0-29b-a4b-ep8"
OWN = {"hc.device_share.lm", "hc.sinkhorn_device_share.lm",
       "hc.stream_hbm_share.lm"}
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"}


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
        CONFIG, "lm-train-4k-hc", 1)
    assert "eighth" in entry["why"] and len(entry["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert set(config["reduced"]) == REDUCED
    assert config["source"] == _config()["source"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    # every language-model metric the three first cells share, this one
    # has; and its own
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k", "trinity-train-16k"}
              <= set(m.get("workloads", []))}
    assert shared < mine and mine - shared == OWN
    for m in spec["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "words_per_s"
            assert m["layer"].startswith("residual streams")
            assert m["source"] == "device_trace" and m["unit"] == "%"
    assert spec["per_layer"][-3:] == [m for m in spec["per_layer"]
                                      if m["name"] in OWN]
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


def test_the_configuration_is_the_published_one_but_for_five_keys():
    c = _config()
    assert set(c["reduced"]) == set(c["published"]) == REDUCED
    assert c["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"]) == (5, 1, 8, 16384, 0)
    # no width differs from the source
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["hc_mult"], c["hc_sinkhorn_iters"], c["hc_eps"],
            c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]) == (
                3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 4, 20, 1e-6,
                -30, 30)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert row["source_url"] == c["source"]
        differ = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differ == set(c["reduced"])
    for key in ("source", "assumed", "deployment", "tiny", "parameters"):
        assert c[key]
    for key in ("hc_in_and_out", "hc_equations", "compute_precision",
                "mtp_streams", "yarn_softmax_scale", "hc_init", "optimizer",
                "learning_rate", "bias_update_speed", "rotary_pairing",
                "document_mask", "init_scale", "router_init_scale"):
        assert c["assumed"][key]
    assert "eight chips share each layer" in c["deployment"]
    assert "stream maps" in c["deployment"]
    assert "ROW AN OUTPUT" in c["parameters"]["hc_layout"]
    # the tiny sizes shrink row counts alone (and run the prediction
    # module, which the chip's cut leaves to a further stage)
    assert not set(c["tiny"]) - (REDUCED | {"published"})
    assert c["tiny"]["num_nextn_predict_layers"] == 1


def test_the_parameters_are_the_programs_count():
    """The file's arithmetic, from ``param_shapes``."""
    import numpy as np
    from multiverso_tpu.models import mla_moe

    c = _config()
    c.pop("tiny")

    class _Cell:
        config = c

    cfg = lm_train_hc._model_config(_Cell)
    shapes = mla_moe.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == c[
        "parameters"]["total"] == 759_346_190
    assert len(shapes) == c["parameters"]["tables"]
    assert shapes["L0.attn.hc_phi"] == (24, 4 * 3584)
    assert cfg.yarn.attention_factor == 1.0
    assert cfg.softmax_scale * 192 ** 0.5 == pytest.approx(2.0048, 1e-4)
    assert {lm_train_hc.table_class(n) for n in shapes} == {
        "plain", "experts", "router", "streams"}
    assert sum(lm_train_hc.table_class(n) == "streams"
               for n in shapes) == 3 * 2 * 5


def test_the_traffic_is_lm_train_8ks_load_at_half_the_positions():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-8k.json"), load("lm-train-4k-hc.json")
    assert {k for k in base if base[k] != mine[k]} == {
        "driver", "why", "sequences", "positions"}
    assert set(base) == set(mine)
    assert mine["driver"] == "lm_train_hc"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"]) == (
                1, 4096, 16, 1.1, [64, 2048])
    assert mine["calibration"] == base["calibration"]


def test_the_maps_must_move_what_the_hand_count_says():
    # one position, two streams of one channel, one block: forward read X
    # (2) and y (1), write X' (2) and the next u (1) = 6; backward read dX'
    # (2), X (2), y (1), write dy (1), then read du (1), X (2), dX' (2),
    # write dX (2) = 13; 19 floats a sublayer, two sublayers
    c = dict(hc_mult=2, hidden_size=1, num_hidden_layers=1,
             num_nextn_predict_layers=0, hc_sinkhorn_iters=1)
    assert hc_shapes.sublayer_bytes(c, 1) == 19 * 4
    assert hc_shapes.step_bytes(c, 1, 1) == 2 * 19 * 4
    # the mean square 2 x 2, the projection 2 x 2 x 8, H_pre X 4, H_res X
    # 8, the post sum 4, Sinkhorn 2 x 2 x 4; times 3
    assert hc_shapes.sublayer_flops(c, 1) == 3 * (4 + 32 + 4 + 8 + 4 + 16)
    # the cell: ten sublayers x 4,096 positions x 33 arrays of 3,584 floats
    c = _config()
    assert hc_shapes.sublayers(c) == 10
    assert hc_shapes.step_bytes(c, 1, 4096) == (
        10 * 4096 * 33 * 3584 * 4) == 19_377_684_480
    assert hc_shapes.sublayers(dict(c, **c["tiny"])) == 6


@pytest.mark.parametrize("filed, maps_s, want", [
    (19.9, 4.0, (20.0, 5.0, 25.0)),    # the join filed 99.5% of busy
    (19.0, 4.0, (None, None, None)),   # under the floor: none is reported
    (19.9, 0.0, (None, None, None)),   # no operation under the scopes
    (None, None, (None, None, None))])  # no trace, or the parent's program
def test_the_device_readers_answer_only_over_a_whole_join(filed, maps_s,
                                                          want):
    seen = {} if filed is None else {
        "seconds": {}, "filed_s": filed, "busy_s": 20.0, "maps_s": maps_s,
        "sinkhorn_s": 1.0}
    peak = 819e9
    ctx = {"run": {"hc_s": seen, "hc_bytes": 0.25 * 4.0 * peak},
           "device_kind": "TPU v5 lite", "trace": {"busy_s": 20.0}}
    got = tuple(hc.read(name, ctx) for name in (
        "hc.device_share.lm", "hc.sinkhorn_device_share.lm",
        "hc.stream_hbm_share.lm"))
    assert got == pytest.approx(want) if want[0] else got == want
    assert hc.scope_seconds("no-such-cell") == {}
    # a run that hands over no bytes reports no share of the roofline
    if want[0]:
        assert hc.read("hc.stream_hbm_share.lm",
                       dict(ctx, run={"hc_s": seen})) is None


def test_the_join_files_the_maps_scopes_by_pass():
    """``scopes_in`` on a made-up trace and record: the operations under
    ``mv.lm.hc.*``, clipped to the window."""
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
        "mv.lm.hc.sinkhorn": {"fwd": [["fusion.1", "f32[4]"]],
                              "bwd": [["fusion.2", "f32[4]"]]},
        "mv.lm.hc.post": {"fwd": [["fusion.3", "f32[4]"]]},
        "mv.lm.attn": {"fwd": [["fusion.4", "f32[4]"]]}}}}
    got = hc.scopes_in(ops, [Span()], [record])
    assert got["seconds"] == {"mv.lm.hc.sinkhorn": {"fwd": 0.5, "bwd": 3.0},
                              "mv.lm.hc.post": {"fwd": 2.0}}
    assert got["every_scope"]["mv.lm.attn"] == {"fwd": 1.0}
    assert (got["sinkhorn_s"], got["maps_s"]) == (3.5, 5.5)
    assert got["filed_s"] == got["busy_s"] == 6.5
    # a program without the scopes (the parent's) answers nothing
    assert hc.scopes_in(ops, [Span()], []) == {}
    bare = {"name": "xla.program", "args": {"scopes": {
        "mv.lm.attn": {"fwd": [["fusion.4", "f32[4]"]]}}}}
    assert hc.scopes_in(ops, [Span()], [bare])["maps_s"] == 0
    assert hc.read("hc.device_share.lm", {"run": {"hc_s": hc.scopes_in(
        ops, [Span()], [bare])}}) is None


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
                                                "streams"}
    assert len(detail["check"]["streams_by_table"]) == 3 * 2 * 3
    assert 0 < detail["check"]["hc_res_error"] <= detail["check"][
        "hc_res_error_limit"]
    assert detail["facts"]["hc_res_error"] == detail["check"]["hc_res_error"]


def test_a_traced_tiny_run_reports_no_device_share_of_the_maps():
    """The per-layer line of a traced run: the shared metrics are there;
    the three device shares of the maps are the chip's to give (on the CPU
    the trace has no device line and they are left out, as the parent's
    would be)."""
    result, _ = run_cell(ROOT, CELL, trace=1, seed=2147483021)
    assert result["correct"] and result["failed"] == 0
    assert not OWN & set(result["metrics"])
    assert "counts.overflow_rows.lm" in result["metrics"]
    assert "xla.program_memory_gb.lm" in result["metrics"]


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_hc_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == set(lm_train_hc.CONTROLS)
    assert not any(v["agrees"] for v in said["controls"].values())
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
    # the draw moves every map from position to position
    for maps in said["program"]["map_spread"].values():
        assert min(maps["pre_sd"][0], maps["post_sd"][0],
                   maps["res_sd"][0]) > 0.02
        assert maps["res_mean_from_identity"] > 0.2
