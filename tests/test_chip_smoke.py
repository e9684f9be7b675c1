"""chip_smoke.py between chip runs: every stage function at a tiny size on
the 8-device CPU mesh, the flash kernel in interpret mode, so the script
the driver runs on the TPU cannot rot unnoticed. ``chip=False`` drops only
the assertions a TPU alone can meet (compiled kernel, device-backed
shards); the comparisons against NumPy and reference_attention all run.
"""

import functools
import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402  (repo-root script, not a package)


def test_tables_stage():
    facts = chip_smoke.stage_tables(rows=1000, cols=16, batch=256,
                                    array_size=5000)
    assert facts["duplicate_ids"] > 0   # the dedupe rule was exercised
    assert facts["rows_max_abs_err"] <= chip_smoke.TABLE_ATOL
    assert len(facts["matrix_devices"]) == 8


def test_we_stage():
    facts = chip_smoke.stage_we(
        fused_tokens=30_000, fused_vocab=500, fused_batch=512,
        shared_negatives=32, ps_tokens=30_000, ps_vocab=500, ps_batch=512,
        ps_block=8000, dim=16)
    assert facts["fused_loss"][-1] < facts["fused_loss"][0]
    assert facts["ps_block_loss"][-1] < facts["ps_block_loss"][0]
    assert facts["ps_blocks_per_pass"] >= 2
    assert facts["fused_compute_dtype"] == "float32"   # bf16 is TPU-only


def test_rows_stage():
    facts = chip_smoke.stage_rows(
        rows_per_shard=101, width=8, batch=64, calls=3,
        block=dict(bucket=512, negative=2, minibatches=3, vocab=2000))
    assert (facts["shards"], facts["cap"]) == (8, 6)
    assert facts["table"] == "f32[808,8]"
    assert facts["even_rounds_past_first"] >= 0
    # 64 ids of one shard, 6 slots a round: up to eleven rounds a call
    assert 3 < facts["last_shard_rounds_past_first"] <= 3 * 10
    # 64 ids without a duplicate over 8 shards: 8 or more in the busiest
    assert 3 <= facts["distinct_rounds_past_first"]
    assert facts["take_rows_temp_mb"] < 1 > facts["partitioner_temp_mb"]
    assert facts["even_take_rows_ms"] > 0 < facts["even_partitioner_ms"]
    # the block's writes ran at the stage's width and batch
    assert facts["block"]["update_rows"] == [64, 3 * 64]


@pytest.mark.parametrize("kernel", [False, True])
def test_rows_stage_block_writes(kernel, monkeypatch):
    from multiverso_tpu.ops import row_combine
    monkeypatch.setattr(row_combine, "HEAD", 32)    # leave the walk rows
    monkeypatch.setattr(row_combine, "CHUNK", 16)
    if kernel:      # the chip's walk of a lane-wide bucket, interpreted
        monkeypatch.setattr(row_combine, "_kernel_interpret", lambda: True)
    facts = chip_smoke.block_writes(bucket=512, width=8, batch=64,
                                    negative=2, minibatches=3, vocab=2000,
                                    walk_rows=(20, 64))
    # ISSUE 45: the walk's two prices on the scan's own bucket, the
    # kernel's held bit for bit to XLA's inside the stage
    walks = facts["walks"]
    assert (walks["table"], walks["kernel"]) == ("f32[513,128]", kernel)
    assert set(walks) == {"table", "kernel", "0", "20", "64"}
    for n in ("0", "20", "64"):
        for how in ("xla", "kernel") if kernel else ("xla",):
            for form in ("alone", "six"):
                assert walks[n][f"{how}_{form}_ms"] > 0, (n, how, form)
                assert (f"{how}_{form}_us_a_row" in walks[n]) == (n != "0")
        assert ("kernel_six_ms" in walks[n]) == kernel
    assert facts["table"] == "f32[513,8]"
    one, cols = facts["one_write_rows"], facts["columns_rows"]
    # a column at a time merges no repeat across columns, and every
    # column's rows below HEAD are in a head
    assert 0 < one["unique"] <= cols["unique"] <= 3 * 3 * 64
    assert 0 < one["head"] <= cols["head"] and one["walk"] > 0
    assert facts["centers_rows"]["unique"] <= 3 * 64
    for name in ("raw", "one_write", "columns", "raw_centers", "centers",
                 "plan_columns", "sums_one_write", "sums_centers"):
        assert facts[f"{name}_ms"] > 0 <= facts[f"{name}_compile_s"], name
    assert max(facts["one_write_max_abs_err"],
               facts["columns_max_abs_err"]) <= chip_smoke.TABLE_ATOL


def test_ps_stage():
    facts = chip_smoke.stage_ps(rows=1000, cols=16, batch=256, chip=False)
    for updater in ("adagrad", "default"):
        assert facts[updater]["max_abs_err"] <= chip_smoke.TABLE_ATOL
    assert facts["wire_plane"] in ("native", "python")


def test_lm_stage():
    facts = chip_smoke.stage_lm(
        vocab=128, dim=32, heads=4, layers=2, seq=32, batch_per_chip=1,
        kernel_shapes=(((1, 2, 64, 16), 32), ((1, 2, 64, 16), (16, 32))),
        expert_calls=(("gated", 128, 128, 2, 512, "gated_silu", 4, 2,
                       "gqa_moe"),
                      ("relu2", 128, 128, 2, 512, "relu2", 4, 2,
                       "nemotron_h")),
        head_calls=(("chunks", 128, 96, 32, 32), ("whole", 64, 96, 32, 64)),
        chip=False)
    assert facts["batch_axis"] == "mv"      # kernel ran under shard_map
    assert facts["loss"][-1] < facts["loss"][0]
    errs = facts["kernel_rel_err"]["(1, 2, 64, 16)/32"]
    assert set(errs) == {"out", "dq", "dk", "dv"}
    assert "(1, 2, 64, 16)/(16, 32)" in facts["kernel_rel_err"]
    # the grouped products, the padding in the last group and in none
    assert set(facts["experts"]) == {"gated", "relu2"}
    for call in facts["experts"].values():
        assert call["experts_ms"] > 0 and call["experts_ms_padded"] > 0
        assert call["experts_tiles"] == 2 and call["experts_tiles_padded"] == 4
        assert call["apart_from_padded"] <= 1e-6
        # the block around them, as a step rematerialises it (the count of
        # its kernels is the chip's: the interpreter compiles none)
        assert call["block_tokens"] == 256 and call["block_ms"] > 0
        assert call["block_kernels"] == {"gmm": 0, "tgmm": 0}
        # the sorted buffer's passes walk chunks: forward, made again and
        # backward, the combine's sums made again where a norm reads them
        assert call["block_loops"]["dispatch"] == 3
        assert call["block_loops"]["combine"] in (2, 3)
    # the chunked loss alone, four chunks and one
    assert set(facts["heads"]) == {"chunks", "whole"}
    for call in facts["heads"].values():
        assert call["loss_grad_ms"] > 0
        assert max(call["rel_err_loss_dh_dhead"]) <= chip_smoke.ATTN_BF16_TOL


def test_product_kernels_rehearses_a_tile_sweep(monkeypatch):
    """``chip_smoke.product_kernels`` off the chip: the six kernels of a
    layer's two products in the interpreter at a tile that divides
    neither width, a clock reading and no device time (that is the
    chip's), and the row tiles the uneven groups visit."""
    import functools

    from multiverso_tpu.parallel import moe

    monkeypatch.setattr(moe, "_gmm", functools.partial(moe._gmm,
                                                       interpret=True))
    monkeypatch.setattr(moe, "_tgmm", functools.partial(moe._tgmm,
                                                        interpret=True))
    got = chip_smoke.product_kernels(
        dim=320, ffn=232, rows=64, sizes=(21, 17, 9),
        tiles=((16, 128, 232), (16, 320, 128)), repeats=1, chip=False)
    assert set(got) == {"16x128x232", "16x320x128"}
    for facts in got.values():
        assert facts["tiles_visited"] == 5 and facts["host_ms"] > 0
        assert facts["sum_ms"] is None and "refused" not in facts
        assert all(facts[f"{p}.{k}"] is None for p in ("up", "down")
                   for k in ("fwd", "dbuf", "dw"))
    only = chip_smoke.product_kernels(
        dim=320, ffn=232, rows=64, sizes=(21, 17, 9),
        tiles=((16, 128, 128),), kinds=("dw",), repeats=1, chip=False)
    assert set(only["16x128x128"]) >= {"up.dw", "down.dw"}
    assert "up.fwd" not in only["16x128x128"]


def test_flash_stage():
    """Whole tiles against sub-tiles of 16 and 8 under blocks of 32 and
    32 x 64, a band and a plain diagonal, in the interpreter."""
    facts = chip_smoke.stage_flash(
        calls=(("band", (1, 4, 128, 16), 2, (32, 32), 40),
               ("causal", (1, 2, 128, 16), 2, (32, 64), None)),
        subs=(16, 8), repeats=1)
    assert set(facts) == {"band", "causal"}
    for call in facts.values():
        assert call["sub_tile"] is None         # the rule leaves 32s whole
        for tag in ("sub0", "sub16", "sub8"):
            for kernel in ("fwd", "dq", "dkv"):
                assert call[f"{tag}_{kernel}_ms"] > 0
                assert call[f"{tag}_{kernel}_compile_s"] >= 0
            assert max(call[f"{tag}_rel_err"]) <= chip_smoke.ATTN_BF16_TOL
        for tag in ("sub16", "sub8"):
            assert max(call[f"{tag}_vs_whole"]) <= 1e-2     # bfloat16 results


def test_ssd_stage(monkeypatch):
    """The chunked scan at a small size: which form ran, ms and compile
    seconds forward and with every gradient of it and of the mixer's call,
    every head held to the recurrence; where the kernels run, the plain
    form's ms beside theirs."""
    facts = chip_smoke.stage_ssd(positions=256, heads=8, head_dim=8,
                                 groups=2, state=16, chunk=32, repeats=1,
                                 one_group=(128, 64))
    assert facts["form"] == "plain"             # the CPU's
    # ALL the heads in one group beside it, at a chunk of its own
    one = facts.pop("one_group")
    assert (one["form"], one["head_blocks"]) == ("plain", 1)
    assert max(one["rel_err_y_dx_ddt_da_db_dc"]) <= chip_smoke.ATTN_BF16_TOL
    assert one["mixer_fwd_bwd_ms"] > 0
    for name in ("fwd", "fwd_bwd", "mixer_fwd", "mixer_fwd_bwd"):
        assert facts[f"{name}_ms"] > 0 and facts[f"{name}_compile_s"] >= 0
    assert not any(name.startswith("plain_") for name in facts)
    errs = facts["rel_err_y_dx_ddt_da_db_dc"]
    assert len(errs) == 6 and max(errs) <= chip_smoke.ATTN_BF16_TOL
    assert "ssd" in dict(chip_smoke.STAGES)
    # the kernels (interpreted here) beside the plain form
    from multiverso_tpu.ops import ssd
    kernels = functools.partial(ssd.ssd_chunked, kernel=True, interpret=True)
    monkeypatch.setattr(ssd, "kernel_heads", lambda *shape: 8)
    monkeypatch.setattr(ssd, "ssd_chunked", kernels)
    facts = chip_smoke.stage_ssd(positions=256, heads=8, head_dim=64,
                                 groups=1, state=128, chunk=128, repeats=1,
                                 one_group=None)
    assert facts["form"] == "kernels" and "one_group" not in facts
    for name in ("fwd", "fwd_bwd", "mixer_fwd", "mixer_fwd_bwd", "plain_fwd",
                 "plain_fwd_bwd"):
        assert facts[f"{name}_ms"] > 0 and facts[f"{name}_compile_s"] >= 0
    assert max(facts["rel_err_y_dx_ddt_da_db_dc"]) <= chip_smoke.ATTN_BF16_TOL


def test_delta_stage():
    """The chunked gated delta rule at a small size: ms and compile
    seconds forward and with every gradient for each way of making the
    chunk's triangular inverse, one key head held to the recurrence with
    float32 and with bfloat16 operands."""
    calls = (("halves", 16, 2), ("solve", 16, 2), ("doubling", 16, 1),
             ("halves", 32, 4))
    facts = chip_smoke.stage_delta(positions=128, key_heads=2, value_heads=4,
                                   head_dim=16, calls=calls, repeats=1,
                                   check_positions=64, dim=32)
    # the mixer's block as a step rematerialises it, the rule's result
    # kept by name and not: a float32 for every element of a value head
    for name in ("kept", "bare"):
        assert facts[f"block_{name}_ms"] > 0
        assert facts[f"block_{name}_temp_gb"] >= 0
    assert facts["block_kept_bytes"] == 4 * 128 * 4 * 16
    for tag in ("halves_q16_k2", "solve_q16_k2", "doubling_q16_k1",
                "halves_q32_k2"):
        for name in ("fwd", "fwd_bwd"):
            assert facts[f"{tag}_{name}_ms"] > 0
            assert facts[f"{tag}_{name}_compile_s"] >= 0
    for how in ("halves", "solve", "doubling"):
        assert max(facts[f"rel_err_{how}_float32_o_dq_dk_dv_dg_dbeta"]) <= 1e-3
        errs = facts[f"rel_err_{how}_bfloat16_o_dq_dk_dv_dg_dbeta"]
        assert len(errs) == 6 and max(errs) <= chip_smoke.ATTN_BF16_TOL
    assert "delta" in dict(chip_smoke.STAGES)
    assert chip_smoke.DELTA_CALLS[0] == ("solve", 64, 2)
    # the cell's call to the flash kernels is among the stage's
    assert ("qwen3next.causal", (1, 16, 16384, 256), 2) in [
        c[:3] for c in chip_smoke.FLASH_CALLS]


def test_conv_stage():
    """The short-convolution mixer at a small size: ms and compile seconds
    forward, with every gradient and of the pass between the products, the
    mixer held to the convolution a position at a time; the least the
    memory allows is a chip's number and is not made up here."""
    facts = chip_smoke.stage_conv(sequences=2, positions=64, dim=32,
                                  repeats=1, check_positions=48)
    for name in ("fwd", "fwd_bwd", "taps"):
        assert facts[f"{name}_ms"] > 0 and facts[f"{name}_compile_s"] >= 0
    assert "taps_least_ms" not in facts
    errs = facts["rel_err_y_du_dwin_dconvw_dwout"]
    assert len(errs) == 5 and max(errs) <= chip_smoke.ATTN_BF16_TOL
    assert "conv" in dict(chip_smoke.STAGES)
    # what the chip's reading is held against, at the cell's shapes: 268 MB
    # over 819 GB/s
    from benchmark import conv_shapes, shapes
    assert abs(conv_shapes.mixer_bytes(2, 8192, 2048) / shapes.peak(
        "TPU v5 lite", "hbm_bytes_per_s") * 1e3 - 0.328) < 1e-3


def test_taps_stage():
    """The mixers' short convolution at a small size: ms and compile
    seconds forward and with every gradient of the kernels (interpreted
    here) and of the plain form, both held to the convolution a position at
    a time across four position tiles; the least the memory allows is a
    chip's number and is not made up here."""
    calls = (("delta", 256, False), ("ssm", 128, True))
    facts = chip_smoke.stage_taps(positions=128, calls=calls, repeats=1,
                                  check_positions=128, tile=(32, 128),
                                  interpret=True, tilings=(((64, 128), 8),))
    assert facts["kernels"] is True
    for name, _, has_bias in calls:
        for tag in ("", "plain_", "64x128r8_"):
            for what in ("fwd", "fwd_bwd"):
                assert facts[f"{name}_{tag}{what}_ms"] > 0
                assert facts[f"{name}_{tag}{what}_compile_s"] >= 0
        assert f"{name}_fwd_least_ms" not in facts
        for tag in ("", "plain_"):
            errs = facts[f"{name}_{tag}rel_err_y_dx_dw_db"]
            assert len(errs) == 3 + has_bias
            assert max(errs) <= chip_smoke.TAPS_F32_TOL
    assert "taps" in dict(chip_smoke.STAGES)
    # off the chip the op itself takes the plain form
    assert chip_smoke.stage_taps(positions=64, calls=calls[:1], repeats=1,
                                 check_positions=64)["kernels"] is False
    # what the chip's readings are held against, at the cells' shapes
    from benchmark import shapes
    least = lambda c: 2 * 4 * 16384 * c / shapes.peak(
        "TPU v5 lite", "hbm_bytes_per_s") * 1e3
    assert abs(least(8192) - 1.311) < 1e-3 and abs(least(6144) - 0.983) < 1e-3


def test_heads_stage():
    """ONE attention layer of each form at a small size: ms, compile
    seconds and the compiled layer's bytes outside its kernels for
    ``mla_moe.heads`` and for the formulation before it, the two forms'
    gradients held to each other; the byte reading itself on a program of
    known traffic."""
    from multiverso_tpu.models import gqa_moe, mla_moe
    cells = (("mla", mla_moe.MLAMoEConfig(), "latent", 2, 32),
             ("gqa", gqa_moe.GQAMoEConfig(qk_norm=True, attn_gate=True),
              "window", 1, 64))
    facts = chip_smoke.stage_heads(cells, repeats=1, attn="xla")
    for name, *_ in cells:
        for tag in ("parent", "new"):
            assert facts[f"{name}_{tag}_ms"] > 0
            assert facts[f"{name}_{tag}_compile_s"] >= 0
            assert facts[f"{name}_{tag}_kernels"] == 0      # XLA's core
            assert facts[f"{name}_{tag}_outside_gb"] >= 0
        assert facts[f"{name}_rel_err"] <= chip_smoke.HEADS_TOL
    assert "heads" in dict(chip_smoke.STAGES)
    assert [c[0] for c in chip_smoke._heads_cells()][:6] == [
        "glm", "mellum_full", "mellum_window", "trinity", "keye", "xing"]
    # result and operands of every top-level instruction, kernels apart
    text = """HloModule m
ENTRY %main (a: f32[1024,256]) -> bf16[1024,256] {
  %a = f32[1024,256]{1,0} parameter(0)
  %fusion.1 = f32[1024,256]{1,0} fusion(f32[1024,256]{1,0} %a), kind=kLoop
  %call = bf16[1024,256]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  %bitcast.2 = bf16[256,1024]{1,0} bitcast(%call)
  ROOT %copy.3 = bf16[1024,256]{1,0} copy(%call)
}
"""
    assert chip_smoke.bytes_outside_kernels(text) == {
        "all_gb": round((2 * 4 + 4 + 2 + 2 + 2) * 1024 * 256 / 1e9, 3),
        "kernels_gb": round(6 * 1024 * 256 / 1e9, 3),
        "outside_gb": round(12 * 1024 * 256 / 1e9, 3), "kernels": 1}
    assert chip_smoke.bytes_outside_kernels("no entry here") == {}


def test_select_stage():
    """The indexer and the selection at a small size: ms of the three
    products, of the whole selection and of a chunk by the counting search
    and by ``lax.top_k`` (the stage itself holds their sets equal, and a
    chunk alone to the whole map); a row's spread; the keys bfloat16
    decides otherwise than float32."""
    facts = chip_smoke.stage_select(positions=128, dim=64, index_heads=2,
                                    index_dim=8, topk=16, chunk=32,
                                    top_k_chunks=2, repeats=1)
    for name in ("operands_ms", "select_ms", "scores_ms_chunk"):
        assert facts[name] > 0
    for name in ("search_ms_chunk", "top_k_ms_chunk"):
        assert name in facts
    assert facts["selected_keys_tail"] == 2 * 32 * 16
    assert facts["row_spread"] > 0
    assert 0 <= facts["bfloat16_decides_otherwise_share"] < 0.1
    assert {"select", "target"} <= set(dict(chip_smoke.STAGES))


def test_target_stage():
    """The indexer's term at a small size, in both its forms: ms forward
    and with the gradients its forward makes, and term and gradients in
    float32 against plain autodiff of the definition over whole arrays."""
    facts = chip_smoke.stage_target(positions=128, dim=64, heads=4,
                                    kv_heads=2, head_dim=8, index_heads=2,
                                    index_dim=8, topk=16, chunk=32,
                                    repeats=1, check_positions=64)
    for tag in ("", "xla_"):        # the two kernels' form, and XLA's
        assert facts[tag + "fwd_ms"] > 0 and facts[tag + "fwd_bwd_ms"] > 0
        assert facts[tag + "term"] > 0
        errs = facts[tag + "rel_err_term_dqi_dki_dw"]
        assert len(errs) == 4 and max(errs) <= 1e-3
    assert max(facts["forms_rel_diff_term_dqi_dki_dw"]) < 3e-2
    # the kernels alone at the first, a middle and the last chunk
    walks = facts["kernels"]["chunks"]
    assert [walks[n]["tiles"] for n in ("0", "1", "3")] == [1, 2, 4]
    assert all(c["stats_ms"] > 0 and c["grads_ms"] > 0
               for c in walks.values())


def test_flash_stage_under_a_selection():
    """The flash stage's last call, tiny: the three kernels with the
    selection operand beside the same call without it, and against
    float32 attention under the same mask."""
    facts = chip_smoke.stage_flash(
        calls=(), repeats=1, selected=dict(
            shape=(1, 4, 128, 16), hkv=2, blocks=((32, 32), (32, 64)),
            topk=16, repeats=1))["keye.selected"]
    assert abs(facts["selected_share"] - (16 * 17 / 2 + 112 * 16)
               / (128 * 129 / 2)) < 1e-3
    for blocks in ("32x32", "32x64"):
        for tag in ("select", "causal"):
            for kernel in ("fwd", "dq", "dkv"):
                assert facts[f"{blocks}_{tag}_{kernel}_ms"] > 0
        assert max(facts[f"{blocks}_rel_err"]) <= chip_smoke.ATTN_BF16_TOL


@pytest.mark.parametrize("head_dim,group", [(16, 4), (8, 2)])
def test_flash_stage_at_heads_under_a_lane_tile(head_dim, group):
    """The stage's arithmetic at the shape of ``lfm2-train-8k``'s call, tiny:
    grouped query heads of less than a lane tile, whole tiles against
    sub-tiles, the kernels against float32 attention; the cell's own call
    is among the stage's."""
    facts = chip_smoke.stage_flash(
        calls=(("small", (2, 2 * group, 64, head_dim), 2, (32, 32), None),),
        subs=(16,), repeats=1)["small"]
    for tag in ("sub0", "sub16"):
        assert max(facts[f"{tag}_rel_err"]) <= chip_smoke.ATTN_BF16_TOL
    assert max(facts["sub16_vs_whole"]) <= 1e-2
    assert ("lfm2.causal", (2, 32, 8192, 64), 8, (1024, 1024), None) in (
        chip_smoke.FLASH_CALLS)


def test_main_refuses_a_cpu(capsys):
    """No TPU: non-zero exit, the reason on stderr, no result on stdout."""
    assert chip_smoke.main() == chip_smoke.EXIT_NO_CHIP
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err and "'cpu'" in captured.err


@pytest.mark.parametrize("fail", [False, True])
def test_last_line_is_the_result_and_nothing_else(monkeypatch, capsys, fail):
    """The driver parses the last stdout line: exactly ``ok`` and
    ``device``, the device exactly ``platform``/``kind``/``count``."""
    def stage():
        if fail:
            raise RuntimeError("boom")
        return {"fact": 1}

    monkeypatch.setattr(chip_smoke, "stage_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "compile_cache_dir": "/x"})
    monkeypatch.setattr(chip_smoke, "STAGES", (("only", stage),))
    rc = chip_smoke.main()
    assert rc == (chip_smoke.EXIT_STAGE_FAILED if fail else 0)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": not fail,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["stage"] == "summary" and summary["claim"] is None
    assert list(summary)[-1] == "claim"


def test_hc_stage_rehearses_the_kernels_in_the_interpreter():
    """The stage as the chip runs it, the three walks' kernels in the
    Pallas interpreter: each kernel's ms and error against its plain form
    (no share of a peak off a TPU), and the rule with them held to the
    reference's sublayer as the plain walks are."""
    from multiverso_tpu.ops import stream_walks

    found = stream_walks.gather
    facts = chip_smoke.stage_hc(positions=128, dim=64, repeats=1,
                                check_positions=128, stack=2, interpret=True)
    walks = facts["walks"]
    assert walks["kernels"] and walks["tiles"] == [64, 64, 64]
    for name in ("gather", "dots", "spread"):
        assert walks[f"{name}_ms"] > 0 and walks[f"{name}_plain_ms"] > 0
        assert walks[f"{name}_rel_err"] <= 2e-6
        assert f"{name}_hbm_share" not in walks
    assert max(facts["rel_err"]) <= chip_smoke.HC_F32_TOL
    assert stream_walks.gather is found         # nothing stays steered


def test_hc_stage():
    """One hyper-connected sublayer's stream maps alone at a small size: ms
    and compile seconds forward and with every gradient, the mix's error,
    and the result and gradients held to the reference's sublayer; the
    least the memory allows is a chip's number and is not made up here."""
    facts = chip_smoke.stage_hc(positions=64, dim=128, repeats=1,
                                check_positions=64, stack=2)
    for what in ("fwd", "fwd_bwd", "rule_plain", "autodiff"):
        assert facts[f"{what}_ms"] > 0 and facts[f"{what}_compile_s"] >= 0
        assert f"{what}_least_ms" not in facts
    # off a TPU the rule's walks are the plain forms, and nothing of the
    # kernels is read; the stack's trace and lowering are timed apart
    assert facts["walks"] == {"kernels": False}
    assert facts["rule_plain_rel_err"] == facts["rel_err"]
    assert facts["stack_trace_s"] > 0 and facts["stack_lower_s"] > 0
    assert 0 < facts["res_error"] < 5e-2
    # the weighted sum, dx, and the four tables' gradients
    assert len(facts["rel_err"]) == 6
    assert max(facts["rel_err"]) <= chip_smoke.HC_F32_TOL
    assert "hc" in dict(chip_smoke.STAGES)
    # what the chip's readings are held against, at the cell's shapes
    from benchmark import hc_shapes, shapes
    whole = hc_shapes.sublayer_bytes({"hc_mult": 4, "hidden_size": 3584},
                                     4096)
    assert whole == 4096 * 33 * 3584 * 4
    assert round(whole / shapes.peak("TPU v5 lite", "hbm_bytes_per_s") * 1e3,
                 2) == 2.37
    # the flash stage's call at two head sizes is among the cells' calls
    assert ("xing4.causal", (1, 32, 4096, 192), 32, (1024, 1024), None,
            128) in chip_smoke.FLASH_CALLS


def test_loop_stage():
    """The looped stack alone at a small size, float32 operands: the scan
    over the passes, the same passes unrolled and one pass alone, each with
    its trace, lowering and compile seconds and its ms a call; the scan
    held to the unrolled passes and to the reference a block at a time."""
    import jax.numpy as jnp

    facts = chip_smoke.stage_loop(positions=64, dim=64, heads=4, head_dim=16,
                                  ffn=96, layers=2, passes=3, repeats=1,
                                  dtype=jnp.float32)
    assert facts["block_runs"] == 6
    for name in ("scan", "unrolled", "pass"):
        for what in ("trace_s", "lower_s", "compile_s", "ms", "temp_gb"):
            assert facts[f"{name}_{what}"] >= 0
        assert facts[f"{name}_ms"] > 0
    kinds = {"exits", "dx", "final_norm", "attn_norm", "attn_post_norm",
             "ffn_norm", "ffn_post_norm", "wq", "wk", "wv", "wo", "wg", "wu",
             "wd"}
    assert set(facts["rel_err"]) == set(facts["scan_against_unrolled"]) == kinds
    assert max(facts["rel_err"].values()) <= chip_smoke.HC_F32_TOL
    assert max(facts["scan_against_unrolled"].values()) <= 1e-5
    assert "loop" in dict(chip_smoke.STAGES)
    # a part of the positions can be held to the reference alone
    part = chip_smoke.stage_loop(positions=64, dim=64, heads=4, head_dim=16,
                                 ffn=96, layers=1, passes=2, repeats=1,
                                 check_positions=32, dtype=jnp.float32)
    assert max(part["rel_err"].values()) <= chip_smoke.HC_F32_TOL
