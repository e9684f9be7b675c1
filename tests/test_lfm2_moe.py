"""The short-convolution decoder under a tied head (``models/lfm2_moe.py``'s
configuration and mixer on ``models/mla_moe.py``'s one decoder path, the
sigmoid route of ``parallel/moe.py`` with no shared expert,
``models/gqa_moe.gqa``'s q/k-normed attention) against its plain reference
(``benchmark/reference/lfm2_moe.py``: the convolution a position at a time)
at small sizes with float32 operands, where program and reference must
agree to rounding."""

import gc
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import multiverso_tpu as mv
from benchmark import conv_shapes
from benchmark.reference import lfm2_moe as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import (afmoe, gqa_moe, lfm2_moe, mla_moe,
                                   nemotron_h)

CFG = lfm2_moe.LFM2MoEConfig(
    vocab=96, dim=48, layer_kinds=("conv", "full", "conv", "conv", "conv"),
    n_dense_layers=1, conv_taps=3, n_heads=8, n_kv_heads=2, head_dim=6,
    rope_theta=1e6, dense_ffn=80, moe_ffn=24, n_experts=32, experts_held=8,
    expert_offset=8, top_k=4, routed_scale=1.0, bias_speed=1e-3, attn="xla",
    loss_chunk=32, compute_dtype=jnp.float32)
LAYERS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
          "full_attention", "conv"]
SCALES = {"conv_w": 0.5}


class Untied(lfm2_moe.LFM2MoEConfig):
    """The same model with a head of its own, to take the tie apart."""
    tied_head = False


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, layer_types=LAYERS, layers_run=[0, 2, 3, 4, 5],
        num_hidden_layers=len(cfg.layer_kinds),
        num_dense_layers=cfg.n_dense_layers, conv_L_cache=cfg.conv_taps,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.eps,
        intermediate_size=cfg.dense_ffn, moe_intermediate_size=cfg.moe_ffn,
        num_experts=cfg.experts_held,
        published={"num_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k, expert_offset=cfg.expert_offset,
        routed_scaling_factor=cfg.routed_scale, vocab_size=cfg.vocab)


def _inputs(cfg, seed=0, batch=2, positions=64):
    # the tied table large enough that both of its gradient's parts count
    params = mla_moe.init(cfg, seed, 0.1, scales=dict(SCALES, embed=0.3))
    # gains away from one, so that a gain's gradient is no symmetric case
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm"))):
        params[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(100 + i), params[name].shape)
    bias = 0.02 * jax.random.normal(jax.random.key(seed + 1),
                                    mla_moe.init_bias(cfg).shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, bias, tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


def test_a_block_has_a_mixer_of_either_kind_and_the_table_is_tied():
    assert [tuple(l) for l in CFG.layers()] == [
        ("L0", "conv", "dense"), ("L1", "full", "experts"),
        ("L2", "conv", "experts"), ("L3", "conv", "experts"),
        ("L4", "conv", "experts")]
    assert mla_moe.expert_layers(CFG) == ("L1", "L2", "L3", "L4")
    assert ref.layer_kinds(_ref_config(CFG)) == (
        "conv", "full_attention", "conv", "conv", "conv")
    shapes = mla_moe.param_shapes(CFG)
    by_block = lambda i: {n.split(".")[1]: s for n, s in shapes.items()
                          if n.startswith(f"L{i}.")}
    assert by_block(0) == {
        "attn_norm": (48,), "win": (48, 144), "conv_w": (3, 48),
        "wout": (48, 48), "ffn_norm": (48,), "wg": (48, 80), "wu": (48, 80),
        "wd": (80, 48)}
    # q and k gains over the head; no gate, no shared expert
    assert by_block(1) == {
        "attn_norm": (48,), "wq": (48, 48), "wk": (48, 12), "wv": (48, 12),
        "wo": (48, 48), "q_norm": (6,), "k_norm": (6,), "ffn_norm": (48,),
        "router": (32, 48), "eg": (8, 48, 24), "eu": (8, 48, 24),
        "ed": (8, 24, 48)}
    assert set(by_block(2)) == {"attn_norm", "win", "conv_w", "wout",
                                "ffn_norm", "router", "eg", "eu", "ed"}
    # one table for the lookup and the logits
    assert {n for n in shapes if "." not in n} == {"embed", "final_norm"}
    assert mla_moe.tied_head(CFG) and not mla_moe.tied_head(Untied())
    assert "head" in mla_moe.param_shapes(Untied(**CFG._asdict()))
    here = mla_moe.held(CFG, 128)
    assert (here.route, here.form, here.routed_scale) == (
        "sigmoid", "gated_silu", 1.0)


def test_the_four_older_models_keep_their_heads_and_spans():
    for cfg in (mla_moe.MLAMoEConfig(), gqa_moe.GQAMoEConfig(),
                afmoe.AFMoEConfig(), nemotron_h.NemotronHConfig()):
        shapes = mla_moe.param_shapes(cfg)
        assert not mla_moe.tied_head(cfg)
        assert shapes["head"] == shapes["embed"] == (cfg.vocab, cfg.dim)
        grid = mla_moe.mixer_grid(cfg, 64)
        # (``conv_kernel_layers`` and ``conv_bytes`` are the state-space
        # mixer's own short convolution's, PR 59)
        assert not any(k in ("conv_layers", "conv_taps", "conv_width",
                             "tied_head", "mixer_flops_token",
                             "step_flops_token") for k in grid)
    assert mla_moe.mixer_grid(afmoe.AFMoEConfig(), 64) == {}
    assert mla_moe.mixer_grid(nemotron_h.NemotronHConfig(), 64)[
        "block_kinds"] == "ssm,shared+experts,ssm,full,shared+experts"


@pytest.mark.parametrize("batch,positions", [(1, 1), (1, 2), (2, 40)])
def test_the_mixer_is_the_convolution_a_position_at_a_time(batch, positions):
    """Against the recurrence from the definition: the first two positions
    of a sequence read zeros before it, and a sequence of a batch never
    reads another's positions."""
    rng = jax.random.split(jax.random.key(5), 5)
    d = CFG.dim
    p = {"win": 0.3 * jax.random.normal(rng[0], (d, 3 * d)),
         "conv_w": 0.6 * jax.random.normal(rng[1], (3, d)),
         "wout": 0.3 * jax.random.normal(rng[2], (d, d))}
    u = jax.random.normal(rng[3], (batch, positions, d))
    got = jax.jit(lambda u, p: lfm2_moe.short_conv(u, p, CFG))(u, p)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.short_conv(u[i], p) for i in range(batch)])
        # the definition once more, by hand, at the sequence's start
        b, c, x = jnp.split(u[0] @ p["win"], 3, -1)
        z = b * x
        first = (c[0] * (p["conv_w"][2] * z[0])) @ p["wout"]
        assert _close(got[0, 0], first)
        if positions > 1:
            second = (c[1] * (p["conv_w"][1] * z[0]
                              + p["conv_w"][2] * z[1])) @ p["wout"]
            assert _close(got[0, 1], second)
    assert got.shape == u.shape and got.dtype == jnp.float32
    assert _close(got, want)
    if batch > 1:       # the second sequence alone gives the same rows
        alone = lfm2_moe.short_conv(u[1:], p, CFG)
        assert _close(got[1:], alone, 1e-6)
        # and every gradient is the recurrence's
        weight = jax.random.normal(rng[4], u.shape)
        grads = jax.jit(jax.grad(lambda u, p: jnp.sum(
            weight * lfm2_moe.short_conv(u, p, CFG)), (0, 1)))(u, p)
        with jax.default_matmul_precision("highest"):
            wants = jax.jit(jax.grad(lambda u, p: sum(jnp.sum(
                weight[i] * ref.short_conv(u[i], p))
                for i in range(batch)), (0, 1)))(u, p)
        assert _close(grads[0], wants[0])
        assert all(_close(grads[1][n], wants[1][n]) for n in p)


def test_the_taps_read_the_other_way_round_are_told_apart():
    """The control of the chip's comparison: the stored rows taken in the
    other order are other numbers than the definition."""
    rng = jax.random.split(jax.random.key(6), 2)
    z = jax.random.normal(rng[0], (16, 8))
    w = jax.random.normal(rng[1], (3, 8))
    want = ref.causal_conv(z, w)
    np.testing.assert_allclose(
        want[5], w[0] * z[3] + w[1] * z[4] + w[2] * z[5], rtol=1e-5)
    with ref.conv_control("taps_reversed"):
        faulty = ref.causal_conv(z, w)
    np.testing.assert_allclose(
        faulty[5], w[2] * z[3] + w[1] * z[4] + w[0] * z[5], rtol=1e-5)
    assert not _close(faulty, want, 1e-2)
    assert _close(ref.causal_conv(z, w), want, 1e-7)


@pytest.mark.parametrize("block,kind", [(0, "conv+dense"),
                                        (1, "full+experts"),
                                        (2, "conv+experts")])
def test_block_of_each_kind_matches_the_reference(block, kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    layer = CFG.layers()[block]
    assert f"{layer.attn}+{layer.ffn}" == kind
    x = 3.0 * jax.random.normal(jax.random.key(9), (2, 48, CFG.dim))
    p = mla_moe._sub(params, layer.name)
    got, aux = jax.jit(lambda x, p: mla_moe._run_block(
        x, p, layer, bias[0], CFG))(x, p)
    if layer.ffn == "dense":
        ffn = lambda u, q: (ref.mlp(u, q["wg"], q["wu"], q["wd"]), None)
    else:
        ffn = lambda u, q: ref.routed_share(
            u, q, bias[0], c, CFG.expert_offset, CFG.experts_held)
        p = ref._experts_3d(p, c)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([jax.jit(lambda x: ref.block(
            x, p, ffn, c, ref.layer_kinds(c)[block])[0])(x[i])
            for i in range(2)])
    assert _close(got, want)
    if layer.ffn == "experts":
        counts, overflow, _ = aux
        assert int(counts.sum()) == 2 * 48 * CFG.top_k and int(overflow) == 0
    else:
        assert aux is None


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_logits_loss_and_every_gradient_match_the_reference(attn, kernel):
    """Every table's gradient, the taps', the q and k gains' and the tied
    table's among them; and the logits the tied head gives."""
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=4)
    params, bias, tokens = _inputs(cfg)
    c = _ref_config(cfg)

    def logits(p):
        x, _ = mla_moe._trunk(p, bias, tokens, cfg)
        return mla_moe.rms_norm(x, p["final_norm"], cfg.eps) @ p["embed"].T

    with jax.default_matmul_precision("highest"):
        got = jax.jit(logits)(params)
    want = jnp.stack([ref.logits(params, bias, tokens[i], c)
                      for i in range(2)])
    assert got.shape == (2, 64, cfg.vocab) and _close(got, want)
    (loss, (counts, overflow, _)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert counts.shape == (4, cfg.n_experts) and int(overflow.sum()) == 0
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    for name in ("embed", "L0.conv_w", "L4.conv_w", "L0.win", "L2.wout",
                 "L1.q_norm", "L1.k_norm", "L1.wk", "L0.wd", "L3.router",
                 "L3.eg", "final_norm"):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
    bad = [n for n in grads if not _close(grads[n], want[n], 5e-5)]
    assert not bad, bad


def test_the_tied_tables_gradient_is_the_lookups_plus_the_heads():
    """With a head of its own (the same values) the embedding takes the
    lookup's rows and the head the chunked loss's float32 gradient; tied,
    one table takes their sum."""
    params, bias, tokens = _inputs(CFG)
    untied = Untied(**CFG._asdict())
    grad = lambda cfg, p: jax.jit(jax.grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(p)
    tied = grad(CFG, params)
    apart = grad(untied, dict(params, head=params["embed"]))
    assert set(apart) == set(tied) | {"head"}
    lookup, head = apart["embed"], apart["head"]
    # ids that the batch never holds take the head's part alone
    unseen = np.setdiff1d(np.arange(CFG.vocab), np.asarray(tokens))
    assert unseen.size and not np.any(np.asarray(lookup)[unseen])
    assert float(jnp.max(jnp.abs(head[unseen]))) > 0
    for part in (lookup, head):     # neither part is small beside the other
        assert float(jnp.linalg.norm(part)) > 0.05 * float(
            jnp.linalg.norm(tied["embed"]))
    assert tied["embed"].dtype == jnp.float32
    assert _close(tied["embed"], lookup + head, 1e-6)
    for n in tied:
        if n != "embed":
            assert _close(tied[n], apart[n], 1e-6), n


@pytest.mark.parametrize("head_dim,group", [(6, 4), (16, 4), (24, 2)])
def test_heads_under_a_lane_tile_agree_between_the_cores(head_dim, group):
    """``gqa_moe.gqa`` under this model's switches at head sizes that are
    no whole lane tile (the cell's is 64): the XLA core and the interpreted
    flash kernels give the same outputs and gradients."""
    cfg = CFG._replace(head_dim=head_dim, n_heads=2 * group, n_kv_heads=2,
                       attn_block=8)
    rng = jax.random.split(jax.random.key(head_dim), 3)
    p = {n: (1.0 + 0.2 * jax.random.normal(jax.random.fold_in(rng[0], i), s)
             if n.endswith("norm")
             else 0.2 * jax.random.normal(jax.random.fold_in(rng[0], i), s))
         for i, (n, s) in enumerate(sorted(cfg.attn_shapes("full").items()))}
    u = jax.random.normal(rng[1], (2, 32, cfg.dim))
    weight = jax.random.normal(rng[2], u.shape)

    def run(core):
        one = cfg._replace(attn=core)
        return jax.jit(jax.value_and_grad(lambda u, p: jnp.sum(
            weight * one.attend(u, p, "full")), (0, 1)))(u, p)

    (got, (du, dp)), (want, (du_w, dp_w)) = run("flash"), run("xla")
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-5
    assert _close(du, du_w, 1e-4)
    assert all(_close(dp[n], dp_w[n], 1e-4) for n in p)
    assert float(jnp.max(jnp.abs(dp_w["q_norm"]))) > 0


def test_lean_reference_is_the_plain_reference(monkeypatch):
    """The memory-saving form the chip's check uses (query rows, experts
    and the loss's positions in blocks) gives the same numbers."""
    from benchmark.reference import afmoe as ref_afmoe

    params, bias, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    monkeypatch.setattr(ref_afmoe, "LEAN_ROWS", 16)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c,
                                                lean=True))(params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[3][n], plain[3][n]) for n in plain[3])


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of the expert layer (the program's layer, told
    which eight experts it holds: offsets 0, 8, 16, 24) are the reference's
    uncut layer over all 32 experts. The layer has no shared expert:
    nothing is computed alike on every chip, so nothing is counted once."""
    cfg = CFG
    c = dict(_ref_config(cfg), num_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 5)
    d, f, e = cfg.dim, cfg.moe_ffn, cfg.n_experts
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "eg": 0.1 * jax.random.normal(rng[1], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[2], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[3], (e, f, d))}
    u = jax.random.normal(rng[4], (2, 48, d))
    bias = jnp.linspace(-0.05, 0.05, e)
    total, seen, offsets = jnp.zeros_like(u), 0, []
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
        out, (counts, overflow, _) = jax.jit(
            lambda u, share, offset=offset: mla_moe.expert_ffn(
                u, share, bias, cfg._replace(expert_offset=offset),
                shared=False))(u, share)
        total = total + out
        seen += int(counts[offset:offset + cfg.experts_held].sum())
        offsets.append(offset)
        assert int(overflow) == 0
    assert offsets == [0, 8, 16, 24]
    assert seen == 2 * 48 * cfg.top_k       # every assignment, once
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.routed_share(u[i], whole, bias, c, 0, e)[0]
                          for i in range(2)])
    assert _close(total, want)


def _products(text: str, *dims: int) -> int:
    """The ``dot_general`` operations of a lowering with every one of
    ``dims`` among an operand's or the result's dimensions."""
    dots = [line.split(" : ")[-1] for line in text.splitlines()
            if "dot_general" in line]
    assert dots
    return sum(all(re.search(rf"[<x]{d}x", line) for d in dims)
               for line in dots)


def test_a_conv_mixer_is_two_products_and_a_loss_three():
    """The lowered forward pass has two products a conv mixer (in and
    out), the lowered step three products of positions x vocabulary (the
    logits and the two gradients: the tie adds none), and the mixer's
    scopes are in the program."""
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    # widths that no other product of the model has
    cfg = CFG._replace(vocab=112, dim=40, head_dim=4, loss_chunk=64)
    assert cfg.vocab not in (cfg.dim, 3 * cfg.dim, cfg.dense_ffn,
                             cfg.moe_ffn)
    params, bias, tokens = _inputs(cfg)
    forward = jax.jit(lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0])
    text = forward.lower(params).as_text(debug_info=True)
    convs = sum(layer.attn == "conv" for layer in cfg.layers())
    assert _products(text, 3 * cfg.dim) == convs == 4      # u W_in
    for scope in ("mv.lm.conv/", "mv.lm.conv.in", "mv.lm.conv.taps",
                  "mv.lm.conv.out"):
        assert scope in text, scope
    # the out-projection beside it: [.., 40] x [40, 40]
    mixer = jax.jit(lambda u, p: lfm2_moe.short_conv(u, p, cfg))
    p = mla_moe._sub(params, "L0")
    alone = mixer.lower(jnp.zeros((2, 64, cfg.dim)), p).as_text()
    assert alone.count("dot_general") == 2
    assert _products(forward.lower(params).as_text(), cfg.vocab) == 1
    step = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(p, bias, tokens,
                                                      cfg)[0]))
    assert _products(step.lower(params).as_text(), cfg.vocab) == 3
    assert mla_moe.loss_grid(cfg, 128) == {"head_products": 3,
                                           "loss_chunks": 2}


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """And the step's span says the blocks' kinds, the mixers' counts and
    the tie, its operation counts those of ``benchmark/conv_shapes.py``."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=4, expert_kernel="xla")
    _, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    params = mla_moe.init(cfg, 0, 0.1, scales=SCALES)
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=SCALES)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert "head" not in tables
    assert len(tables) == 2 + 8 + 12 + 3 * 9
    for n, t in tables.items():     # the tables hold ``init``'s values
        np.testing.assert_allclose(
            t.get().reshape(params[n].shape), np.asarray(params[n]),
            rtol=1e-6, err_msg=n)
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr),
                              bias=bias + 0.0)      # the step donates it
    before = len(ttrace.events())
    loss, counts = trainer.step(tokens)
    trainer.adopt()
    c = _ref_config(cfg)
    want_loss, want_counts, _, grads = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(counts[:, :cfg.n_experts],
                                  np.asarray(want_counts))
    assert int(counts[:, cfg.n_experts].sum()) == 0
    for n, t in tables.items():
        want, _, _, _ = ref.adam_step(np.asarray(params[n]), 0.0, 0.0, 0,
                                      np.asarray(grads[n]), lr, b1, b2, eps)
        moved = t.get().reshape(params[n].shape) - np.asarray(params[n])
        sure = np.abs(np.asarray(grads[n])) > 1e-4 * np.abs(
            np.asarray(grads[n])).max()
        np.testing.assert_allclose(moved[sure], (want - params[n])[sure],
                                   atol=2e-2 * lr, err_msg=n)
        assert int(trainer.states[n]["ustate"]["t"]) == 1
    np.testing.assert_allclose(
        np.asarray(trainer.bias),
        ref.bias_rule(np.asarray(bias), np.asarray(want_counts),
                      cfg.bias_speed), atol=1e-7)
    args = [e for e in ttrace.events()[before:]
            if e["name"] == "lm.step"][0]["args"]
    assert args["block_kinds"] == ("conv+dense,full+experts,conv+experts,"
                                   "conv+experts,conv+experts")
    assert (args["expert_form"], args["conv_layers"], args["conv_taps"],
            args["conv_width"], args["tied_head"]) == (
                "gated_silu", 4, 3, 3 * cfg.dim, 1)
    assert args["mixer_flops_token"] == 4 * conv_shapes.mixer_flops(cfg.dim)
    assert args["step_flops_token"] == conv_shapes.step_flops_token(c, 64)
    # the one causal core: the conv mixers are no kind of attention
    assert (args["attn_kinds"], args["block_norms"], args["kv_group"]) == (
        "full", 2, 4)
    assert args["head_products"] == 3
    assert args["routed_rows"] == 4 * 2 * 64 * cfg.top_k
    # an operator's view of the same counts
    from tools import dump_metrics
    lines = dump_metrics._mixer_lines([{"name": "lm.step", "args": args}])
    share = 100.0 * args["mixer_flops_token"] / args["step_flops_token"]
    assert lines[0] == "  blocks: " + args["block_kinds"]
    assert f"= {share:.2f}%; tied head" in lines[1] and "4 of 3 taps" in (
        lines[1])
    assert dump_metrics._mixer_lines([{"name": "lm.step", "args": {}}]) == []
    nemotron = mla_moe.mixer_grid(nemotron_h.NemotronHConfig(), 64)
    assert dump_metrics._mixer_lines(
        [{"name": "lm.step", "args": nemotron}]) == [
            "  blocks: ssm,shared+experts,ssm,full,shared+experts",
            "    short convolution: the kernels in 0 mixer(s) (0: the plain "
            "form), 0 MB a mixer a pass at the least",
            "    state-space scan: the kernels in 0 mixer(s) (0: the plain "
            "form), 0 MB a mixer a forward pass at the least",
            "      the plain form because: no TPU"]


def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_conv

    class _Cell:
        config = c

    return c, lm_train_conv._model_config(_Cell)


def test_published_sizes_give_the_configurations_parameter_count():
    c, cfg = _published()
    assert [tuple(l)[1:] for l in cfg.layers()] == [
        ("conv", "dense"), ("full", "experts"), ("conv", "experts"),
        ("conv", "experts"), ("conv", "experts")]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.kv_group) == (
        32, 8, 64, 4)
    shapes = mla_moe.param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    mixer = lambda i, names: count(
        lambda n: n.startswith(f"L{i}.") and n.split(".")[1] in names)
    assert mixer(0, ("win", "conv_w", "wout")) == 16_783_360
    assert mixer(1, ("wq", "wk", "wv", "wo", "q_norm", "k_norm")) == (
        10_485_888)
    assert mixer(0, ("wg", "wu", "wd")) == 44_040_192
    assert mixer(1, ("router", "eg", "eu", "ed")) == 88_145_920
    assert count(lambda n: n.startswith("L0.")) == 60_827_648
    assert count(lambda n: n.startswith("L1.")) == 98_635_904
    assert count(lambda n: n.startswith("L2.")) == 104_933_376
    assert count(lambda n: "." not in n) == 33_556_480
    assert count(lambda n: True) == 507_820_160 == c["parameters"]
    assert shapes["L2.eg"] == (8, 2048, 1792)
    here = mla_moe.held(cfg, 16384)
    assert here.tile == (512, 512, 896) and here.buffer_rows == 32768


def test_published_sizes_give_the_mixers_share_of_the_step():
    """The counts the cell's span carries, at the cell's shapes: the four
    conv mixers are the largest part of a token's needed operations."""
    c, cfg = _published()
    grid = mla_moe.mixer_grid(cfg, 8192)
    assert grid["mixer_flops_token"] == 4 * conv_shapes.mixer_flops(2048) == (
        134_217_728)
    assert grid["step_flops_token"] == conv_shapes.step_flops_token(c, 8192)
    # conv mixers, dense FFN, held experts at the even share, head,
    # attention's projections and core, four routers
    assert grid["step_flops_token"] == (
        134_217_728 + 88_080_384 + 88_080_384 + 67_108_864 + 20_971_520
        + 33_558_528 + 524_288)
    share = 100.0 * grid["mixer_flops_token"] / grid["step_flops_token"]
    assert 30.9 < share < 31.1
    assert conv_shapes.mixer_bytes(2, 8192, 2048) == 268_435_456
    # the head-64 blocks the kernels are called with
    assert mla_moe.attn_blocks(cfg, 8192) == (1024, 1024)
