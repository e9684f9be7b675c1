"""The one-mixer-a-block decoder (``models/nemotron_h.py``'s configuration
and state-space mixer on ``models/mla_moe.py``'s one decoder path, relu2
experts of ``parallel/moe.py``, ``models/gqa_moe.gqa``'s attention without
positions) against its plain reference (``benchmark/reference/
nemotron_h.py``: the recurrence a position at a time) at small sizes with
float32 operands, where program and reference must agree to rounding."""

import gc
import json
import os
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
from benchmark.reference import nemotron_h as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import afmoe, gqa_moe, mla_moe, nemotron_h
from multiverso_tpu.parallel import moe

CFG = nemotron_h.NemotronHConfig(
    vocab=96, dim=48, pattern="MEM*E", ssm_heads=4, ssm_head_dim=8,
    ssm_groups=2, ssm_state=16, conv_kernel=4, chunk=16, n_heads=8,
    n_kv_heads=2, head_dim=8, moe_ffn=24, shared_ffn=40, n_experts=16,
    experts_held=1, expert_offset=5, top_k=3, routed_scale=2.5,
    bias_speed=1e-3, attn="xla", loss_chunk=32, compute_dtype=jnp.float32)
BLOCKS = {"ssm": 0, "experts": 1, "full": 3}


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, hybrid_override_pattern=cfg.pattern + "MEME",
        num_hidden_layers=len(cfg.pattern),
        mamba_num_heads=cfg.ssm_heads, mamba_head_dim=cfg.ssm_head_dim,
        n_groups=cfg.ssm_groups, ssm_state_size=cfg.ssm_state,
        conv_kernel=cfg.conv_kernel, chunk_size=cfg.chunk,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, layer_norm_epsilon=cfg.eps,
        moe_intermediate_size=cfg.moe_ffn,
        moe_shared_expert_intermediate_size=cfg.shared_ffn,
        n_routed_experts=cfg.experts_held,
        published={"n_routed_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k, expert_offset=cfg.expert_offset,
        routed_scaling_factor=cfg.routed_scale)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, scales={"embed": 1.0,
                                                  "conv_w": 0.3})
    # gains away from one, so that a gain's gradient is no symmetric case
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm")
                                    or n.endswith("skip"))):
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


def test_a_block_has_one_mixer_and_the_layer_list_says_which():
    assert [tuple(l) for l in CFG.layers()] == [
        ("L0", "ssm", None), ("L1", None, "shared+experts"),
        ("L2", "ssm", None), ("L3", "full", None),
        ("L4", None, "shared+experts")]
    assert mla_moe.expert_layers(CFG) == ("L1", "L4")
    shapes = mla_moe.param_shapes(CFG)
    by_block = lambda i: {n.split(".")[1]: s for n, s in shapes.items()
                          if n.startswith(f"L{i}.")}
    # one norm a block; the in-projection is [z | xBC | dt]
    assert by_block(0) == {
        "attn_norm": (48,), "win": (48, 32 + (32 + 2 * 2 * 16) + 4),
        "conv_w": (4, 96), "conv_b": (96,), "a_log": (4,), "dt_bias": (4,),
        "skip": (4,), "gate_norm": (32,), "wout": (32, 48)}
    # two matrices an expert and no gate; the shared expert at its own width
    assert by_block(1) == {
        "ffn_norm": (48,), "router": (16, 48), "eu": (1, 48, 24),
        "ed": (1, 24, 48), "su": (48, 40), "sd": (40, 48)}
    assert by_block(3) == {"attn_norm": (48,), "wq": (48, 64),
                           "wk": (48, 16), "wv": (48, 16), "wo": (64, 48)}
    assert mla_moe.held(CFG, 128).form == "relu2"


def test_the_three_older_models_layers_shapes_and_forms_are_unchanged():
    glm, mellum, trinity = (mla_moe.MLAMoEConfig(), gqa_moe.GQAMoEConfig(),
                            afmoe.AFMoEConfig())
    assert [tuple(l) for l in glm.layers()] == [
        ("L0", "latent", "dense"), ("L1", "latent", "shared+experts"),
        ("L2", "latent", "shared+experts"),
        ("mtp", "latent", "shared+experts")]
    assert [tuple(l) for l in mellum.layers()] == [
        ("L0", "window", "experts"), ("L1", "window", "experts"),
        ("L2", "window", "experts"), ("L3", "full", "experts")]
    assert [tuple(l) for l in trinity.layers()] == [
        ("L0", "window", "dense")] + [
            (f"L{i}", kind, "shared+experts")
            for i, kind in enumerate(trinity.layer_kinds) if i]
    kinds = lambda cfg, layer: {n.split(".")[1]
                                for n in mla_moe.param_shapes(cfg)
                                if n.startswith(layer + ".")}
    assert kinds(glm, "L1") == {
        "attn_norm", "wdq", "q_norm", "wuq", "wdkv", "kv_norm", "wukv", "wo",
        "ffn_norm", "router", "eg", "eu", "ed", "sg", "su", "sd"}
    assert kinds(mellum, "L3") == {"attn_norm", "wq", "wk", "wv", "wo",
                                   "ffn_norm", "router", "eg", "eu", "ed"}
    assert kinds(trinity, "L1") == {
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wgate",
        "ffn_norm", "attn_post_norm", "ffn_post_norm", "router", "eg", "eu",
        "ed", "sg", "su", "sd"}
    for cfg in (glm, mellum, trinity):
        shapes = mla_moe.param_shapes(cfg)
        assert cfg.expert_form == "gated_silu"
        assert mla_moe.held(cfg, 64).form == "gated_silu"
        assert shapes["L1.eg"] == shapes["L1.eu"] == (
            cfg.experts_held, cfg.dim, cfg.moe_ffn)
        # nothing of a one-mixer list in their spans
        assert mla_moe.mixer_grid(cfg, 64) == {}
    for cfg in (glm, trinity):      # the shared expert at the experts' width
        assert mla_moe.param_shapes(cfg)["L1.sg"] == (cfg.dim, cfg.moe_ffn)
    assert mla_moe.attn_grid(glm._replace(attn="flash"), 64)[
        "block_norms"] == 2
    assert mla_moe.attn_grid(trinity._replace(attn="flash"), 64)[
        "block_norms"] == 4


def test_first_values_follow_the_mamba2_rule():
    """``A`` in [1, 16], the step sizes in [time_step_min, time_step_max],
    the skip at 1; the tables hold the same values as ``init``."""
    params = mla_moe.init(CFG._replace(ssm_heads=64, ssm_groups=8), 5)
    a = np.exp(np.asarray(params["L0.a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.max() - a.min() > 10
    step = np.asarray(jax.nn.softplus(params["L0.dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    assert step.max() / step.min() > 10
    np.testing.assert_array_equal(np.asarray(params["L0.skip"]), 1.0)
    np.testing.assert_array_equal(np.asarray(params["L0.gate_norm"]), 1.0)
    assert not np.array_equal(params["L0.a_log"], params["L2.a_log"])
    assert 0.05 < float(jnp.std(params["L0.win"])) / 0.02 - 0.95 < 0.1


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_of_each_kind_matches_the_reference(kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    layer = CFG.layers()[BLOCKS[kind]]
    assert (layer.attn or layer.ffn.split("+")[-1]) == kind
    x = 3.0 * jax.random.normal(jax.random.key(9), (2, 48, CFG.dim))
    p = mla_moe._sub(params, layer.name)
    got, aux = jax.jit(lambda x, p: mla_moe._run_block(
        x, p, layer, bias[0], CFG))(x, p)
    eps = CFG.eps
    with jax.default_matmul_precision("highest"):
        if kind == "ssm":
            one = lambda x: x + ref.mamba2(
                ref.rms(x, p["attn_norm"], eps), p, c)
        elif kind == "full":
            one = lambda x: x + ref.attention(
                ref.rms(x, p["attn_norm"], eps), p, c)
        else:
            q = ref._experts_3d(p, c)
            one = lambda x: x + ref.expert_layer(
                ref.rms(x, p["ffn_norm"], eps), q, bias[0], c,
                CFG.expert_offset, CFG.experts_held)[0]
        want = jnp.stack([jax.jit(one)(x[i]) for i in range(2)])
    assert _close(got, want)
    if kind == "experts":
        counts, overflow, _ = aux
        assert int(counts.sum()) == 2 * 48 * CFG.top_k and int(overflow) == 0
    else:
        assert aux is None


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_and_every_gradient_match_the_reference(attn, kernel):
    """Every table's gradient: the convolution's taps and bias, ``A_log``,
    the step sizes' bias, the skip and the gated norm's gains among them."""
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=4)
    params, bias, tokens = _inputs(cfg)
    (loss, (counts, overflow, _)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, _ref_config(cfg)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert counts.shape == (2, cfg.n_experts) and int(overflow.sum()) == 0
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    for name in ("L0.conv_w", "L0.conv_b", "L0.a_log", "L2.dt_bias",
                 "L2.skip", "L0.gate_norm", "L1.su", "L4.eu", "L3.wk"):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
    bad = [n for n in grads if not _close(grads[n], want[n], 5e-5)]
    assert not bad, bad


def test_lean_reference_is_the_plain_reference(monkeypatch):
    """The memory-saving form the chip's check uses (the recurrence in
    stretches, query rows, experts and the loss's positions in blocks)
    gives the same numbers."""
    from benchmark.reference import afmoe as ref_afmoe

    params, bias, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    monkeypatch.setattr(ref_afmoe, "LEAN_ROWS", 16)
    monkeypatch.setattr(ref, "LEAN_STEPS", 8)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c,
                                                lean=True))(params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[3][n], plain[3][n]) for n in plain[3])


@pytest.mark.parametrize("how", ["sums_bfloat16", "no_carry"])
def test_a_faulty_scan_is_told_apart_from_the_reference(how):
    """The controls of the chip's comparison: a state kept in bfloat16 and
    a scan that drops what one chunk hands the next are other numbers than
    the recurrence, by far more than the program's rounding."""
    params, _, _ = _inputs(CFG)
    c = _ref_config(CFG)
    p = mla_moe._sub(params, "L0")
    # inputs of the published model's size (a unit input through 2,688
    # columns of 0.02 is 1) and a long memory: decays near 1
    p = dict(p, win=10.0 * p["win"], a_log=p["a_log"] - 6.0,
             dt_bias=p["dt_bias"] + 2.0)
    u = jax.random.normal(jax.random.key(4), (64, CFG.dim))
    with jax.default_matmul_precision("highest"):
        want = ref.mamba2(u, p, c)
        with ref.scan_control(how):
            faulty = ref.mamba2(u, p, c)
        got = nemotron_h.mamba2(u[None], p, CFG)[0]
    assert _close(got, want)
    assert not _close(faulty, want, 1e-3)
    if how == "no_carry":       # the first chunk has nothing to be handed
        assert _close(faulty[:CFG.chunk], want[:CFG.chunk])


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips' shares of the routed part (the program's layer, told
    which expert it holds), with the shared expert counted once, are the
    reference's uncut layer over all sixteen experts."""
    cfg = CFG
    c = dict(_ref_config(cfg), n_routed_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 6)
    d, f, fs, e = cfg.dim, cfg.moe_ffn, cfg.shared_ffn, cfg.n_experts
    assert e // cfg.experts_held == 16
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "su": 0.1 * jax.random.normal(rng[1], (d, fs)),
             "sd": 0.1 * jax.random.normal(rng[2], (fs, d)),
             "eu": 0.1 * jax.random.normal(rng[3], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[4], (e, f, d))}
    u = jax.random.normal(rng[5], (2, 48, d))
    bias = jnp.linspace(-0.05, 0.05, e)
    shared = mla_moe.relu2_mlp(u, whole["su"], whole["sd"], cfg)
    total, seen = shared, 0
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eu", "ed")})
        out, (counts, overflow, _) = jax.jit(
            lambda u, share, offset=offset: mla_moe.expert_ffn(
                u, share, bias, cfg._replace(expert_offset=offset)))(u, share)
        total = total + (out - shared)
        seen += int(counts[offset:offset + cfg.experts_held].sum())
        assert int(overflow) == 0
    assert seen == 2 * 48 * cfg.top_k       # every assignment, once
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(u[i], whole, bias, c, 0, e)[0]
                          for i in range(2)])
    assert _close(total, want)


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_a_relu2_expert_is_two_products(kernel):
    """``held_expert_layer`` under the relu2 form takes no gate matrix and
    gives ``gate * relu(u W_up)^2 W_down`` for the experts held."""
    t, d, f, e = 32, 128, 128, 4
    rng = jax.random.split(jax.random.key(8), 4)
    u = jax.random.normal(rng[0], (t, d))
    params = {"router": 0.2 * jax.random.normal(rng[1], (e, d)),
              "w_up": 0.1 * jax.random.normal(rng[2], (2, d, f)),
              "w_down": 0.1 * jax.random.normal(rng[3], (2, f, d))}
    cfg = moe.HeldExperts(num_experts=e, experts_held=2, expert_offset=1,
                          top_k=2, routed_scale=2.5, form="relu2",
                          dtype=jnp.float32)
    out, counts, overflow, _ = moe.held_expert_layer(
        u, params, jnp.zeros(e), cfg, kernel)
    chosen, gates, _ = moe.sigmoid_route(u, params["router"], jnp.zeros(e),
                                         cfg)
    want = jnp.zeros((t, d))
    with jax.default_matmul_precision("highest"):
        for held in range(2):
            gate = jnp.where(chosen == held + 1, gates, 0.0).sum(-1)
            want += gate[:, None] * (jnp.square(jax.nn.relu(
                u @ params["w_up"][held])) @ params["w_down"][held])
    assert int(overflow) == 0 and int(counts.sum()) == 2 * t
    assert _close(out, want, 1e-4)
    with pytest.raises(ValueError, match="no expert form"):
        moe.held_expert_layer(u, params, jnp.zeros(e),
                              cfg._replace(form="gelu"), kernel)


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """And the step's span says the blocks' kinds, the experts' form and
    the scan's counts."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=4, expert_kernel="xla")
    _, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    scales = {"embed": 1.0, "conv_w": 0.3}
    params = mla_moe.init(cfg, 0, 0.1, scales=scales)
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=scales)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert len(tables) == 3 + 2 * 9 + 2 * 6 + 5
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
    want_loss, want_counts, _, grads = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, _ref_config(cfg)))(
            params)
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
    assert args["block_kinds"] == "ssm,shared+experts,ssm,full,shared+experts"
    assert (args["expert_form"], args["ssm_layers"], args["ssm_chunks"],
            args["ssm_heads"], args["ssm_state"]) == ("relu2", 2, 4, 4, 16)
    # the scan's kernels take no shape this small, and no CPU
    assert (args["ssd_kernel_layers"], args["ssd_bytes"]) == (
        0, 4 * 64 * (2 * 4 * 8 + 2 * 2 * 16 + 4))
    # the one causal core, with no positions and one norm a block
    assert (args["attn_kinds"], args["block_norms"], args["kv_group"]) == (
        "full", 1, 4)
    assert args["routed_rows"] == 2 * 2 * 64 * cfg.top_k


def test_published_sizes_give_the_configurations_parameter_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_hybrid

    class _Cell:
        config = c

    cfg = lm_train_hybrid._model_config(_Cell)
    assert [(l.attn or "E")[0] for l in cfg.layers()] == list("sEsEsfEsE")
    shapes = mla_moe.param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    assert count(lambda n: n.startswith("L0.")) == 38_744_896
    assert count(lambda n: n.startswith("L1.")) == 100_125_312
    assert count(lambda n: n.startswith("L5.")) == 23_399_040
    assert count(lambda n: "." not in n) == 88_083_072
    assert count(lambda n: True) == 666_962_944 == c["parameters"]
    assert shapes["L0.win"] == (2688, 4096 + 6144 + 64)
    assert shapes["L1.eu"] == (8, 2688, 1856)
    assert shapes["L1.su"] == (2688, 3712)


def test_the_step_counts_the_mixers_whose_scan_runs_the_kernels(monkeypatch):
    """``lm.step``'s two static counts of the scan: ``ssd_kernel_layers``
    (every mixer or none, by this device and the shapes:
    ``ssd.kernel_heads``) and ``ssd_bytes`` (``x``, ``B``, ``C``, ``dt`` read
    and ``y`` written once, float32, ONE mixer's forward pass)."""
    from multiverso_tpu.ops import ssd
    from tools import dump_metrics

    cell = nemotron_h.NemotronHConfig(
        pattern="MEMEM*EME", ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state=128, chunk=128)
    want = 4 * 16384 * (2 * 64 * 64 + 2 * 8 * 128 + 64)
    assert want == 675_282_944
    grid = cell.ssm_grid(16384)
    assert (grid["ssd_kernel_layers"], grid["ssd_bytes"]) == (0, want)

    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    grid = mla_moe.mixer_grid(cell, 16384)
    assert (grid["ssd_kernel_layers"], grid["ssd_bytes"]) == (4, want)
    assert grid["conv_kernel_layers"] == 4
    # a chunk that is no lane tile, positions that are no whole chunks and
    # heads of 8 run the plain form on a chip too
    assert cell._replace(chunk=64).ssm_grid(16384)["ssd_kernel_layers"] == 0
    assert cell.ssm_grid(16384 + 64)["ssd_kernel_layers"] == 0
    assert CFG.ssm_grid(64)["ssd_kernel_layers"] == 0
    # the timeline's line for an operator
    lines = dump_metrics._mixer_lines([{"name": "lm.step", "args": grid}])
    assert lines[-1] == ("    state-space scan: the kernels in 4 mixer(s) "
                         "(0: the plain form), 675 MB a mixer a forward "
                         "pass at the least")
