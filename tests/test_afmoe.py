"""The gated, q/k-normed grouped-query decoder with rotary positions in
its window layers alone, four norms a block and a sigmoid route beside a
shared expert (``models/afmoe.py``'s configuration on ``models/mla_moe.py``'s
one decoder path and ``models/gqa_moe.gqa``'s one attention) against its
plain reference (``benchmark/reference/afmoe.py``) at small sizes with
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
from benchmark.reference import afmoe as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import afmoe, gqa_moe, mla_moe
from multiverso_tpu.ops.attention_kernels import causal_pairs, live_pairs

CFG = afmoe.AFMoEConfig(
    vocab=96, dim=64, n_heads=8, n_kv_heads=2, head_dim=8, window=16,
    layer_kinds=("window", "window", "full"), n_dense_layers=1,
    rope_theta=1e4, dense_ffn=96, moe_ffn=48, n_experts=16, experts_held=2,
    expert_offset=4, top_k=4, routed_scale=2.826, bias_speed=1e-3,
    embed_scale=8.0, attn="xla", loss_chunk=32, compute_dtype=jnp.float32)
KINDS = {"window": "sliding_attention", "full": "full_attention"}
BLOCKS = {"dense-window": 0, "experts-window": 1, "experts-full": 2}


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.window, rope_theta=cfg.rope_theta,
        layer_types=[KINDS[cfg.layer_kinds[0]], "full_attention"]
        + [KINDS[k] for k in cfg.layer_kinds[cfg.n_dense_layers:]],
        num_hidden_layers=len(cfg.layer_kinds),
        num_dense_layers=cfg.n_dense_layers, rms_norm_eps=cfg.eps,
        intermediate_size=cfg.dense_ffn, moe_intermediate_size=cfg.moe_ffn,
        num_shared_experts=1, num_experts=cfg.experts_held,
        published={"num_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k, expert_offset=cfg.expert_offset,
        route_scale=cfg.routed_scale, mup_enabled=True)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, scales={"embed": 0.02})
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


def test_the_layer_list_and_the_shapes_are_data_for_all_three_models():
    assert CFG.layers() == (mla_moe.Layer("L0", "window", "dense"),
                            mla_moe.Layer("L1", "window", "shared+experts"),
                            mla_moe.Layer("L2", "full", "shared+experts"))
    assert mla_moe.expert_layers(CFG) == ("L1", "L2")
    assert (CFG.route, CFG.routed_scale, CFG.balance_coef, CFG.kv_group) == (
        "sigmoid", 2.826, 0.0, 4)
    own = {"q_norm", "k_norm", "wgate", "attn_post_norm", "ffn_post_norm"}
    block = {n.split(".")[1] for n in mla_moe.param_shapes(CFG)
             if n.startswith("L1.")}
    assert block == own | {"attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
                           "router", "eg", "eu", "ed", "sg", "su", "sd"}
    assert mla_moe.param_shapes(CFG)["L1.wgate"] == (64, 64)
    assert mla_moe.param_shapes(CFG)["L1.k_norm"] == (8,)
    # Mellum2's and GLM's configurations give what they gave
    mellum = gqa_moe.GQAMoEConfig()
    assert [tuple(l) for l in mellum.layers()] == [
        ("L0", "window", "experts"), ("L1", "window", "experts"),
        ("L2", "window", "experts"), ("L3", "full", "experts")]
    assert (mellum.qk_norm, mellum.attn_gate, mellum.rope_kinds,
            mellum.post_norms, mellum.embed_scale) == (
                False, False, ("window", "full"), False, 1.0)
    assert {n.split(".")[1] for n in mla_moe.param_shapes(mellum)
            if n.startswith("L3.")} == {
                "attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "router",
                "eg", "eu", "ed"}
    glm = mla_moe.MLAMoEConfig()
    assert (glm.post_norms, glm.embed_scale) == (False, 1.0)
    assert [tuple(l) for l in glm.layers()] == [
        ("L0", "latent", "dense"), ("L1", "latent", "shared+experts"),
        ("L2", "latent", "shared+experts"),
        ("mtp", "latent", "shared+experts")]
    assert {n.split(".")[1] for n in mla_moe.param_shapes(glm)
            if n.startswith("L1.")} == {
                "attn_norm", "wdq", "q_norm", "wuq", "wdkv", "kv_norm",
                "wukv", "wo", "ffn_norm", "router", "eg", "eu", "ed", "sg",
                "su", "sd"}
    assert not own & {n.split(".")[-1] for n in mla_moe.param_shapes(glm)
                      if n.split(".")[-1] != "q_norm"}


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_of_each_kind_matches_the_reference(kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    layer = CFG.layers()[BLOCKS[kind]]
    assert f"{layer.ffn.split('+')[-1]}-{layer.attn}" == kind
    x = 3.0 * jax.random.normal(jax.random.key(9), (2, 48, CFG.dim))
    p = mla_moe._sub(params, layer.name)
    row = BLOCKS[kind] - 1
    got, aux = jax.jit(lambda x, p: mla_moe._run_block(
        x, p, layer, None if row < 0 else bias[row], CFG))(x, p)
    if row < 0:
        ffn = lambda u, q: (ref.mlp(u, q["wg"], q["wu"], q["wd"]), None)
        q = p
    else:
        ffn = lambda u, q: ref.expert_layer(u, q, bias[row], c,
                                            CFG.expert_offset,
                                            CFG.experts_held)
        q = ref._experts_3d(p, c)
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda x, q: ref.block(x, q, ffn, c, KINDS[layer.attn]))
        both = [one(x[i], q) for i in range(2)]
    assert _close(got, jnp.stack([y for y, _ in both]))
    if row >= 0:
        counts, overflow, _ = aux
        assert int(counts.sum()) == 2 * 48 * CFG.top_k and int(overflow) == 0
        np.testing.assert_array_equal(
            np.asarray(counts), np.asarray(sum(a[0] for _, a in both)))


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_and_every_gradient_match_the_reference(attn, kernel):
    """Every table's gradient, ``wgate``, the q and k gains and the four
    norms' gains among them; under the flash kernels' interpreter the
    window of 16 is two k blocks of 8 wide."""
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=4)
    if attn == "flash":
        assert mla_moe.attn_blocks(cfg, 64) == (8, 8)
    params, bias, tokens = _inputs(cfg)
    (loss, (counts, overflow, _)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, _ref_config(cfg)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(overflow.sum()) == 0
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    for name in ("L1.wgate", "L2.q_norm", "L2.k_norm", "L0.attn_post_norm",
                 "L1.ffn_post_norm", "L1.attn_norm", "L2.ffn_norm"):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
    bad = [n for n in grads if not _close(grads[n], want[n])]
    assert not bad, bad


def test_lean_reference_is_the_plain_reference(monkeypatch):
    """The memory-saving form the chip's check uses (query rows, experts
    and the loss's positions in blocks) gives the same numbers."""
    params, bias, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    monkeypatch.setattr(ref, "LEAN_ROWS", 16)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c,
                                                lean=True))(params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[3][n], plain[3][n]) for n in plain[3])


@pytest.mark.parametrize("kind,blind", [("full", True), ("window", False)])
def test_a_full_layer_has_no_positions_and_a_window_layer_has(kind, blind):
    """With the earlier positions' inputs shuffled among themselves the
    last position's attention output stays in a full layer (no rotary: a
    causal sum over a set) and moves in a window layer, in the program and
    in the reference alike."""
    params, _, _ = _inputs(CFG)
    layer = next(l for l in CFG.layers() if l.attn == kind)
    p = mla_moe._sub(params, layer.name)
    s = CFG.window          # every earlier position is inside the window
    u = jax.random.normal(jax.random.key(11), (1, s, CFG.dim))
    order = np.r_[np.random.default_rng(0).permutation(s - 1), s - 1]
    assert (order[:-1] != np.arange(s - 1)).any()
    c = _ref_config(CFG)
    for attend in (lambda v: CFG.attend(v, p, kind)[0],
                   lambda v: ref.attention(v[0], p, c, KINDS[kind])):
        with jax.default_matmul_precision("highest"):
            attend = jax.jit(attend)
            a, b = attend(u)[-1], attend(u[:, order])[-1]
        assert _close(b, a, 1e-5) == blind


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips' shares of the routed part (the program's layer, told
    which two experts it holds), with the shared expert counted once, are
    the reference's uncut layer over all sixteen experts."""
    cfg = CFG
    c = dict(_ref_config(cfg), num_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 8)
    d, f, e = cfg.dim, cfg.moe_ffn, cfg.n_experts
    assert e // cfg.experts_held == 8
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "sg": 0.1 * jax.random.normal(rng[1], (d, f)),
             "su": 0.1 * jax.random.normal(rng[2], (d, f)),
             "sd": 0.1 * jax.random.normal(rng[3], (f, d)),
             "eg": 0.1 * jax.random.normal(rng[4], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[5], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[6], (e, f, d))}
    u = jax.random.normal(rng[7], (2, 48, d))
    bias = jnp.linspace(-0.05, 0.05, e)
    shared = mla_moe.gated_mlp(u, whole["sg"], whole["su"], whole["sd"], cfg)
    total, seen = shared, 0
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
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


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """And the step's span says what the attention does around its core
    (the flash kernels' interpreter is the core here, so that the span has
    its grid: 64 positions in 8 x 8 blocks)."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=4, expert_kernel="xla")
    _, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    scales = {"embed": 0.02}
    params = mla_moe.init(cfg, 0, 0.1, scales=scales)
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=scales)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    # 11 of attention and 4 norms a block; 3 dense, 7 expert matrices
    assert len(tables) == 3 + (11 + 3) + 2 * (11 + 7)
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
    # the selection biases moved by the rule, at the configuration's speed
    np.testing.assert_allclose(
        np.asarray(trainer.bias),
        ref.bias_rule(np.asarray(bias), np.asarray(want_counts),
                      cfg.bias_speed), atol=1e-7)
    args = [e for e in ttrace.events()[before:]
            if e["name"] == "lm.step"][0]["args"]
    assert args["attn_kinds"] == "window,window,full"
    assert (args["attn_gated"], args["qk_norm"], args["rope_kinds"],
            args["block_norms"], args["embed_scale"], args["kv_group"]) == (
                1, 1, "window", 4, 8.0, 4)
    # 36 causal pairs; a band of 16 is three blocks wide (8 + 7 + 6), its
    # middle block unmasked
    assert (args["attn_pairs_live_window"], args["attn_pairs_masked_window"],
            args["attn_pairs_causal_window"]) == (21, 14, 36)
    # whole tiles of 8 x 8 at these sizes, against the positions under the
    # diagonal (64 * 65 / 2) and in a band of 16 (16 * 17 / 2 + 48 * 16)
    assert (args["attn_positions_computed"], args["attn_positions_needed"],
            args["attn_positions_computed_window"],
            args["attn_positions_needed_window"]) == (36 * 64, 2080,
                                                      21 * 64, 904)
    assert args["routed_rows"] == 2 * 2 * 64 * cfg.top_k


def test_the_other_models_spans_say_two_norms_and_no_multiplier():
    other = mla_moe.attn_grid(gqa_moe.GQAMoEConfig(attn="flash"), 64)
    assert (other["block_norms"], other["embed_scale"], other["attn_gated"],
            other["qk_norm"], other["rope_kinds"]) == (2, 1.0, 0, 0,
                                                      "window,full")
    glm = mla_moe.attn_grid(mla_moe.MLAMoEConfig(attn="flash"), 64)
    assert glm["block_norms"] == 2 and "attn_gated" not in glm


@pytest.mark.parametrize("q_inner", [False, True])
@pytest.mark.parametrize("window", [16, 20, 24])
def test_live_pairs_under_a_window_wider_than_a_block(window, q_inner):
    """A window of 2, 2.5 and 3 blocks of 8 against the dense mask written
    out, in both walks: a pair is walked exactly when it holds a live
    position, and masked exactly when it also holds a dead one."""
    s, b = 64, 8
    i, j = np.indices((s, s))
    live = (i >= j) & (i - j < window)
    tiles = live.reshape(s // b, b, s // b, b).transpose(0, 2, 1, 3)
    qi, kj, crossing = live_pairs(s, b, b, q_inner, window)
    want = {(a, c): not tiles[a, c].all() for a in range(s // b)
            for c in range(s // b) if tiles[a, c].any()}
    got = {(int(a), int(c)): bool(m) for a, c, m in zip(qi, kj, crossing)}
    assert got == want and len(qi) == len(want)
    inner, outer = (qi, kj) if q_inner else (kj, qi)
    order = list(zip(outer.tolist(), inner.tolist()))
    assert order == sorted(order)
    assert causal_pairs(s, b, b, window)["masked"] == sum(want.values())


def test_the_cells_walk_is_45_of_136_pairs_its_first_full_block_unmasked():
    cfg = CFG._replace(head_dim=128, window=2048, attn_block=512)
    assert mla_moe.attn_blocks(cfg, 16384) == (1024, 1024)
    band = causal_pairs(16384, 1024, 1024, 2048)
    assert (band["live"], causal_pairs(16384, 1024, 1024)["live"]) == (45,
                                                                        136)
    qi, kj, crossing = live_pairs(16384, 1024, 1024, window=2048)
    by_offset = {d: set(crossing[qi - kj == d].tolist()) for d in (0, 1, 2)}
    assert by_offset == {0: {True}, 1: {False}, 2: {True}}
    assert band["masked"] == 16 + 14
    # the span's counts at the cell's sizes: the crossed pairs cut into
    # sub-tiles of 256 compute 1.125 of the band and 1.016 of the triangle
    grid = mla_moe.attn_grid(cfg._replace(attn="flash"), 16384)
    assert (grid["attn_positions_computed_window"],
            grid["attn_positions_needed_window"],
            grid["attn_positions_computed"],
            grid["attn_positions_needed"]) == (
                35_389_440, 31_458_304, 136_314_880, 134_225_920)


def test_published_sizes_give_the_configurations_parameter_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-ep8.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_afmoe

    class Cell:
        config = c

    cfg = lm_train_afmoe._model_config(Cell)
    assert cfg.layer_kinds == ("window", "window", "window", "window", "full")
    assert [l.ffn for l in cfg.layers()] == ["dense"] + ["shared+experts"] * 4
    assert (cfg.kv_group, cfg.window, cfg.head_size, cfg.top_k) == (
        8, 2048, 128, 8)
    assert (cfg.routed_scale, cfg.bias_speed, cfg.route) == (
        2.826, 0.001, "sigmoid")
    assert abs(cfg.embed_scale - 2048 ** 0.5) < 1e-12
    shapes = mla_moe.param_shapes(cfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 705_473_792 and len(shapes) == 3 + 14 + 4 * 18
    layer = lambda i: sum(int(np.prod(s)) for n, s in shapes.items()
                          if n.startswith(f"L{i}."))
    assert (layer(0), layer(4)) == (65_020_160, 134_488_320)
    assert int(np.prod(shapes["L4.wgate"])) == 8_388_608
    held = mla_moe.held(cfg, 16384)
    assert held.buffer_rows == 32768 and held.tile == (512, 512, 512)
    assert (held.num_experts, held.experts_held, held.top_k) == (128, 16, 8)
    # every width is the source's
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    assert c["source"] == row["source_url"]
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "num_dense_layers", "num_experts",
                   "vocab_size"}
    for key, value in row["config"].items():
        assert c[key] == (value if key not in cut else c[key]), key
        if key in cut:
            assert c["published"][key] == value
    marked = [k for k, v in c["assumed"].items() if v.startswith("(+)")]
    assert len(marked) == 6 and all("afmoe" in c["assumed"][k]
                                    for k in marked)
