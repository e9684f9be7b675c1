"""The grouped-query / window-and-full / routed-experts decoder
(``models/gqa_moe.py`` on ``models/mla_moe.py``'s one decoder path,
``parallel/moe.held_expert_layer`` under its softmax route) against its
plain reference (``benchmark/reference/gqa_window_moe.py``) at small sizes
with float32 operands, where program and reference must agree to
rounding."""

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
from benchmark.reference import gqa_window_moe as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import gqa_moe, mla_moe
from multiverso_tpu.parallel import moe

YARN = mla_moe.Yarn(16.0, 32, 32.0, 1.0, 1.2772588722239782)
CFG = gqa_moe.GQAMoEConfig(
    vocab=96, dim=64, n_heads=8, n_kv_heads=2, head_dim=8, window=16,
    layer_kinds=("window", "full"), rope_theta=5e5, yarn=YARN, moe_ffn=48,
    n_experts=16, experts_held=4, expert_offset=4, top_k=4, balance_coef=0.01,
    attn="xla", loss_chunk=32, compute_dtype=jnp.float32)
KINDS = {"window": "sliding_attention", "full": "full_attention"}


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
        sliding_window=cfg.window,
        layer_types=["full_attention"] + [KINDS[k] for k in cfg.layer_kinds],
        num_hidden_layers=len(cfg.layer_kinds),
        rope_parameters={
            "full_attention": dict(cfg.yarn._asdict(), rope_type="yarn",
                                   rope_theta=cfg.rope_theta),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        rms_norm_eps=cfg.eps, moe_intermediate_size=cfg.moe_ffn,
        num_experts=cfg.experts_held,
        published={"num_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k, expert_offset=cfg.expert_offset,
        router_aux_loss_coef=cfg.balance_coef)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, mla_moe.init_bias(cfg), tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


def test_the_layer_list_is_data_for_both_models():
    assert CFG.layers() == (mla_moe.Layer("L0", "window", "experts"),
                            mla_moe.Layer("L1", "full", "experts"))
    glm = mla_moe.MLAMoEConfig(n_dense_layers=1, n_moe_layers=2, n_mtp=1)
    assert [tuple(l) for l in glm.layers()] == [
        ("L0", "latent", "dense"), ("L1", "latent", "shared+experts"),
        ("L2", "latent", "shared+experts"),
        ("mtp", "latent", "shared+experts")]
    assert mla_moe.expert_layers(glm) == ("L1", "L2", "mtp")
    assert mla_moe.expert_layers(CFG) == ("L0", "L1")


@pytest.mark.parametrize("kind", ["window", "full"])
def test_block_of_each_kind_matches_the_reference(kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    layer = CFG.layers()[0 if kind == "window" else 1]
    x = jax.random.normal(jax.random.key(9), (2, 48, CFG.dim))
    p = mla_moe._sub(params, layer.name)
    got, (counts, overflow, balance) = mla_moe._run_block(
        x, p, layer, bias[0], CFG)
    with jax.default_matmul_precision("highest"):
        both = [ref.block(x[i], ref._experts_3d(p, c), c, KINDS[kind])
                for i in range(2)]
    assert _close(got, jnp.stack([y for y, _ in both]))
    assert int(counts.sum()) == 2 * 48 * CFG.top_k and int(overflow) == 0
    want_counts = sum(a[0] for _, a in both)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    want = ref.balance_term(want_counts, sum(a[1] for _, a in both), 96, c)
    assert abs(float(balance) - float(want)) < 1e-5


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_and_every_gradient_match_the_reference(attn, kernel):
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=8)
    params, bias, tokens = _inputs(cfg)
    (loss, (counts, overflow, balance)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, want_terms, want = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    np.testing.assert_allclose(np.asarray(balance), np.asarray(want_terms),
                               rtol=1e-5)
    assert int(overflow.sum()) == 0
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    bad = [n for n in grads if not _close(grads[n], want[n])]
    assert not bad, bad
    # the load-balance term reaches the routers: without it they move less
    plain = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(
        p, bias, tokens, cfg._replace(balance_coef=0.0))[0]))(params)
    assert not _close(plain["L0.router"], want["L0.router"], 1e-4)


def test_lean_reference_is_the_plain_reference():
    params, _, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, tokens, c))(params)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, tokens, c, lean=True))(
        params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[4][n], plain[4][n]) for n in plain[4])


def test_softmax_route_and_its_balance_term_are_their_numpy_statements():
    rng = np.random.default_rng(0)
    u = rng.normal(size=(200, 32)).astype(np.float32)
    w = (0.3 * rng.normal(size=(16, 32))).astype(np.float32)
    cfg = moe.HeldExperts(num_experts=16, experts_held=4, top_k=3,
                          route="softmax")
    chosen, gates, counts, balance = moe.softmax_route(
        jnp.asarray(u), jnp.asarray(w), cfg)
    logits = u.astype(np.float64) @ w.T.astype(np.float64)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    top = np.argsort(-p, axis=1)[:, :3]
    assert (np.sort(np.asarray(chosen), 1) == np.sort(top, 1)).all()
    picked = np.take_along_axis(p, np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(gates),
                               picked / picked.sum(1, keepdims=True),
                               rtol=1e-5)
    want_counts = np.bincount(top.ravel(), minlength=16)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    want = 16 * np.sum(want_counts / (200 * 3) * p.mean(0))
    assert abs(float(balance) - want) < 1e-5
    # an even router reads 1; the term takes a gradient through P alone
    flat = moe.softmax_route(jnp.asarray(u), jnp.zeros((16, 32)), cfg)[3]
    assert abs(float(flat) - 1.0) < 1e-6
    g = jax.grad(lambda w: moe.softmax_route(jnp.asarray(u), w, cfg)[3])(
        jnp.asarray(w))
    share = want_counts / (200 * 3)
    dlogits = p * (16 * share[None, :] - (16 * p @ share)[:, None]) / 200
    np.testing.assert_allclose(np.asarray(g), dlogits.T @ u, rtol=2e-4,
                               atol=1e-7)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of the routed result (no shared part to count
    once) are the reference's uncut layer over all sixteen experts."""
    cfg = CFG
    c = dict(_ref_config(cfg), num_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 5)
    d, f, e = cfg.dim, cfg.moe_ffn, cfg.n_experts
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "eg": 0.1 * jax.random.normal(rng[1], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[2], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[3], (e, f, d))}
    u = jax.random.normal(rng[4], (2, 48, d))
    total = 0.0
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
        out, (_, overflow, _) = mla_moe.expert_ffn(
            u, share, None, cfg._replace(expert_offset=offset), shared=False)
        total = total + out
        assert int(overflow) == 0
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.routed_share(u[i], whole, c, 0, e)[0]
                          for i in range(2)])
    assert _close(total, want)


@pytest.mark.parametrize("shared", [False, True])
def test_the_route_is_the_configurations_not_the_shared_experts(shared):
    """``expert_ffn`` scores as ``cfg.route`` says whether or not a shared
    expert stands beside the routed ones: under this model's softmax a
    shared expert only adds its own product."""
    cfg = CFG
    assert (cfg.route, cfg.routed_scale) == ("softmax", 1.0)
    assert mla_moe.MLAMoEConfig().route == "sigmoid"
    assert mla_moe.held(cfg, 96).route == "softmax"
    rng = jax.random.split(jax.random.key(4), 8)
    d, f, e, h = cfg.dim, cfg.moe_ffn, cfg.n_experts, cfg.experts_held
    p = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
         "eg": 0.1 * jax.random.normal(rng[1], (h, d, f)),
         "eu": 0.1 * jax.random.normal(rng[2], (h, d, f)),
         "ed": 0.1 * jax.random.normal(rng[3], (h, f, d)),
         "sg": 0.1 * jax.random.normal(rng[4], (d, f)),
         "su": 0.1 * jax.random.normal(rng[5], (d, f)),
         "sd": 0.1 * jax.random.normal(rng[6], (f, d))}
    u = jax.random.normal(rng[7], (2, 48, d))
    out, (counts, _, balance) = mla_moe.expert_ffn(u, p, None, cfg, shared)
    _, _, want_counts, want_balance = moe.softmax_route(
        u.reshape(-1, d), p["router"], mla_moe.held(cfg, 96))
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert float(balance) == float(want_balance) > 0
    alone, _ = mla_moe.expert_ffn(u, p, None, cfg, False)
    beside = mla_moe.gated_mlp(u, p["sg"], p["su"], p["sd"], cfg)
    assert _close(out, alone + beside if shared else alone)


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG
    params, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps))
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert len(tables) == 3 + 10 * len(cfg.layer_kinds)
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr))
    loss, counts = trainer.step(tokens)
    trainer.adopt()
    want_loss, want_counts, _, _, grads = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
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
    # no selection bias in this family: nothing moved it
    np.testing.assert_array_equal(np.asarray(trainer.bias), np.asarray(bias))


def test_the_step_span_carries_the_balance_term_and_the_layer_kinds():
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=8, expert_kernel="xla")
    _, _, tokens = _inputs(cfg)
    trainer = mla_moe.Trainer(cfg, mla_moe.make_tables(cfg, 0, 0.1,
                                                       updater="adam"))
    before = len(ttrace.events())
    trainer.step(tokens)
    trainer.adopt()
    step = [e for e in ttrace.events()[before:] if e["name"] == "lm.step"][0]
    args = step["args"]
    # 64 positions in 16 x 16 blocks (attn_block 8, doubled at a head of
    # 8): 10 causal pairs; a band of 16 keeps the diagonal pair and the
    # one under it, and its far edge crosses that one
    assert args["attn_kinds"] == "window,full" and args["kv_group"] == 4
    assert (args["attn_pairs_live_window"], args["attn_pairs_masked_window"],
            args["attn_pairs_causal_window"]) == (7, 7, 10)
    assert args["attn_pairs_live"] == 10 and args["attn_pairs_masked"] == 4
    # whole tiles at these sizes: the positions computed are the pairs',
    # the needed ones the triangle's (64 * 65 / 2) and the band's
    assert (args["attn_positions_computed"], args["attn_positions_needed"],
            args["attn_positions_computed_window"],
            args["attn_positions_needed_window"]) == (2560, 2080, 1792, 904)
    assert 0.015 < args["aux_loss"] < 0.04      # 0.01 x two terms near 1


def test_the_balance_passes_bring_a_skewed_router_inside_the_limit():
    """The benchmark's calibration (the load-balance terms alone move the
    routers' tables alone, at a rate that shrinks to a floor) on routers
    whose rows share a direction with every hidden state; nothing but the
    routers moves, and their Adam state ends at zero."""
    from benchmark.drivers import lm_train_window

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(n_experts=8, experts_held=2, expert_offset=0, top_k=2,
                       vocab=512, loss_chunk=1024, balance_coef=1e-3)
    tables = mla_moe.make_tables(cfg, 1, 0.1, updater="adam")
    skew = np.linspace(-0.5, 0.5, 8)[:, None] * np.ones((1, cfg.dim))
    mean = tables["embed"].get().mean(0, keepdims=True)
    pool = jax.random.randint(jax.random.key(2), (4, 2, 256), 0, cfg.vocab)

    class Cell:
        traffic = {"calibration": {
            "start_rate": 0.02, "shrink": 0.5, "passes_per_rate": 6,
            "floor_rate": 0.0025, "max_passes": 120,
            "load_max_over_mean": 1.15, "held_share_within": 2.0}}

    trainer = mla_moe.Trainer(cfg, tables)
    for name in ("L0.router", "L1.router"):
        st = trainer.states[name]
        rows = cfg.n_experts
        st["data"] = st["data"].at[:rows].set(
            0.05 * st["data"][:rows] + jnp.asarray(skew * mean * 40,
                                                   jnp.float32))
    state = {"cfg": cfg, "cell": Cell, "trainer": trainer, "pool": pool,
             "balance": jax.jit(mla_moe.make_balance_step(cfg, tables),
                                donate_argnums=(0,))}
    others = {n: np.asarray(st["data"]) for n, st in trainer.states.items()
              if not n.endswith(".router")}
    result = lm_train_window._calibrate(state)
    assert max(result["first_max_over_mean"]) > 1.5
    assert result["balanced"] and max(
        result["last_turn_max_over_mean"]) <= 1.15
    assert all(abs(x - 25.0) <= 2.0 for x in result["last_turn_held_share"])
    for n, before in others.items():        # nothing else moved
        np.testing.assert_array_equal(np.asarray(trainer.states[n]["data"]),
                                      before)
    for name in ("L0.router", "L1.router"):
        assert all(not np.asarray(leaf).any() for leaf in
                   jax.tree.leaves(trainer.states[name]["ustate"]))


def test_published_sizes_give_the_issues_parameter_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-ep4.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_window

    class Cell:
        config = c

    cfg = lm_train_window._model_config(Cell)
    assert cfg.layer_kinds == ("window", "window", "window", "full")
    assert (cfg.kv_group, cfg.window, cfg.head_size) == (8, 1024, 128)
    shapes = mla_moe.param_shapes(cfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 595_153_152 and len(shapes) == 43
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("L1."))
    assert layer == 120_476_160
    held = mla_moe.held(cfg, 16384)
    assert held.buffer_rows == 65536 and held.tile == (512, 768, 896)
    assert mla_moe.attn_blocks(cfg, 8192) == (1024, 1024)
    # crossed pairs in sub-tiles of 256: 1.25 of the band, 1.031 of the
    # triangle, where whole tiles computed 2.00 and 1.125
    grid = mla_moe.attn_grid(cfg._replace(attn="flash"), 8192)
    assert (grid["attn_positions_computed_window"],
            grid["attn_positions_needed_window"],
            grid["attn_positions_computed"],
            grid["attn_positions_needed"]) == (
                9_830_400, 7_864_832, 34_603_008, 33_558_528)
    # every width is the source's
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"].startswith("Mellum2-12B"))
    cut = set(c["reduced"])
    assert cut == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        assert c[key] == (value if key not in cut else c[key]), key
        if key in cut:
            assert c["published"][key] == value


# the eight expert configurations of the benchmark: seven at the tile the
# rule gave them before PR 67, written out, and the one width that no
# multiple of 128 divides at the new one
CELL_TILES = {
    "glm-4.7-flash-ep8": (2048, 1536, (512, 512, 512)),
    "mellum2-12b-a2.5b-ep4": (2304, 896, (512, 768, 896)),
    "trinity-mini-ep8": (2048, 1024, (512, 512, 512)),
    "lfm2-8b-a1b-ep4": (2048, 1792, (512, 512, 896)),
    "keye-vl-2.0-30b-a3b-ep8": (2048, 768, (512, 512, 768)),
    "qwen3-next-80b-a3b-ep16": (2048, 512, (512, 512, 512)),
    "xing4.0-29b-a4b-ep8": (3584, 1024, (512, 512, 512)),
    # 1,856 = 14.5 x 128 is one tile, whole; rows by 256 beside it
    "nemotron-3-nano-30b-a3b-ep16": (2688, 1856, (256, 896, 1856)),
}


@pytest.mark.parametrize("dim,ffn,want", [
    (64, 48, (128, 128, 128)),          # a test's widths: the kernel's own
    (1024, 384, (128, 512, 384)),
    (2688, 2176, (128, 896, 128)),      # 17 x 128: only 128 divides it
    (1856, 200, (256, 1856, 200)),      # whole either way round
    (2048, 2100, (512, 512, 768)),      # too wide to take whole: 3 tiles
] + sorted(CELL_TILES.values()), ids=lambda v: str(v).replace(" ", ""))
def test_the_grouped_products_tile_follows_the_widths(dim, ffn, want):
    assert moe.product_tile(dim, ffn) == want
    # the matrices' float32 gradient blocks fit 16 MiB three times over,
    # and only a tile with a whole width is cut for them
    for m, k, n in (moe.weights_tile(want),
                    moe.weights_tile((want[0], want[2], want[1]))):
        assert m == want[0] and 12 * k * n <= 12 << 20
    if dim % 128 == ffn % 128 == 0:
        assert moe.weights_tile(want) == want


@pytest.mark.parametrize("name", sorted(CELL_TILES))
def test_the_tiles_are_pinned_at_the_benchmarks_own_widths(name):
    """``CELL_TILES``' widths are what the benchmark's configuration
    files hand ``mla_moe.held`` as ``cfg.dim`` and ``cfg.moe_ffn``."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        c = json.load(f)
    assert (c["hidden_size"], c["moe_intermediate_size"]) == (
        CELL_TILES[name][:2])


@pytest.mark.parametrize("yarn", [None, YARN,
                                  mla_moe.Yarn(16.0, 8192, 32.0, 1.0, 1.277)])
def test_rotary_frequencies_match_the_reference_written_out(yarn):
    d, theta = (128, 5e5) if yarn and yarn[1] == 8192 else (8, 5e5)
    rope = ({"rope_type": "default", "rope_theta": theta} if yarn is None
            else dict(yarn._asdict(), rope_type="yarn", rope_theta=theta))
    freq, factor = mla_moe.rotary_frequencies(d, theta, yarn)
    want, want_factor = ref.frequencies(d, rope)
    np.testing.assert_allclose(np.asarray(freq), want, rtol=1e-6)
    assert factor == want_factor == (1.0 if yarn is None else yarn[4])
    plain = theta ** (-np.arange(0, d, 2) / d)
    if yarn is None:
        np.testing.assert_allclose(want, plain)
    else:       # the fastest dimension is kept, the slowest divided by 16
        assert want[0] == plain[0]
        np.testing.assert_allclose(want[-1], plain[-1] / 16)
        assert np.all(np.diff(want / plain) <= 0)
    if d == 128:
        # the published model: dimensions that turn 32 times or more in
        # 8,192 positions (i <= 18) keep their frequency, those that turn
        # once or less (i >= 35) are interpolated
        assert np.all(want[:19] == plain[:19]) and want[19] < plain[19]
        np.testing.assert_allclose(want[35:], plain[35:] / 16)
        assert want[34] > plain[34] / 16
