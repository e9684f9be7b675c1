"""The four-stream, hyper-connected MLA / routed-experts decoder
(``models/xing4.py`` on ``models/mla_moe.py``'s one path) against its plain
reference (``benchmark/reference/xing4.py``) at small sizes with float32
operands, where program and reference must agree to rounding; the flash
kernels at two head sizes under an explicit scale; the share tied to the
model; the new tables' layout and their step through Adam."""

import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import multiverso_tpu as mv
from benchmark.reference import xing4 as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import mla_moe, xing4
from multiverso_tpu.ops import attention_kernels as ak

CFG = xing4.Xing4Config(
    vocab=96, dim=64, n_heads=2, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, dense_ffn=160,
    n_dense_layers=1, n_moe_layers=2, moe_ffn=32, n_experts=16,
    experts_held=4, expert_offset=4, top_k=4, n_mtp=0, attn="xla",
    loss_chunk=32, compute_dtype=jnp.float32)
# the draw the benchmark's configuration makes, at the tiny width
SCALES = {"hc_phi": 1.0 / np.sqrt(CFG.streams * CFG.dim), "hc_b": 1.0,
          "router": 0.07}


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    """As ``tests/test_mla_moe.py``'s: this file compiles a dozen models."""
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    y = cfg.yarn
    return dict(
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            type="yarn", factor=y.factor, beta_fast=y.beta_fast,
            beta_slow=y.beta_slow, mscale=cfg.mscale_all_dim,
            mscale_all_dim=cfg.mscale_all_dim,
            original_max_position_embeddings=(
                y.original_max_position_embeddings)),
        rms_norm_eps=cfg.eps, first_k_dense_replace=cfg.n_dense_layers,
        num_hidden_layers=cfg.n_dense_layers + cfg.n_moe_layers,
        moe_intermediate_size=cfg.moe_ffn, n_routed_experts=cfg.experts_held,
        num_experts_per_tok=cfg.top_k, routed_scaling_factor=cfg.routed_scale,
        num_nextn_predict_layers=cfg.n_mtp, mtp_loss_weight=cfg.mtp_weight,
        expert_offset=cfg.expert_offset, hc_mult=cfg.streams,
        hc_sinkhorn_iters=cfg.sinkhorn_iters, hc_eps=cfg.hc_eps,
        mhc_h_res_clamp_min=cfg.res_clamp[0],
        mhc_h_res_clamp_max=cfg.res_clamp[1])


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, SCALES)
    bias = 0.02 * jax.random.normal(jax.random.key(seed + 1),
                                    mla_moe.init_bias(cfg).shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, bias, tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


def _streams(seed=9, batch=2, positions=32):
    return jax.random.normal(jax.random.key(seed),
                             (batch, positions, CFG.streams, CFG.dim))


def _maps(x, p, branch, cfg=CFG):
    """The program's three maps, a position a row as the reference has
    them: (pre [B, S, n], post [B, S, n], res [B, S, n, n])."""
    n = cfg.streams
    pre, post, res = mla_moe.stream_maps(
        x, *(p[f"{branch}.hc_{k}"] for k in ("phi", "b", "alpha")), cfg)
    lead = x.shape[:2]
    return (pre.T.reshape(*lead, n), post.T.reshape(*lead, n),
            jnp.moveaxis(res, -1, 0).reshape(*lead, n, n))


# ---------------------------------------------------------------------- #
# the maps
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("branch", ["attn", "ffn"])
@pytest.mark.parametrize("which", ["pre", "post", "res"])
def test_a_map_matches_the_reference(branch, which):
    params, _, _ = _inputs(CFG)
    p, x, c = mla_moe._sub(params, "L1"), _streams(), _ref_config(CFG)
    at = ("pre", "post", "res").index(which)
    got = _maps(x, p, branch)[at]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.stream_maps(x[i], ref._sub(p, branch), c)[at]
                          for i in range(x.shape[0])])
    assert _close(got, want, 1e-5)
    # the draw moves the maps from position to position, and the mix is
    # neither the identity nor uniform
    assert float(jnp.std(got, axis=(0, 1)).min()) > 0.02
    if which == "res":
        mean = np.asarray(got.mean((0, 1)))
        assert np.abs(mean - np.eye(CFG.streams)).max() > 0.2
        assert np.abs(mean - 1.0 / CFG.streams).max() > 0.05


def test_sinkhorn_rows_and_columns_sum_to_one():
    params, _, _ = _inputs(CFG)
    p, x = mla_moe._sub(params, "L0"), _streams(3)
    _, _, res = mla_moe.stream_maps(
        x, p["attn.hc_phi"], p["attn.hc_b"], p["attn.hc_alpha"], CFG)
    rows, cols = res.sum(1), res.sum(0)
    # the last normalisation is the rows': exact to rounding; the columns
    # to the iteration's error, which the step reads back
    assert float(jnp.abs(rows - 1).max()) < 1e-5
    assert float(jnp.abs(cols - 1).max()) < 2e-2
    assert float(mla_moe.res_error(res)) == pytest.approx(
        float(jnp.abs(cols - 1).max()), rel=1e-6)
    assert float(res.min()) >= 0
    # one iteration alone is far from doubly stochastic
    _, _, once = mla_moe.stream_maps(
        x, p["attn.hc_phi"], p["attn.hc_b"], p["attn.hc_alpha"],
        CFG._replace(sinkhorn_iters=1))
    assert float(mla_moe.res_error(once)) > 10 * float(mla_moe.res_error(res))


def test_the_clamp_bounds_the_residual_scores():
    """With a gain of 100 the scores pass +-30: the clamp holds them, the
    exponentials stay finite and program and reference still agree."""
    params, _, _ = _inputs(CFG)
    p = dict(mla_moe._sub(params, "L0"))
    p["attn.hc_alpha"] = jnp.asarray([1.0, 1.0, 100.0])
    x, c = _streams(4), _ref_config(CFG)
    got = _maps(x, p, "attn")[2]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.stream_maps(x[i], ref._sub(p, "attn"), c)[2]
                          for i in range(x.shape[0])])
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _close(got, want, 1e-4)
    wide = CFG._replace(res_clamp=(-200.0, 200.0))
    assert not _close(_maps(x, p, "attn", wide)[2], want, 1e-4)


@pytest.mark.parametrize("kind", ["dense", "expert"])
def test_block_matches_the_reference(kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    x = _streams()
    name = "L0" if kind == "dense" else "L1"
    p = mla_moe._sub(params, name)
    layer = CFG.layers()[0 if kind == "dense" else 1]
    got, aux, error = mla_moe._run_block(
        x, p, layer, None if kind == "dense" else bias[0], CFG)
    if kind == "dense":
        ffn = lambda u, q: (ref.mlp(u, q["wg"], q["wu"], q["wd"]), None)
        q = p
    else:
        ffn = lambda u, q: ref.expert_layer(u, q, bias[0], c,
                                            CFG.expert_offset,
                                            CFG.experts_held)
        q = ref._experts_3d(p, c)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.block(x[i], q, ffn, c)[0] for i in range(2)])
    assert got.shape == x.shape and _close(got, want)
    assert 0 < float(error) < 2e-2
    if kind == "expert":
        counts, overflow, _ = aux
        assert int(counts.sum()) == 2 * 32 * CFG.top_k and int(overflow) == 0


# ---------------------------------------------------------------------- #
# the whole model
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("n_mtp", [0, 1])
@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_counts_and_every_gradient_match_the_reference(attn, kernel,
                                                            n_mtp):
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=16,
                       n_mtp=n_mtp)
    params, bias, tokens = _inputs(cfg)
    (loss, (counts, overflow, _, error)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, _ref_config(cfg)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert counts.shape[0] == cfg.n_moe_layers + n_mtp
    assert int(overflow.sum()) == 0 and 0 < float(error) < 2e-2
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    hc = [n for n in grads if ".hc_" in n]
    assert len(hc) == 3 * 2 * (cfg.n_dense_layers + cfg.n_moe_layers + n_mtp)
    assert all(float(jnp.abs(grads[n]).max()) > 0 for n in hc)
    bad = [n for n in grads if not _close(grads[n], want[n], 5e-5)]
    assert not bad, bad


@pytest.mark.parametrize("how", ref.CONTROLS)
def test_a_faulty_map_is_told_apart(how):
    """What the benchmark's controls rest on: the maps computed wrongly in
    one way move the loss and the gradients far past rounding."""
    params, bias, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    want_loss, _, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    with ref.maps_control(how):
        loss, _, _, got = jax.jit(
            lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    moved = max(float(jnp.linalg.norm(got[n] - want[n])
                      / (jnp.linalg.norm(want[n]) + 1e-30)) for n in want)
    assert moved > 0.05 and abs(float(loss) - float(want_loss)) > 1e-4


def test_the_eight_shares_add_up_through_a_hyper_connected_block():
    """The share tied to the model: every chip of the deployment runs the
    same block on the same streams with its own experts; what each WRITES
    to the streams, ``x' - H_res x`` = outer(H_post, attention's result
    carried on + Shared + its routed part), adds up over the shares, with
    the shared expert and attention's part counted once, to what the uncut
    layer (all 16 experts given to the reference) writes."""
    shares = CFG.n_experts // CFG.experts_held
    whole = CFG._replace(experts_held=CFG.n_experts, expert_offset=0)
    params, bias, _ = _inputs(whole)
    p, x = mla_moe._sub(params, "L1"), _streams(5)
    layer, c = whole.layers()[1], _ref_config(whole)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.block(
            x[i], ref._experts_3d(p, c),
            lambda u, q: ref.expert_layer(u, q, bias[0], c, 0,
                                          whole.n_experts), c)[0]
            for i in range(x.shape[0])])

    def cut(name, value, k):
        if name not in ("eg", "eu", "ed"):
            return value
        rows = value.shape[0] // shares
        return value[k * rows:(k + 1) * rows]

    got = []
    for k in range(shares):
        cfg = CFG._replace(expert_offset=k * CFG.experts_held)
        mine = {n: cut(n, v, k) for n, v in p.items()}
        got.append(mla_moe._run_block(x, mine, layer, bias[0], cfg)[0])
    # two shares differ by their routed parts alone; what they have in
    # common (the streams' mix, attention's and the shared expert's part)
    # is the reference's block with every expert's result zeroed
    none = dict(p, ed=jnp.zeros_like(p["ed"]))
    with jax.default_matmul_precision("highest"):
        common = jnp.stack([ref.block(
            x[i], ref._experts_3d(none, c),
            lambda u, q: ref.expert_layer(u, q, bias[0], c, 0,
                                          whole.n_experts), c)[0]
            for i in range(x.shape[0])])
    total = common + sum(g - common for g in got)
    assert _close(total, want, 5e-5)
    assert not _close(got[0], want, 1e-2)       # one share alone is not it


# ---------------------------------------------------------------------- #
# latent attention at two head sizes
# ---------------------------------------------------------------------- #
def _qkv(seed, b=1, h=2, s=64, d=24, dv=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k = (jax.random.normal(keys[i], (b, h, s, d), dtype) for i in (0, 1))
    v, g = (jax.random.normal(keys[i], (b, h, s, dv), dtype) for i in (2, 3))
    return q, k, v, g


def _dense_attention(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    n = q.shape[2]
    s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :], s,
                  -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)])
@pytest.mark.parametrize("scale", [None, 0.37])
def test_flash_attention_at_two_head_sizes_under_a_scale(blocks, scale):
    """Forward, dQ, dK and dV of the kernels (interpreted) at q, k of 24 and
    v of 16 against the XLA core and the dense definition."""
    q, k, v, g = _qkv(0)
    used = 1 / np.sqrt(q.shape[-1]) if scale is None else scale

    def of(core):
        out, back = jax.vjp(core, q, k, v)
        return (out,) + back(g)

    got = of(lambda q, k, v: ak.flash_attention(
        q, k, v, True, *blocks, interpret=True, scale=scale))
    xla = of(lambda q, k, v: mla_moe._xla_attention(q, k, v, scale=scale))
    with jax.default_matmul_precision("highest"):
        want = of(lambda q, k, v: _dense_attention(q, k, v, used))
    assert got[0].shape == v.shape and got[3].shape == v.shape
    for a, b, c in zip(got, xla, want):
        assert a.shape == b.shape == c.shape
        assert _close(a, c, 2e-5) and _close(b, c, 2e-5)


def test_flash_attention_without_the_new_arguments_is_the_call_it_was():
    """One head size and no scale: the jaxpr of a call (forward and
    backward) is the same text whether or not ``scale`` is named."""
    q, k, _, _ = _qkv(1, d=16)
    text = lambda **kw: str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: ak.flash_attention(
            q, k, v, True, 16, 16, interpret=True, **kw).sum(), (0, 1, 2)))(
                q, k, k))
    assert text() == text(scale=None)
    assert text() != text(scale=0.3)


def test_a_selection_takes_no_scale():
    q, k, _, _ = _qkv(2, d=16)
    with pytest.raises(ValueError, match="no scale"):
        ak.flash_attention(q, k, k, True, 16, 16, interpret=True,
                           select=jnp.ones((1, 64, 64), jnp.int8), scale=0.5)


def test_mla_scales_by_the_configuration_and_turns_by_yarn():
    params, _, _ = _inputs(CFG)
    p, c = mla_moe._sub(params, "L0"), _ref_config(CFG)
    u = jax.random.normal(jax.random.key(7), (1, 48, CFG.dim))
    got = mla_moe.mla(u, p, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(u[0], p, c)
    assert _close(got, want)
    assert CFG.softmax_scale == pytest.approx(ref.softmax_scale(c))
    # 2.005 / sqrt(192) at the published sizes
    real = xing4.Xing4Config(qk_nope_dim=128, qk_rope_dim=64)
    assert real.softmax_scale * np.sqrt(192) == pytest.approx(2.0048, 1e-4)
    # and neither is a no-op at these sizes
    assert not _close(mla_moe.mla(u, p, CFG._replace(yarn=None)), want, 1e-3)
    freq, factor = mla_moe.rotary_frequencies(64, 1e4, real.yarn)
    want_freq, want_factor = ref.frequencies(64, dict(
        _ref_config(real), rope_theta=1e4))
    np.testing.assert_allclose(np.asarray(freq), want_freq, rtol=1e-6)
    assert factor == want_factor == 1.0


# ---------------------------------------------------------------------- #
# one stream is what it was
# ---------------------------------------------------------------------- #
def test_one_stream_has_no_hyper_connection_and_the_old_results():
    """A configuration of one stream has none of the new tables, its block
    returns two things and its step five, its span nothing new. (That the
    seven older models' lowered steps are the parent's text is held by
    hash in ``tests/test_qwen3_next.py`` and ``tests/test_keye_moe.py``,
    which pass untouched.)"""
    glm = mla_moe.MLAMoEConfig(attn="xla", compute_dtype=jnp.float32)
    assert mla_moe.streams_of(glm) == 1
    assert not [n for n in mla_moe.param_shapes(glm) if "hc_" in n]
    assert mla_moe.stream_grid(glm, 2, 64) == {}
    assert glm.yarn is None and glm.softmax_scale is None
    assert glm.head_size == glm.v_head_dim
    params = mla_moe.init(glm, 0, 0.1)
    x = jax.random.normal(jax.random.key(0), (1, 16, glm.dim))
    assert len(mla_moe._run_block(x, mla_moe._sub(params, "L0"),
                                  glm.layers()[0], None, glm)) == 2
    tokens = jnp.zeros((1, 16), jnp.int32)
    _, aux = mla_moe.loss_fn(params, mla_moe.init_bias(glm), tokens, glm)
    assert len(aux) == 3


# ---------------------------------------------------------------------- #
# the tables
# ---------------------------------------------------------------------- #
def test_parameter_count_of_the_published_widths():
    """ISSUE 60's arithmetic, from ``param_shapes``: the chip's share of
    Xing4.0-29B-A4B is 759,346,190 parameters."""
    cfg = _published()
    sizes = {n: int(np.prod(s)) for n, s in mla_moe.param_shapes(cfg).items()}
    layer = lambda name: sum(v for n, v in sizes.items()
                             if n.startswith(name + "."))
    assert sizes["L0.attn.hc_phi"] + sizes["L0.attn.hc_b"] \
        + sizes["L0.attn.hc_alpha"] == 344_091
    assert layer("L0") == 128_196_918
    assert layer("L1") == layer("L4") == 128_426_294
    assert sizes["embed"] + sizes["head"] + sizes["final_norm"] == 117_444_096
    assert sum(sizes.values()) == 759_346_190


def _published(**kw):
    return xing4.Xing4Config(
        vocab=16384, dim=3584, n_heads=32, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, dense_ffn=9216,
        n_dense_layers=1, n_moe_layers=4, moe_ffn=1024, n_experts=64,
        experts_held=8, top_k=4, **kw)


def test_span_fields_of_several_streams():
    cfg = _published()
    grid = mla_moe.stream_grid(cfg, 1, 4096)
    assert grid == {"streams": 4, "sinkhorn_iters": 20, "hc_sublayers": 10,
                    "hc_stream_bytes": 5 * 4096 * 4 * 3584 * 4,
                    # the backward walks (PR 62): plain off a TPU; (2n + 2)
                    # + (n + 1) + (3n + 1) + n arrays a sublayer
                    "hc_kernel_sublayers": 0,
                    "hc_bwd_stream_bytes": 10 * 32 * 4096 * 3584 * 4}
    assert mla_moe.kept_grid(cfg, 1, 4096)["kept_bytes"] > 0


def test_the_step_moves_the_new_tables_by_adam():
    """The step through the tables: every table, the hyper-connections'
    three kinds among them, moves by Adam's first step (the whole rate,
    against the gradient's sign), the step reads the stream-mix error back
    and the span carries the streams' fields."""
    from multiverso_tpu.telemetry import trace as ttrace

    from jax.sharding import Mesh

    cfg = CFG._replace(n_mtp=1)
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    try:
        tables = mla_moe.make_tables(
            cfg, 5, 0.1, updater=updaters.AdamUpdater(
                beta1=0.9, beta2=0.95, eps=1e-8), scales=SCALES)
        assert tables["L0.attn.hc_phi"].shape == (
            cfg.streams ** 2 + 2 * cfg.streams, cfg.streams * cfg.dim)
        assert tables["L0.ffn.hc_b"].shape == (24,)
        assert tables["mtp.attn.hc_alpha"].shape == (3,)
        np.testing.assert_array_equal(
            np.asarray(tables["L1.ffn.hc_alpha"].get()), np.ones(3))
        before = {n: np.asarray(t.get()) for n, t in tables.items()}
        params = {n: jnp.asarray(v).reshape(mla_moe.param_shapes(cfg)[n])
                  for n, v in before.items()}
        tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab)
        grads = jax.grad(lambda p: mla_moe.loss_fn(
            p, mla_moe.init_bias(cfg), tokens, cfg)[0])(params)
        lr = 1e-3
        trainer = mla_moe.Trainer(cfg, tables,
                                  updaters.AddOption(learning_rate=lr))
        before_events = len(ttrace.events())
        loss, counts = trainer.step(tokens)
        trainer.adopt()
        span = [e for e in ttrace.events()[before_events:]
                if e["name"] == "lm.step"][0]["args"]
        assert span["streams"] == 4 and span["sinkhorn_iters"] == 20
        assert span["hc_sublayers"] == 8
        assert span["hc_stream_bytes"] == 4 * 2 * 64 * 4 * cfg.dim * 4
        assert span["hc_res_error"] == trainer.hc_res_error
        assert np.isfinite(loss) and counts.shape == (3, cfg.n_experts + 1)
        assert 0 < trainer.hc_res_error < 2e-2
        for n in ("L0.attn.hc_phi", "L1.ffn.hc_b", "mtp.ffn.hc_alpha",
                  "L2.attn.hc_alpha", "L0.wdq"):
            g = np.asarray(grads[n]).reshape(before[n].shape)
            moved = np.asarray(tables[n].get()) - before[n]
            big = np.abs(g) > 1e-6
            assert big.any()
            np.testing.assert_allclose(moved[big], -lr * np.sign(g[big]),
                                       rtol=2e-2)
    finally:
        mv.shutdown()


def test_the_timeline_prints_the_streams():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import dump_metrics

    args = dict(mla_moe.stream_grid(_published(), 1, 4096), hc_res_error=0.004)
    lines = dump_metrics._stream_lines([{"name": "lm.step", "args": args}])
    assert lines == ["  residual streams: 4, mixed round 10 sublayers by 20 "
                     "Sinkhorn rounds each; kept block inputs 1174 MB; "
                     "largest mix error 0.004 over 1 steps",
                     "  their backward walks: kernels in 0 of 10 sublayers "
                     "(0: the plain forms), 18790 MB of streams read and "
                     "written a step"]
    assert dump_metrics._stream_lines([{"name": "lm.step", "args": {}}]) == []
    assert "mv.lm.hc.expand" in dump_metrics.__doc__


def test_the_step_carries_the_maps_scopes():
    """Every scope of the stream maps is in the lowered step, so that the
    program's map files the maps' operations."""
    cfg = CFG._replace(n_mtp=1)
    params, bias, tokens = _inputs(cfg)
    text = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(
        p, bias, tokens, cfg)[0])).lower(params).as_text(debug_info=True)
    for scope in ("expand", "norm", "project", "sinkhorn", "pre", "post",
                  "reduce"):
        assert f"mv.lm.hc.{scope}" in text, scope
