"""The two-branch hybrid decoder (``models/granite_h.py``'s configuration on
``models/mla_moe.py``'s one decoder path: ``nemotron_h.mamba2`` at ONE group,
``gqa_moe.gqa`` under a published softmax scale, a dense MLP behind every
mixer, the residual, embedding and logit multipliers, the tied head)
against its plain reference (``benchmark/reference/granite_h.py``: the
recurrence a position at a time) at small sizes with float32 operands, where
program and reference must agree to rounding; and ``ops/ssd.py``'s scan at
one group of more heads than a grid step holds, the plain form and the two
kernels under the interpreter, against the recurrence."""

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
from benchmark.reference import granite_h as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import granite_h, mla_moe
from multiverso_tpu.ops import ssd

CFG = granite_h.GraniteHConfig(
    vocab=96, dim=48, layer_types=("mamba", "attention", "mamba"),
    ssm_heads=4, ssm_head_dim=8, ssm_groups=1, ssm_state=16, conv_kernel=4,
    chunk=16, n_heads=6, n_kv_heads=2, head_dim=8, dense_ffn=80,
    embed_scale=12.0, residual_scale=0.22, softmax_scale=0.125,
    logit_scale=0.125, attn="xla", loss_chunk=32, compute_dtype=jnp.float32)
# first values at which the logits spread (0.9 a number after ``/ 8``) and a
# branch's 0.22 F weighs beside ``12 Emb`` and the scores spread, so that every
# multiplier shows
SCALES = {"embed": 1.0, "conv_w": 0.3, "wout": 1.0, "wo": 1.0, "wd": 1.0,
          "wq": 0.4, "wk": 0.4}
# the configuration's field and the file's key of each published multiplier,
# with a value it is moved to (the file's ``logits_scaling`` is a divisor)
MULTIPLIERS = {
    "embed_scale": ("embedding_multiplier", 7.0, 7.0),
    "residual_scale": ("residual_multiplier", 0.5, 0.5),
    "softmax_scale": ("attention_multiplier", 0.6, 0.6),
    "logit_scale": ("logits_scaling", 0.4, 2.5)}


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, num_hidden_layers=len(cfg.layer_types),
        layer_types=list(cfg.layer_types) + ["mamba", "mamba"],
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state,
        mamba_d_conv=cfg.conv_kernel, mamba_chunk_size=cfg.chunk,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        rms_norm_eps=cfg.eps, embedding_multiplier=cfg.embed_scale,
        residual_multiplier=cfg.residual_scale,
        attention_multiplier=cfg.softmax_scale,
        logits_scaling=1.0 / cfg.logit_scale)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, scales=SCALES)
    # gains away from one, so that a gain's gradient is no symmetric case
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm")
                                    or n.endswith("skip"))):
        params[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(100 + i), params[name].shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


def _loss(params, tokens, cfg):
    return mla_moe.loss_fn(params, mla_moe.init_bias(cfg), tokens, cfg)[0]


def test_a_block_is_a_mixer_and_a_dense_mlp_and_the_head_is_tied():
    assert [tuple(l) for l in CFG.layers()] == [
        ("L0", "ssm", "dense"), ("L1", "full", "dense"),
        ("L2", "ssm", "dense")]
    assert mla_moe.expert_layers(CFG) == ()
    shapes = mla_moe.param_shapes(CFG)
    assert "head" not in shapes and shapes["embed"] == (96, 48)
    by_block = lambda i: {n.split(".")[1]: s for n, s in shapes.items()
                          if n.startswith(f"L{i}.")}
    # two norms a block; the in-projection is [z | xBC | dt] with ONE
    # group's B and C
    assert by_block(0) == {
        "attn_norm": (48,), "ffn_norm": (48,),
        "win": (48, 32 + (32 + 2 * 1 * 16) + 4), "conv_w": (4, 64),
        "conv_b": (64,), "a_log": (4,), "dt_bias": (4,), "skip": (4,),
        "gate_norm": (32,), "wout": (32, 48), "wg": (48, 80),
        "wu": (48, 80), "wd": (80, 48)}
    assert by_block(1) == {
        "attn_norm": (48,), "ffn_norm": (48,), "wq": (48, 48),
        "wk": (48, 16), "wv": (48, 16), "wo": (48, 48), "wg": (48, 80),
        "wu": (48, 80), "wd": (80, 48)}
    # the Mamba-2 rule's first values are nemotron's
    params = mla_moe.init(CFG._replace(ssm_heads=64), 5)
    a = np.exp(np.asarray(params["L0.a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.max() - a.min() > 10
    np.testing.assert_array_equal(np.asarray(params["L0.skip"]), 1.0)


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_block_of_each_kind_matches_the_reference(kind):
    """Both branches under the residual multiplier, through
    rematerialisation."""
    params, _ = _inputs(CFG)
    c = _ref_config(CFG)
    i = CFG.layer_types.index(kind)
    layer = CFG.layers()[i]
    x = 3.0 * jax.random.normal(jax.random.key(9), (2, 48, CFG.dim))
    p = mla_moe._sub(params, layer.name)
    got, aux = jax.jit(lambda x, p: mla_moe._run_block(
        x, p, layer, None, CFG))(x, p)
    assert aux is None
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([jax.jit(lambda x: ref.block(x, p, c, kind))(x[i])
                          for i in range(2)])
    assert _close(got, want)
    # the multiplier is on BOTH branches: with the MLP's output matrix at
    # zero the block is x + 0.22 Mixer alone, and likewise the other way
    for name in ("wd", "wout" if kind == "mamba" else "wo"):
        q = dict(p, **{name: jnp.zeros_like(p[name])})
        once, _ = mla_moe._run_block(x, q, layer, None, CFG)
        twice, _ = mla_moe._run_block(
            x, q, layer, None, CFG._replace(residual_scale=0.44))
        assert _close(twice - x, 2.0 * (once - x), 1e-5), name


@pytest.mark.parametrize("attn", ["xla", "flash"])
def test_loss_and_every_gradient_match_the_reference(attn):
    """Every table's gradient, the tied table's among them; the flash
    kernels (interpreted) under the published softmax scale."""
    cfg = CFG._replace(attn=attn, attn_block=4)
    params, tokens = _inputs(cfg)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(p, tokens, cfg)))(params)
    want_loss, want = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    for name in ("embed", "L0.conv_w", "L0.a_log", "L2.dt_bias", "L2.skip",
                 "L0.gate_norm", "L0.wg", "L1.wk", "L1.wd", "final_norm"):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
    bad = [n for n in grads if not _close(grads[n], want[n], 5e-5)]
    assert not bad, bad


@pytest.mark.parametrize("field", sorted(MULTIPLIERS))
def test_each_multiplier_alone_changes_the_loss(field):
    """Moved off its value alone, a multiplier moves the program's loss as
    it moves the reference's: one that the program dropped (or applied
    elsewhere) fails here."""
    key, moved, file_value = MULTIPLIERS[field]
    params, tokens = _inputs(CFG)
    at = lambda cfg: float(jax.jit(lambda p: _loss(p, tokens, cfg))(params))
    want_at = lambda c: float(jax.jit(
        lambda p: ref.loss(p, tokens, c))(params))
    base, other = at(CFG), at(CFG._replace(**{field: moved}))
    # (float32 noise is a millionth of the loss)
    assert abs(other - base) > 3e-4 * base, (base, other)
    c = _ref_config(CFG)
    assert abs(base - want_at(c)) < 1e-5 * base
    assert abs(other - want_at(dict(c, **{key: file_value}))) < 1e-5 * other


@pytest.mark.parametrize("how", ref.CONTROLS)
def test_every_control_of_the_reference_changes_its_loss(how):
    """What ``lm_granite_control.py`` puts in the measured step's place is
    another computation than the reference's own."""
    params, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    _, want = jax.jit(lambda p: ref.loss_and_grads(p, tokens, c))(params)

    def faulty(p):
        with ref.control(how):
            return ref.loss_and_grads(p, tokens, c)

    _, got = jax.jit(faulty)(params)
    # some table's gradient moves by more than a thousandth of its size
    assert any(not _close(got[n], want[n], 1e-3) for n in want), how
    with pytest.raises(ValueError, match="no control"):
        with ref.control("nothing"):
            pass


def test_the_tied_tables_gradient_is_the_embeddings_part_and_the_heads():
    """``d embed`` = the lookup's gradient (through ``12 * Emb``) + the
    head's (through ``/ 8``): the same model with a head table of its own,
    holding the same values, gives the two apart."""
    class Untied(granite_h.GraniteHConfig):
        tied_head = False

    params, tokens = _inputs(CFG)
    untied = Untied(*CFG)
    assert "head" in mla_moe.param_shapes(untied)
    tied = jax.jit(jax.grad(lambda p: _loss(p, tokens, CFG)))(params)
    apart = jax.jit(jax.grad(lambda p: _loss(p, tokens, untied)))(
        dict(params, head=params["embed"]))
    for part in ("embed", "head"):
        assert float(jnp.max(jnp.abs(apart[part]))) > 0, part
    assert _close(tied["embed"], apart["embed"] + apart["head"], 1e-5)
    # each part scales with its own multiplier alone: the head's gradient
    # carries 1 / 8 once more than the logits do
    moved = jax.jit(jax.grad(lambda p: _loss(
        p, tokens, Untied(*CFG._replace(embed_scale=24.0)))))(
            dict(params, head=params["embed"]))
    assert not _close(moved["embed"], apart["embed"], 1e-3)
    want = jax.jit(lambda p: ref.loss_and_grads(
        p, tokens, _ref_config(CFG))[1])(params)
    assert _close(tied["embed"], want["embed"], 5e-5)


# ---------------------------------------------------------------------- #
# the scan at one group of more heads than a grid step holds
# ---------------------------------------------------------------------- #
def recurrence(x, dt, a, b, c):
    """H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t, y_t = H_t C_t."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))

    def step(state, each):
        xt, dtt, bt, ct = each
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, jnp.sum(state * ct[..., None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n)),
                        tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1)


# (sequences, positions, heads, head, groups, state), the chunk: ONE group
# of 32 heads (two blocks of 16, the configuration's chunk of 256 walked as
# lane tiles), and the older cell's 8 groups of 8 at its chunk of 128
SCANS = {"one_group_32_heads": ((1, 512, 32, 64, 1, 128), 256),
         "eight_groups_of_8": ((1, 256, 64, 64, 8, 128), 128)}


def _scan_inputs(dims, seed=1):
    b, s, h, p, g, n = dims
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h))),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=-3.0, maxval=2.5)),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)),
            jax.random.normal(k[5], (h,)))


@pytest.fixture(scope="module")
def scans():
    """``y`` and the six gradients of the recurrence, once a shape."""
    out = {}
    for case, (dims, _) in SCANS.items():
        args = _scan_inputs(dims)
        weight = jax.random.normal(jax.random.key(7), args[0].shape)
        skipped = lambda *t: recurrence(*t[:5]) + t[5][:, None] * t[0]
        out[case] = (args, weight, jax.jit(skipped)(*args), jax.jit(jax.grad(
            lambda *t: jnp.sum(weight * skipped(*t)), range(6)))(*args))
    return out


@pytest.mark.parametrize("form", ["plain", "kernels", "kernels_whole"])
@pytest.mark.parametrize("case", sorted(SCANS))
def test_the_scan_in_blocks_of_heads_is_the_recurrence(scans, case, form):
    """``y`` and all six gradients (x, dt, A, B, C, the skip), float32
    operands: the plain form's walk over blocks, the two kernels on three
    operands, and on the mixer's ONE ``[x | B | C]`` array (the gradient's
    one block, a group's dB and dC summed over its blocks in it)."""
    dims, chunk = SCANS[case]
    b, s, h, p, g, n = dims
    args, weight, want_y, want = scans[case]
    if case == "one_group_32_heads":
        assert ssd.head_block(h // g, p) == 16      # two blocks a group

    def scan(x, dt, a, bm, cm, skip):
        if form == "kernels_whole":
            whole = jnp.concatenate([x.reshape(b, s, h * p),
                                     bm.reshape(b, s, g * n),
                                     cm.reshape(b, s, g * n)], -1)
            x, bm, cm = jnp.split(whole, (h * p, h * p + g * n), axis=-1)
            x, bm, cm = (x.reshape(b, s, h, p), bm.reshape(b, s, g, n),
                         cm.reshape(b, s, g, n))
        else:
            whole = None
        return ssd.ssd_chunked(x, dt, a, bm, cm, chunk, jnp.float32,
                               skip=skip, whole=whole,
                               kernel=form != "plain", interpret=True)

    got_y = jax.jit(scan)(*args)
    assert _close(got_y, want_y, 2e-5)
    got = jax.jit(jax.grad(lambda *t: jnp.sum(weight * scan(*t)),
                           range(6)))(*args)
    # the plain form takes the configuration's 256 positions as ONE chunk,
    # whose float32 running sum of ``dt A`` reaches thousands at these
    # decays (up to 36 a position; a trained mixer's are under 2): the
    # differences of two such sums carry their rounding
    tol = 4e-4 if form == "plain" and chunk == 256 else 5e-5
    for name, grad, w in zip(("x", "dt", "a", "b", "c", "skip"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert grad.shape == w.shape and _close(grad, w, tol), (name, form)


def test_the_kernels_walk_is_the_older_cells_at_its_shapes():
    """8 groups of 8: a group is one block, and the walk's grid, blocks and
    states are what they were (units = groups)."""
    def call_of(case, chunk):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(lambda *t: ssd.ssd_chunked(
            *t, chunk, kernel=True, interpret=True))(
                *_scan_inputs(SCANS[case][0])[:5]).jaxpr)
        eqn, = found
        return eqn

    eqn = call_of("eight_groups_of_8", 128)
    assert eqn.params["grid_mapping"].grid == (1, 8, 2)
    sizes = lambda eqn, i: tuple(
        d.block_size for d in
        eqn.params["grid_mapping"].block_mappings[i].block_shape)
    assert [sizes(eqn, i) for i in range(3)] == [
        (1, 128, 512), (1, 128, 128), (1, 128, 128)]
    eqn = call_of("one_group_32_heads", 256)
    # two blocks of 16 heads, four chunks of a lane tile
    assert eqn.params["grid_mapping"].grid == (1, 2, 4)
    assert sizes(eqn, 0) == (1, 128, 1024)


# ---------------------------------------------------------------------- #
# the step through the tables, and the cell's configuration
# ---------------------------------------------------------------------- #
def test_one_adam_step_of_every_table_matches_the_reference():
    """And the step's span says the blocks' kinds, the scan's counts and
    the four multipliers."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=4)
    _, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    scales = SCALES
    params = mla_moe.init(cfg, 0, 0.1, scales=scales)
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=scales)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert len(tables) == 2 + 2 * 13 + 9
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr))
    before = len(ttrace.events())
    loss, counts = trainer.step(tokens)
    trainer.adopt()
    want_loss, grads = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    assert counts.shape[0] == 0             # no router
    for n, t in tables.items():
        want, _, _, _ = ref.adam_step(np.asarray(params[n]), 0.0, 0.0, 0,
                                      np.asarray(grads[n]), lr, b1, b2, eps)
        moved = t.get().reshape(params[n].shape) - np.asarray(params[n])
        sure = np.abs(np.asarray(grads[n])) > 1e-4 * np.abs(
            np.asarray(grads[n])).max()
        np.testing.assert_allclose(moved[sure], (want - params[n])[sure],
                                   atol=2e-2 * lr, err_msg=n)
        assert int(trainer.states[n]["ustate"]["t"]) == 1
    args = [e for e in ttrace.events()[before:]
            if e["name"] == "lm.step"][0]["args"]
    assert args["block_kinds"] == "ssm+dense,full+dense,ssm+dense"
    assert "expert_form" not in args and "routed_rows" not in args
    assert (args["ssm_layers"], args["ssm_chunks"], args["ssm_chunk"],
            args["ssm_heads"], args["ssm_groups"], args["ssm_state"],
            args["ssm_head_blocks"]) == (2, 4, 16, 4, 1, 16, 1)
    # the scan's kernels take no shape this small, and no CPU, and say so
    assert (args["ssd_kernel_layers"], args["ssd_kernel_why"]) == (0, "no TPU")
    assert (args["embed_scale"], args["residual_scale"], args["logit_scale"],
            args["softmax_scale"], args["tied_head"]) == (
                12.0, 0.22, 0.125, 0.125, 1)
    assert (args["attn_kinds"], args["block_norms"], args["kv_group"]) == (
        "full", 2, 3)


def test_published_sizes_give_the_configurations_parameter_count(monkeypatch):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-pp4.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_ssm

    class _Cell:
        config = c

    cfg = lm_train_ssm._model_config(_Cell)
    assert [layer.attn for layer in cfg.layers()] == (
        ["ssm"] * 5 + ["full"] + ["ssm"] * 4)
    assert all(layer.ffn == "dense" for layer in cfg.layers())
    shapes = mla_moe.param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    assert count(lambda n: n.startswith("L0.")) == 76_182_976
    assert count(lambda n: n.startswith("L5.")) == 60_821_504
    assert count(lambda n: "." not in n) == 12_544 * 2048 + 2048
    assert count(lambda n: True) == 772_160_448 == c["parameters"]["total"]
    assert len(shapes) == c["parameters"]["tables"]
    assert shapes["L0.win"] == (2048, 4096 + 4352 + 64)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.softmax_scale,
            cfg.logit_scale) == (12.0, 0.22, 0.015625, 0.125)
    assert cfg.softmax_scale == 1.0 / cfg.head_dim and mla_moe.tied_head(cfg)
    # on a chip every mixer's scan runs the kernels: ONE group of 64 heads
    # in four blocks of 16, the chunk of 256 walked as lane tiles
    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    grid = mla_moe.mixer_grid(cfg, 8192)
    assert (grid["ssd_kernel_layers"], grid["ssm_head_blocks"],
            grid["ssm_chunk"], grid["ssd_kernel_chunk"], grid["ssm_chunks"],
            grid["ssm_groups"]) == (9, 4, 256, 128, 32, 1)
    assert "ssd_kernel_why" not in grid
