"""What lies between a projection and the attention core (PR 63):
``mla_moe.heads`` / ``out_of_heads`` and the pass of ``ops/head_turns.py``
against the formulation they replaced (``chip_smoke.parent_gqa`` /
``parent_mla``: the equations of ``gqa_moe.py``'s and ``mla_moe.py``'s
docstrings, written out with ``matmul``, ``rms_norm`` and ``rotary``),
against the float32 references, and the kernels in the interpreter against
the plain forms."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke
from multiverso_tpu.models import (afmoe, gqa_moe, keye_moe, lfm2_moe,
                                   mla_moe, nemotron_h, qwen3_next, xing4)
from multiverso_tpu.ops import head_turns

YARN = mla_moe.Yarn(16.0, 32, 32.0, 1.0, 1.2772588722239782)
GQA = gqa_moe.GQAMoEConfig(dim=64, n_heads=8, n_kv_heads=2, head_dim=8,
                           window=16, yarn=YARN, attn="xla")
# (configuration, layer kind): every switch of the two attention forms
CASES = {
    "gqa.full.yarn": (GQA, "full"),
    "gqa.window": (GQA, "window"),
    "gqa.ungrouped": (GQA._replace(n_kv_heads=8), "full"),
    "gqa.qk_norm": (GQA._replace(qk_norm=True), "window"),
    "gqa.gate": (GQA._replace(attn_gate=True), "full"),
    "gqa.head_of_64": (GQA._replace(n_heads=4, n_kv_heads=2, head_dim=64),
                       "full"),
    "afmoe.window": (afmoe.AFMoEConfig(attn="xla"), "window"),
    "afmoe.full.no_positions": (afmoe.AFMoEConfig(attn="xla"), "full"),
    "lfm2": (lfm2_moe.LFM2MoEConfig(attn="xla"), "full"),
    "nemotron.no_positions": (nemotron_h.NemotronHConfig(attn="xla"),
                              "full"),
    "qwen3next.rope_dim": (qwen3_next.Qwen3NextConfig(attn="xla"), "full"),
    "keye": (keye_moe.KeyeMoEConfig(attn="xla"), "sparse"),
    "mla.nope_rope": (mla_moe.MLAMoEConfig(attn="xla"), "latent"),
    "mla.xing.yarn.two_head_sizes": (xing4.Xing4Config(attn="xla"),
                                     "latent"),
}


def _operands(cfg, kind, seed=0, batch=2, positions=48):
    shapes = cfg.attn_shapes(kind)
    keys = jax.random.split(jax.random.key(seed), len(shapes) + 2)
    p = {n: (1 + 0.1 * jax.random.normal(key, sh) if n.endswith("norm")
             else 0.3 * jax.random.normal(key, sh))
         for (n, sh), key in zip(sorted(shapes.items()), keys)}
    u = jax.random.normal(keys[-1], (batch, positions, cfg.dim))
    weight = jax.random.normal(keys[-2], (batch, positions, cfg.dim))
    return u, p, weight


def _core(cfg, kind):
    window = cfg.window if kind == "window" else None
    scale = getattr(cfg, "softmax_scale", None)
    return lambda q, k, v: mla_moe._xla_attention(q, k, v, window,
                                                  scale=scale)


def _forms(cfg, kind):
    """(the operands of the core, the layer) of the parent formulation
    and of this tree's."""
    core = _core(cfg, kind)
    if kind == "latent":
        return ((lambda u, p: chip_smoke.parent_mla(u, p, cfg),
                 lambda u, p: chip_smoke.parent_mla(u, p, cfg, core)),
                (lambda u, p: mla_moe.mla_heads_of(u, p, cfg),
                 lambda u, p: mla_moe.mla(u, p, cfg)))

    def layer(u, p):        # ``gqa_moe.gqa`` whatever the kind's own core
        return gqa_moe.out_of(core(*gqa_moe.heads_of(u, p, cfg, kind)), u, p,
                              cfg)

    return ((lambda u, p: chip_smoke.parent_gqa(u, p, cfg, kind),
             lambda u, p: chip_smoke.parent_gqa(u, p, cfg, kind, core)),
            (lambda u, p: gqa_moe.heads_of(u, p, cfg, kind), layer))


def _worst(got, want):
    return max(jax.tree.leaves(jax.tree.map(
        lambda g, t: float(jnp.max(jnp.abs(g - t)))
        / (float(jnp.max(jnp.abs(t))) + 1e-30), got, want)))


def _written(f, *args):
    """``f(*args)`` compiled with every float operation as it is written."""
    return jax.jit(f).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_cores_operands_are_the_parent_formulations_bit_for_bit(case):
    """bfloat16 operands, float32 sums, float32 norm and rotary, ONE
    rounding where the parent rounds: q, k and v are the same bits, and
    the layer's output the same number. Both programs compiled with every
    float operation done as it is written (LLVM at level 0, as
    ``conftest.same_floats`` does): XLA:CPU's default contracts a multiply
    and an add of one fused loop into one rounding, and the two programs'
    loops are not the same loops (1 element in 3,072 of a normed q moves
    by its last bit)."""
    cfg, kind = CASES[case]
    cfg = cfg._replace(compute_dtype=jnp.bfloat16)
    u, p, _ = _operands(cfg, kind)
    (parent_heads, parent_layer), (new_heads, new_layer) = _forms(cfg, kind)
    got, want = _written(new_heads, u, p), _written(parent_heads, u, p)
    for name, g, t in zip("qkv", got, want):
        assert g.dtype == t.dtype == jnp.bfloat16 and g.shape == t.shape
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32)),
            np.asarray(t.astype(jnp.float32)), err_msg=f"{case} {name}")
    assert _worst(jax.jit(new_layer)(u, p), jax.jit(parent_layer)(u, p)) \
        < 1e-6


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_is_plain_autodiffs_of_the_parent_formulation(case):
    """``dx`` and the ``dW`` of every part, the gains and the gate's among
    them, under the one differentiation rule against ``jax.grad`` of the
    parent's lines."""
    cfg, kind = CASES[case]
    cfg = cfg._replace(compute_dtype=jnp.float32)
    u, p, weight = _operands(cfg, kind, seed=1)
    (_, parent_layer), (_, new_layer) = _forms(cfg, kind)
    grad = lambda layer: jax.jit(jax.grad(
        lambda u, p: jnp.sum(weight * layer(u, p)), (0, 1)))(u, p)
    got, want = grad(new_layer), grad(parent_layer)
    assert set(got[1]) == set(want[1]) == set(p)
    errs = jax.tree.map(lambda g, t: _worst(g, t), got, want)
    assert max(jax.tree.leaves(errs)) < 2e-5, errs
    # and at bfloat16, where the core's cotangents arrive rounded
    cfg = cfg._replace(compute_dtype=jnp.bfloat16)
    (_, parent_layer), (_, new_layer) = _forms(cfg, kind)
    errs = jax.tree.map(lambda g, t: _worst(g, t), grad(new_layer),
                        grad(parent_layer))
    assert max(jax.tree.leaves(errs)) < chip_smoke.HEADS_TOL, errs


def _ref_gqa(cfg):
    from tests.test_gqa_moe import _ref_config
    return _ref_config(cfg._replace(layer_kinds=("window", "full")))


def test_the_layers_equal_the_float32_references():
    """The grouped-query layer of each kind and the latent layer against
    ``benchmark/reference``'s attention under the files' own limit."""
    from benchmark.reference import gqa_window_moe, mla_moe as mla_ref
    from tests.test_gqa_moe import KINDS, _close
    from tests.test_mla_moe import CFG as MLA, _ref_config as mla_config

    cfg = GQA._replace(compute_dtype=jnp.float32)
    for kind in ("window", "full"):
        u, p, _ = _operands(cfg, kind, seed=2)
        got = jax.jit(lambda u, p: gqa_moe.gqa(u, p, cfg, kind))(u, p)
        with jax.default_matmul_precision("highest"):
            want = jnp.stack([gqa_window_moe.attention(
                u[i], p, _ref_gqa(cfg), KINDS[kind]) for i in range(2)])
        assert _close(got, want), kind
    u, p, _ = _operands(MLA, "latent", seed=3)
    got = jax.jit(lambda u, p: mla_moe.mla(u, p, MLA))(u, p)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([mla_ref.mla(u[i], p, mla_config(MLA))
                          for i in range(2)])
    assert _close(got, want)


def test_positions_by_section_turn_as_rotary_turns_them():
    """``heads`` under position ids of three axes dealt by sections is
    ``rotary(positions=, sections=)`` on the product, bit for bit, and a
    text token's equal ids are the plain form."""
    b, s, d, h, hd = 2, 24, 32, 4, 16
    k = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(k[0], (b, s, d))
    w = 0.3 * jax.random.normal(k[1], (d, h, hd))
    ids = jax.random.randint(k[2], (3, b, s), 0, 40)
    sections, theta = (2, 3, 3), 1e4
    how = mla_moe.Heads(jnp.bfloat16, rope=hd, theta=theta, sections=sections)

    def parent(x, w, ids):
        y = mla_moe.matmul(x, w.reshape(d, h * hd), False, jnp.bfloat16,
                           jnp.float32).reshape(b, s, h, hd)
        return mla_moe.rotary(y, theta, None, ids, sections).astype(
            jnp.bfloat16).transpose(0, 2, 1, 3)

    new = lambda x, w, ids: mla_moe.heads(x, w, how, positions=ids)
    same = lambda a, c: np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(c.astype(jnp.float32)))
    same(_written(new, x, w, ids), _written(parent, x, w, ids))
    text = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
    same(_written(new, x, w, text), _written(
        lambda x, w: mla_moe.heads(x, w, how._replace(sections=None)), x, w))
    weight = jax.random.normal(k[3], (b, h, s, hd))
    grad = lambda f: jax.jit(jax.grad(lambda x, w: jnp.sum(
        weight * f(x, w, ids).astype(jnp.float32)), (0, 1)))(x, w)
    assert _worst(grad(new), grad(parent)) < chip_smoke.HEADS_TOL


# ---------------------------------------------------------------------- #
# the kernels, in the interpreter
# ---------------------------------------------------------------------- #
# (head, lo, rope, normed, a batch element's own tables)
TURNS = {
    "whole_head_of_128": (128, 0, 128, False, False),
    "last_64_of_256": (256, 192, 64, False, False),
    "last_64_of_192": (192, 128, 64, False, False),
    "normed_whole_head": (128, 0, 128, True, False),
    "normed_first_64_of_256": (256, 0, 64, True, False),
    "normed_head_of_64": (64, 0, 64, True, False),
    "a_norm_alone": (128, 0, 0, True, False),
    "tables_of_a_batch_element": (128, 0, 128, False, True),
}


def _turn_operands(case, b=2, h=3, s=32, seed=5):
    hd, lo, rope, normed, own = TURNS[case]
    k = jax.random.split(jax.random.key(seed), 5)
    y = jax.random.normal(k[0], (b, h, s, hd))
    g = jax.random.normal(k[1], (b, h, s, hd)).astype(jnp.bfloat16)
    gain = 1 + 0.1 * jax.random.normal(k[2], (hd,)) if normed else None
    tables = (None, None)
    if rope:
        ids = (jax.random.randint(k[3], (1, b, s), 0, 99) if own else None)
        tables = mla_moe._turning(
            mla_moe.Heads(jnp.bfloat16, lo=lo, rope=rope, theta=1e4,
                          sections=(rope // 2,) if own else None), s, hd, ids)
        assert tables[0].shape == ((b, s, hd) if own else (s, hd))
    return y, g, gain, tables, head_turns.Turn(lo, rope, 1e-6, jnp.bfloat16)


@pytest.mark.parametrize("case", sorted(TURNS))
def test_the_kernels_are_the_plain_forms(case):
    y, g, gain, tables, turn = _turn_operands(case)
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    want = head_turns.plain_fwd(y, *tables, gain, turn)
    got = head_turns.forward(y, *tables, gain, turn, tile=16, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == y.shape
    np.testing.assert_allclose(f32(got), f32(want), rtol=1e-2, atol=1e-6)
    want, want_gain = head_turns.plain_bwd(g, y, *tables, gain, turn)
    got, got_gain = head_turns.backward(g, y, *tables, gain, turn, tile=16,
                                        interpret=True)
    np.testing.assert_allclose(f32(got), f32(want), rtol=1e-2, atol=1e-6)
    assert (got_gain is None) == (gain is None)
    if gain is not None:
        np.testing.assert_allclose(got_gain, want_gain, rtol=1e-5, atol=1e-5)


def test_the_plain_forms_are_norm_and_rotary_and_their_transposes():
    """``plain_fwd`` is ``rotary(rms_norm(y))`` rounded, bit for bit, and
    ``plain_bwd`` its ``jax.vjp``."""
    y, g, gain, tables, turn = _turn_operands("normed_first_64_of_256")

    def definition(y, gain):
        n = mla_moe.rms_norm(y, gain, turn.eps)
        return jnp.concatenate([
            mla_moe.rotary(n[..., :64].transpose(0, 2, 1, 3), 1e4
                           ).transpose(0, 2, 1, 3), n[..., 64:]], -1)

    np.testing.assert_array_equal(
        np.asarray(head_turns.plain_fwd(y, *tables, gain, turn
                                        ).astype(jnp.float32)),
        np.asarray(definition(y, gain).astype(jnp.bfloat16
                                              ).astype(jnp.float32)))
    dy, dgain = jax.vjp(definition, y, gain)[1](g.astype(jnp.float32))
    got, got_gain = head_turns.plain_bwd(g, y, *tables, gain, turn)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), dy,
                               rtol=1e-2, atol=1e-5)
    np.testing.assert_allclose(got_gain, dgain, rtol=1e-4, atol=1e-4)


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_the_kernels_names_are_no_flash_kernels(monkeypatch):
    """``benchmark/layers/attn.kernels_in`` counts every custom call whose
    name holds ``mv.lm.attn``: a layer's gradient under the kernels holds
    one forward and one backward pass a turned part, by their own names."""
    monkeypatch.setattr(head_turns, "kernel_tile", lambda s, hd, dtype: 16)
    cfg = GQA._replace(compute_dtype=jnp.bfloat16, head_dim=64, n_heads=4,
                       qk_norm=True)
    u, p, weight = _operands(cfg, "full")
    names = _pallas_names(jax.grad(lambda u, p: jnp.sum(
        weight * gqa_moe.gqa(u, p, cfg, "full")), (0, 1)), u, p)
    assert sorted(names) == [head_turns.BWD] * 2 + [head_turns.FWD] * 2
    assert not any("mv.lm.attn" in n for n in names)
    for name in (head_turns.FWD, head_turns.BWD):
        assert "mv.lm.attn" not in name


def test_a_shape_the_rule_refuses_runs_the_plain_form():
    assert head_turns.kernel_tile(8192, 128, jnp.bfloat16) is None   # no TPU
    y, g, gain, tables, turn = _turn_operands("whole_head_of_128")
    text = jax.jit(lambda y: head_turns.forward(y, *tables, gain, turn)
                   ).lower(y).as_text()
    assert "custom_call" not in text
    with pytest.raises(ValueError, match="does not divide"):
        head_turns.forward(y, *tables, gain, turn, tile=24, interpret=True)


def test_the_step_counts_what_lies_between_projection_and_core(monkeypatch):
    """``lm.step``'s static counts (``mla_moe.heads_grid`` through
    ``attn_grid``): the layers whose core operands ``heads`` makes, the
    bytes a step the pass reads and writes, and the layers whose pass is
    the kernels' on this device."""
    cells = {c[0]: c[1:] for c in chip_smoke._heads_cells()}
    mellum, _, b, s = cells["mellum_full"]
    grid = lambda cfg, s, b: {k: v for k, v in mla_moe.attn_grid(
        cfg._replace(attn="flash"), s, b).items() if k.startswith("heads")}
    # q and k of four layers: float32 in and bfloat16 out twice (forward,
    # made again), bfloat16 in and out backward
    want = 4 * b * s * (32 + 4) * 128 * (2 * 6 + 4)
    assert grid(mellum, s, b) == {"heads_layers": 4, "heads_kernel_layers": 0,
                                  "heads_turned_bytes": want}   # the CPU's
    monkeypatch.setattr(head_turns, "kernel_tile", head_turns.tile_of)
    assert grid(mellum, s, b)["heads_kernel_layers"] == 4
    # a norm's backward pass reads the float32 sum as well, and a layer
    # without positions still norms
    trinity, _, b, s = cells["trinity"]
    assert grid(trinity, s, b) == {
        "heads_layers": 5, "heads_kernel_layers": 5,
        "heads_turned_bytes": 5 * s * (32 + 4) * 128 * (2 * 6 + 4 + 4)}
    glm, _, b, s = cells["glm"]     # one layer's widths, the default's four
    assert grid(glm, s, b) == {
        "heads_layers": 4, "heads_kernel_layers": 4,
        "heads_turned_bytes": 4 * b * s * 20 * 256 * 16}
    # no positions and no norm: every part goes as its product writes it
    assert grid(nemotron_h.NemotronHConfig(), 512, 1) == {
        "heads_layers": 1, "heads_kernel_layers": 0, "heads_turned_bytes": 0}
    # a sequence no tile divides, a head that is not whole half lane tiles
    assert mla_moe.heads_grid(mellum, ("full",), 1, 1000)[
        "heads_kernel_layers"] == 0
    assert grid(mellum._replace(head_dim=96), 8192, 1)[
        "heads_kernel_layers"] == 0
    assert mla_moe.attn_grid(mellum._replace(attn="xla"), 64) == {}
