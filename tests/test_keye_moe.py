"""The decoder that attends over the keys a learned indexer selects
(``models/keye_moe.py``'s configuration, indexer, selection and term on
``models/mla_moe.py``'s one decoder path; ``gqa_moe``'s projections round
the core; the flash kernels' selection operand) against its plain reference
(``benchmark/reference/keye_moe.py``: whole [S, S] arrays and
``lax.top_k``) at small sizes with float32 operands, where program and
reference must agree to rounding and their selections EXACTLY."""

import gc
import hashlib
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
from benchmark import sparse_shapes
from benchmark.reference import keye_moe as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import (afmoe, gqa_moe, keye_moe, lfm2_moe,
                                   mla_moe)
from multiverso_tpu.ops import attention_kernels, index_kernels
from multiverso_tpu.ops.attention_kernels import flash_attention

CFG = keye_moe.KeyeMoEConfig(
    vocab=96, dim=48, n_heads=4, n_kv_heads=2, head_dim=8,
    layer_kinds=("sparse", "sparse"), rope_theta=1e7, mrope_section=(1, 1, 2),
    moe_ffn=24, n_experts=16, experts_held=4, expert_offset=4, top_k=4,
    index_heads=2, index_dim=4, index_topk=8, index_chunk=16, attn="xla",
    loss_chunk=32, compute_dtype=jnp.float32)
INDEXER = ("wq_i", "wk_i", "k_i_norm", "k_i_bias", "ww_i")


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, num_hidden_layers=len(cfg.layer_kinds),
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        rope_scaling={"mrope_section": list(cfg.mrope_section)},
        sa_config={"indexer_num_heads": cfg.index_heads,
                   "indexer_head_dim": cfg.index_dim,
                   "indexer_num_kv_heads": 1, "topk": cfg.index_topk},
        rms_norm_eps=cfg.eps, moe_intermediate_size=cfg.moe_ffn,
        num_experts=cfg.experts_held,
        published={"num_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k, expert_offset=cfg.expert_offset,
        router_aux_loss_coef=cfg.balance_coef,
        index_loss_coef=cfg.index_coef, vocab_size=cfg.vocab)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.2, scales={"k_i_bias": 0.1})
    # gains away from one, so that a gain's gradient is no symmetric case
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm"))):
        params[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(100 + i), params[name].shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, mla_moe.init_bias(cfg), tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


def _layer_inputs(cfg, seed=5, batch=2, positions=64):
    params, _, _ = _inputs(cfg, seed)
    u = jax.random.normal(jax.random.key(seed), (batch, positions, cfg.dim))
    return u, mla_moe._sub(params, "L0")


# ---------------------------------------------------------------------- #
# the model against its reference
# ---------------------------------------------------------------------- #
def test_the_configuration_is_a_sparse_mixer_beside_experts_in_every_layer():
    shapes = mla_moe.param_shapes(CFG)
    assert [tuple(layer) for layer in CFG.layers()] == [
        ("L0", "sparse", "experts"), ("L1", "sparse", "experts")]
    for name in INDEXER + ("q_norm", "k_norm", "wq", "wo", "router", "eg"):
        assert f"L1.{name}" in shapes, name
    assert shapes["L0.wq_i"] == (48, 2 * 4) and shapes["L0.wk_i"] == (48, 4)
    assert shapes["L0.ww_i"] == (48, 2) and shapes["L0.k_i_bias"] == (4,)
    assert "head" in shapes and "L0.wgate" not in shapes
    assert (CFG.route, CFG.qk_norm, CFG.attn_gate, CFG.window) == (
        "softmax", True, False, None)
    assert mla_moe._rule_of(CFG, "L0.k_i_norm") == "ones"


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_logits_both_loss_terms_and_every_gradient_match_the_reference(
        attn, kernel):
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=16)
    params, bias, tokens = _inputs(cfg)
    c = _ref_config(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    counts, overflow, balance, terms = aux
    want, (ce, want_counts, _, want_balance, want_terms, differ, _), want_g = (
        jax.jit(lambda p: ref.loss_and_grads(p, tokens, c))(params))
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    np.testing.assert_allclose(terms, want_terms, rtol=2e-5)
    np.testing.assert_allclose(balance, want_balance, rtol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(overflow.sum()) == 0 and float(terms.min()) > 0.01
    assert set(grads) == set(want_g)
    for name in grads:
        assert _close(grads[name], want_g[name], 5e-5), name
    # the reference under the program's own selection finds no other key
    chosen = jax.jit(lambda p: keye_moe.layer_selections(p, tokens, cfg))(
        params)
    again, (_, _, _, _, _, differ, far), _ = jax.jit(
        lambda p, sel: ref.loss_and_grads(p, tokens, c, sel))(
            params, chosen)
    assert differ.tolist() == [0, 0] and float(far.max()) == 0.0
    assert abs(float(again) - float(want)) < 1e-6 * float(want)
    # and the logits, which the loss never shows whole
    x, _ = mla_moe._trunk(params, bias, tokens, cfg)
    final = mla_moe.rms_norm(x, params["final_norm"], cfg.eps)
    assert _close(final @ params["head"].T, ref.logits(params, tokens, c))


def test_each_loss_term_moves_its_own_tensors_and_exactly_no_other():
    params, bias, tokens = _inputs(CFG)

    def parts(p):
        loss, (_, _, balance, terms) = mla_moe.loss_fn(p, bias, tokens, CFG)
        index = CFG.index_coef * jnp.sum(terms)
        return loss - index, index

    rest, index = (jax.jit(jax.grad(lambda p, i=i: parts(p)[i]))(params)
                   for i in range(2))
    for name in params:
        mine = name.split(".")[-1] in INDEXER
        zero, moved = (rest, index) if mine else (index, rest)
        assert float(jnp.max(jnp.abs(zero[name]))) == 0.0, name
        assert float(jnp.max(jnp.abs(moved[name]))) > 0.0, name


def test_a_selection_of_every_key_is_the_full_kind():
    cfg = CFG._replace(index_topk=64)
    full = gqa_moe.GQAMoEConfig(
        dim=cfg.dim, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, yarn=None,
        qk_norm=True, attn="xla", compute_dtype=jnp.float32, eps=cfg.eps)
    u, p = _layer_inputs(cfg)
    got, term = jax.jit(lambda u, p: keye_moe.sparse_gqa(u, p, cfg))(u, p)
    want = jax.jit(lambda u, p: gqa_moe.gqa(u, p, full, "full"))(u, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(term) > 0.0


def _top_k_sets(scores, topk):
    """``lax.top_k``'s selection of every causal row, a row at a time."""
    b, s, _ = scores.shape
    t = np.arange(s)
    causal = np.where(t[None, :] <= t[:, None], np.asarray(scores), -np.inf)
    want = np.zeros((b, s, s), np.int8)
    for i in range(b):
        for r in range(s):
            _, at = jax.lax.top_k(jnp.asarray(causal[i, r]),
                                  min(topk, r + 1))
            want[i, r, np.asarray(at)] = 1
    return want


@pytest.mark.parametrize("ties", ["none", "planted", "all_equal", "zeros"])
def test_the_selected_set_is_top_ks_with_ties_to_the_lower_position(ties):
    cfg = CFG
    scores = jax.random.normal(jax.random.key(7), (2, 64, 64))
    if ties == "planted":
        # every row's scores from nine values: ties at every threshold
        scores = jnp.round(scores * 2) / 2
    elif ties == "all_equal":
        scores = jnp.full_like(scores, -1.25)
    elif ties == "zeros":
        # both zeros are one score (relu's 0.0 under a negative weight)
        scores = jnp.where(scores > 0.5, scores, jnp.where(
            scores > 0, 0.0, -0.0))
    got = jax.jit(lambda x: keye_moe.select(x, cfg))(scores)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(got), _top_k_sets(jnp.where(scores == 0, 0.0, scores),
                                     cfg.index_topk))
    assert int(got.sum()) == 2 * cfg.index_grid(64)["attn_positions_selected"]


def test_chunks_of_rows_are_one_chunk():
    u, p = _layer_inputs(CFG)
    outs = []
    for chunk in (16, 64):
        cfg = CFG._replace(index_chunk=chunk)
        outs.append(jax.jit(lambda u, p, cfg=cfg: (
            keye_moe.index_scores(u, p, cfg),
            keye_moe.selection(*keye_moe.index_operands(u, p, cfg), cfg),
            *keye_moe.sparse_gqa(u, p, cfg)))(u, p))
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                  np.asarray(outs[1][1]))
    with pytest.raises(ValueError, match="do not divide"):
        keye_moe.selection(*keye_moe.index_operands(
            u, p, CFG._replace(index_chunk=24)), CFG._replace(index_chunk=24))


def test_a_remade_forward_selects_what_the_forward_selected():
    u, p = _layer_inputs(CFG)
    assert jax.jit(lambda u, p: keye_moe.selection_remade(u, p, CFG))(
        u, p).tolist() == [0, 0]


# ---------------------------------------------------------------------- #
# what a rematerialised block keeps of the selection
# ---------------------------------------------------------------------- #
SPARSE = mla_moe.Layer("L0", "sparse", "experts")


def _without(cfg, *names, **attrs):
    """``cfg`` with ``names`` taken out of its own ``kept_names`` (and the
    class attributes given): what its blocks' policy held before."""
    kept = tuple(n for n in type(cfg).kept_names if n not in names)
    return type("Without", (type(cfg),), dict(attrs, kept_names=kept))(*cfg)


def _count(jaxpr, primitive: str) -> int:
    """The equations of that primitive in a jaxpr and in every jaxpr its
    equations hold."""
    return sum((e.primitive.name == primitive)
               + sum(_count(sub, primitive)
                     for sub in jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


def _bits(tree):
    return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


def _sparse_block(cfg):
    """One sparse block's loss (with both of the loss's terms) over its
    input and parameters, and both."""
    x, p = _layer_inputs(cfg)
    weight = jax.random.normal(jax.random.key(12), x.shape)

    def loss(cfg, remat=True):
        def run(x, p):
            y, (_, _, balance, term) = mla_moe._run_block(
                x, p, SPARSE, None, cfg, remat=remat)
            return (jnp.sum(y * weight) + cfg.balance_coef * balance
                    + cfg.index_coef * term)
        return run
    return loss, x, p


def test_a_remade_block_makes_no_selection_again():
    """The step's gradient: a layer's selection (the chunks' ``lax.map``,
    a chunk's 32 counting passes, the cut of the ties) stands once and
    not twice; with the name out of the policy the block made again makes
    it again. The block's residuals hold the int8 [B, S, S] array."""
    from jax._src.ad_checkpoint import saved_residuals

    params, bias, tokens = _inputs(CFG)
    layers = len(CFG.layers())
    assert mla_moe.kept_names(CFG) == (mla_moe.moe.KEPT_NAMES
                                       + keye_moe.KEPT_NAMES)
    assert keye_moe.KEPT_NAMES == (keye_moe.KEEP_GRADS,
                                   keye_moe.KEEP_SELECTION)
    bare = _without(CFG, keye_moe.KEEP_SELECTION)
    assert mla_moe.kept_names(bare)[-1] == keye_moe.KEEP_GRADS
    counts = {}
    for name, cfg in (("kept", CFG), ("bare", bare)):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params).jaxpr
        counts[name] = {k: _count(jaxpr, k) for k in ("scan", "cond")}
    # the map over the chunks and the threshold's passes, both ``scan``s
    assert counts["kept"]["cond"] == layers
    assert counts["bare"]["cond"] == 2 * layers
    assert counts["bare"]["scan"] - counts["kept"]["scan"] == 3 * layers
    loss, x, p = _sparse_block(CFG)
    chosen = jax.core.ShapedArray((2, 64, 64), jnp.int8)
    kept = lambda cfg: [why for aval, why in saved_residuals(loss(cfg), x, p)
                        if aval == chosen]
    assert len(kept(CFG)) == 1 and keye_moe.KEEP_SELECTION in kept(CFG)[0]
    assert kept(bare) == []


def test_keeping_the_selection_moves_no_gradient_by_a_bit():
    """Every table's float32 gradient with the selection kept is the
    gradient with the name out of the policy, and a sparse block's is the
    un-rematerialised block's, bit for bit."""
    params, bias, tokens = _inputs(CFG)
    grads = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params)
    got, bare = grads(CFG), grads(_without(CFG, keye_moe.KEEP_SELECTION))
    assert set(got[1]) == set(mla_moe.param_shapes(CFG))
    for n in got[1]:
        assert float(jnp.abs(got[1][n]).max()) > 0, n
    for a, b in zip(_bits(got), _bits(bare)):
        np.testing.assert_array_equal(a, b)
    loss, x, p = _sparse_block(CFG)
    kept, still = (jax.jit(jax.value_and_grad(loss(CFG, remat), (0, 1)))(x, p)
                   for remat in (True, False))
    for a, b in zip(_bits(kept), _bits(still)):
        np.testing.assert_array_equal(a, b)


def test_the_backward_pass_reads_the_forwards_selection(monkeypatch):
    """Poison: the selection among the residuals of a sparse block's
    forward pass (one int8 array [B, S, S]; none without the name) is
    swapped for every causal key before the backward pass runs. The core's
    and the projections' gradients are then those of a block that selects
    every causal key (its backward pass read the kept array), and the
    indexer's are the unpoisoned block's: the term's kept gradients are
    the forward's."""
    # no expert product kept, so that what is made again follows the poison
    cfg = _without(CFG, keeps_products=False)
    assert mla_moe.kept_names(cfg) == keye_moe.KEPT_NAMES
    loss, x, p = _sparse_block(cfg)
    is_chosen = lambda a: (getattr(a, "shape", None) == (2, 64, 64)
                           and a.dtype == jnp.int8)
    _, back = jax.vjp(loss(cfg), x, p)
    leaves, tree = jax.tree.flatten(back)
    assert sum(map(is_chosen, leaves)) == 1
    bare = jax.vjp(loss(_without(cfg, keye_moe.KEEP_SELECTION)), x, p)[1]
    assert not any(map(is_chosen, jax.tree.leaves(bare)))
    every = jnp.broadcast_to(jnp.tril(jnp.ones((64, 64), jnp.int8)),
                             (2, 64, 64))
    clean = back(jnp.ones(()))
    poisoned = jax.tree.unflatten(
        tree, [every if is_chosen(a) else a for a in leaves])(jnp.ones(()))
    monkeypatch.setattr(keye_moe, "selection", lambda qi, ki, w, cfg: every)
    want = jax.vjp(loss(cfg), x, p)[1](jnp.ones(()))
    np.testing.assert_array_equal(*_bits((poisoned[0], want[0])))
    for n in sorted(p):
        other = clean if n in INDEXER else want
        np.testing.assert_array_equal(
            *_bits((poisoned[1][n], other[1][n])), err_msg=n)
        if n in ("wq", "wk", "wv", "wo", "wq_i", "wk_i", "ww_i"):
            assert (np.asarray(clean[1][n]) != np.asarray(want[1][n])).any()


def test_the_steps_span_counts_what_the_sparse_layers_keep():
    """``lm.step``'s ``kept_names`` and ``kept_bytes``: six names, and
    beside the expert layers' the term's float32 gradients and a layer's
    int8 selection, every layer, from the shapes."""
    cfg = _published()
    grid = mla_moe.kept_grid(cfg, 1, 16384)
    bare = mla_moe.kept_grid(_without(cfg, *keye_moe.KEPT_NAMES), 1, 16384)
    assert (grid["kept_names"], bare["kept_names"]) == (6, 4)
    index = cfg.index_grid(16384)
    assert cfg.kept_bytes(1, 16384) == (
        index["target_kept_bytes"] + 4 * index["select_bytes"])
    assert grid["kept_bytes"] - bare["kept_bytes"] == (
        4 * (4 * 16384 * (16 * 64 + 64 + 16) + 16384 * 16384))
    assert cfg.kept_bytes(2, 64) == 2 * cfg.kept_bytes(1, 64)


# ---------------------------------------------------------------------- #
# the kernels' selection operand
# ---------------------------------------------------------------------- #
def _random_selection(key, b, s, share=0.3):
    keep = jax.random.uniform(key, (b, s, s)) < share
    t = jnp.arange(s)
    # a query always keeps itself: no row is empty
    return ((keep | jnp.eye(s, dtype=bool)[None])
            & (t[None, :] <= t[:, None])[None]).astype(jnp.int8)


@pytest.mark.parametrize("blocks", [(32, 32), (16, 32), (32, 16), (64, 64)])
@pytest.mark.parametrize("heads", [(4, 2), (4, 1), (2, 2)])
def test_interpreted_kernels_under_a_selection_are_the_masked_xla_core(
        blocks, heads):
    b, s, d = 2, 64, 8
    h, hkv = heads
    keys = jax.random.split(jax.random.key(11), 5)
    q = jax.random.normal(keys[0], (b, h, s, d))
    k = jax.random.normal(keys[1], (b, hkv, s, d))
    v = jax.random.normal(keys[2], (b, hkv, s, d))
    weight = jax.random.normal(keys[3], (b, h, s, d))
    chosen = _random_selection(keys[4], b, s)
    # one query whose keys all lie in its LAST k block: the first tiles of
    # its row hold no live position
    chosen = chosen.at[0, 63, :48].set(0)

    def through(core):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) * weight), (0, 1, 2)))(
                q, k, v)

    got = through(lambda q, k, v: flash_attention(
        q, k, v, True, *blocks, select=chosen))
    want = through(lambda q, k, v: mla_moe._xla_attention(
        q, k, v, None, chosen))
    assert abs(float(got[0]) - float(want[0])) < 1e-4
    for a, w, name in zip(got[1], want[1], "qkv"):
        assert a.shape == w.shape and _close(a, w, 1e-5), name


def test_a_selection_goes_with_a_causal_call_of_its_own_shape():
    q = jnp.zeros((1, 2, 32, 8))
    with pytest.raises(ValueError, match="selection"):
        flash_attention(q, q, q, True, 16, 16,
                        select=jnp.ones((1, 32, 16), jnp.int8))
    with pytest.raises(ValueError, match="no window"):
        flash_attention(q, q, q, True, 16, 16, window=8,
                        select=jnp.ones((1, 32, 32), jnp.int8))
    walk, _ = attention_kernels._walk_of(
        q, q, True, 16, 16, None, 8, jnp.ones((1, 32, 32), jnp.int8))
    assert (walk.heads, walk.sub) == (2, None)       # whole tiles
    assert attention_kernels._walk_of(q, q, True, 16, 16, None, 8)[0] == (
        attention_kernels._Walk(True, 32, 16, 16, False, None, 1, 8))


# The lowered text of the parent commit's programs (StableHLO without
# locations, sha256's first 16 digits), made with ``git archive 91eb0c6``
# beside this tree: the cells that have no selection must compile what they
# compiled. The five MODELS' entries are THIS tree's text since PR 63 (the
# parent's were f3ca378f315449a4, 0b3e794482f2059c, 06ebccb9b2fd87b1,
# d8be808c75a5fa2e, 1176c1bf3112ad40): what lies between a projection and
# the core is ``mla_moe.heads`` / ``out_of_heads`` in every model, so every
# step's text moved ON PURPOSE (``tests/test_head_turns.py`` holds the new
# lines to the parent's formulation bit for bit and gradient for
# gradient); the two kernels' calls must NOT move, and did not.
PARENT = {"gqa.xla": "dd417f7a7132a1fc", "gqa.flash": "58107a305cd7131b",
          "afmoe.xla": "9a893072e43c71af", "lfm2.xla": "5bc1d38d9eeb95f5",
          "mla.xla": "969aac75f944a61b", "flash.causal": "dba2fefc55cb0118",
          "flash.window": "81d121c63573ec2a"}
MODELS = {"gqa.xla": gqa_moe.GQAMoEConfig(attn="xla"),
          "gqa.flash": gqa_moe.GQAMoEConfig(attn="flash", attn_block=32),
          "afmoe.xla": afmoe.AFMoEConfig(attn="xla"),
          "lfm2.xla": lfm2_moe.LFM2MoEConfig(attn="xla"),
          "mla.xla": mla_moe.MLAMoEConfig(attn="xla")}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_without_a_selection_the_programs_lower_to_the_parents_text(name):
    if name in MODELS:
        cfg = MODELS[name]
        params = jax.eval_shape(lambda: mla_moe.init(cfg, 0))
        bias = jax.eval_shape(lambda: mla_moe.init_bias(cfg))
        text = jax.jit(jax.value_and_grad(
            lambda p, b, t: mla_moe.loss_fn(p, b, t, cfg),
            has_aux=True)).lower(
                params, bias, jnp.zeros((2, 64), jnp.int32)).as_text()
    else:
        window = 16 if name == "flash.window" else None
        q = jax.ShapeDtypeStruct((1, 4, 64, 8), jnp.float32)
        k = jax.ShapeDtypeStruct((1, 2, 64, 8), jnp.float32)
        text = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, True, 32, 32, None, window).sum(),
            (0, 1, 2))).lower(q, k, k).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[name]


# ---------------------------------------------------------------------- #
# positions by axis
# ---------------------------------------------------------------------- #
def test_equal_ids_on_every_axis_are_plain_rotary_bit_for_bit():
    x = jax.random.normal(jax.random.key(1), (2, 40, 3, 16))
    place = jnp.broadcast_to(jnp.arange(40)[None, None, :], (3, 2, 40))
    for yarn in (None, mla_moe.Yarn(4.0, 16, 32.0, 1.0, 1.1)):
        np.testing.assert_array_equal(
            np.asarray(mla_moe.rotary(x, 1e4, yarn)),
            np.asarray(mla_moe.rotary(x, 1e4, yarn, place, (2, 3, 3))))
    with pytest.raises(ValueError, match="sections"):
        mla_moe.rotary(x, 1e4, None, place, (2, 3, 4))


def test_distinct_ids_turn_each_section_by_its_own_axis():
    s, h, r, sections, theta = 24, 3, 16, (2, 3, 3), 1e7
    x = jax.random.normal(jax.random.key(2), (2, s, h, r))
    ids = jax.random.randint(jax.random.key(3), (3, 2, s), 0, 500)
    got = mla_moe.rotary(x, theta, None, ids, sections)
    want = jnp.stack([ref.rope_by_axis(x[i], ids[:, i], sections, theta)
                      for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert not np.allclose(np.asarray(got),
                           np.asarray(mla_moe.rotary(x, theta)))
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.qwen2_vl.modeling_qwen2_vl")
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.asarray(ids, np.float64)[..., None] * inv       # [3, B, S, R/2]
    emb = np.concatenate([angle, angle], -1)
    to = lambda a: torch.tensor(np.asarray(a, np.float32))
    q, _ = hf.apply_multimodal_rotary_pos_emb(
        to(x).permute(0, 2, 1, 3), to(x).permute(0, 2, 1, 3),
        to(np.cos(emb)), to(np.sin(emb)), list(sections))
    np.testing.assert_allclose(np.asarray(got),
                               q.permute(0, 2, 1, 3).numpy(), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------- #
# the deployment's shares, the lowered step, the span
# ---------------------------------------------------------------------- #
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips' shares of the expert layer (the program's layer, told
    which sixteen of 128 experts it holds: offsets 0, 16, ..., 112) are the
    reference's uncut layer over all 128. No shared expert: nothing is
    computed alike on every chip, so nothing is counted once."""
    cfg = CFG._replace(n_experts=128, experts_held=16, top_k=8)
    c = dict(_ref_config(cfg), num_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 5)
    d, f, e = cfg.dim, cfg.moe_ffn, cfg.n_experts
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "eg": 0.1 * jax.random.normal(rng[1], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[2], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[3], (e, f, d))}
    u = jax.random.normal(rng[4], (2, 48, d))
    total, seen, offsets = jnp.zeros_like(u), 0, []
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
        out, (counts, overflow, _) = jax.jit(
            lambda u, share, offset=offset: mla_moe.expert_ffn(
                u, share, None, cfg._replace(expert_offset=offset),
                shared=False))(u, share)
        total = total + out
        seen += int(counts[offset:offset + cfg.experts_held].sum())
        offsets.append(offset)
        assert int(overflow) == 0
    assert offsets == list(range(0, 128, 16))
    assert seen == 2 * 48 * cfg.top_k       # every assignment, once
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.routed_share(u[i], whole, c, 0, e)[0]
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


def _pallas_calls(jaxpr, found=None):
    """{a Pallas call's name: its name stack} over ``jaxpr`` and every
    jaxpr inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = str(eqn.source_info.name_stack)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize("attn", ["xla", "flash"])
def test_the_lowered_step_has_the_indexers_products_and_the_four_scopes(attn):
    # widths that no other product of the model has
    cfg = CFG._replace(vocab=112, dim=40, index_heads=3, index_dim=6,
                       loss_chunk=64, attn=attn)
    params, bias, tokens = _inputs(cfg)
    layers = len(cfg.layers())
    forward = jax.jit(lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0])
    text = forward.lower(params).as_text(debug_info=True)
    for scope in ("mv.lm.attn.index", "mv.lm.attn.select",
                  "mv.lm.attn.target", "mv.lm.attn.sparse",
                  "mv.lm.attn.qknorm"):
        assert scope in text, scope
    plain = forward.lower(params).as_text()
    # u W_qI (18 wide) a layer; the index heads' dots [.., 3, 16 rows, 64
    # keys] a layer: once for the selection, once for the term and twice
    # back from it (the term makes its gradients beside itself)
    assert _products(plain, cfg.dim, 18) == layers
    # where the core is the flash kernels the term's three are inside two
    # kernels a chunk, a key tile at a time, and XLA's are the selection's
    term_dots = 3 * layers if attn == "xla" else 0
    assert _products(plain, 3, 16, 64, 6) == layers + term_dots
    heads = lambda text: _products(text, cfg.n_kv_heads, 2, 16, 64, 8)
    assert heads(plain) == layers
    step = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(p, bias, tokens,
                                                      cfg)[0]))
    lowered = step.lower(params).as_text()
    assert _products(lowered, cfg.vocab) == 3
    # the remade blocks keep the term's gradients and the selection by
    # name: no pass makes the query heads' probabilities a second time,
    # and the remade forward makes no index score
    assert heads(lowered) == layers
    assert _products(lowered, 3, 16, 64, 6) == layers + term_dots
    assert mla_moe.kept_names(cfg) == (mla_moe.moe.KEPT_NAMES
                                       + keye_moe.KEPT_NAMES)
    assert mla_moe.kept_names(gqa_moe.GQAMoEConfig()) == (
        mla_moe.moe.KEPT_NAMES)
    # the term alone: its two kernels sit inside ``mv.lm.attn.index`` under
    # names of their own (``benchmark/layers/attn`` counts every custom
    # call whose name holds ``mv.lm.attn`` against the flash kernels), and
    # no float32 array of index heads x chunk x positions is left in it
    u, p = _layer_inputs(cfg)
    q, k, _ = gqa_moe.heads_of(u, p, cfg, "sparse")
    operands = keye_moe.index_operands(u, p, cfg)
    chosen = keye_moe.selection(*operands, cfg)
    term = lambda *a: keye_moe.index_loss(*a, q, k, chosen, cfg)
    calls = _pallas_calls(jax.make_jaxpr(term)(*operands).jaxpr)
    whole = "x3x16x64xf32" in jax.jit(term).lower(*operands).as_text()
    if attn == "xla":
        assert not calls and whole
        return
    assert sorted(calls) == sorted((index_kernels.GRADS, index_kernels.STATS))
    for name, stack in calls.items():
        assert "mv.lm.attn" not in name
        assert "mv.lm.attn.index" in stack, (name, stack)
    assert not whole


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """And the step's span says the selection's counts, those of
    ``benchmark/sparse_shapes.py``, and the indexer's term."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=16, expert_kernel="xla")
    _, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    scales = {"k_i_bias": 0.1}
    params = mla_moe.init(cfg, 0, 0.2, scales=scales)
    tables = mla_moe.make_tables(
        cfg, 0, 0.2, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=scales)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert len(tables) == 3 + 2 * 17
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr))
    before = len(ttrace.events())
    loss, counts = trainer.step(tokens)
    trainer.adopt()
    c = _ref_config(cfg)
    want_loss, (_, want_counts, _, _, want_terms, _, _), grads = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, c))(params)
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(counts[:, :cfg.n_experts],
                                  np.asarray(want_counts))
    for n, t in tables.items():
        want, _, _, _ = ref.adam_step(np.asarray(params[n]), 0.0, 0.0, 0,
                                      np.asarray(grads[n]), lr, b1, b2, eps)
        moved = t.get().reshape(params[n].shape) - np.asarray(params[n])
        sure = np.abs(np.asarray(grads[n])) > 1e-4 * np.abs(
            np.asarray(grads[n])).max()
        np.testing.assert_allclose(moved[sure], (want - params[n])[sure],
                                   atol=2e-2 * lr, err_msg=n)
    args = [e for e in ttrace.events()[before:]
            if e["name"] == "lm.step"][0]["args"]
    assert args["block_kinds"] == "sparse+experts,sparse+experts"
    assert args["attn_kinds"] == "sparse,sparse"
    assert abs(args["index_loss"] - float(want_terms.sum())) < 1e-5
    assert args["aux_loss"] > 0
    assert (args["index_heads"], args["index_dim"], args["index_topk"],
            args["index_chunk"]) == (2, 4, 8, 16)
    assert args["attn_positions_selected"] == (
        sparse_shapes.selected_positions(64, 8)) == 484
    assert args["attn_positions_causal"] == 64 * 65 // 2
    assert args["attn_positions_computed"] == 3 * 32 * 32     # whole tiles
    assert args["select_bytes"] == sparse_shapes.select_bytes(1, 64)
    assert args["index_flops_token"] == 2 * sparse_shapes.index_flops(c, 64)
    assert args["target_flops_token"] == 2 * sparse_shapes.target_flops(c, 64)
    assert args["step_flops_token"] == sparse_shapes.step_flops_token(c, 64)
    from tools import dump_metrics
    lines = dump_metrics._mixer_lines([{"name": "lm.step", "args": args}])
    assert lines[0] == "  blocks: sparse+experts,sparse+experts"
    assert "484 of 2080 causal positions a head selected = 23.27%" in lines[1]
    assert "3072 computed = 6.35x the selected" in lines[1]


def _published():
    return keye_moe.KeyeMoEConfig(
        vocab=18992, dim=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        layer_kinds=("sparse",) * 4, mrope_section=(16, 24, 24), moe_ffn=768,
        n_experts=128, experts_held=16, top_k=8, index_heads=16,
        index_dim=64, index_topk=2048, index_chunk=512, attn="flash")


def test_published_sizes_give_the_configurations_counts():
    cfg = _published()
    shapes = mla_moe.param_shapes(cfg)
    count = lambda names: sum(int(np.prod(shapes[n])) for n in names)
    layer = [n for n in shapes if n.startswith("L0.")]
    assert count(n for n in layer
                 if n.split(".")[-1] in INDEXER) == 2_261_120
    assert count(layer) == 96_899_456
    assert count(shapes) == 465_391_104
    assert mla_moe.attn_blocks(cfg, 16384) == (1024, 1024)
    grid = cfg.index_grid(16384)
    assert grid["attn_positions_selected"] == 31_458_304
    assert grid["attn_positions_causal"] == 134_225_920
    assert grid["attn_positions_computed"] == 136 * 1024 * 1024
    assert grid["select_bytes"] == 268_435_456
    assert abs(100.0 * grid["attn_positions_selected"]
               / grid["attn_positions_causal"] - 23.44) < 0.005
    mechanism = (grid["index_flops_token"] + grid["core_flops_token"]
                 + grid["target_flops_token"])
    assert round(100.0 * mechanism / grid["step_flops_token"]) == 50
    assert round(grid["step_flops_token"] / 1e6) == 543
    attn = mla_moe.attn_grid(cfg, 16384)
    assert attn["attn_positions_computed"] == 136 * 1024 * 1024
    assert mla_moe.held(cfg, 16384).tile == (512, 512, 768 // 2) or (
        mla_moe.held(cfg, 16384).tile[2] in (128, 256, 384, 768))
