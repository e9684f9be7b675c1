"""A rematerialised block keeps the held experts' grouped products into
the experts' width: their results are named (``parallel/moe.KEPT_NAMES``)
and ``mla_moe._run_block``'s checkpoint saves those names alone, so the
backward pass of an expert block runs none of them (and no sort) a second
time, only the one product out of the experts' width, to the same
gradients bit for bit, whatever the kernels left in the rows past the
routed ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models import (afmoe, gqa_moe, keye_moe, lfm2_moe,
                                   mla_moe, nemotron_h, qwen3_next)
from multiverso_tpu.parallel import moe

FORMS = {"gated_silu": 3, "relu2": 2}       # an expert's matrices
PRODUCTS = {"xla": "ragged_dot_general", "interpret": "pallas_call"}
TOKENS, ROWS = 96, 384


def _block(form: str, route: str, kernel: str):
    """One expert block without attention, the tiny decoder's widths
    under the form and route a case names: 96 tokens choosing 4 of 16
    experts, 4 held from the fifth on, so about 96 rows in a buffer of
    384 (what the routing can send at the most: three tiles of 128)."""
    config = type("Block", (mla_moe.MLAMoEConfig,), {
        "expert_form": form, "route": route,
        "balance_coef": 0.01 if route == "softmax" else 0.0})
    cfg = config(dim=64, moe_ffn=32, n_experts=16, experts_held=4,
                 expert_offset=4, top_k=4, expert_kernel=kernel,
                 compute_dtype=jnp.float32)
    held = mla_moe.held(cfg, TOKENS)
    assert (held.form, held.route, held.buffer_rows) == (form, route, ROWS)
    shapes = mla_moe._ffn_shapes(cfg, "experts")
    keys = jax.random.split(jax.random.key(len(form) + len(route)),
                            len(shapes) + 2)
    p = {n: 0.2 * jax.random.normal(k, s)
         for (n, s), k in zip(sorted(shapes.items()), keys)}
    p["ffn_norm"] = jnp.ones((cfg.dim,))
    x = jax.random.normal(keys[-2], (2, TOKENS // 2, cfg.dim))
    weight = jax.random.normal(keys[-1], x.shape)
    return cfg, x, p, weight


LAYER = mla_moe.Layer("L0", None, "experts")


def _loss(cfg, weight, remat: bool):
    bias = jnp.linspace(-0.02, 0.02, cfg.n_experts)

    def loss(x, p):
        y, (_, _, balance) = mla_moe._run_block(x, p, LAYER, bias, cfg,
                                                remat=remat)
        return jnp.sum(y * weight) + cfg.balance_coef * balance
    return loss


def _count(jaxpr, primitive: str) -> int:
    """The equations of that primitive in a jaxpr and in every jaxpr its
    equations hold (a checkpoint's, a custom rule's, a kernel's)."""
    return sum((e.primitive.name == primitive)
               + sum(_count(sub, primitive)
                     for sub in jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


def _bare(cfg):
    """The configuration whose blocks keep nothing, as every block was
    rematerialised before (and ``NemotronHConfig``'s still are)."""
    return type("Bare", (type(cfg),), {"keeps_products": False})(*cfg)


cases = pytest.mark.parametrize("route", ["sigmoid", "softmax"])
forms = pytest.mark.parametrize("form", sorted(FORMS))
kernels = pytest.mark.parametrize("kernel", sorted(PRODUCTS))


@cases
@forms
@kernels
def test_a_blocks_gradient_runs_one_forward_product_again(form, route,
                                                          kernel):
    """Forward, the input's gradient, the matrix's, and the forward
    product out of the experts' width once more (its result is not
    kept): and one sort and one ``top_k`` (the gates are read AT the kept
    choice under both routes, so nothing of the backward pass can choose
    otherwise). A bare checkpoint runs every forward product, the sort
    and the route's ``top_k`` again."""
    cfg, x, p, weight = _block(form, route, kernel)
    # in the interpreter a grouped product's metadata is no kernel, and
    # ``tgmm`` is a kernel as ``gmm`` is: one ``pallas_call`` each
    grad = jax.grad(_loss(cfg, weight, True), (0, 1))
    kept = jax.make_jaxpr(grad)(x, p).jaxpr
    assert _count(kept, PRODUCTS[kernel]) == 3 * FORMS[form] + 1
    assert _count(kept, "sort") == 1 and _count(kept, "top_k") == 1
    still = jax.make_jaxpr(jax.grad(_loss(cfg, weight, False), (0, 1)))(
        x, p).jaxpr
    assert _count(still, PRODUCTS[kernel]) == 3 * FORMS[form]
    bare = jax.make_jaxpr(jax.grad(_loss(_bare(cfg), weight, True), (0, 1)))(
        x, p).jaxpr
    assert _count(bare, PRODUCTS[kernel]) == 4 * FORMS[form]
    assert _count(bare, "sort") == 2 and _count(bare, "top_k") == 2


def test_the_softmax_gates_at_the_choice_are_top_ks_own_values():
    """The select over the experts that reads the softmax route's gates
    at the choice gives ``top_k``'s values to the bit, and a gate's
    gradient goes to the chosen probability alone."""
    cfg, x, p, _ = _block("gated_silu", "softmax", "xla")
    here, u = mla_moe.held(cfg, TOKENS), x.reshape(TOKENS, cfg.dim)
    chosen, gates, counts, _ = moe.softmax_route(u, p["router"], here)
    probs = jax.nn.softmax(jnp.dot(u, p["router"].T, precision="highest"))
    picked, order = jax.lax.top_k(probs, cfg.top_k)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(order))
    np.testing.assert_array_equal(
        *_bits((gates, picked / picked.sum(-1, keepdims=True))))
    assert int(counts.sum()) == TOKENS * cfg.top_k
    weight = jax.random.normal(jax.random.key(7), gates.shape)
    by_select = jax.grad(lambda r: jnp.sum(
        moe.softmax_route(u, r, here)[1] * weight))(p["router"])

    def plain(r):
        probs = jax.nn.softmax(jnp.dot(u, r.T, precision="highest"))
        picked = jnp.take_along_axis(probs, order, axis=-1)
        return jnp.sum(picked / picked.sum(-1, keepdims=True) * weight)
    want = jax.grad(plain)(p["router"])
    np.testing.assert_allclose(np.asarray(by_select), np.asarray(want),
                               atol=1e-6 * float(jnp.abs(want).max()))


def _bits(tree):
    return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


@cases
@forms
@kernels
def test_the_gradients_are_a_bare_checkpoints_bit_for_bit(form, route,
                                                          kernel):
    """Keeping a result changes no float: the same gradients as with
    nothing kept and as with nothing rematerialised."""
    cfg, x, p, weight = _block(form, route, kernel)
    got = jax.jit(jax.value_and_grad(_loss(cfg, weight, True), (0, 1)))(x, p)
    still = jax.jit(jax.value_and_grad(_loss(cfg, weight, False), (0, 1)))(
        x, p)
    bare = jax.jit(jax.value_and_grad(_loss(_bare(cfg), weight, True),
                                      (0, 1)))(x, p)
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(got))
    for n in ("eg", "eu", "ed")[3 - FORMS[form]:]:
        assert float(jnp.abs(got[1][1][n]).max()) > 0
    assert float(jnp.abs(got[1][1]["router"]).max()) > 0
    for other in (still, bare):
        for a, b in zip(_bits(got), _bits(other)):
            np.testing.assert_array_equal(a, b)


@cases
@forms
@pytest.mark.parametrize("chunk", [100, 128])
def test_the_chunked_passes_under_a_checkpoint_keep_every_bit(
        form, route, chunk, monkeypatch):
    """The dispatch's and the combine's loops (``moe._walk``), four trips
    of 100 rows over a buffer of 384 that is no multiple of them, or
    three of 128: made again under the block's checkpoint beside the kept
    row order, they give the unrematerialised block's gradients."""
    monkeypatch.setattr(moe, "CHUNK", chunk)
    cfg, x, p, weight = _block(form, route, "xla")
    # every token to the four held experts: 384 rows, the buffer's all
    p = dict(p, router=p["router"].at[4:8].add(12.0))
    x = x + 1.0
    _, (counts, _, _) = mla_moe._run_block(
        x, p, LAYER, jnp.zeros((cfg.n_experts,)), cfg, remat=False)
    assert int(counts[4:8].sum()) == ROWS
    got, still, bare = (
        jax.jit(jax.value_and_grad(_loss(c, weight, remat), (0, 1)))(x, p)
        for c, remat in ((cfg, True), (cfg, False), (_bare(cfg), True)))
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(got))
    assert float(jnp.abs(got[1][1]["ed"]).max()) > 0
    for other in (still, bare):
        for a, b in zip(_bits(got), _bits(other)):
            np.testing.assert_array_equal(a, b)


@cases
@forms
def test_poisoned_rows_in_the_kept_results_reach_no_gradient(form, route,
                                                             monkeypatch):
    """The rows past ``held_rows`` of a kept result are what the forward
    kernel left there (the backward pass no longer makes them again):
    NaN there, in every product's result, moves no gradient by a bit;
    and the kernel in the interpreter, which leaves NaN there by itself,
    gives ``ragged_dot``'s gradients."""
    cfg, x, p, weight = _block(form, route, "xla")
    grads = lambda c: jax.jit(jax.value_and_grad(_loss(c, weight, True),
                                                 (0, 1)))(x, p)
    want = grads(cfg)
    product = moe._grouped_matmul_xla
    poisoned = []

    def poison(lhs, rhs, group_sizes, dtype):
        out = product(lhs, rhs, group_sizes, dtype)
        live = jnp.arange(out.shape[0]) < group_sizes.sum()
        poisoned.append(out.shape)
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(moe, "_grouped_matmul_xla", poison)
    got = grads(cfg)
    monkeypatch.undo()
    assert len(poisoned) == FORMS[form]
    assert all(shape[0] == ROWS for shape in poisoned)
    for a, b in zip(_bits(got), _bits(want)):
        np.testing.assert_array_equal(a, b)
    kernel = grads(cfg._replace(expert_kernel="interpret"))
    for a, b in zip(jax.tree.leaves(kernel), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * float(jnp.abs(b).max()) + 1e-6)


def _named(jaxpr, found):
    for e in jaxpr.eqns:
        if e.primitive.name == "name":
            found.append((e.params["name"], e.outvars[0].aval))
        for sub in jax.core.jaxprs_in_params(e.params):
            _named(sub, found)
    return found


TINY = {"glm": mla_moe.MLAMoEConfig(), "mellum2": gqa_moe.GQAMoEConfig(),
        "trinity": afmoe.AFMoEConfig(),
        "nemotron": nemotron_h.NemotronHConfig(),
        "lfm2": lfm2_moe.LFM2MoEConfig(), "keye": keye_moe.KeyeMoEConfig(),
        "qwen3next": qwen3_next.Qwen3NextConfig()}
# what a configuration's own mixer names beside the expert layers' results
OWN = {"keye": keye_moe.KEPT_NAMES, "qwen3next": qwen3_next.KEPT_NAMES}


@pytest.mark.parametrize("model", sorted(TINY))
def test_kept_grid_counts_what_the_loss_names(model):
    """``lm.step``'s ``kept_names``, ``expert_products_kept`` and
    ``kept_bytes`` are the results the traced loss names and the
    configuration keeps: the names' count, the products' count and the
    bytes of all of them (``NemotronHConfig`` keeps none: no room; Keye
    keeps its term's gradients and its selection, Qwen3-Next the delta
    rule's result)."""
    cfg = TINY[model]._replace(attn="xla", expert_kernel="xla")
    names, own = mla_moe.kept_names(cfg), OWN.get(model, ())
    assert names == (() if model == "nemotron" else moe.KEPT_NAMES + own)
    tokens = jnp.zeros((2, 64), jnp.int32)
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in mla_moe.param_shapes(cfg).items()}
    named = _named(jax.make_jaxpr(
        lambda p, b, t: mla_moe.loss_fn(p, b, t, cfg)[0])(
            shapes, mla_moe.init_bias(cfg), tokens).jaxpr, [])
    assert set(own) <= {name for name, _ in named} <= set(
        moe.KEPT_NAMES + own)
    layers = len(mla_moe.expert_layers(cfg))
    matrices = FORMS[cfg.expert_form] - 1       # into the experts' width
    whole = (moe.KEEP_TAKE, moe.KEEP_CHOSEN)        # int32: no product's
    products = [aval for name, aval in named if name not in whole + own]
    assert len(products) == layers * matrices
    assert all(sum(name == n for name, _ in named) == layers for n in whole)
    assert all(aval.dtype == cfg.compute_dtype for aval in products)
    assert all(aval.dtype == jnp.int32 for name, aval in named
               if name in whole)
    kept = [(name, aval) for name, aval in named if name in names]
    assert mla_moe.kept_grid(cfg, *tokens.shape) == {
        "kept_names": len(names),
        "expert_products_kept": (len(products) if names else 0),
        "kept_bytes": sum(a.size * a.dtype.itemsize for _, a in kept)}
