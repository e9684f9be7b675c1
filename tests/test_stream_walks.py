"""The one differentiation rule of a hyper-connected sublayer
(``models/mla_moe._hyper``) and the walks of its backward pass
(``ops/stream_walks.py``): the kernels in the Pallas interpreter against the
plain forms, the rule's gradients against plain autodiff of the forward's
own lines (``_hyper.fun``: what every pass was before PR 62), with the plain
walks and with the kernels, and what the set-up budget rests on: one
equation a kernel whatever the sublayer, nothing traced twice, and a step
that is no longer than the parent's."""

import functools
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

from multiverso_tpu.models import mla_moe, xing4
from multiverso_tpu.ops import index_kernels
from multiverso_tpu.ops import stream_walks as sw

# 2 x 64 positions are a whole lane tile, so that the kernels take them
CFG = xing4.Xing4Config(
    vocab=96, dim=128, n_heads=2, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, dense_ffn=160,
    n_dense_layers=1, n_moe_layers=1, moe_ffn=32, n_experts=16,
    experts_held=4, expert_offset=4, top_k=4, n_mtp=0, attn="xla",
    expert_kernel="xla", loss_chunk=32, compute_dtype=jnp.float32)
SCALES = {"hc_phi": 1.0 / np.sqrt(CFG.streams * CFG.dim), "hc_b": 1.0,
          "router": 0.07}
TOL = 2e-5          # tests/test_xing4.py holds its block to the same


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def walks(request, monkeypatch):
    """The rule's walks as ``request.param`` says: ``plain`` (what a CPU
    runs anyway) or ``kernels``: the Pallas kernels, in the interpreter,
    wherever :func:`stream_walks.tiles_for` finds tiles."""
    if request.param == "kernels":
        monkeypatch.setattr(sw, "walk_tiles",
                            lambda t, n, c, *dtypes: sw.tiles_for(t, n, c))
        for name in ("gather", "dots", "spread"):
            monkeypatch.setattr(sw, name, functools.partial(
                getattr(sw, name), interpret=True))
    return request.param


def _autodiff(monkeypatch):
    """Plain autodiff of the forward's own lines in the rule's place."""
    monkeypatch.setattr(mla_moe, "_hyper", mla_moe._hyper.fun)


def _close(got, want, tol=TOL):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) / scale < tol


def _all_close(got, want, tol=TOL):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    return [i for i, (g, w) in enumerate(zip(got, want))
            if not _close(g, w, tol)]


# ---------------------------------------------------------------------- #
# the kernels against the plain forms
# ---------------------------------------------------------------------- #
def _operands(t=256, n=4, c=64, seed=0):
    k = jax.random.split(jax.random.key(seed), 10)
    outs = n * n + 2 * n
    return dict(
        g=jax.random.normal(k[0], (t, n, c)),
        x=jax.random.normal(k[1], (t, n, c)),
        y=jax.random.normal(k[2], (t, c)), du=jax.random.normal(k[3], (t, c)),
        post=jax.random.uniform(k[4], (n, t)),
        pre=jax.random.uniform(k[5], (n, t)),
        res=jax.random.uniform(k[6], (n, n, t)),
        a=jax.random.normal(k[7], (outs, t)), q=jax.random.normal(k[8], (t,)),
        phi=jax.random.normal(k[9], (outs, n * c)) / np.sqrt(n * c))


WALKS = {"gather": ("g", "x", "y", "post"), "dots": ("du", "x"),
         "spread": ("g", "x", "du", "phi", "pre", "res", "a", "q")}


SMALL = sw.Tiles(16, 32, 16)


@pytest.mark.parametrize("tiles", [SMALL, None],
                         ids=["several_steps", "one_step"])
@pytest.mark.parametrize("walk", list(WALKS))
def test_a_kernel_in_the_interpreter_is_the_plain_form(walk, tiles):
    ops = _operands()
    args = [ops[name] for name in WALKS[walk]]
    tiles = tiles or sw.tiles_for(256, 4, 64)
    assert tiles == SMALL or tiles == (64, 64, 64)
    got = getattr(sw, walk)(*args, tiles=tiles, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = getattr(sw, walk + "_plain")(*args)
    assert not _all_close(got, want, 2e-6)


def test_the_walks_choose_from_what_they_see():
    """No option: off a TPU the plain forms run; the tiles are the shapes'
    (the published cell's: a lane tile of positions a block, and 512
    channels beside them); positions that are no whole lanes,
    channels that are no whole sublane tiles and other types have none."""
    assert sw.walk_tiles(4096, 4, 3584, jnp.float32) is None    # a CPU
    assert sw.tiles_for(4096, 4, 3584) == sw.Tiles(512, 512, 512)
    assert sw.tiles_for(4096, 4, 3584 + 4) is None
    assert sw.tiles_for(4096 + 64, 4, 3584) is None
    assert sw.tiles_for(4096, 8, 3584) is None       # 153 maps a position
    counts = sw.step_counts(10, 4096, 4, 3584)
    assert counts == {"hc_kernel_sublayers": 0,
                      "hc_bwd_stream_bytes": 10 * 32 * 4 * 4096 * 3584}
    ops = _operands()
    got = sw.gather(ops["g"], ops["x"], ops["y"], ops["post"])
    want = sw.gather_plain(ops["g"], ops["x"], ops["y"], ops["post"])
    assert all(bool(jnp.all(a == b)) for a, b in zip(got, want))


# ---------------------------------------------------------------------- #
# the rule against plain autodiff
# ---------------------------------------------------------------------- #
def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, SCALES)
    bias = 0.02 * jax.random.normal(jax.random.key(seed + 1),
                                    mla_moe.init_bias(cfg).shape)
    x = jax.random.normal(jax.random.key(seed + 2),
                          (batch, positions, cfg.streams, cfg.dim))
    weight = jax.random.normal(jax.random.key(seed + 3), x.shape)
    return params, bias, x, weight


def _sublayer_loss(x, p, weight):
    """One sublayer round a branch with a table of its own and a second
    result that the loss reads: a float beside an integer."""
    def branch(u, q):
        y = jnp.tanh(u * q["gain"])
        return y, (jnp.mean(y * y), jnp.argmax(y, -1))

    out, (extra, _), _ = mla_moe.block(x, p, None, branch, CFG)
    return jnp.sum(weight * out) + 3.0 * extra


def _block_loss(layer, bias):
    def loss(x, p, weight):
        out, aux, _ = mla_moe._run_block(x, p, layer, bias, CFG)
        balance = 0.0 if aux is None else aux[2]
        return jnp.sum(weight * out) + 2.0 * balance
    return loss


def _cases():
    params, bias, x, weight = _inputs(CFG)
    gain = jax.random.normal(jax.random.key(7), (CFG.dim,))
    one = dict(mla_moe._sub(params, "L0"), gain=gain)
    one = {n: v for n, v in one.items()
           if n.startswith("ffn") or n == "gain"}
    dense, expert = CFG.layers()[:2]
    return {
        "one_sublayer": (_sublayer_loss, (x, one, weight)),
        "a_block_of_two": (_block_loss(dense, None),
                           (x, mla_moe._sub(params, "L0"), weight)),
        "an_expert_block_and_its_aux": (
            _block_loss(expert, bias[0]),
            (x, mla_moe._sub(params, "L1"), weight))}


@pytest.mark.parametrize("walks", ["plain", "kernels"], indirect=True)
@pytest.mark.parametrize("case", ["one_sublayer", "a_block_of_two",
                                  "an_expert_block_and_its_aux"])
def test_the_rules_gradients_are_plain_autodiffs(case, walks, monkeypatch):
    """The streams', every branch parameter's, ``phi``'s, ``b``'s and
    ``alpha``'s gradient under the rule against plain autodiff of today's
    ``stream_maps`` and the two mixes, in float32."""
    loss, args = _cases()[case]
    both = lambda: jax.jit(jax.value_and_grad(loss, (0, 1)))(*args)
    got = both()
    _autodiff(monkeypatch)
    with jax.default_matmul_precision("highest"):
        want = both()
    assert set(got[1][1]) == set(want[1][1])
    bad = [n for n in want[1][1]
           if not _close(got[1][1][n], want[1][1][n])]
    assert not bad, bad
    assert _close(got[0], want[0]) and _close(got[1][0], want[1][0])
    hc = [n for n in want[1][1] if ".hc_" in n]
    assert hc and all(float(jnp.abs(got[1][1][n]).max()) > 0 for n in hc)


@pytest.mark.parametrize("walks", ["plain", "kernels"], indirect=True)
def test_the_prediction_module_under_four_streams_still_trains(
        walks, monkeypatch):
    """The whole loss with the prediction module: every table's gradient
    under the rule against plain autodiff, and none of the module's own is
    zero."""
    cfg = CFG._replace(n_mtp=1)
    params = mla_moe.init(cfg, 0, 0.1, SCALES)
    bias = mla_moe.init_bias(cfg)
    tokens = jax.random.randint(jax.random.key(2), (2, 64), 0, cfg.vocab)
    grads = lambda: jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg),
        has_aux=True))(params)
    (loss, aux), got = grads()
    _autodiff(monkeypatch)
    (want_loss, want_aux), want = grads()
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(aux[0]), np.asarray(want_aux[0]))
    assert float(aux[3]) == float(want_aux[3]) > 0      # hc_res_error
    bad = [n for n in want if not _close(got[n], want[n], 5e-5)]
    assert not bad, bad
    module = [n for n in got if n.startswith("mtp.") and ".hc_" in n]
    assert len(module) == 6
    assert all(float(jnp.abs(got[n]).max()) > 0 for n in module)


def test_the_streams_gradient_is_float32_and_no_gradient_is_stopped():
    params, _, x, weight = _inputs(CFG)
    loss, args = _cases()["a_block_of_two"]
    dx, dp = jax.grad(loss, (0, 1))(*args)
    assert dx.dtype == jnp.float32 and dx.shape == x.shape
    assert all(v.dtype == jnp.float32 for v in dp.values())
    assert all(float(jnp.abs(v).max()) > 0 for v in dp.values())


# ---------------------------------------------------------------------- #
# what the set-up budget rests on
# ---------------------------------------------------------------------- #
def _equations(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    found = [] if found is None else found
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    _equations(sub, found)
    return found


def _step_jaxpr(cfg, positions=64):
    params = mla_moe.init(cfg, 0, 0.1)
    bias = mla_moe.init_bias(cfg)
    tokens = jnp.zeros((2, positions), jnp.int32)
    return jax.make_jaxpr(jax.grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params)


# the step at these shapes before the rule (820bc74, plain autodiff of the
# maps: 15,418) plus what PR 63 writes out between a projection and the
# core: ``mla_moe.heads`` / ``out_of_heads`` spell the products, the turn's
# tables and, OFF the chip, the pass's plain form (``head_turns.plain_fwd``
# / ``plain_bwd``: the where, the rolls and their slices, which the chip
# traces as ONE kernel call), 226 equations a latent layer over forward,
# forward made again and backward, 904 in these four layers; trace and
# lowering of this step read 2.8 + 0.8 s on both trees (CHANGES.md, PR 63)
PARENT_STEP_EQUATIONS = 15418 + 904


def test_the_four_stream_step_is_no_longer_than_the_parents():
    """The rule writes nothing out number by number: the gradient step of a
    tiny four-stream model with a prediction module has the parent's count
    of equations, within a twentieth (the rule itself was 3.9% over the
    plain maps: the branch's gradients are named for every table a block
    has; the count pinned now is that tree's with PR 63's layers)."""
    cfg = CFG._replace(dim=64, n_moe_layers=2, n_mtp=1)
    count = len(_equations(_step_jaxpr(cfg)))
    assert abs(count - PARENT_STEP_EQUATIONS) < PARENT_STEP_EQUATIONS // 20, \
        count


@pytest.mark.parametrize("walks", ["kernels"], indirect=True)
def test_every_sublayer_binds_one_equation_a_kernel(walks):
    """What jax's lowering cache compares: in the step's jaxpr all
    sublayers' ``pallas_call`` equations of one kernel, attention's and the
    feed-forward's alike, carry EQUAL parameters (the one traced jaxpr, the
    same name, grid and specs), so a module lowers a kernel once; and a
    second trace of the step traces no kernel again."""
    cfg = CFG._replace(n_mtp=1)
    calls = {}
    for eqn in _equations(_step_jaxpr(cfg)):
        if eqn.primitive.name == "pallas_call":
            calls.setdefault(eqn.params["name"], []).append(eqn.params)
    sublayers = mla_moe.stream_grid(cfg, 2, 64)["hc_sublayers"]
    assert sublayers == 6 and set(calls) == {sw.GATHER, sw.DOTS, sw.SPREAD}
    for name, found in calls.items():
        assert len(found) == sublayers, name
        assert all(params == found[0] for params in found), name
    traced = len(index_kernels._TRACED)
    _step_jaxpr(cfg)
    assert len(index_kernels._TRACED) == traced


# the parent's rematerialised GLM blocks, forward and every gradient (428
# and 1,160 before PR 63, whose ``heads`` / ``out_of_heads`` write 52 more
# a latent layer: see PARENT_STEP_EQUATIONS)
PARENT_GLM_EQUATIONS = {"dense": 428 + 52, "shared+experts": 1160 + 52}


@pytest.mark.parametrize("kind", list(PARENT_GLM_EQUATIONS))
def test_a_one_stream_block_traces_what_it_traced(kind):
    """``streams == 1`` never reaches the rule: a GLM block's jaxpr has the
    parent's count of equations, forward and every gradient."""
    glm = mla_moe.MLAMoEConfig(attn="xla", expert_kernel="xla",
                               compute_dtype=jnp.float32)
    layer = next(l for l in glm.layers() if l.ffn == kind)
    params = mla_moe.init(glm, 0, 0.1)
    bias = None if kind == "dense" else mla_moe.init_bias(glm)[0]
    x = jnp.zeros((1, 16, glm.dim))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, p: jnp.sum(mla_moe._run_block(x, p, layer, bias, glm)[0]),
        (0, 1)))(x, mla_moe._sub(params, layer.name))
    assert len(_equations(jaxpr)) == PARENT_GLM_EQUATIONS[kind]


def test_the_backward_pass_is_scoped_for_the_benchmarks_join():
    """The walks lie under ``mv.lm.hc.bwd`` and Sinkhorn's backward pass
    under ``mv.lm.hc.sinkhorn``, in the backward pass's part of the lowered
    step: ``benchmark/layers/hc.scopes_in`` sums every scope that starts
    ``mv.lm.hc.``."""
    params, _, x, weight = _inputs(CFG)
    loss, args = _cases()["a_block_of_two"]
    text = jax.jit(jax.grad(loss, (0, 1))).lower(*args).as_text(
        debug_info=True)
    assert "mv.lm.hc.bwd" in text
    bwd = [line for line in text.splitlines() if "mv.lm.hc.bwd" in line]
    assert bwd and all("transpose(" in line for line in bwd)
    assert any("transpose(" in line and "mv.lm.hc.sinkhorn" in line
               for line in text.splitlines())
