"""The looped decoder (``models/ouro.py``'s configuration on
``models/mla_moe.py``'s one decoder path: a stack of sandwich-normed dense
blocks run several times with the same tables as ONE loop of the program,
an exit gate after every pass, the expected cross-entropy over the exits
less an entropy term) against its plain reference
(``benchmark/reference/ouro.py``) at small sizes with float32 operands,
where program and reference must agree to rounding; a shared table's
gradient as the sum of its uses; the chunked loss's gradient to trained
weights; the step through the tables without a router."""

import gc
import hashlib
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
from benchmark.reference import ouro as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import mla_moe, ouro, qwen3_next, xing4

CFG = ouro.OuroConfig(
    vocab=96, dim=64, n_heads=4, n_kv_heads=4, head_dim=16, n_layers=2,
    passes=4, exit_coef=0.05, rope_theta=1e4, dense_ffn=96, attn="xla",
    loss_chunk=64, compute_dtype=jnp.float32)
LAYER_TABLES = sorted(n for n in mla_moe.param_shapes(CFG)
                      if n.startswith("L"))
TABLES = sorted(mla_moe.param_shapes(CFG))
EXITS = ("loss", "p_mean", "entropy", "p")


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
        intermediate_size=cfg.dense_ffn, num_hidden_layers=cfg.n_layers,
        total_ut_steps=cfg.passes, rms_norm_eps=cfg.eps,
        rope_theta=cfg.rope_theta,
        exit_entropy_coef=getattr(cfg, "exit_coef", 0.0))


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, scales={"embed": 1.0, "b": 0.5})
    # gains away from one, so that a gain's gradient is no symmetric case
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm"))):
        params[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(100 + i), params[name].shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


@pytest.fixture(scope="module")
def both():
    """(program, reference): each (loss, exits, gradients) on one batch."""
    params, tokens = _inputs(CFG)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, mla_moe.init_bias(CFG), tokens, CFG),
        has_aux=True))(params)
    assert [a.shape for a in aux[:3]] == [(0, 0), (0,), (0,)]
    want = jax.jit(lambda p: ref.loss_and_grads(
        p, tokens, _ref_config(CFG)))(params)
    return (loss, aux[3], grads), want


def test_the_layer_list_and_the_shapes_are_data():
    assert CFG.layers() == (mla_moe.Layer("L0", "full", "dense"),
                            mla_moe.Layer("L1", "full", "dense"))
    assert mla_moe.expert_layers(CFG) == () and mla_moe.passes_of(CFG) == 4
    assert {n.split(".")[1] for n in LAYER_TABLES} == {
        "attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm", "wq",
        "wk", "wv", "wo", "wg", "wu", "wd"}
    shapes = mla_moe.param_shapes(CFG)
    # every layer's tables ONCE, whatever the passes; the gate's two
    assert len(shapes) == 2 * 11 + 5
    assert (shapes["exit.w"], shapes["exit.b"]) == ((64,), (1,))
    assert mla_moe.init_bias(CFG).shape == (0, 0)
    assert mla_moe.kept_names(CFG) == ()


def test_the_loss_matches_the_reference(both):
    (loss, _, _), (want, _, _) = both
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)


@pytest.mark.parametrize("what", EXITS)
def test_the_exits_match_the_reference(both, what):
    (_, exits, _), (_, want, _) = both
    assert exits[what].shape == want[what].shape
    assert _close(exits[what], want[what], 1e-5), what


@pytest.mark.parametrize("name", TABLES)
def test_every_gradient_matches_the_reference(both, name):
    (_, _, grads), (_, _, want) = both
    assert float(jnp.max(jnp.abs(want[name]))) > 0
    assert _close(grads[name], want[name], 5e-5), name


@pytest.fixture(scope="module")
def untied():
    """The program's gradients beside those of the reference given a copy
    of every layer's parameters for every pass."""
    params, tokens = _inputs(CFG, seed=3)
    grads = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(
        p, mla_moe.init_bias(CFG), tokens, CFG)[0]))(params)
    copies = {n: v for n, v in params.items() if not n.startswith("L")}
    copies.update({f"P{t}.{n}": v for t in range(CFG.passes)
                   for n, v in params.items() if n.startswith("L")})
    each = jax.jit(jax.grad(lambda p: ref.loss(
        p, tokens, _ref_config(CFG))[0]))(copies)
    return grads, each


@pytest.mark.parametrize("name", LAYER_TABLES)
def test_a_shared_tables_gradient_is_the_sum_of_its_uses(untied, name):
    grads, each = untied
    uses = [each[f"P{t}.{name}"] for t in range(CFG.passes)]
    assert all(float(jnp.max(jnp.abs(u))) > 0 for u in uses)
    # no one use is the whole of it
    assert not _close(grads[name], uses[-1], 1e-2)
    assert _close(grads[name], sum(uses), 5e-5), name


@pytest.mark.parametrize("how", ref.CONTROLS)
def test_a_faulty_loop_is_told_apart(both, how):
    _, (want, want_exits, want_grads) = both
    params, tokens = _inputs(CFG)
    with ref.loop_control(how):
        loss, exits, grads = jax.jit(lambda p: ref.loss_and_grads(
            p, tokens, _ref_config(CFG)))(params)
    far = lambda n: not _close(grads[n], want_grads[n], 1e-2)
    if how == "untrained_weights":      # the forward pass is the model's
        assert abs(float(loss) - float(want)) < 1e-6
        assert far("exit.w") and far("exit.b") and far("L0.wq")
    else:
        assert abs(float(loss) - float(want)) > 1e-4
        assert far("exit.w") and far("L1.wd")
    assert exits["p"].shape[0] == CFG.passes - (how == "one_pass_less")
    with pytest.raises(ValueError):
        with ref.loop_control("no_such_fault"):
            pass


@pytest.mark.parametrize("what", ["value", "gradients", "text"])
def test_one_pass_without_an_exit_term_is_the_decoder_it_was(what):
    """``passes = 1``: no gate, no loop, ``loss_fn``'s one loss a position
    through the path every older configuration takes."""
    cfg = CFG._replace(passes=1)
    assert "exit.w" not in mla_moe.param_shapes(cfg)
    assert mla_moe.loop_grid(cfg) == {} and mla_moe.loss_grid(cfg, 128) == {
        "head_products": 3, "loss_chunks": 2}
    params, tokens = _inputs(cfg)
    c = _ref_config(cfg)

    def plain(p):
        with jax.default_matmul_precision("highest"):
            total = 0.0
            for seq in tokens:
                x, = ref.exit_states(p, seq, c)
                total += jnp.sum(ref._ce_each(
                    x, p["head"], jnp.roll(seq, -1), False)[:-1])
            return total / (tokens.shape[0] * (tokens.shape[1] - 1))

    fn = lambda p: mla_moe.loss_fn(p, mla_moe.init_bias(cfg), tokens, cfg)
    if what == "text":
        text = jax.jit(jax.grad(lambda p: fn(p)[0])).lower(params).as_text(
            debug_info=True)
        assert "mv.lm.loop" not in text and "mv.lm.head" in text
        return
    (loss, aux), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(params)
    want, want_grads = jax.jit(jax.value_and_grad(plain))(params)
    assert len(aux) == 3            # counts of no rows, and no exits
    if what == "value":
        assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    else:
        assert set(grads) == set(want_grads)
        for n in grads:
            assert _close(grads[n], want_grads[n], 5e-5), n


@pytest.mark.parametrize("passes", [2, 4, 7])
def test_the_exit_distribution_sums_to_one_and_the_last_takes_the_rest(
        passes):
    exits = jax.random.normal(jax.random.key(passes), (passes, 3, 5, 16))
    w = jax.random.normal(jax.random.key(1), (16,))
    p, entropy = mla_moe.exit_distribution(exits, w, jnp.asarray([0.3]))
    assert p.shape == (passes, 3, 5) and entropy.shape == (3, 5)
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(exits[:-1] @ w + 0.3)
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(lam[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p[-1]),
                               np.asarray(jnp.prod(1 - lam, 0)), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(ref.exit_distribution(
            exits.reshape(passes, 15, 16), w, 0.3)).reshape(p.shape),
        rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(entropy),
                               np.asarray(-jnp.sum(p * jnp.log(p), 0)),
                               rtol=1e-4)
    assert float(entropy.max()) <= np.log(passes) + 1e-6
    # a gate that never fires leaves everything to the last pass
    late, none = mla_moe.exit_distribution(exits, 0 * w, jnp.asarray([-40.0]))
    np.testing.assert_allclose(np.asarray(late[-1]), 1.0, atol=1e-6)
    assert float(jnp.abs(none).max()) < 1e-6


@pytest.mark.parametrize("coef", [0.0, 0.05])
def test_the_entropy_term_alone_moves_the_gate_where_the_losses_are_equal(
        coef):
    """A head of zeros gives every exit the loss ``ln V`` at every
    position: ``sum_t p_t l_t`` is ``ln V`` whatever ``p``, and what is
    left to move the gate is ``-coef`` times the entropy's gradient."""
    cfg = CFG._replace(exit_coef=coef)
    params, tokens = _inputs(cfg)
    params["head"] = jnp.zeros_like(params["head"])
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, mla_moe.init_bias(cfg), tokens, cfg),
        has_aux=True))(params)
    np.testing.assert_allclose(np.asarray(aux[3]["loss"]),
                               np.log(cfg.vocab), rtol=1e-6)
    assert abs(float(loss) - (np.log(cfg.vocab) - coef * float(
        aux[3]["entropy"]))) < 1e-5

    def entropy_alone(gate):
        exits = mla_moe._passes(
            mla_moe._embed(params, tokens, cfg), params, cfg)
        _, h = mla_moe.exit_distribution(exits, gate["exit.w"],
                                         gate["exit.b"])
        return jnp.mean(h[:, :-1])

    gate = {n: params[n] for n in ("exit.w", "exit.b")}
    want = jax.jit(jax.grad(entropy_alone))(gate)
    for n in gate:
        if coef:
            assert float(jnp.abs(want[n]).max()) > 1e-4
            assert _close(grads[n], -coef * want[n], 1e-4), n
        else:
            assert float(jnp.abs(grads[n]).max()) < 1e-7, n


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("fn", ["_chunked_ce", "_chunked_ce_each"])
def test_the_chunked_loss_hands_trained_weights_their_gradient(fn, chunk):
    """Weights that are a function of a trained parameter: the chunked
    loss's gradient to them is each position's loss, and it reaches the
    parameter as the unchunked loss's does."""
    cfg = CFG._replace(loss_chunk=chunk)
    n, d, v = 64, 16, 40
    keys = jax.random.split(jax.random.key(7), 4)
    h = jax.random.normal(keys[0], (n, d))
    head = jax.random.normal(keys[1], (v, d))
    targets = jax.random.randint(keys[2], (n,), 0, v)
    theta = jax.random.normal(keys[3], (n,))
    weigh = lambda theta: jax.nn.sigmoid(theta) / n

    def chunked(h, head, theta):
        out = getattr(mla_moe, fn)(h, head, targets, weigh(theta), cfg)
        return out if fn == "_chunked_ce" else out[0]

    def whole(h, head, theta):
        logp = jax.nn.log_softmax(jnp.dot(
            h, head.T, precision=jax.lax.Precision.HIGHEST), -1)
        each = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return jnp.sum(weigh(theta) * each)

    got = jax.jit(jax.value_and_grad(chunked, (0, 1, 2)))(h, head, theta)
    want = jax.jit(jax.value_and_grad(whole, (0, 1, 2)))(h, head, theta)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * float(want[0])
    for g, w in zip(got[1], want[1]):
        assert float(jnp.abs(w).max()) > 0 and _close(g, w, 2e-5)
    if fn == "_chunked_ce_each":
        # each position's unweighted loss, with and without a gradient
        logp = jax.nn.log_softmax(h @ head.T, -1)
        each = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        bare = mla_moe._chunked_ce_each(h, head, targets, weigh(theta), cfg)
        assert _close(bare[1], each, 1e-4)
        assert abs(float(bare[0]) - float(got[0])) < 1e-6


def _cell_config(with_file=False):
    """The cell's configuration as its driver builds it (and the file's
    dictionary)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-pp6.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_loop

    class _Cell:
        config = c

    cfg = lm_train_loop._model_config(_Cell)
    return (cfg, c) if with_file else cfg


def test_published_sizes_give_the_configurations_parameter_count():
    cfg, c = _cell_config(with_file=True)
    shapes = mla_moe.param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    assert count(lambda n: n.startswith("L0.")) == 51_388_416
    assert count(lambda n: n in ("embed", "head")) == 201_326_592
    assert count(lambda n: n.startswith("exit.")) == 2_049
    assert count(lambda n: True) == 612_438_017 == c["parameters"]["total"]
    assert len(shapes) == c["parameters"]["tables"] == 8 * 11 + 5
    assert (cfg.passes, cfg.exit_coef, cfg.n_layers, cfg.kv_group) == (
        4, 0.05, 8, 1)
    assert shapes["L7.wg"] == (2048, 5632) and shapes["head"] == (49152, 2048)
    # four exits' positions walk the chunked loss as one
    assert mla_moe.loss_grid(cfg, 4096) == {
        "head_products": 12, "loss_chunks": 4 * 4096 // cfg.loss_chunk}
    assert mla_moe.loop_grid(cfg) == {
        "loop_passes": 4, "loop_layers": 8, "loop_block_runs": 32}


def _count(jaxpr, primitive: str) -> int:
    """The equations of that primitive in a jaxpr and in every jaxpr its
    equations hold (a scan's, a checkpoint's, a custom rule's)."""
    return sum((e.primitive.name == primitive)
               + sum(_count(sub, primitive)
                     for sub in jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


@pytest.mark.parametrize("passes", [2, 4])
def test_the_step_holds_one_pass_of_blocks_whatever_the_passes(passes):
    """The passes are ONE loop of the program: a layer's flash kernels
    stand in the step once (forward, forward made again, dQ, dK with dV),
    not once a pass, and so does every other line of a block."""
    cfg = CFG._replace(passes=passes, attn="flash", attn_block=16)
    params, tokens = _inputs(cfg)
    grad = jax.grad(lambda p: mla_moe.loss_fn(
        p, mla_moe.init_bias(cfg), tokens, cfg)[0])
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    assert _count(jaxpr, "pallas_call") == 4 * cfg.n_layers
    assert _count(jaxpr, "scan") >= 2       # the passes, forward and back
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for scope in ("mv.lm.loop", "mv.lm.loop.exit", "mv.lm.head",
                  "mv.lm.norm.final", "mv.lm.attn", "mv.lm.dense"):
        assert scope in text, scope
    # the lowered text grows by nothing a pass
    other = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(
        p, mla_moe.init_bias(cfg), tokens,
        cfg._replace(passes=passes + 1))[0])).lower(params).as_text()
    assert abs(len(other.splitlines())
               - len(jax.jit(grad).lower(params).as_text().splitlines())) < 40


def test_a_loop_takes_dense_two_branch_blocks_alone():
    class WithExperts(type(CFG)):
        def layers(self):
            return (mla_moe.Layer("L0", "full", "experts"),)

    with pytest.raises(ValueError, match="run several times"):
        mla_moe._passes(jnp.zeros((1, 8, 64)), {}, WithExperts(*CFG))


# The lowered text of the parent commit's ``loss_fn`` (StableHLO without
# locations, sha256's first 16 digits), made with ``git archive c945ec0``
# beside this tree, for the two kinds ``tests/test_qwen3_next.py``'s table
# has no entry for: the loop over the passes, the exit loss and a step
# without a router are paths of their own, and a tiny configuration of
# every older kind lowers to what it lowered to.
PARENT = {"qwen3_next": "271d1ded8f61ca32", "xing4": "93b2d91140a4e78e"}
MODELS = {"qwen3_next": qwen3_next.Qwen3NextConfig,
          "xing4": xing4.Xing4Config}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_two_more_older_models_lower_to_the_parents_text(name):
    cfg = MODELS[name](attn="xla")
    params = jax.eval_shape(lambda: mla_moe.init(cfg, 0))
    bias = jax.eval_shape(lambda: mla_moe.init_bias(cfg))
    text = jax.jit(jax.value_and_grad(
        lambda p, b, t: mla_moe.loss_fn(p, b, t, cfg), has_aux=True)).lower(
            params, bias, jnp.zeros((2, 64), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[name]


@pytest.fixture(scope="module")
def trained():
    """Two steps through the Adam tables, the second dispatched ahead:
    (what each call handed back, the first step's span, the trainer, the
    parameters before, the reference's gradients on the first batch)."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    try:
        cfg = CFG._replace(attn="flash", attn_block=16)
        scales = {"embed": 1.0, "b": 0.5}
        params = mla_moe.init(cfg, 0, 0.1, scales=scales)
        tables = mla_moe.make_tables(
            cfg, 0, 0.1, updater=updaters.AdamUpdater(
                beta1=0.9, beta2=0.95, eps=1e-8), scales=scales)
        _, tokens = _inputs(cfg)
        lr = 1e-3
        trainer = mla_moe.Trainer(cfg, tables,
                                  updaters.AddOption(learning_rate=lr))
        before = len(ttrace.events())
        first = trainer.step(tokens)
        moved = {n: np.asarray(st["data"][:mla_moe.table_shape(
            mla_moe.param_shapes(cfg)[n])[0]]) for n, st in
            trainer.states.items()}
        exits = trainer.exits
        none = trainer.step_ahead(tokens)
        second = trainer.drain()
        trainer.adopt()
        span = [e for e in ttrace.events()[before:]
                if e["name"] == "lm.step"][0]["args"]
        want = jax.jit(lambda p: ref.loss_and_grads(
            p, tokens, _ref_config(cfg)))(params)
        yield dict(cfg=cfg, first=first, none=none, second=second, span=span,
                   tables=tables, params=params, moved=moved, want=want,
                   exits=exits, lr=lr, trainer=trainer)
    finally:
        mv.shutdown()


@pytest.mark.parametrize("call", ["step", "step_ahead", "adopt"])
def test_a_configuration_without_experts_goes_through_the_trainer(trained,
                                                                  call):
    t = trained
    if call == "step":
        loss, counts = t["first"]
        assert counts.shape == (0, 1)
        assert abs(loss - float(t["want"][0])) < 1e-5 * loss
        assert mla_moe.routing_counts(counts, t["cfg"]) == {}
        assert t["trainer"].bias.shape == (0, 0)
    elif call == "step_ahead":
        assert t["none"] is None
        assert np.isfinite(t["second"][0]) and t["second"][0] < t["first"][0]
        assert t["trainer"].steps == 2
    else:
        assert set(t["tables"]) == set(mla_moe.param_shapes(t["cfg"]))
        for n, table in t["tables"].items():
            assert int(t["trainer"].states[n]["ustate"]["t"]) == 2
            assert np.isfinite(np.asarray(table.get())).all()


@pytest.mark.parametrize("name", ["L0.wq", "L1.wd", "L1.ffn_post_norm",
                                  "final_norm", "head", "embed", "exit.w",
                                  "exit.b"])
def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam(
        trained, name):
    """A layer's table takes ONE delta a step, the sum of its uses."""
    t = trained
    grad = np.asarray(t["want"][2][name])
    old = np.asarray(t["params"][name])
    want, _, _, _ = ref.adam_step(old, 0.0, 0.0, 0, grad, t["lr"], 0.9, 0.95,
                                  1e-8)
    moved = t["moved"][name].reshape(old.shape) - old
    sure = np.abs(grad) > 1e-4 * np.abs(grad).max()
    assert sure.any()
    np.testing.assert_allclose(moved[sure], (want - old)[sure],
                               atol=2e-2 * t["lr"], err_msg=name)


@pytest.mark.parametrize("fact", ["loop_passes", "loop_layers",
                                  "loop_block_runs", "exit_p", "exit_loss",
                                  "exit_entropy", "exit_expected_pass",
                                  "head_products", "heads_layers"])
def test_the_step_span_carries_the_loops_and_the_exits_facts(trained, fact):
    span, exits, (_, want, _) = (trained["span"], trained["exits"],
                                 trained["want"])
    p = np.asarray(want["p_mean"], np.float64)
    expected = {
        "loop_passes": 4, "loop_layers": 2, "loop_block_runs": 8,
        "exit_p": p, "exit_loss": np.asarray(want["loss"]),
        "exit_entropy": float(want["entropy"]),
        "exit_expected_pass": float(np.sum(p * np.arange(1, 5))),
        # three products an exit; every layer's operands made a pass
        "head_products": 12, "heads_layers": 8}[fact]
    np.testing.assert_allclose(np.asarray(span[fact]), expected, rtol=1e-4)
    assert "routed_rows" not in span and "aux_loss" not in span
    assert exits["p"].shape == (4, 2, 64)
    np.testing.assert_allclose(exits["p"], np.asarray(want["p"]), atol=2e-5)


def test_the_timeline_prints_the_loop():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import dump_metrics

    args = dict(mla_moe.loop_grid(CFG), exit_p=[0.4, 0.3, 0.2, 0.1],
                exit_loss=[6.0, 5.5, 5.25, 5.0], exit_entropy=1.25,
                exit_expected_pass=2.0)
    lines = dump_metrics._loop_lines([{"name": "lm.step", "args": args}])
    assert lines == [
        "  looped stack: 2 layers x 4 passes = 8 block runs a step (one "
        "loop in the program); over 1 steps the exits' mean loss 6.000 "
        "5.500 5.250 5.000, exit distribution 0.400 0.300 0.200 0.100 "
        "(expected pass 2.00), entropy 1.250 of ln 4 = 1.386"]
    assert dump_metrics._loop_lines([{"name": "lm.step", "args": {}}]) == []
    assert "mv.lm.loop.exit" in dump_metrics.__doc__
