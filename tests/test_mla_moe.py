"""The MLA / routed-experts / prediction-module decoder
(``models/mla_moe.py``, ``parallel/moe.held_expert_layer``) against its
plain reference (``benchmark/reference/mla_moe.py``) at small sizes, with
the widths in the published model's ratios (head = nope + rope = value
width, rope a quarter of it, an expert 3/4 of the hidden width) and
float32 operands, where program and reference must agree to rounding."""

import gc
import hashlib
import inspect
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
from benchmark.reference import mla_moe as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import mla_moe
from multiverso_tpu.parallel import moe

CFG = mla_moe.MLAMoEConfig(
    vocab=96, dim=64, n_heads=2, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_dim=6, qk_rope_dim=2, v_head_dim=8, dense_ffn=320,
    n_dense_layers=1, n_moe_layers=2, moe_ffn=48, n_experts=16,
    experts_held=4, expert_offset=4, top_k=4, n_mtp=1, attn="xla",
    loss_chunk=32, compute_dtype=jnp.float32)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    """This file compiles some thirty models. Left in jit's caches they
    make every later collection in this worker slower, and the timing
    tests that may follow in it (``tests/test_profiler.py``) count a
    collection inside a 5 ms step as time nobody accounts for."""
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.eps, first_k_dense_replace=cfg.n_dense_layers,
        num_hidden_layers=cfg.n_dense_layers + cfg.n_moe_layers,
        moe_intermediate_size=cfg.moe_ffn, n_routed_experts=cfg.experts_held,
        num_experts_per_tok=cfg.top_k, routed_scaling_factor=cfg.routed_scale,
        num_nextn_predict_layers=cfg.n_mtp, mtp_loss_weight=cfg.mtp_weight,
        expert_offset=cfg.expert_offset)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1)
    bias = 0.02 * jax.random.normal(jax.random.key(seed + 1),
                                    mla_moe.init_bias(cfg).shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, bias, tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


@pytest.mark.parametrize("kind", ["dense", "expert"])
def test_block_matches_the_reference(kind):
    params, bias, _ = _inputs(CFG)
    c = _ref_config(CFG)
    x = jax.random.normal(jax.random.key(9), (2, 32, CFG.dim))
    name = "L0" if kind == "dense" else "L1"
    p = mla_moe._sub(params, name)
    layer = CFG.layers()[0 if kind == "dense" else 1]
    assert layer.name == name
    got, aux = mla_moe._run_block(x, p, layer,
                                  None if kind == "dense" else bias[0], CFG)
    if kind == "dense":
        ffn = lambda u, q: (ref.mlp(u, q["wg"], q["wu"], q["wd"]), None)
    else:
        ffn = lambda u, q: ref.expert_layer(u, q, bias[0], c,
                                            CFG.expert_offset,
                                            CFG.experts_held)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.block(x[i], p, ffn, c)[0] for i in range(2)])
    assert _close(got, want)
    if kind == "expert":
        counts, overflow, _ = aux
        assert int(counts.sum()) == 2 * 32 * CFG.top_k and int(overflow) == 0


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_and_every_gradient_match_the_reference(attn, kernel):
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=16)
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
    bad = [n for n in grads if not _close(grads[n], want[n])]
    assert not bad, bad


def _plain_ce(h, head, targets, weights, cfg):
    """The unchunked cross-entropy, left to autodiff."""
    logits = mla_moe.matmul(h, head, True, cfg.compute_dtype, jnp.float32)
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.sum(weights * (jax.nn.logsumexp(logits, -1) - at))


def _checkpointed_ce(h, head, targets, weights, cfg):
    """``_chunked_ce`` as the tree had it before its gradients were made
    in the forward pass: a chunk under ``jax.checkpoint``, its logits made
    again in the backward pass."""
    n, d = h.shape
    chunk = min(cfg.loss_chunk, n)
    one = jax.checkpoint(lambda head, hc, tc, wc: _plain_ce(hc, head, tc, wc,
                                                            cfg))
    xs = (h.reshape(n // chunk, chunk, d), targets.reshape(-1, chunk),
          weights.reshape(-1, chunk))
    return jax.lax.scan(lambda total, x: (total + one(head, *x), None),
                        jnp.zeros((), jnp.float32), xs)[0]


def _head_inputs(n, zeros, seed=11):
    k = jax.random.split(jax.random.key(seed), 4)
    h = jax.random.normal(k[0], (n, CFG.dim))
    head = 0.3 * jax.random.normal(k[1], (CFG.vocab, CFG.dim))
    targets = jax.random.randint(k[2], (n,), 0, CFG.vocab)
    weights = jnp.ones((n,))
    if zeros:
        weights = (jax.random.uniform(k[3], (n,)) < 0.7).astype(jnp.float32)
    return h, head, targets, weights


@pytest.mark.parametrize("scale", [1.0, CFG.mtp_weight])
@pytest.mark.parametrize("zeros", [False, True])
@pytest.mark.parametrize("chunk", [32, 128])
def test_chunked_loss_and_its_gradients_are_plain_autodiffs(chunk, zeros,
                                                            scale):
    """Four chunks and one, every position weighted and some not, under a
    cotangent of 1 and of the module's weight: value and the gradients to
    the hidden state, the head and the weights."""
    cfg = CFG._replace(loss_chunk=chunk)
    h, head, targets, weights = _head_inputs(128, zeros)
    both = lambda ce: jax.jit(jax.value_and_grad(
        lambda h, head, w: scale * ce(h, head, targets, w, cfg),
        (0, 1, 2)))(h, head, weights)
    got, want = both(mla_moe._chunked_ce), both(_plain_ce)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _close(g, w, 1e-5)
    # a caller that takes no gradient runs the loss alone
    assert _close(jax.jit(lambda: mla_moe._chunked_ce(
        h, head, targets, weights, cfg))(), want[0] / scale, 1e-5)


@pytest.mark.parametrize("scale", [1.0, CFG.mtp_weight])
@pytest.mark.parametrize("chunk", [32, 128])
def test_chunked_loss_in_bfloat16_gives_the_checkpointed_losss_gradients(
        chunk, scale):
    """With the normaliser in the weights the gradients are cast to
    bfloat16 where the checkpointed chunk's were: they lie within one
    bfloat16 unit of the largest value."""
    cfg = CFG._replace(loss_chunk=chunk, compute_dtype=jnp.bfloat16)
    h, head, targets, weights = _head_inputs(128, zeros=True)
    norm = scale / float(weights.sum())
    got = jax.jit(jax.grad(lambda h, head: mla_moe._chunked_ce(
        h, head, targets, norm * weights, cfg), (0, 1)))(h, head)
    want = jax.jit(jax.grad(lambda h, head: norm * _checkpointed_ce(
        h, head, targets, weights, cfg), (0, 1)))(h, head)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(g - w))) <= 2.0 ** -8 * float(
            jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("n_mtp", [0, 1])
def test_loss_fn_is_the_plain_losses_mean(n_mtp, monkeypatch):
    """Through ``loss_fn`` with and without the prediction module: the
    loss and every gradient are those of the plain cross-entropy in the
    chunked loss's place."""
    cfg = CFG._replace(n_mtp=n_mtp)
    params, bias, tokens = _inputs(cfg)
    both = lambda: jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params)
    got = both()
    monkeypatch.setattr(mla_moe, "_chunked_ce", _plain_ce)
    want = both()
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * float(want[0])
    bad = [n for n in want[1] if not _close(got[1][n], want[1][n], 1e-5)]
    assert not bad, bad


def _vocabulary_products(fn, *args) -> int:
    """The ``dot_general`` operations of ``fn``'s lowering with the
    vocabulary among an operand's or the result's dimensions (a scan's
    body is lowered once)."""
    text = jax.jit(fn).lower(*args).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots
    return sum(bool(re.search(rf"[<x]{CFG.vocab}x", line.split(" : ")[-1]))
               for line in dots)


@pytest.mark.parametrize("n_mtp", [0, 1])
def test_a_loss_is_three_products_of_positions_x_vocabulary(n_mtp):
    """One logits product a loss in the forward pass, and under a gradient
    the two gradients' products beside it: nothing is made again."""
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(n_mtp=n_mtp, n_moe_layers=1)
    assert CFG.vocab not in (cfg.dim, 2 * cfg.dim, cfg.dense_ffn, cfg.moe_ffn)
    params, bias, tokens = _inputs(cfg)
    losses = 1 + n_mtp
    assert _vocabulary_products(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]), params
                                ) == 3 * losses
    assert mla_moe.loss_grid(cfg, tokens.size) == {
        "head_products": 3 * losses, "loss_chunks": 128 // cfg.loss_chunk}
    tables = mla_moe.make_tables(cfg, 0, 0.1, updater="adam")
    states = {n: t.program_state() for n, t in tables.items()}
    assert _vocabulary_products(mla_moe.make_forward(cfg), states, bias,
                                tokens) == losses
    assert _vocabulary_products(mla_moe.make_train_step(cfg, tables), states,
                                bias, tokens) == 3 * losses


def test_lean_reference_is_the_plain_reference():
    """The memory-saving form the chip's check uses gives the same
    numbers."""
    params, bias, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c))(params)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, bias, tokens, c,
                                                lean=True))(params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[3][n], plain[3][n]) for n in plain[3])


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of the routed part (the program's layer, told
    which four experts it holds), with the shared expert counted once,
    are the reference's uncut layer over all sixteen experts."""
    cfg = CFG._replace(experts_held=4)
    c = dict(_ref_config(cfg), n_routed_experts=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 8)
    d, f, e = cfg.dim, cfg.moe_ffn, cfg.n_experts
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "sg": 0.1 * jax.random.normal(rng[1], (d, f)),
             "su": 0.1 * jax.random.normal(rng[2], (d, f)),
             "sd": 0.1 * jax.random.normal(rng[3], (f, d)),
             "eg": 0.1 * jax.random.normal(rng[4], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[5], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[6], (e, f, d))}
    u = jax.random.normal(rng[7], (2, 48, d))
    bias = jnp.linspace(-0.05, 0.05, e)
    total = None
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
        out, (counts, overflow, _) = mla_moe.expert_ffn(
            u, share, bias, cfg._replace(expert_offset=offset))
        shared = mla_moe.gated_mlp(u, share["sg"], share["su"], share["sd"],
                                   cfg)
        routed = out - shared
        total = shared + routed if total is None else total + routed
        assert int(overflow) == 0
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(u[i], whole, bias, c, 0, e)[0]
                          for i in range(2)])
    assert _close(total, want)


@pytest.mark.parametrize("force", ["all_to_one_held", "none_to_held"])
def test_a_forced_router_drops_nothing_and_makes_no_nan(force):
    params, _, _ = _inputs(CFG)
    p = mla_moe._sub(params, "L1")
    u = jax.random.normal(jax.random.key(5), (2, 32, CFG.dim))
    lo, held = CFG.expert_offset, CFG.experts_held
    bias = np.zeros(CFG.n_experts, np.float32)
    bias[lo:lo + held] = -10.0
    if force == "all_to_one_held":
        bias[lo + 1] = 10.0
    bias = jnp.asarray(bias)
    got, (counts, overflow, _) = mla_moe.expert_ffn(u, p, bias, CFG)
    c = _ref_config(CFG)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(u[i], p, bias, c, lo, held)[0]
                          for i in range(2)])
    assert bool(jnp.all(jnp.isfinite(got))) and _close(got, want)
    here = np.asarray(counts)[lo:lo + held]
    assert int(overflow) == 0
    assert here.tolist() == ([0, 64, 0, 0] if force == "all_to_one_held"
                             else [0, 0, 0, 0])
    grads = jax.grad(lambda q: mla_moe.expert_ffn(u, q, bias, CFG)[0].sum())(p)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads.values())


def test_a_buffer_sized_under_the_load_counts_what_it_leaves_out():
    cfg = moe.HeldExperts(num_experts=8, experts_held=2, top_k=2,
                          buffer_rows=128, dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(0), (256, 32))
    p = {"router": jnp.zeros((8, 32)),
         "w_gate": jnp.ones((2, 32, 16)), "w_up": jnp.ones((2, 32, 16)),
         "w_down": jnp.ones((2, 16, 32))}
    bias = jnp.asarray([1.0, 1.0, 0, 0, 0, 0, 0, 0])     # all 512 rows here
    _, counts, overflow, _ = moe.held_expert_layer(u, p, bias, cfg)
    assert counts.tolist()[:2] == [256, 256] and int(overflow) == 512 - 128
    _, _, none, _ = moe.held_expert_layer(u, p, bias,
                                          cfg._replace(buffer_rows=None))
    assert int(none) == 0


def test_bias_rule_is_its_numpy_statement():
    counts = np.asarray([[5, 1, 3, 3], [0, 0, 12, 0]])
    bias = np.asarray([[0.1, -0.1, 0.0, 0.2], [0.0, 0.0, 0.0, 0.0]],
                      np.float32)
    got = moe.bias_update(jnp.asarray(bias), jnp.asarray(counts), 0.01)
    np.testing.assert_allclose(np.asarray(got),
                               ref.bias_rule(bias, counts, 0.01), atol=1e-7)
    # at the mean the bias stays
    assert np.asarray(got)[0, 2] == 0.0 and np.asarray(got)[0, 3] == 0.2


def test_calibration_brings_a_skewed_router_under_1_15():
    """The benchmark's calibration (forward-only passes, the bias rule at
    a speed that shrinks to the published one) on a router whose rows
    share a direction with every hidden state."""
    from benchmark.drivers import lm_train

    cfg = CFG._replace(n_experts=8, experts_held=2, expert_offset=0, top_k=2,
                       n_moe_layers=1, n_mtp=0, vocab=512, loss_chunk=1024)
    params = mla_moe.init(cfg, 1, 0.1)
    skew = jnp.linspace(-0.5, 0.5, 8)[:, None] * jnp.ones((1, cfg.dim))
    params["L1.router"] = 0.05 * params["L1.router"] + skew * jnp.mean(
        params["embed"], 0, keepdims=True) * 40
    shapes = mla_moe.param_shapes(cfg)
    pool = jax.random.randint(jax.random.key(2), (4, 2, 1024), 0, cfg.vocab)

    class Cell:
        traffic = {"calibration": {
            "start_speed": 0.064, "shrink": 0.5, "passes_per_speed": 6,
            "max_passes": 120, "load_max_over_mean": 1.15,
            "held_share_within": 1.0}}

    class Trainer:
        states = {n: {"data": jnp.pad(
            v.reshape(mla_moe.table_shape(shapes[n])),
            [(0, 1)] + [(0, 0)] * (len(mla_moe.table_shape(shapes[n])) - 1))}
                  for n, v in params.items()}
        bias = mla_moe.init_bias(cfg)

    state = {"cfg": cfg, "cell": Cell, "trainer": Trainer, "pool": pool,
             "forward": jax.jit(mla_moe.make_forward(cfg))}
    _, before = state["forward"](Trainer.states, Trainer.bias, pool[0])
    assert lm_train._layer_readings(
        np.asarray(before), cfg)["max_over_mean"].max() > 1.5
    result = lm_train._calibrate(state)
    assert result["balanced"] and max(
        result["last_turn_max_over_mean"]) <= 1.15
    assert abs(result["last_turn_held_share"][0] - 25.0) <= 1.0


def _source_digest(*functions) -> str:
    return hashlib.sha256("".join(
        inspect.getsource(f) for f in functions).encode()).hexdigest()[:16]


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """Every parameter lies in a table under Adam; a step moves each by
    NumPy's Adam on the reference's gradient, leaves ``t`` at 1, and
    leaves ``transformer.py``'s capacity-dropping layer as it was."""
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:2]), ("mv",)))
    capacity = moe.MoEConfig(num_experts=4, dim=16, hidden=32, axis="mv",
                             top_k=2)
    cap_params = moe.init_experts(capacity, seed=3)
    x = jax.random.normal(jax.random.key(4), (2, 16, 16))
    cap_before = moe.moe_layer(x, cap_params, capacity)[0]

    # one layer of each kind (dense, expert, the module): every kind of
    # parameter, two routers
    cfg = CFG._replace(n_moe_layers=1)
    params, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps))
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    assert all(type(t.updater) is updaters.AdamUpdater
               for t in tables.values())
    for n, t in tables.items():       # the tables hold init()'s values
        np.testing.assert_allclose(       # (a jitted draw rounds once more)
            t.get().reshape(params[n].shape), np.asarray(params[n]),
            rtol=1e-6, atol=1e-8)
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr), bias=bias)
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
        # a step of Adam is lr where the gradient is well over eps; where
        # it is near rounding, sign and size hang on the last bit
        sure = np.abs(np.asarray(grads[n])) > 1e-4 * np.abs(
            np.asarray(grads[n])).max()
        np.testing.assert_allclose(moved[sure], (want - params[n])[sure],
                                   atol=2e-2 * lr, err_msg=n)
        assert int(trainer.states[n]["ustate"]["t"]) == 1
    np.testing.assert_allclose(
        np.asarray(trainer.bias),
        ref.bias_rule(np.asarray(bias), np.asarray(want_counts),
                      cfg.bias_speed), atol=1e-7)
    cap_after = moe.moe_layer(x, cap_params, capacity)[0]
    np.testing.assert_array_equal(np.asarray(cap_before),
                                  np.asarray(cap_after))
    # ... and its code is the parent commit's, letter for letter
    assert _source_digest(moe.top_k_gates, moe._route, moe._local_moe,
                          moe.moe_layer) == CAPACITY_PATH_DIGEST


def test_a_step_ahead_reads_back_the_step_before_it():
    """Queueing the next step before reading the last one's loss trains
    the same steps: same losses, same counts, one read-back a step."""
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(n_moe_layers=1, n_mtp=0)     # the order is the host's
    batches = [_inputs(cfg, seed)[2] for seed in (0, 3, 6)]

    def run(ahead):
        trainer = mla_moe.Trainer(
            cfg, mla_moe.make_tables(cfg, 0, 0.1, updater="adam"),
            updaters.AddOption(learning_rate=1e-3))
        if not ahead:
            out = [trainer.step(b) for b in batches]
        else:
            out = [trainer.step_ahead(b) for b in batches]
            assert out[0] is None
            with pytest.raises(RuntimeError):
                trainer.step(batches[0])
            out = out[1:] + [trainer.drain()]
            assert trainer.drain() is None
        trainer.adopt()
        return out

    for (loss_a, counts_a), (loss_b, counts_b) in zip(run(False), run(True)):
        assert loss_a == loss_b
        np.testing.assert_array_equal(counts_a, counts_b)


CAPACITY_PATH_DIGEST = "8fe7ca0a5dbeb84c"


def test_tables_are_one_a_parameter_with_token_rows_for_the_vocabulary():
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    tables = mla_moe.make_tables(CFG, 0, 0.1, updater="adam")
    from multiverso_tpu.tables.array_table import ArrayTable
    from multiverso_tpu.tables.matrix_table import MatrixTable
    assert isinstance(tables["embed"], MatrixTable)
    assert tables["embed"].shape == tables["head"].shape == (CFG.vocab,
                                                             CFG.dim)
    assert isinstance(tables["L0.attn_norm"], ArrayTable)
    # the held experts' stack lies as rows of one matrix
    assert tables["L1.eg"].shape == (CFG.experts_held * CFG.dim, CFG.moe_ffn)
    assert tables["L1.router"].shape == (CFG.n_experts, CFG.dim)
    n = sum(int(np.prod(s)) for s in mla_moe.param_shapes(CFG).values())
    assert sum(int(np.prod(t.shape)) for t in tables.values()) == n


def test_published_sizes_give_the_issues_parameter_count():
    cfg = mla_moe.MLAMoEConfig(
        vocab=19360, dim=2048, n_heads=20, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256, dense_ffn=10240,
        n_dense_layers=1, n_moe_layers=4, moe_ffn=1536, n_experts=64,
        experts_held=8)
    shapes = mla_moe.param_shapes(cfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert round(total / 1e6, 1) == 706.5 and len(shapes) == 99
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("L1."))
    assert round(layer / 1e6, 2) == 106.83
