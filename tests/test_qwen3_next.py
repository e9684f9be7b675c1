"""The delta-rule decoder (``models/qwen3_next.py``'s configuration and
linear-attention mixer over ``ops/delta_rule.py``'s chunked rule, on
``models/mla_moe.py``'s one decoder path, ``models/gqa_moe.gqa`` gated,
q/k-normed and partly rotary, ``parallel/moe.py``'s softmax route beside a
gated shared expert) in three steps: the chunked rule against the
recurrence; the plain reference (``benchmark/reference/qwen3_next.py``)
against the INSTALLED modelling code (``transformers`` 4.57.6,
``models/qwen3_next``: ``torch``, CPU, float32, eager); the program against
the reference at small sizes with float32 operands, where the two must
agree to rounding."""

import gc
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import multiverso_tpu as mv
from benchmark.reference import qwen3_next as ref
from multiverso_tpu import updaters
from multiverso_tpu.models import (afmoe, gqa_moe, keye_moe, lfm2_moe, mla_moe,
                                   nemotron_h, qwen3_next)
from multiverso_tpu.ops.delta_rule import (gated_delta_chunked,
                                           unit_lower_inverse)

CFG = qwen3_next.Qwen3NextConfig(
    vocab=96, dim=48, n_layers=4, full_every=4, lin_key_heads=2,
    lin_value_heads=4, lin_key_dim=16, lin_value_dim=8, conv_kernel=4,
    delta_chunk=16, n_heads=4, n_kv_heads=2, head_dim=16, rope_dim=4,
    rope_theta=1e7, moe_ffn=24, shared_ffn=40, n_experts=16, experts_held=1,
    expert_offset=5, top_k=3, balance_coef=1e-3, attn="xla", loss_chunk=32,
    compute_dtype=jnp.float32)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_models():
    yield
    jax.clear_caches()
    gc.collect()


def _ref_config(cfg, held=None):
    """The configuration file's keys, as the reference reads them."""
    return dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        full_attention_interval=cfg.full_every,
        linear_num_key_heads=cfg.lin_key_heads,
        linear_num_value_heads=cfg.lin_value_heads,
        linear_key_head_dim=cfg.lin_key_dim,
        linear_value_head_dim=cfg.lin_value_dim,
        linear_conv_kernel_dim=cfg.conv_kernel, chunk_size=cfg.delta_chunk,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        partial_rotary_factor=cfg.rope_dim / cfg.head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.eps,
        moe_intermediate_size=cfg.moe_ffn,
        shared_expert_intermediate_size=cfg.shared_ffn,
        num_experts=cfg.experts_held if held is None else held,
        published={"num_experts": cfg.n_experts},
        num_experts_per_tok=cfg.top_k,
        expert_offset=cfg.expert_offset if held is None else 0,
        router_aux_loss_coef=cfg.balance_coef)


def _inputs(cfg, seed=0, batch=2, positions=64):
    params = mla_moe.init(cfg, seed, 0.1, scales={"embed": 1.0,
                                                  "conv_w": 0.3})
    # gains away from one, so that a gain's gradient is no symmetric case;
    # the step's bias spread, so that heads forget at different speeds
    for i, name in enumerate(sorted(n for n in params if n.endswith("norm")
                                    or n.endswith("dt_bias"))):
        params[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.key(100 + i), params[name].shape)
    tokens = jax.random.randint(jax.random.key(seed + 2), (batch, positions),
                                0, cfg.vocab)
    return params, mla_moe.init_bias(cfg), tokens


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want.reshape(got.shape)))) / scale < tol


# ---------------------------------------------------------------------- #
# the chunked rule against the recurrence
# ---------------------------------------------------------------------- #
def _rule_inputs(b=2, s=192, hk=2, hv=4, d=16, p=8, seed=0):
    """As the mixer makes them: unit keys, scaled unit queries, ``beta`` a
    sigmoid, ``g = -A softplus(.)`` with ``A`` from 1e-3 (a head that
    remembers over the whole length) to 16 (one that forgets within a
    position)."""
    k = jax.random.split(jax.random.key(seed), 6)
    a = jnp.exp(jnp.linspace(np.log(1e-3), np.log(16.0), hv))
    return (ref.l2norm(jax.random.normal(k[0], (b, s, hk, d))) / d ** 0.5,
            ref.l2norm(jax.random.normal(k[1], (b, s, hk, d))),
            jax.random.normal(k[2], (b, s, hv, p)),
            -a * jax.nn.softplus(jax.random.normal(k[3], (b, s, hv)) + 1.0),
            jax.nn.sigmoid(jax.random.normal(k[4], (b, s, hv))),
            jax.random.normal(k[5], (b, s, hv, p)))


def _recurrence(q, k, v, g, beta):
    r = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t, r, axis=2) for t in (q, k))
    return jax.vmap(ref.delta_rule)(q, k, v, g, beta)


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_rule_is_the_recurrence(chunk, group):
    """Values and all five gradients over three chunks or twelve, within
    1e-4 of the largest element; the heads that remember read what the
    chunks before them left."""
    *args, weight = _rule_inputs()
    chunked = lambda *t: gated_delta_chunked(*t, chunk, jnp.float32, group)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(weight * _recurrence(*t)), range(5)))(*args)
        got, grads = jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(weight * chunked(*t)), range(5)))(*args)
        o, o_want = jax.jit(chunked)(*args), _recurrence(*args)
    assert _close(o, o_want, 1e-4)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for a, w in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(w))) > 0 and _close(a, w, 1e-4)
    # the carried state is no rounding: the slowest head's last chunk alone
    # is another number
    with jax.default_matmul_precision("highest"):
        alone = chunked(*(t[:, -chunk:] for t in args))
    assert not _close(alone[:, :, 0], o_want[:, -chunk:, 0], 1e-2)


def test_the_rule_says_what_it_cannot_chunk():
    q, k, v, g, beta, _ = _rule_inputs(s=48)
    with pytest.raises(ValueError, match="chunks of 32"):
        gated_delta_chunked(q, k, v, g, beta, 32)
    with pytest.raises(ValueError, match="3 value heads over 2 key heads"):
        gated_delta_chunked(q, k, v[:, :, :3], g[..., :3], beta[..., :3], 16)


@pytest.mark.parametrize("q", [2, 16, 64])
def test_unit_lower_inverse_is_the_inverse(q):
    """Against NumPy's inverse, with entries as large as equal keys under
    ``beta`` near 1 give (all ones under the diagonal)."""
    rng = np.random.default_rng(q)
    for m in (np.tril(rng.normal(size=(3, q, q)), -1),
              np.tril(np.ones((1, q, q)), -1)):
        with jax.default_matmul_precision("highest"):
            got = np.asarray(unit_lower_inverse(jnp.asarray(m, jnp.float32)))
        want = np.linalg.inv(np.eye(q) + m)
        np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


# ---------------------------------------------------------------------- #
# the reference against the installed modelling code
# ---------------------------------------------------------------------- #
def _hf():
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    conf = pytest.importorskip(
        "transformers.models.qwen3_next.configuration_qwen3_next")
    torch.manual_seed(0)
    return torch, hf, conf


def _hf_config(conf, cfg):
    return conf.Qwen3NextConfig(
        vocab_size=cfg.vocab, hidden_size=cfg.dim, intermediate_size=64,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.eps, rope_theta=cfg.rope_theta,
        partial_rotary_factor=cfg.rope_dim / cfg.head_dim,
        linear_conv_kernel_dim=cfg.conv_kernel,
        linear_key_head_dim=cfg.lin_key_dim,
        linear_value_head_dim=cfg.lin_value_dim,
        linear_num_key_heads=cfg.lin_key_heads,
        linear_num_value_heads=cfg.lin_value_heads,
        moe_intermediate_size=cfg.moe_ffn,
        shared_expert_intermediate_size=cfg.shared_ffn,
        num_experts_per_tok=cfg.top_k, num_experts=cfg.n_experts,
        norm_topk_prob=True, full_attention_interval=cfg.full_every,
        attn_implementation="eager")


def _np(t):
    return jnp.asarray(t.detach().numpy())


def _delta_weights(module, c):
    """The reference's names from an installed ``Qwen3NextGatedDeltaNet``,
    through the stated column permutations."""
    qkvz, ba = ref.columns_qkvz(c), ref.columns_ba(c)
    return {"wqkvz": _np(module.in_proj_qkvz.weight).T[:, qkvz],
            "wba": _np(module.in_proj_ba.weight).T[:, ba],
            # a row a tap
            "conv_w": _np(module.conv1d.weight)[:, 0, :].T,
            "a_log": _np(module.A_log), "dt_bias": _np(module.dt_bias),
            "gate_norm": _np(module.norm.weight),
            "wout": _np(module.out_proj.weight).T}


def _attention_weights(module, c):
    wq, wgate = ref.columns_q(c)
    q_proj = _np(module.q_proj.weight).T
    return {"wq": q_proj[:, wq], "wgate": q_proj[:, wgate],
            "wk": _np(module.k_proj.weight).T,
            "wv": _np(module.v_proj.weight).T,
            "wo": _np(module.o_proj.weight).T,
            # the stored gain is 1 + w
            "q_norm": 1.0 + _np(module.q_norm.weight),
            "k_norm": 1.0 + _np(module.k_norm.weight)}


def _moe_weights(module):
    stack = lambda name: jnp.stack(
        [_np(getattr(e, name).weight).T for e in module.experts])
    shared = module.shared_expert
    return {"router": _np(module.gate.weight),
            "eg": stack("gate_proj"), "eu": stack("up_proj"),
            "ed": stack("down_proj"),
            "sg": _np(shared.gate_proj.weight).T,
            "su": _np(shared.up_proj.weight).T,
            "sd": _np(shared.down_proj.weight).T,
            "sgate": _np(module.shared_expert_gate.weight)[0]}


def _shake(torch, module):
    """Every parameter away from its first value (a norm's ``w`` from 0, a
    gain from 1), so that no term of the comparison is a symmetric case."""
    with torch.no_grad():
        for p in module.parameters():
            p.add_(0.1 * torch.randn_like(p))


@pytest.mark.parametrize("form", ["recurrent", "chunk"])
def test_the_recurrence_is_the_installed_rule(form):
    """``torch_recurrent_gated_delta_rule`` and
    ``torch_chunk_gated_delta_rule`` on the same arrays: both take the
    norm and the scale inside, on raw q and k already repeated to the
    value heads."""
    torch, hf, _ = _hf()
    _, _, v, g, beta, _ = _rule_inputs(s=96)
    raw = jax.random.normal(jax.random.key(7), (2, 2, 96, 4, 16))
    to = lambda a: torch.tensor(np.asarray(a, np.float32))
    rule = (hf.torch_recurrent_gated_delta_rule if form == "recurrent"
            else hf.torch_chunk_gated_delta_rule)
    kwargs = ({"initial_state": None, "output_final_state": False}
              if form == "recurrent" else {"chunk_size": 16})
    want, _ = rule(to(raw[0]), to(raw[1]), to(v), g=to(g), beta=to(beta),
                   use_qk_l2norm_in_kernel=True, **kwargs)
    with jax.default_matmul_precision("highest"):
        got = jax.vmap(ref.delta_rule)(
            ref.l2norm(raw[0]) / 16 ** 0.5, ref.l2norm(raw[1]), v, g, beta)
    assert _close(got, _np(want), 1e-4)


def test_the_delta_mixer_is_the_installed_module():
    torch, hf, conf = _hf()
    c = _ref_config(CFG)
    module = hf.Qwen3NextGatedDeltaNet(_hf_config(conf, CFG), 0)
    _shake(torch, module)
    u = torch.randn(2, 40, CFG.dim)
    want = module(u)
    p = _delta_weights(module, c)
    with jax.default_matmul_precision("highest"):
        got = jnp.stack([ref.delta_net(_np(u)[i], p, c) for i in range(2)])
    assert _close(got, _np(want), 1e-4)
    # the permutations are no identity: the same weights unpermuted differ
    plain = dict(p, wqkvz=_np(module.in_proj_qkvz.weight).T)
    with jax.default_matmul_precision("highest"):
        assert not _close(ref.delta_net(_np(u)[0], plain, c), _np(want)[0],
                          1e-2)


def _positions(torch, hf, hf_cfg, s):
    rotary = hf.Qwen3NextRotaryEmbedding(hf_cfg)
    return rotary(torch.zeros(1, s, hf_cfg.hidden_size),
                  torch.arange(s)[None])


def _causal(torch, s):
    return torch.full((s, s), float("-inf")).triu(1)[None, None]


def test_the_attention_is_the_installed_module():
    torch, hf, conf = _hf()
    c, hf_cfg = _ref_config(CFG), _hf_config(conf, CFG)
    module = hf.Qwen3NextAttention(hf_cfg, 3)
    _shake(torch, module)
    s = 40
    u = torch.randn(2, s, CFG.dim)
    want, _ = module(u, _positions(torch, hf, hf_cfg, s), _causal(torch, s))
    p = _attention_weights(module, c)
    with jax.default_matmul_precision("highest"):
        got = jnp.stack([ref.attention(_np(u)[i], p, c) for i in range(2)])
        with ref.rule_control("rope_whole"):
            whole = ref.attention(_np(u)[0], p, c)
    assert _close(got, _np(want), 1e-4)
    assert not _close(whole, _np(want)[0], 1e-2)


def test_the_expert_layer_is_the_installed_module():
    """The uncut layer: all 16 experts given, the gated shared expert
    beside them."""
    torch, hf, conf = _hf()
    c = _ref_config(CFG, held=CFG.n_experts)
    module = hf.Qwen3NextSparseMoeBlock(_hf_config(conf, CFG))
    _shake(torch, module)
    u = torch.randn(2, 40, CFG.dim)
    want, _ = module(u)
    p = _moe_weights(module)
    with jax.default_matmul_precision("highest"):
        got = jnp.stack([ref.expert_layer(_np(u)[i], p, c, 0,
                                          CFG.n_experts)[0]
                         for i in range(2)])
        lean = ref.expert_layer(_np(u)[0], p, c, 0, CFG.n_experts,
                                lean=True)[0]
    assert _close(got, _np(want), 1e-4) and _close(lean, got[0])


def test_a_whole_tiny_model_gives_the_installed_models_logits():
    """Four layers, one period: three ``linear_attention`` and one
    ``full_attention``, every weight loaded through the reference's stated
    departures (a norm's stored gain ``1 + w``, the sorted columns)."""
    torch, hf, conf = _hf()
    c = _ref_config(CFG, held=CFG.n_experts)
    model = hf.Qwen3NextForCausalLM(_hf_config(conf, CFG)).eval()
    _shake(torch, model)
    assert [l.layer_type for l in model.model.layers] == list(
        ref.layer_kinds(c))
    params = {"embed": _np(model.model.embed_tokens.weight),
              "head": _np(model.lm_head.weight),
              "final_norm": 1.0 + _np(model.model.norm.weight)}
    for i, layer in enumerate(model.model.layers):
        mixer = (_attention_weights(layer.self_attn, c)
                 if layer.layer_type == "full_attention"
                 else _delta_weights(layer.linear_attn, c))
        block = dict(
            mixer, **_moe_weights(layer.mlp),
            attn_norm=1.0 + _np(layer.input_layernorm.weight),
            ffn_norm=1.0 + _np(layer.post_attention_layernorm.weight))
        params.update({f"L{i}.{k}": v for k, v in block.items()})
    assert set(params) == set(mla_moe.param_shapes(
        CFG._replace(experts_held=CFG.n_experts)))
    tokens = torch.randint(0, CFG.vocab, (2, 48))
    with torch.no_grad():
        want = model(input_ids=tokens).logits
    got = jnp.stack([ref.logits(params, jnp.asarray(tokens.numpy())[i], c)
                     for i in range(2)])
    assert _close(got, _np(want), 1e-4)


def test_partial_rotary_is_the_installed_functions():
    """``gqa_moe.heads_of``'s positions under ``rope_dim`` against
    ``apply_rotary_pos_emb``: the first 4 of a head's 16 turn, the rest
    pass; and the reference's."""
    torch, hf, conf = _hf()
    hf_cfg = _hf_config(conf, CFG)
    s = 24
    cos, sin = _positions(torch, hf, hf_cfg, s)
    assert cos.shape[-1] == CFG.rope_dim
    x = jax.random.normal(jax.random.key(3), (2, s, CFG.n_heads,
                                              CFG.head_dim))
    to = torch.tensor(np.asarray(x)).permute(0, 2, 1, 3)
    want, _ = hf.apply_rotary_pos_emb(to, to, cos, sin)
    want = _np(want.permute(0, 2, 1, 3))
    r = CFG.rope_dim
    got = jnp.concatenate([mla_moe.rotary(x[..., :r], CFG.rope_theta),
                           x[..., r:]], -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[..., r:]),
                                  np.asarray(x[..., r:]))
    again = jnp.concatenate([ref.rope(x[0][..., :r], CFG.rope_theta),
                             x[0][..., r:]], -1)
    np.testing.assert_allclose(np.asarray(again), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    # and through the program's own lines: ``heads_of`` under an identity
    # projection and unit gains turns the normed heads
    width = CFG.n_heads * CFG.head_dim
    cfg = CFG._replace(dim=width)
    eye = jnp.eye(width)
    p = {"wq": eye, "wk": eye[:, :width // 2], "wv": eye[:, :width // 2],
         "q_norm": jnp.ones(CFG.head_dim), "k_norm": jnp.ones(CFG.head_dim)}
    q, _, _ = gqa_moe.heads_of(x.reshape(2, s, width), p, cfg, "full")
    normed = mla_moe.rms_norm(x, 1.0, cfg.eps)
    to = torch.tensor(np.asarray(normed)).permute(0, 2, 1, 3)
    want, _ = hf.apply_rotary_pos_emb(to, to, cos, sin)
    np.testing.assert_allclose(np.asarray(q), want.numpy(), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------- #
# the program against the reference
# ---------------------------------------------------------------------- #
def test_the_layer_list_shapes_and_first_values():
    layers = CFG.layers()
    assert [l.attn for l in layers] == ["delta", "delta", "delta", "full"]
    assert {l.ffn for l in layers} == {"shared+experts"}
    shapes = mla_moe.param_shapes(CFG)
    assert shapes["L0.wqkvz"] == (48, 2 * 32 + 2 * 32)
    assert shapes["L0.wba"] == (48, 8) and shapes["L0.conv_w"] == (4, 96)
    assert shapes["L0.a_log"] == shapes["L0.dt_bias"] == (4,)
    assert shapes["L0.gate_norm"] == (8,) and shapes["L0.wout"] == (32, 48)
    assert shapes["L0.sgate"] == shapes["L3.sgate"] == (48,)
    assert shapes["L3.wgate"] == shapes["L3.wq"] == (48, 64)
    assert shapes["L3.q_norm"] == (16,) and "L3.wqkvz" not in shapes
    assert "L0.wq" not in shapes and "head" in shapes
    params = mla_moe.init(CFG._replace(lin_value_heads=64), 3)
    a = np.exp(np.asarray(params["L0.a_log"]))
    assert 0 < a.min() < 4 and 12 < a.max() < 16        # Uniform(0, 16)
    for name in ("L0.dt_bias", "L1.gate_norm", "L3.q_norm", "final_norm"):
        np.testing.assert_array_equal(np.asarray(params[name]), 1.0)
    # a configuration without the switch has no gate on its shared expert
    assert "L1.sgate" not in mla_moe.param_shapes(afmoe.AFMoEConfig())


@pytest.mark.parametrize("attn,kernel", [("xla", "xla"),
                                         ("flash", "interpret")])
def test_loss_and_every_gradient_match_the_reference(attn, kernel):
    """Every table's gradient: the taps, ``A_log``, the step's bias, the
    gated norm's gain, the two gate projections, the q/k norms' gains and
    the shared expert's gate among them."""
    cfg = CFG._replace(attn=attn, expert_kernel=kernel, attn_block=4)
    params, bias, tokens = _inputs(cfg)
    (loss, (counts, overflow, balance)), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, want_counts, _, terms, want = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    np.testing.assert_allclose(np.asarray(balance), np.asarray(terms),
                               rtol=1e-5)
    assert counts.shape == (4, cfg.n_experts) and int(overflow.sum()) == 0
    assert set(grads) == set(want) == set(mla_moe.param_shapes(cfg))
    for name in ("L0.conv_w", "L0.a_log", "L1.dt_bias", "L2.gate_norm",
                 "L0.wba", "L0.sgate", "L3.sgate", "L3.wgate", "L3.q_norm",
                 "L3.k_norm", "L2.eu", "L1.router"):
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
    bad = [n for n in grads if not _close(grads[n], want[n], 5e-5)]
    assert not bad, bad


def test_bfloat16_compute_stays_within_the_other_models_limits():
    """With bfloat16 operands the loss moves by under 1e-2 of itself and a
    gradient by under a quarter of its norm, the limits ``test_mla_moe``
    and its siblings hold a bfloat16 step to at these sizes (a few hundred
    tokens, where one token routed otherwise is a visible part of a
    table's gradient): a wrong term is off by its whole size."""
    cfg = CFG._replace(compute_dtype=jnp.bfloat16)
    params, bias, tokens = _inputs(cfg)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg), has_aux=True))(params)
    want_loss, _, _, _, want = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, _ref_config(cfg)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-2 * float(want_loss)
    plain = [n for n in grads if n.split(".")[-1] not in (
        "router", "eg", "eu", "ed")]
    off = {n: float(jnp.linalg.norm(grads[n] - want[n].reshape(
        grads[n].shape)) / (jnp.linalg.norm(want[n]) + 1e-30)) for n in plain}
    assert max(off.values()) < 0.25, max(off.items(), key=lambda kv: kv[1])


def test_lean_reference_is_the_plain_reference(monkeypatch):
    """The memory-saving form the chip's check uses (the recurrence in
    stretches, a mixer's stages, query rows, experts and the loss's
    positions in blocks) gives the same numbers."""
    from benchmark.reference import afmoe as ref_afmoe

    params, _, tokens = _inputs(CFG)
    c = _ref_config(CFG)
    plain = jax.jit(lambda p: ref.loss_and_grads(p, tokens, c))(params)
    monkeypatch.setattr(ref_afmoe, "LEAN_ROWS", 16)
    monkeypatch.setattr(ref, "LEAN_STEPS", 8)
    lean = jax.jit(lambda p: ref.loss_and_grads(p, tokens, c,
                                                lean=True))(params)
    assert abs(float(plain[0]) - float(lean[0])) < 1e-5
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(lean[1]))
    assert all(_close(lean[4][n], plain[4][n]) for n in plain[4])


@pytest.mark.parametrize("how", ref.CONTROLS)
def test_a_faulty_model_is_told_apart_from_the_reference(how):
    """The controls of the chip's comparison are other numbers than the
    model, by far more than the program's rounding; and the first chunk of
    a rule that only forgets what it is handed is still right."""
    params, _, _ = _inputs(CFG)
    c = _ref_config(CFG)
    layer = "L3" if how == "rope_whole" else "L0"
    p = mla_moe._sub(params, layer)
    # inputs of the published model's size and a long memory: decays near 1
    p = dict(p, a_log=p["a_log"] - 4.0) if layer == "L0" else p
    u = 4.0 * jax.random.normal(jax.random.key(4), (64, CFG.dim))
    mixer = ref.attention if layer == "L3" else ref.delta_net
    with jax.default_matmul_precision("highest"):
        want = mixer(u, p, c)
        with ref.rule_control(how):
            faulty = mixer(u, p, c)
        got = CFG.attend(u[None], p, "full" if layer == "L3" else "delta")[0]
    assert _close(got, want)
    assert not _close(faulty, want, 1e-3)
    if how in ("no_carry", "no_correction"):
        q = CFG.delta_chunk
        assert _close(faulty[:q], want[:q]) == (how == "no_carry")
    with pytest.raises(ValueError, match="no control"):
        with ref.rule_control("else"):
            pass


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips' shares of the routed part (the program's layer, told
    which expert it holds), with the GATED shared expert counted once, are
    the reference's uncut layer over all sixteen experts."""
    cfg = CFG
    c = _ref_config(cfg, held=cfg.n_experts)
    rng = jax.random.split(jax.random.key(3), 9)
    d, f, fs, e = cfg.dim, cfg.moe_ffn, cfg.shared_ffn, cfg.n_experts
    assert e // cfg.experts_held == 16
    whole = {"router": 0.2 * jax.random.normal(rng[0], (e, d)),
             "sg": 0.1 * jax.random.normal(rng[1], (d, fs)),
             "su": 0.1 * jax.random.normal(rng[2], (d, fs)),
             "sd": 0.1 * jax.random.normal(rng[3], (fs, d)),
             "sgate": 0.3 * jax.random.normal(rng[4], (d,)),
             "eg": 0.1 * jax.random.normal(rng[5], (e, d, f)),
             "eu": 0.1 * jax.random.normal(rng[6], (e, d, f)),
             "ed": 0.1 * jax.random.normal(rng[7], (e, f, d))}
    u = jax.random.normal(rng[8], (2, 48, d))
    with jax.default_matmul_precision("highest"):
        shared = jnp.stack([ref.shared_expert(u[i], whole)
                            for i in range(2)])
    total, seen = shared, 0
    for offset in range(0, e, cfg.experts_held):
        share = dict(whole, **{k: whole[k][offset:offset + cfg.experts_held]
                               for k in ("eg", "eu", "ed")})
        out, (counts, overflow, _) = jax.jit(
            lambda u, share, offset=offset: mla_moe.expert_ffn(
                u, share, None, cfg._replace(expert_offset=offset)))(u, share)
        total = total + (out - shared)
        seen += int(counts[offset:offset + cfg.experts_held].sum())
        assert int(overflow) == 0
    assert seen == 2 * 48 * cfg.top_k       # every assignment, once
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_layer(u[i], whole, c, 0, e)[0]
                          for i in range(2)])
    assert _close(total, want)
    # and the gate is no 1: without it the sum is another number
    ungated = jnp.stack([ref.mlp(u[i], whole["sg"], whole["su"], whole["sd"])
                         for i in range(2)])
    assert not _close(total - shared + ungated, want, 1e-2)


def test_one_step_through_the_adam_tables_is_reference_gradient_plus_adam():
    """And the step's span says the blocks' kinds and the rule's counts."""
    from multiverso_tpu.telemetry import trace as ttrace

    mv.init(mesh=Mesh(np.asarray(jax.devices()[:1]), ("mv",)))
    cfg = CFG._replace(attn="flash", attn_block=4, expert_kernel="xla")
    _, bias, tokens = _inputs(cfg)
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    scales = {"embed": 1.0, "conv_w": 0.3}
    params = mla_moe.init(cfg, 0, 0.1, scales=scales)
    tables = mla_moe.make_tables(
        cfg, 0, 0.1, updater=updaters.AdamUpdater(beta1=b1, beta2=b2,
                                                  eps=eps), scales=scales)
    assert set(tables) == set(mla_moe.param_shapes(cfg))
    # embed, head, final_norm; a mixer's 9 and an expert layer's 8 a layer
    assert len(tables) == 3 + 4 * (9 + 8)
    for n, t in tables.items():     # the tables hold ``init``'s values
        np.testing.assert_allclose(
            t.get().reshape(params[n].shape), np.asarray(params[n]),
            rtol=1e-6, err_msg=n)
    trainer = mla_moe.Trainer(cfg, tables,
                              updaters.AddOption(learning_rate=lr))
    before = len(ttrace.events())
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
    args = [e for e in ttrace.events()[before:]
            if e["name"] == "lm.step"][0]["args"]
    assert args["block_kinds"] == ",".join(
        ["delta+shared+experts"] * 3 + ["full+shared+experts"])
    assert (args["delta_layers"], args["delta_chunks"], args["delta_heads"],
            args["delta_chunk"], args["delta_state"], args["delta_steps"]) == (
                3, 4, 4, 16, 16 * 8, 4)
    # the one causal core is the only kind the flash kernel runs
    assert (args["attn_kinds"], args["kv_group"], args["block_norms"]) == (
        "full", 2, 2)
    assert args["routed_rows"] == 4 * 2 * 64 * cfg.top_k
    assert "aux_loss" in args
    from tools import dump_metrics
    lines = dump_metrics._mixer_lines([{"name": "lm.step", "args": args}])
    assert lines[0].startswith("  blocks: delta+shared+experts,")
    assert ("delta mixers: 3 of 4 value heads, 4 chunks of 16 = 4 dependent "
            "scan steps a group, a state of 128 floats a head") in lines[1]


def test_delta_grid_counts_by_hand():
    """At the cell's sizes: 256 chunks and as many dependent steps a
    layer, a state of 128 x 128 a head; a mixer's operations a token."""
    cfg = _cell_config()
    grid = cfg.delta_grid(16384)
    assert (grid["delta_layers"], grid["delta_chunks"], grid["delta_heads"],
            grid["delta_chunk"], grid["delta_state"], grid["delta_steps"]) == (
                3, 256, 32, 64, 16384, 256)
    low = 64 * 65 // 2
    chunk = (16 * 2 * 128 * (low - 64 + low)
             + 32 * (2 * low * 256 + 2 * low * 128 + 6 * 64 * 128 * 128))
    assert qwen3_next.rule_flops_chunk(cfg) == chunk
    mixer = (2 * 2048 * (12288 + 64) + 2 * 4096 * 2048 + 2 * 4 * 8192
             + chunk // 64)
    assert qwen3_next.mixer_flops_token(cfg) == mixer
    assert grid["delta_flops_token"] == 3 * mixer
    ffn = 2 * 2048 * 512 + 6 * 2048 * 512 + 2 * 2048 + (
        6 * 2048 * 512 * 10 * 32 // 512)
    full = 2 * 2048 * 256 * (3 * 16 + 2 * 2) + 2 * 256 * 16 * 16385
    assert grid["step_flops_token"] == (2 * 2048 * 18992 + 3 * mixer + full
                                        + 4 * ffn)
    assert mla_moe.mixer_grid(cfg, 16384)["delta_steps"] == 256
    # a list with no such mixer says nothing of it
    assert "delta_layers" not in mla_moe.mixer_grid(
        nemotron_h.NemotronHConfig(), 64)
    assert mla_moe.mixer_grid(gqa_moe.GQAMoEConfig(), 64) == {}


def test_the_mixers_scopes_are_in_the_lowered_step():
    params, bias, tokens = _inputs(CFG)
    text = jax.jit(jax.grad(lambda p: mla_moe.loss_fn(
        p, bias, tokens, CFG)[0])).lower(params).as_text(debug_info=True)
    for scope in ("mv.lm.delta/", "mv.lm.delta.conv", "mv.lm.delta.gates",
                  "mv.lm.delta.rule", "mv.lm.delta.norm", "mv.lm.attn.full",
                  "mv.lm.attn.gate", "mv.lm.attn.qknorm",
                  "mv.lm.moe.shared"):
        assert scope in text, scope


# ---------------------------------------------------------------------- #
# what a rematerialised block keeps of the mixer
# ---------------------------------------------------------------------- #
DELTA = mla_moe.Layer("L0", "delta", "shared+experts")


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


def _delta_block(cfg):
    """One delta block's loss over its input and parameters, and both."""
    params, _, _ = _inputs(cfg)
    p = mla_moe._sub(params, "L0")
    x = jax.random.normal(jax.random.key(11), (2, 64, cfg.dim))
    weight = jax.random.normal(jax.random.key(12), x.shape)

    def loss(cfg, remat=True):
        def run(x, p):
            y, (_, _, balance) = mla_moe._run_block(x, p, DELTA, None, cfg,
                                                    remat=remat)
            return jnp.sum(y * weight) + cfg.balance_coef * balance
        return run
    return loss, x, p


def test_a_remade_block_runs_the_rules_forward_scans_once_fewer():
    """The step's gradient: the rule's two ``scan`` equations (the groups'
    ``lax.map`` and a group's scan over its chunks) and its triangular
    solve stand once fewer a delta layer than with the name out of the
    policy: forward and each group made again, and no third time in the
    block made again. The block's residuals hold the named array."""
    from jax._src.ad_checkpoint import saved_residuals

    params, bias, tokens = _inputs(CFG)
    deltas = sum(layer.attn == "delta" for layer in CFG.layers())
    assert mla_moe.kept_names(CFG) == (mla_moe.moe.KEPT_NAMES
                                       + qwen3_next.KEPT_NAMES)
    counts = {}
    for name, cfg in (("kept", CFG),
                      ("bare", _without(CFG, *qwen3_next.KEPT_NAMES))):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params).jaxpr
        counts[name] = {k: _count(jaxpr, k)
                        for k in ("scan", "triangular_solve")}
    assert counts["bare"]["scan"] - counts["kept"]["scan"] == 2 * deltas
    # a solve forward, made again, and transposed: 3 a layer, 4 before
    assert counts["kept"]["triangular_solve"] == 3 * deltas
    assert counts["bare"]["triangular_solve"] == 4 * deltas
    # a block's residuals: its arguments and what its checkpoint hands
    # out, of which one array is the rule's result [B, S, Hv, dv]
    loss, x, p = _delta_block(CFG)
    o = jax.core.ShapedArray((2, 64, CFG.lin_value_heads, CFG.lin_value_dim),
                             jnp.float32)
    kept = lambda cfg: [why for aval, why in saved_residuals(loss(cfg), x, p)
                        if aval == o]
    assert len(kept(CFG)) == 1 and "remat" in kept(CFG)[0]
    assert kept(_without(CFG, *qwen3_next.KEPT_NAMES)) == []


def test_keeping_the_rules_result_moves_no_gradient_by_a_bit():
    """Every table's float32 gradient with the rule's result kept is the
    gradient with the name out of the policy, and a delta block's is the
    un-rematerialised block's, bit for bit."""
    params, bias, tokens = _inputs(CFG)
    grads = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]))(params)
    got, bare = grads(CFG), grads(_without(CFG, *qwen3_next.KEPT_NAMES))
    assert set(got[1]) == set(mla_moe.param_shapes(CFG))
    for n in got[1]:
        assert float(jnp.abs(got[1][n]).max()) > 0, n
    for a, b in zip(_bits(got), _bits(bare)):
        np.testing.assert_array_equal(a, b)
    loss, x, p = _delta_block(CFG)
    kept, still = (jax.jit(jax.value_and_grad(loss(CFG, remat), (0, 1)))(x, p)
                   for remat in (True, False))
    for a, b in zip(_bits(kept), _bits(still)):
        np.testing.assert_array_equal(a, b)


def test_the_backward_pass_reads_the_forwards_result_of_the_rule(
        monkeypatch):
    """Poison: the rule's result among the residuals of a delta block's
    forward pass (one array of its shape; none without the name) is
    swapped for another before the backward pass runs. The gradients are
    then those of a block whose rule hands ``close`` that other value: the
    backward pass read the kept array and made the rule's result no second
    time."""
    # nothing else kept, so that what is made again follows the poison
    cfg = _without(CFG, keeps_products=False)
    assert mla_moe.kept_names(cfg) == qwen3_next.KEPT_NAMES
    loss, x, p = _delta_block(cfg)
    shape = (2, 64, cfg.lin_value_heads, cfg.lin_value_dim)
    is_o = lambda a: getattr(a, "shape", None) == shape
    _, back = jax.vjp(loss(cfg), x, p)
    leaves, tree = jax.tree.flatten(back)
    assert sum(map(is_o, leaves)) == 1
    bare = jax.vjp(loss(_without(cfg, *qwen3_next.KEPT_NAMES)), x, p)[1]
    assert not any(map(is_o, jax.tree.leaves(bare)))
    clean = back(jnp.ones(()))
    shift = jax.random.normal(jax.random.key(13), shape)
    poisoned = jax.tree.unflatten(
        tree, [a + shift if is_o(a) else a for a in leaves])(jnp.ones(()))
    assert any((a != b).any() for a, b in zip(_bits(poisoned), _bits(clean)))
    rule = qwen3_next.gated_delta_chunked
    monkeypatch.setattr(qwen3_next, "gated_delta_chunked",
                        lambda *a, **kw: rule(*a, **kw) + shift)
    want = jax.vjp(loss(cfg), x, p)[1](jnp.ones(()))
    for a, b in zip(_bits(poisoned), _bits(want)):
        np.testing.assert_array_equal(a, b)


def test_the_steps_span_counts_what_the_rule_keeps():
    """``lm.step``'s ``kept_names`` and ``kept_bytes``: the five names of
    the blocks' policy, and beside the expert layers' the rule's float32
    result of every delta layer, from the shapes."""
    cfg = _cell_config()
    grid = mla_moe.kept_grid(cfg, 1, 16384)
    bare = mla_moe.kept_grid(_without(cfg, *qwen3_next.KEPT_NAMES), 1, 16384)
    assert (grid["kept_names"], bare["kept_names"]) == (5, 4)
    assert cfg.kept_bytes(1, 16384) == 3 * 4 * 16384 * 32 * 128
    assert grid["kept_bytes"] - bare["kept_bytes"] == 805_306_368
    assert grid["expert_products_kept"] == bare["expert_products_kept"] == 8


def _cell_config(with_file=False):
    """The cell's configuration as its driver builds it (and the file's
    dictionary)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        c = json.load(f)
    from benchmark.drivers import lm_train_delta

    class _Cell:
        config = c

    cfg = lm_train_delta._model_config(_Cell)
    return (cfg, c) if with_file else cfg


def test_published_sizes_give_the_configurations_parameter_count():
    cfg, c = _cell_config(with_file=True)
    assert [l.attn for l in cfg.layers()] == ["delta"] * 3 + ["full"]
    shapes = mla_moe.param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    assert count(lambda n: n.startswith("L0.")) == 138_582_208
    assert count(lambda n: n.startswith("L3.")) == 132_127_232
    assert count(lambda n: "." not in n) == 77_793_280
    assert count(lambda n: True) == 625_667_136 == c["parameters"]
    assert shapes["L0.wqkvz"] == (2048, 12288)
    assert shapes["L0.conv_w"] == (4, 8192)
    assert shapes["L3.wq"] == shapes["L3.wgate"] == (2048, 4096)
    assert shapes["L3.wk"] == (2048, 512)
    assert shapes["L1.eu"] == (32, 2048, 512)
    assert shapes["L1.router"] == (512, 2048)
    assert (cfg.rope_dim, cfg.head_dim, cfg.kv_group) == (64, 256, 8)
    # the held experts' buffer is twice the even load of a 16,384-token step
    assert mla_moe.held(cfg, 16384).buffer_rows == 20480


# The lowered text of the parent commit's programs (StableHLO without
# locations, sha256's first 16 digits), made with ``git archive 7b285c9``
# beside this tree: a tiny configuration of each of the six language-model
# kinds the benchmark's ten cells run must lower to what it lowered to.
# ``keye`` is THIS tree's text since PR 57 (the parent's was
# 281095075d04bf5c): its sparse layers name their selection, which the
# blocks' policy keeps, so the lowered backward pass makes no selection
# again. ``qwen3_next`` (no entry) names the delta rule's result in the
# same PR: ``test_a_remade_block_runs_the_rules_forward_scans_once_fewer``
# holds what changed there. ``nemotron_h`` is THIS tree's text since PR 59
# (the parent's was f19145b4231045ba): the mixer makes its convolution's
# operand as a product of its own from the table's column window, and
# ``test_the_in_projections_two_products_are_the_parents_one`` holds the
# step to the parent's one product, as it does ``qwen3_next``'s.
# ALL SIX are THIS tree's text since PR 63 (the entries before it were
# 1176c1bf3112ad40, f3ca378f315449a4, 06ebccb9b2fd87b1, cb7c9d0aae93395e,
# d8be808c75a5fa2e, 6d0bfafb9008b220): every model's attention reaches its
# core through ``mla_moe.heads`` and leaves it through ``out_of_heads``
# (one product a part with the head axis before the positions, one
# differentiation rule), so every step's text moved ON PURPOSE;
# ``tests/test_head_turns.py`` holds the new lines to the parent's
# formulation bit for bit and gradient for gradient.
PARENT = {"mla": "969aac75f944a61b", "gqa": "dd417f7a7132a1fc",
          "afmoe": "9a893072e43c71af", "nemotron_h": "443da8f4cc17c58b",
          "lfm2": "5bc1d38d9eeb95f5", "keye": "94022a33919fd895"}
MODELS = {"mla": mla_moe.MLAMoEConfig, "gqa": gqa_moe.GQAMoEConfig,
          "afmoe": afmoe.AFMoEConfig, "nemotron_h": nemotron_h.NemotronHConfig,
          "lfm2": lfm2_moe.LFM2MoEConfig, "keye": keye_moe.KeyeMoEConfig}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_six_older_models_steps_lower_to_the_parents_text(name):
    cfg = MODELS[name](attn="xla")
    params = jax.eval_shape(lambda: mla_moe.init(cfg, 0))
    bias = jax.eval_shape(lambda: mla_moe.init_bias(cfg))
    text = jax.jit(jax.value_and_grad(
        lambda p, b, t: mla_moe.loss_fn(p, b, t, cfg), has_aux=True)).lower(
            params, bias, jnp.zeros((2, 64), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[name]


# ---------------------------------------------------------------------- #
# PR 59: the convolution's operand is a product of its own
# ---------------------------------------------------------------------- #
def _parents_mamba2(u, p, cfg):
    """``nemotron_h.mamba2`` as the parent commit had it: ONE in-projection,
    its result split, the taps as shifted slices of a padded array."""
    from multiverso_tpu.ops.ssd import ssd_chunked
    b, s, _ = u.shape
    h, hd, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                   cfg.ssm_state)
    inner, dt_ = h * hd, cfg.compute_dtype
    conv = inner + 2 * g * n
    proj = mla_moe.matmul(u, p["win"], False, dt_, jnp.float32)
    z, xbc, dt = jnp.split(proj, (inner, inner + conv), axis=-1)
    taps = cfg.conv_kernel
    past = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = p["conv_b"] + sum(past[:, i:i + s] * p["conv_w"][i]
                            for i in range(taps))
    xbc = jax.nn.silu(xbc)
    x, bm, cm = jnp.split(xbc, (inner, inner + g * n), axis=-1)
    x = x.reshape(b, s, h, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_chunked(x, dt, -jnp.exp(p["a_log"]), bm.reshape(b, s, g, n),
                    cm.reshape(b, s, g, n), cfg.chunk, dt_)
    y = y + p["skip"][:, None] * x
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.eps)
    y = y.reshape(b, s, inner) * p["gate_norm"]
    return mla_moe.matmul(y, p["wout"], False, dt_, jnp.float32)


def _parents_delta_net(u, p, cfg):
    """``qwen3_next.gated_delta_net`` as the parent commit had it."""
    b, s, _ = u.shape
    hk, hv = cfg.lin_key_heads, cfg.lin_value_heads
    dk, dv, dt_ = cfg.lin_key_dim, cfg.lin_value_dim, cfg.compute_dtype
    key, value = hk * dk, hv * dv

    def feed(u, wqkvz, wba, conv_w, a_log, dt_bias):
        proj = mla_moe.matmul(u, wqkvz, False, dt_, jnp.float32)
        ba = mla_moe.matmul(u, wba, False, dt_, jnp.float32)
        qkv, z = jnp.split(proj, (2 * key + value,), axis=-1)
        taps = cfg.conv_kernel
        past = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(past[:, i:i + s] * conv_w[i]
                              for i in range(taps)))
        q, k, v = jnp.split(qkv, (key, 2 * key), axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        q = unit(q.reshape(b, s, hk, dk)) * dk ** -0.5
        k = unit(k.reshape(b, s, hk, dk))
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        return q, k, v.reshape(b, s, hv, dv), g, beta, z

    def close(o, z, gain, wout):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.eps) * gain
        o = (o * jax.nn.silu(z.reshape(b, s, hv, dv))).reshape(b, s, value)
        return mla_moe.matmul(o, wout, False, dt_, jnp.float32)

    q, k, v, g, beta, z = jax.checkpoint(feed)(
        u, p["wqkvz"], p["wba"], p["conv_w"], p["a_log"], p["dt_bias"])
    o = checkpoint_name(
        gated_delta_chunked(q, k, v, g, beta, cfg.delta_chunk, dt_),
        qwen3_next.KEPT_NAMES[0])
    return jax.checkpoint(close)(o, z, p["gate_norm"], p["wout"])


@pytest.mark.parametrize("name", ["nemotron_h", "qwen3_next"])
@pytest.mark.parametrize("level", [None, 0], ids=["default", "as_written"])
def test_the_in_projections_two_products_are_the_parents_one(
        name, level, monkeypatch):
    """The mixers make the convolution's operand as a product of its own
    from the column window of the one table they have (PR 59): every output
    column is the dot product it was, so the step's LOSS is the parent's
    (the parent's mixers, one product and its result split, are kept above
    and stand in the model's place for the comparison): bit for bit with
    LLVM at level 0, every float operation done as it is written, and to
    the last place as XLA:CPU compiles by default (it blocks a product by
    its width: ``conftest.same_floats`` says the same of PR 38's). The
    gradients are the parent's to float32's last places and no nearer at
    either level: the gradient to the mixer's input was one sum over all
    the table's columns and is now the sum of two, over the window and
    over the rest, and a float32 sum in another order rounds otherwise."""
    module, mixer, parents, cfg = {
        "nemotron_h": (nemotron_h, "mamba2", _parents_mamba2,
                       nemotron_h.NemotronHConfig(attn="xla")),
        "qwen3_next": (qwen3_next, "gated_delta_net", _parents_delta_net,
                       CFG)}[name]
    params = mla_moe.init(cfg, 3, 0.1, scales={"embed": 1.0, "conv_w": 0.3})
    bias = mla_moe.init_bias(cfg)
    tokens = jax.random.randint(jax.random.key(5), (2, 64), 0, cfg.vocab)
    options = ({} if level is None
               else {"xla_backend_optimization_level": level})
    step = lambda: jax.jit(jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, bias, tokens, cfg)[0]),
        compiler_options=options)(params)
    loss, grads = step()
    monkeypatch.setattr(module, mixer, parents)
    want_loss, want = step()
    if level == 0:
        assert np.array_equal(np.asarray(loss), np.asarray(want_loss))
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    assert sorted(grads) == sorted(want)
    worst = max((float(np.abs(np.asarray(grads[n]) - np.asarray(want[n])).max()
                       / np.abs(np.asarray(want[n])).max()), n)
                for n in want if np.abs(np.asarray(want[n])).max() > 0)
    assert worst[0] < 5e-5, worst       # 7.3e-6 at the most, read here
