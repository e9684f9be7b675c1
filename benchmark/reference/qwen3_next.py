"""Plain reference of the decoder that ``qwen3-next-80b-a3b-ep16`` trains
(three gated delta-rule linear-attention layers to one gated, partly rotary
grouped-query layer, every layer routed experts beside a gated shared
one): ``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no chunk, no
triangular inverse, no sort, no cache; the delta rule is the recurrence
itself, a position at a time; loss and gradients by autodiff; Adam in NumPy
(``reference/mla_moe.adam_step``). Independent of ``multiverso_tpu``: it
shares the parameters' names and shapes and nothing else. The rounding
control (``rounded_operands``) is ``reference/mla_moe``'s, so that one
switch rounds every reference; :func:`rule_control` is this file's own.

The equations are those of ``transformers`` 4.57.6
``models/qwen3_next/modeling_qwen3_next.py``, which is installed beside
this file's tests, and ``tests/test_qwen3_next.py`` holds this file to it
(the two delta-rule functions, the three modules, a whole tiny model's
logits), so nothing here is a recollection. ``c`` is the configuration
file's dictionary, with the file's own keys.

* layer ``i`` is ``full_attention`` where ``(i + 1) %
  full_attention_interval == 0`` and ``linear_attention`` otherwise: ``h = x
  + Mixer(N(x))``, ``y = h + MoE(N(h))``, eps ``rms_norm_eps``; a final
  norm; the head untied.
* linear attention (:func:`delta_net`): ``[q | k | v | z] = u W_qkvz``,
  ``[b | a] = u W_ba``; ``[q | k | v] = silu(conv([q | k | v]))``, depthwise
  and causal over ``linear_conv_kernel_dim`` taps (four shifted sums), no
  bias, zeros before the start; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; ``q, k <- x rsqrt(sum x^2 + 1e-6)`` a head, ``q
  <- q / sqrt(linear_key_head_dim)``; key head ``h`` is read by value heads
  ``h R ..``; the rule :func:`delta_rule`: ``S <- exp(g_t) S``, ``d_t =
  beta_t (v_t - S^T k_t)``, ``S <- S + k_t (x) d_t``, ``o_t = S^T q_t`` from
  ``S = 0``; ``RMSNorm(o) * w * silu(z)`` over each value head (the norm
  first, then the gate); ``W_out``.
* full attention: ``q = u W_q``, ``gate = u W_gate``, ``k``, ``v``; an
  RMSNorm over every head of q and of k; rotary (``rope_theta``, half-split
  pairing) on the first ``partial_rotary_factor x head_dim`` of a head, the
  rest passes; causal softmax over ``sqrt(head_dim)``; ``o *
  sigmoid(gate)``; ``W_o``.
* MoE: ``p = softmax(u W_r^T)`` over all ``published.num_experts``, the
  ``num_experts_per_tok`` largest, renormalised (``norm_topk_prob``); the
  sum over the chosen experts GIVEN of ``g_e (silu(u W_g,e) * (u W_u,e))
  W_d,e``; ``+ sigmoid(u . w_sg) Shared(u)``.
* Loss: mean cross-entropy over the positions that have a next token, plus
  ``router_aux_loss_coef`` x the sum over the layers of ``E x sum_e f_e
  P_e`` (``reference/gqa_window_moe.balance_term``).

Departures from the installed file, none of which changes a number a test
could not hold: a norm's gain is STORED as ``g = 1 + w`` (the file stores
``w`` and computes ``1 + w``: the same function and the same gradient);
``W_qkvz``, ``W_ba`` and ``W_q`` are stored with their columns sorted by
kind (``[q | k | v | z]``, ``[b | a]``, ``W_q`` and ``W_gate`` apart) where
the file interleaves them by head (``columns_*`` below give the
permutations the tests load weights through); the load-balance term is a
layer's own and summed (the file's ``load_balancing_loss_func`` pools the
layers' router outputs into one term); the multi-token-prediction module
is left out (the file ignores its weights too). And for memory alone, with
``lean=True``: a block and a mixer's three stages (what feeds the rule, the
rule, what follows it) are each under ``jax.checkpoint``; the recurrence
runs over stretches of ``LEAN_STEPS`` positions, each under
``jax.checkpoint`` (32 heads' states of 128 x 128 floats are 2 MB a
position: 34 GB a sequence of 16,384 if every one were kept); attention a
head and ``LEAN_ROWS`` query rows at a time, the given experts one after
another and the cross-entropy in blocks, as ``reference/afmoe``'s.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import _ce_sum, _head, _sub
from benchmark.reference.gqa_window_moe import balance_term, route
from benchmark.reference.mla_moe import (MARGINS, _mm, adam_step, mlp, rms,
                                         rope, rounded_operands)

__all__ = ["MARGINS", "adam_step", "rounded_operands", "rule_control",
           "loss", "loss_and_grads"]

LEAN_STEPS = 128

# What stands in the model's place: ``None`` (the model as it is) or one of
# :data:`CONTROLS`: see ``rule_control``.
CONTROLS = ("no_carry", "no_correction", "sums_bfloat16", "rope_whole")
_RULE = None


@contextlib.contextmanager
def rule_control(how):
    """While this holds (it is read when a function is TRACED), the model
    computes as a faulty program's would, for the comparison's controls
    (``drivers/lm_train_delta.CONTROLS``, ``lm_delta_control.py``), which
    have to tell each apart.
    ``"no_carry"`` drops the state at every ``chunk_size``-th position, as
    a chunked rule that leaves out what one chunk hands the next.
    ``"no_correction"`` takes off ``v_t`` what the state AT THE CHUNK'S
    START (decayed to ``t``) answers for ``k_t`` and not what the state
    itself does: the chunked rule with ``T`` left out (``U = Vb``, ``W = Kb
    o exp(G)``), plain gated linear attention inside a chunk.
    ``"sums_bfloat16"`` keeps in bfloat16 what the configuration says is
    float32: the state is rounded after every position's update (and with
    it the correction it answers, which is what ``T`` carries inside a
    chunk), and the running sum of ``g`` since the chunk's start is kept
    rounded, a position's decay taken from two rounded sums' difference
    (on the chip, at the family's first values, this moves a gradient by a
    seventh of the program's own rounding and is NOT among the driver's
    controls: ``benchmark/LM_DELTA.md``). ``"rope_whole"`` turns all of a head's dimensions and not its first
    ``partial_rotary_factor``."""
    global _RULE
    if how is not None and how not in CONTROLS:
        raise ValueError(f"no control named {how!r}")
    before, _RULE = _RULE, how
    try:
        yield
    finally:
        _RULE = before


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, chunk: int = 64, lean=False):
    """The recurrence, a position at a time: q, k [S, H, D] (normed, q
    scaled, already repeated to the value heads), v [S, H, P], g, beta [S,
    H] -> o [S, H, P]. ``chunk`` is read by the controls alone."""
    s, h, d = k.shape
    how = _RULE
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)

    def step(carry, each):
        state, start, since = carry     # since: the sum of g from the chunk's start
        qt, kt, vt, gt, bt, t = each
        first = t % chunk == 0
        if how == "sums_bfloat16":
            before = jnp.where(first, 0.0, since)
            since = low(before + gt)
            gt = since - before
        keep = jnp.exp(gt)[:, None, None]
        if how == "no_carry":
            keep = jnp.where(first, 0.0, keep)
        before_t = state
        state = answers = keep * before_t
        if how == "no_correction":
            start = answers = keep * jnp.where(first, before_t, start)
        d_t = bt[:, None] * (vt - jnp.einsum("hdp,hd->hp", answers, kt))
        state = state + kt[:, :, None] * d_t[:, None, :]
        if how == "sums_bfloat16":
            state = low(state)
        return (state, start, since), jnp.einsum("hdp,hd->hp", state, qt)

    def stretch(carry, each):
        return jax.lax.scan(step, carry, each)

    zero = jnp.zeros((h, d, v.shape[-1]), jnp.float32)
    first = (zero, zero, jnp.zeros((h,), jnp.float32))
    each = (q, k, v, g, beta, jnp.arange(s))
    if not lean or s <= LEAN_STEPS or s % LEAN_STEPS:
        return stretch(first, each)[1]
    each = jax.tree.map(
        lambda t: t.reshape((-1, LEAN_STEPS) + t.shape[1:]), each)
    _, o = jax.lax.scan(jax.checkpoint(stretch), first, each)
    return o.reshape(s, h, v.shape[-1])


def delta_net(u, p, c, lean=False):
    """u [S, D] -> [S, D]. With ``lean`` the three stages (what feeds the
    rule, the rule, what follows it) are each under ``jax.checkpoint``."""
    s = u.shape[0]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    taps = c["linear_conv_kernel_dim"]
    key, value = hk * dk, hv * dv
    wrap = jax.checkpoint if lean else (lambda f: f)

    def before(u, wqkvz, wba, conv_w, a_log, dt_bias):
        proj, ba = _mm(u, wqkvz), _mm(u, wba)
        qkv, z = proj[:, :2 * key + value], proj[:, 2 * key + value:]
        past = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv], 0)
        qkv = jax.nn.silu(sum(past[i:i + s] * conv_w[i]
                              for i in range(taps)))
        q = l2norm(qkv[:, :key].reshape(s, hk, dk)) / np.sqrt(dk)
        k = l2norm(qkv[:, key:2 * key].reshape(s, hk, dk))
        # key head h is read by value heads h R .. h R + R - 1
        q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
        return q, k, qkv[:, 2 * key:].reshape(s, hv, dv), g, beta, z

    def after(o, z, gain, wout):
        o = rms(o, gain, c["rms_norm_eps"]) * jax.nn.silu(
            z.reshape(s, hv, dv))
        return _mm(o.reshape(s, value), wout)

    q, k, v, g, beta, z = wrap(before)(u, p["wqkvz"], p["wba"], p["conv_w"],
                                       p["a_log"], p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, c["chunk_size"], lean)
    return wrap(after)(o, z, p["gate_norm"], p["wout"])


def rotary_dim(c) -> int:
    return int(c["head_dim"] * c["partial_rotary_factor"])


def attention(u, p, c, lean=False):
    """u [S, D] -> [S, D]: causal, grouped-query, gated, q/k-normed, rotary
    over the first :func:`rotary_dim` of a head."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    r = d if _RULE == "rope_whole" else rotary_dim(c)
    q = rms(_mm(u, p["wq"]).reshape(s, h, d), p["q_norm"], eps)
    k = rms(_mm(u, p["wk"]).reshape(s, hkv, d), p["k_norm"], eps)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    q, k = (jnp.concatenate([rope(t[..., :r], c["rope_theta"]), t[..., r:]],
                            -1) for t in (q, k))
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    one = lambda q, k, v: _head(q, k, v, 0, lean)
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    o = o.transpose(1, 0, 2).reshape(s, h * d)
    return _mm(o * jax.nn.sigmoid(_mm(u, p["wgate"])), p["wo"])


def routed_share(u, p, c, offset, n_given, lean=False):
    """The routed part alone: what experts ``offset .. offset + n_given -
    1`` give (``p["eg"]``, ``p["eu"]`` [n_given, D, F], ``p["ed"]``
    [n_given, F, D]). Every given expert is applied to every token, and its
    result taken under its gate (0 where the token did not choose it).
    Returns (result, (counts, summed probabilities, ties))."""
    gates, counts, prob_sum, ties = route(u, p["router"], c)
    mine = gates[:, offset:offset + n_given].T          # [e, t]

    def expert(eg, eu, ed, gate):
        return _mm(jax.nn.silu(_mm(u, eg)) * _mm(u, eu) * gate[:, None], ed)

    each = (p["eg"], p["eu"], p["ed"], mine)
    if lean:
        out, _ = jax.lax.scan(
            lambda acc, e: (acc + jax.checkpoint(expert)(*e), None),
            jnp.zeros_like(u), each)
    else:
        out = jax.vmap(expert)(*each).sum(0)
    return out, (counts, prob_sum, ties)


def shared_expert(u, p):
    """``sigmoid(u . w_sg) Shared(u)``: one gate a token."""
    return jax.nn.sigmoid(u @ p["sgate"])[:, None] * mlp(
        u, p["sg"], p["su"], p["sd"])


def expert_layer(u, p, c, offset, n_given, lean=False):
    """The gated shared expert + the given experts' part of the routed
    result."""
    out, aux = routed_share(u, p, c, offset, n_given, lean)
    return shared_expert(u, p) + out, aux


def layer_kinds(c):
    return tuple("full_attention" if (i + 1) % c["full_attention_interval"]
                 == 0 else "linear_attention"
                 for i in range(c["num_hidden_layers"]))


def block(x, p, c, kind: str, lean=False):
    eps = c["rms_norm_eps"]
    mixer = attention if kind == "full_attention" else delta_net
    h = x + mixer(rms(x, p["attn_norm"], eps), p, c, lean)
    f, aux = expert_layer(rms(h, p["ffn_norm"], eps), p, c,
                          c.get("expert_offset", 0), c["num_experts"], lean)
    return h + f, aux


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f, d = c["num_experts"], c["moe_intermediate_size"], c["hidden_size"]
    return dict(p, eg=p["eg"].reshape(h, d, f), eu=p["eu"].reshape(h, d, f),
                ed=p["ed"].reshape(h, f, d))


def trunk(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (the last block's output [S, D], each
    layer's (counts, summed probabilities, ties))."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    x = params["embed"][tokens]
    aux = []
    for i, kind in enumerate(layer_kinds(c)):
        x, a = wrap(lambda x, p, kind=kind: block(x, p, c, kind, lean))(
            x, _experts_3d(_sub(params, f"L{i}"), c))
        aux.append(a)
    return x, aux


def logits(params, tokens, c):
    """One sequence's logits [S, V], for the comparison with the installed
    model."""
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(params, tokens, c)
        return _mm(rms(x, params["final_norm"], c["rms_norm_eps"]),
                   params["head"].T)


def sequence_loss(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of CE over the positions with a
    target, counts [layers, E], summed probabilities [layers, E], ties
    [layers, len(MARGINS)])."""
    s = tokens.shape[0]
    x, aux = trunk(params, tokens, c, lean)
    main = _ce_sum(x, params["final_norm"], params["head"],
                   jnp.roll(tokens, -1), (jnp.arange(s) < s - 1).astype(
                       jnp.float32), c, lean)
    return (main,) + tuple(jnp.stack(t) for t in zip(*aux))


def loss(params, tokens, c, lean=False):
    """tokens [B, S] -> (loss, (counts [layers, E], ties [layers,
    len(MARGINS)], the load-balance terms [layers])), float32 at the
    highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        one = lambda t: sequence_loss(params, t, c, lean)
        if lean:
            main, counts, prob_sum, ties = jax.lax.map(jax.checkpoint(one),
                                                       tokens)
        else:
            main, counts, prob_sum, ties = jax.vmap(one)(tokens)
        counts, prob_sum = counts.sum(0), prob_sum.sum(0)
        terms = jnp.stack([balance_term(counts[i], prob_sum[i], b * s, c)
                           for i in range(counts.shape[0])])
        total = (main.sum() / (b * (s - 1))
                 + c["router_aux_loss_coef"] * terms.sum())
        return total, (counts, ties.sum(0), terms)


def loss_and_grads(params, tokens, c, lean=False):
    """(loss, counts, ties, load-balance terms, gradients by name)."""
    (value, (counts, ties, terms)), grads = jax.value_and_grad(
        lambda p: loss(p, tokens, c, lean), has_aux=True)(params)
    return value, counts, ties, terms, grads


def route_alone(u, router, c):
    """``route`` on its own, at the reference's precision: (counts [E],
    ties [len(MARGINS)], the load-balance term) for an input ``u``
    [S, D]."""
    with jax.default_matmul_precision("highest"):
        _, counts, prob_sum, ties = route(u, router, c)
        return counts, ties, balance_term(counts, prob_sum, u.shape[0], c)


# ---------------------------------------------------------------------- #
# the installed file's column orders, for loading its weights
# ---------------------------------------------------------------------- #
def columns_qkvz(c) -> np.ndarray:
    """``W_qkvz``'s columns here, as indices into the installed
    ``in_proj_qkvz``'s outputs: that file lays a key head's ``[q | k | its R
    value heads' v | their z]`` side by side, head after head
    (``fix_query_key_value_ordering``)."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    r = hv // hk
    per = 2 * dk + 2 * r * dv
    head = np.arange(hk)[:, None] * per
    parts = [(0, dk), (dk, dk), (2 * dk, r * dv), (2 * dk + r * dv, r * dv)]
    return np.concatenate([(head + lo + np.arange(n)[None, :]).ravel()
                           for lo, n in parts])


def columns_ba(c) -> np.ndarray:
    """``W_ba``'s columns here (``[b | a]``, a value head each) as indices
    into the installed ``in_proj_ba``'s outputs (a key head's ``[its R
    b | its R a]``, head after head)."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    r = hv // hk
    head = np.arange(hk)[:, None] * 2 * r
    return np.concatenate([(head + lo + np.arange(r)[None, :]).ravel()
                           for lo in (0, r)])


def columns_q(c):
    """(``W_q``'s, ``W_gate``'s) columns here as indices into the installed
    ``q_proj``'s outputs (a head's ``[q | gate]``, head after head)."""
    h, d = c["num_attention_heads"], c["head_dim"]
    head = np.arange(h)[:, None] * 2 * d
    return tuple((head + lo + np.arange(d)[None, :]).ravel()
                 for lo in (0, d))
