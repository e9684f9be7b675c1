"""Plain reference of the short-convolution / grouped-query decoder under a
tied head that ``lfm2-8b-a1b-ep4`` trains: ``jax.numpy``, float32, every
matrix product at ``jax.default_matmul_precision("highest")``, no kernel, no
sort, no cache, a dense mask; the convolution is the definition itself, a
position at a time; loss and gradients by autodiff; Adam in NumPy
(``reference/mla_moe.adam_step``). Independent of ``multiverso_tpu``: it
shares the parameters' names and shapes and nothing else. The rounding
control (``rounded_operands``) is ``reference/mla_moe``'s, so that one
switch rounds every reference; :func:`conv_control` is this file's own.

The equations (LFM2-8B-A1B's ``config.json``, ``model_type`` ``lfm2_moe``).
``c`` is the configuration file's dictionary, with the file's own keys.
The block, the convolution mixer, the attention and the tie are the
installed ``transformers/models/lfm2/modeling_lfm2.py``'s (the dense
family's file; this machine has no ``lfm2_moe`` directory). What no
installed file bears out is marked (+) and recorded in the configuration's
``assumed``.

* block (``Lfm2DecoderLayer``): ``h = x + Mixer(RMSNorm_operator(x))``, ``y
  = h + F(RMSNorm_ffn(h))``, eps ``norm_eps``; two input norms, no output
  norm; the model ends in one RMSNorm and a head TIED to the embedding
  (``Lfm2Config.tie_word_embeddings`` defaults ``True``): logits ``h
  Emb^T``. Block ``i`` of the run is layer ``layers_run[i]`` of the
  published ``layer_types``.
* conv mixer (``Lfm2ShortConv.slow_forward``): ``[B | C | x'] = u W_in`` (D
  -> 3D, no bias, split in that order); ``z = B * x'``; ``c_t = sum_{i <
  L} w[i] * z_{t - (L - 1) + i}``, ``L = conv_L_cache`` taps a channel,
  ``z`` zero before the sequence's first position, no bias
  (``conv_bias`` false), no activation; ``o = (C * c) W_out``.
* attention (``Lfm2Attention``): q ``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``, k and v ``num_key_value_heads``,
  no bias; an RMSNorm over every head of q and of k, one gain each, BEFORE
  the positions; rotary (``rope_theta``, element ``i`` paired with ``i +
  head/2``) on q and k; query head ``h`` reads key-value head ``h //
  (heads / key-value heads)``; causal softmax over ``sqrt(head)``; ``o
  W_o``.
* dense FFN (``Lfm2MLP``): ``w2(silu(w1 u) * w3 u)`` at
  ``intermediate_size`` AS THE KEY GIVES IT (the dense family's
  ``block_auto_adjust_ff_dim`` is not among the row's keys and is not
  applied), in the first ``num_dense_layers`` blocks.
* expert layer (+): ``s = sigmoid(u W_r^T)`` over all
  ``published.num_experts``; the ``num_experts_per_tok`` largest of ``s +
  b`` chosen (``use_expert_bias``: ``b`` chooses and does not weigh);
  gates ``s_chosen / (sum(s_chosen) + 1e-6) * routed_scaling_factor``
  (``norm_topk_prob``); result the sum over the chosen experts GIVEN
  (numbers ``offset`` to ``offset + n - 1``) of ``g_e w2_e(silu(w1_e u) *
  w3_e u)`` at ``moe_intermediate_size``: what the others would add is
  left out. No shared expert. ``b`` takes no gradient;
  ``reference/mla_moe.bias_rule`` is its update.
* Loss: mean cross-entropy over the positions that have a next token.

Departures, each for memory alone and none of them changes a number: with
``lean=True`` a sequence, a block, a head of attention and a block of
``LEAN_ROWS`` of a head's query rows are each computed under
``jax.checkpoint`` and in a ``lax.map``, the given experts are applied one
after another and the cross-entropy is summed in blocks, as
``reference/afmoe``'s. The router is stored a row an expert ([E, D]), the
convolution a row a tap ([L, D]: ``w[i]`` is ``conv.weight[:, 0, i]``).
"""

from __future__ import annotations

import contextlib
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import _ce_sum, _head, _sub
from benchmark.reference.mla_moe import (MARGINS, _mm, adam_step, bias_rule,
                                         mlp, rms, rope, rounded_operands)

__all__ = ["MARGINS", "adam_step", "bias_rule", "rounded_operands",
           "conv_control", "logits", "loss", "loss_and_grads"]

# What the convolution is computed as: ``None`` (the definition) or
# ``"taps_reversed"``: see ``conv_control``.
_CONV = None


@contextlib.contextmanager
def conv_control(how):
    """While this holds (it is read when a function is TRACED), the
    convolution computes as a faulty program's would, for the comparison's
    control (``lm_conv_control.py``), which has to tell it apart.
    ``"taps_reversed"`` reads the taps in the other order (``w[i]`` on
    ``z_{t - i}``): a program that took the stored row a tap the other way
    round."""
    global _CONV
    before, _CONV = _CONV, how
    try:
        yield
    finally:
        _CONV = before


def causal_conv(z, w):
    """``c_t = sum_i w[i] * z_{t - (L - 1) + i}`` a position at a time: z
    [S, D], w [L, D]; the carry is the last ``L - 1`` positions' ``z``,
    zeros before the first."""
    taps = w.shape[0]
    if _CONV == "taps_reversed":
        w = w[::-1]

    def step(past, zt):             # past [L - 1, D], oldest first
        seen = jnp.concatenate([past, zt[None]], 0)
        return seen[1:], jnp.sum(w * seen, 0)

    return jax.lax.scan(step, jnp.zeros((taps - 1, z.shape[1])), z)[1]


def short_conv(u, p):
    """u [S, D] -> [S, D]."""
    gate_in, gate_out, x = jnp.split(_mm(u, p["win"]), 3, axis=-1)
    return _mm(gate_out * causal_conv(gate_in * x, p["conv_w"]), p["wout"])


def attention(u, p, c, lean=False):
    """u [S, D] -> [S, D]: causal, grouped-query, q/k-normed, rotary."""
    s = u.shape[0]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d, eps = c["hidden_size"] // h, c["norm_eps"]
    q = rms(_mm(u, p["wq"]).reshape(s, h, d), p["q_norm"], eps)
    k = rms(_mm(u, p["wk"]).reshape(s, hkv, d), p["k_norm"], eps)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    one = lambda q, k, v: _head(q, k, v, 0, lean)
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * d), p["wo"])


def route(u, router, bias, c):
    """gates [S, E] (0 where not chosen), counts [E] (tokens that chose
    each expert), ties [len(MARGINS)]: the tokens whose gap between the
    last chosen and the first unchosen ``s + b`` is under each margin."""
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ router.T)
    top, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias)[None, :], k + 1)
    chosen = jnp.zeros_like(s).at[
        jnp.arange(u.shape[0])[:, None], idx[:, :k]].set(1.0)
    picked = s * chosen
    gates = c["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-6)
    gap = jax.lax.stop_gradient(top[:, k - 1] - top[:, k])
    ties = jnp.stack([jnp.sum(gap < m) for m in MARGINS]).astype(jnp.int32)
    return gates, chosen.sum(0).astype(jnp.int32), ties


def routed_share(u, p, bias, c, offset, n_given, lean=False):
    """The expert layer: what experts ``offset .. offset + n_given - 1``
    give (``p["eg"]``, ``p["eu"]`` [n_given, D, F], ``p["ed"]`` [n_given,
    F, D]). Every given expert is applied to every token, and its result
    taken under its gate (0 where the token did not choose it). Returns
    (result, (counts, ties))."""
    gates, counts, ties = route(u, p["router"], bias, c)
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
    return out, (counts, ties)


def block(x, p, ffn, c, kind: str, lean=False):
    eps = c["norm_eps"]
    u = rms(x, p["attn_norm"], eps)
    h = x + (short_conv(u, p) if kind == "conv"
             else attention(u, p, c, lean))
    f, aux = ffn(rms(h, p["ffn_norm"], eps), p)
    return h + f, aux


def layer_kinds(c) -> Tuple[str, ...]:
    """The mixer kind of every block run: the published ``layer_types`` at
    ``layers_run``."""
    return tuple(c["layer_types"][i] for i in c["layers_run"])


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f, d = c["num_experts"], c["moe_intermediate_size"], c["hidden_size"]
    return dict(p, eg=p["eg"].reshape(h, d, f), eu=p["eu"].reshape(h, d, f),
                ed=p["ed"].reshape(h, f, d))


def trunk(params, bias, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (the last block's output [S, D],
    [each expert layer's (counts, ties)])."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    offset, n_given = c.get("expert_offset", 0), c["num_experts"]
    dense_ffn = lambda u, q: (mlp(u, q["wg"], q["wu"], q["wd"]), None)
    x = params["embed"][tokens]
    aux = []
    for i, kind in enumerate(layer_kinds(c)):
        p = _sub(params, f"L{i}")
        if i < c["num_dense_layers"]:
            x, _ = wrap(lambda x, p, kind=kind: block(
                x, p, dense_ffn, c, kind, lean))(x, p)
            continue
        sparse = lambda x, p, b, kind=kind: block(
            x, p, lambda u, q: routed_share(u, q, b, c, offset, n_given,
                                            lean), c, kind, lean)
        x, a = wrap(sparse)(x, _experts_3d(p, c), bias[len(aux)])
        aux.append(a)
    return x, aux


def logits(params, bias, tokens, c):
    """One sequence ``tokens`` [S] -> [S, V]: the final norm, then the
    embedding as the head."""
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(params, bias, tokens, c)
        return _mm(rms(x, params["final_norm"], c["norm_eps"]),
                   params["embed"].T)


def sequence_loss(params, bias, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of CE over the positions with a
    target, counts [layers, E], ties [layers, len(MARGINS)]), a row an
    expert layer."""
    s = tokens.shape[0]
    x, aux = trunk(params, bias, tokens, c, lean)
    # the head is the embedding; _ce_sum reads the final norm's eps under
    # afmoe's key
    main = _ce_sum(x, params["final_norm"], params["embed"],
                   jnp.roll(tokens, -1), (jnp.arange(s) < s - 1).astype(
                       jnp.float32), {"rms_norm_eps": c["norm_eps"]}, lean)
    return (main,) + tuple(jnp.stack(t) for t in zip(*aux))


def loss(params, bias, tokens, c, lean=False):
    """tokens [B, S] -> (loss, (counts [layers, E], ties [layers,
    len(MARGINS)])), float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        one = lambda t: sequence_loss(params, bias, t, c, lean)
        if lean:
            main, counts, ties = jax.lax.map(jax.checkpoint(one), tokens)
        else:
            main, counts, ties = jax.vmap(one)(tokens)
        return main.sum() / (b * (s - 1)), (counts.sum(0), ties.sum(0))


def loss_and_grads(params, bias, tokens, c, lean=False):
    """(loss, counts, ties, gradients by name)."""
    (value, (counts, ties)), grads = jax.value_and_grad(
        lambda p: loss(p, bias, tokens, c, lean), has_aux=True)(params)
    return value, counts, ties, grads


def route_alone(u, router, bias, c):
    """``route`` on its own, at the reference's precision: (counts [E],
    ties [len(MARGINS)]) for an input ``u`` [S, D]."""
    with jax.default_matmul_precision("highest"):
        _, counts, ties = route(u, router, bias, c)
        return counts, ties
