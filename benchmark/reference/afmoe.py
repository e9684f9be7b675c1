"""Plain reference of the gated, q/k-normed grouped-query / window-and-full
/ sigmoid-routed decoder that ``trinity-mini-ep8`` trains: ``jax.numpy``,
float32, every matrix product at ``jax.default_matmul_precision("highest")``,
no kernel, no sort, no cache, a dense mask; loss and gradients by autodiff;
Adam in NumPy (``reference/mla_moe.adam_step``). Independent of
``multiverso_tpu``: it shares the parameters' names and shapes and nothing
else. The rounding control (``rounded_operands``) is ``reference/mla_moe``'s,
so that one switch rounds every reference.

The equations (Trinity-Mini's ``config.json``, ``model_type`` ``afmoe``).
``c`` is the configuration file's dictionary, with the file's own keys.
What the catalog's keys do not say is the ``afmoe`` modelling code's as
the configuration's ``assumed`` records it, marked (+) here.

* embedding: ``x0 = Emb(t) * sqrt(hidden_size)`` where ``mup_enabled`` (+).
* block, every ``N`` an RMSNorm with its own gain, eps ``rms_norm_eps``:
  ``h = x + N_post_attn(Attn(N_in(x)))``, ``y = h + N_post_mlp(F(N_pre_mlp(
  h)))`` (the two post norms +). ``F`` is the gated MLP ``(silu(u W_g) * (u
  W_u)) W_d`` of width ``intermediate_size`` in the first
  ``num_dense_layers`` layers, ``Shared(u) + Routed(u)`` after them. The
  layers kept are the first ``num_dense_layers`` entries of ``layer_types``
  and then its LAST ``num_hidden_layers - num_dense_layers`` (a period ends
  with its full layer).
* Attn: ``q = u W_q`` -> ``num_attention_heads`` heads of ``head_dim``;
  ``k = u W_k``, ``v = u W_v`` -> ``num_key_value_heads`` heads; ``g = u
  W_gate`` (+); ``q = RMSNorm(q)``, ``k = RMSNorm(k)`` over ``head_dim``,
  one gain for q and one for k (+); rotary (``rope_theta``, element ``i``
  paired with ``i + head_dim/2``, no scaling) on q and k in a
  ``sliding_attention`` layer ONLY: a ``full_attention`` layer applies no
  position (+). Query head ``h`` reads key-value head ``h // (heads /
  key-value heads)``. Scores over ``sqrt(head_dim)``; position ``i`` sees
  ``j`` where ``0 <= i - j`` and, in a sliding layer, ``i - j <
  sliding_window``; softmax; ``o = core * sigmoid(g)``; ``o W_o``.
* Routed: ``s = sigmoid(u W_r^T)`` over all ``published.num_experts``
  (``score_func``); the ``num_experts_per_tok`` largest of ``s + b`` are
  chosen (``n_group`` 1: no group limit); gates ``s_chosen / (sum(s_chosen)
  + 1e-20) * route_scale`` (``route_norm``); result the sum over the chosen
  experts of ``g_e (silu(u W_g,e) * (u W_u,e)) W_d,e``, taken over the
  experts GIVEN (numbers ``offset`` to ``offset + n - 1``): what the others
  would add is left out. ``b`` takes no gradient; ``reference/mla_moe.
  bias_rule`` at ``load_balance_coeff`` is its update (+). ``Shared`` is
  one gated MLP of ``num_shared_experts x moe_intermediate_size``.
* Loss: mean cross-entropy over the positions that have a next token; no
  load-balance term.

Departures, each for memory alone and none of them changes a number: with
``lean=True`` a sequence, a block, a head of attention and a block of
``LEAN_ROWS`` of a head's query rows are each computed under
``jax.checkpoint`` and in a ``lax.map`` (16,384 x 16,384 float32 scores of
one head are 1 GB), the given experts are applied one after another (16 x
16,384 x 1,024 hidden floats are 1 GB an array), and the cross-entropy is
summed in blocks of ``LEAN_ROWS`` positions over all S positions, the last
of which has weight 0. The router is stored a row an expert ([E, D]).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mla_moe import (MARGINS, _mm, _product, _r,
                                         adam_step, bias_rule, mlp, rms,
                                         rope, rounded_operands)

__all__ = ["MARGINS", "adam_step", "bias_rule", "rounded_operands", "loss",
           "loss_and_grads"]

LEAN_ROWS = 2048


def _rows(q, k, v, first, window):
    """Query rows ``first ..`` of one head under the dense mask: q [R, d],
    k, v [S, d]; ``window`` 0 for a full layer."""
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = (i >= j) & ((i - j < window) | (window == 0))
    scores = jnp.where(seen, _mm(q, k.T) / np.sqrt(q.shape[-1]), -jnp.inf)
    return _mm(jax.nn.softmax(scores, -1), v)


def _head(q, k, v, window, lean=False):
    s, d = q.shape
    if not lean or s <= LEAN_ROWS or s % LEAN_ROWS:
        return _rows(q, k, v, 0, window)
    one = jax.checkpoint(lambda t: _rows(t[0], k, v, t[1], window))
    return jax.lax.map(one, (q.reshape(-1, LEAN_ROWS, d),
                             jnp.arange(0, s, LEAN_ROWS))).reshape(s, d)


def attention(u, p, c, kind: str, lean=False):
    """u [S, D] -> [S, D]; ``kind`` is the layer's ``layer_types`` entry."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    window = c["sliding_window"] if kind == "sliding_attention" else 0
    q = rms(_mm(u, p["wq"]).reshape(s, h, d), p["q_norm"], eps)
    k = rms(_mm(u, p["wk"]).reshape(s, hkv, d), p["k_norm"], eps)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    if kind == "sliding_attention":         # a full layer sees no position
        q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    one = lambda q, k, v: _head(q, k, v, window, lean)
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    o = o.transpose(1, 0, 2).reshape(s, h * d)
    return _mm(o * jax.nn.sigmoid(_mm(u, p["wgate"])), p["wo"])


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
    gates = c["route_scale"] * picked / (picked.sum(-1, keepdims=True)
                                         + 1e-20)
    gap = jax.lax.stop_gradient(top[:, k - 1] - top[:, k])
    ties = jnp.stack([jnp.sum(gap < m) for m in MARGINS]).astype(jnp.int32)
    return gates, chosen.sum(0).astype(jnp.int32), ties


def routed_share(u, p, bias, c, offset, n_given, lean=False):
    """The routed part alone: what experts ``offset .. offset + n_given -
    1`` give (``p["eg"]``, ``p["eu"]`` [n_given, D, F], ``p["ed"]``
    [n_given, F, D]). Every given expert is applied to every token, and its
    result taken under its gate (0 where the token did not choose it).
    Returns (result, (counts, ties))."""
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


def expert_layer(u, p, bias, c, offset, n_given, lean=False):
    """``Shared(u)`` + the given experts' part of the routed result."""
    out, aux = routed_share(u, p, bias, c, offset, n_given, lean)
    return mlp(u, p["sg"], p["su"], p["sd"]) + out, aux


def block(x, p, ffn, c, kind: str, lean=False):
    eps = c["rms_norm_eps"]
    h = x + rms(attention(rms(x, p["attn_norm"], eps), p, c, kind, lean),
                p["attn_post_norm"], eps)
    f, aux = ffn(rms(h, p["ffn_norm"], eps), p)
    return h + rms(f, p["ffn_post_norm"], eps), aux


def layer_kinds(c) -> Tuple[str, ...]:
    dense = c["num_dense_layers"]
    return (tuple(c["layer_types"][:dense])
            + tuple(c["layer_types"][dense - c["num_hidden_layers"]:]))


def _sub(params, prefix):
    return {k[len(prefix) + 1:]: v for k, v in params.items()
            if k.startswith(prefix + ".")}


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f, d = c["num_experts"], c["moe_intermediate_size"], c["hidden_size"]
    return dict(p, eg=p["eg"].reshape(h, d, f), eu=p["eu"].reshape(h, d, f),
                ed=p["ed"].reshape(h, f, d))


def embed_scale(c) -> float:
    return float(np.sqrt(c["hidden_size"])) if c["mup_enabled"] else 1.0


def _ce_sum(hidden, norm, head, targets, weights, c, lean):
    """Sum over positions of ``weights * CE``."""
    def part(hidden, targets, weights):
        logp = jax.nn.log_softmax(
            _mm(rms(hidden, norm, c["rms_norm_eps"]), head.T), -1)
        return -jnp.sum(weights * jnp.take_along_axis(
            logp, targets[:, None], -1)[:, 0])

    s, d = hidden.shape
    if not lean or s <= LEAN_ROWS or s % LEAN_ROWS:
        return part(hidden, targets, weights)
    return jax.lax.map(
        lambda t: jax.checkpoint(part)(*t),
        (hidden.reshape(-1, LEAN_ROWS, d), targets.reshape(-1, LEAN_ROWS),
         weights.reshape(-1, LEAN_ROWS))).sum()


def sequence_loss(params, bias, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of CE over the positions with a
    target, counts [layers, E], ties [layers, len(MARGINS)]), a row an
    expert layer."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    offset, n_given = c.get("expert_offset", 0), c["num_experts"]
    dense_ffn = lambda u, q: (mlp(u, q["wg"], q["wu"], q["wd"]), None)
    s = tokens.shape[0]
    x = params["embed"][tokens] * embed_scale(c)
    aux = []
    for i, kind in enumerate(layer_kinds(c)):
        p = _sub(params, f"L{i}")
        if i < c["num_dense_layers"]:
            x, _ = wrap(lambda x, p, kind=kind: block(
                x, p, dense_ffn, c, kind, lean))(x, p)
            continue
        sparse = lambda x, p, b, kind=kind: block(
            x, p, lambda u, q: expert_layer(u, q, b, c, offset, n_given,
                                            lean), c, kind, lean)
        x, a = wrap(sparse)(x, _experts_3d(p, c), bias[len(aux)])
        aux.append(a)
    main = _ce_sum(x, params["final_norm"], params["head"],
                   jnp.roll(tokens, -1), (jnp.arange(s) < s - 1).astype(
                       jnp.float32), c, lean)
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
