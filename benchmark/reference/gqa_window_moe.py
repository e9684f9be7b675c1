"""Plain reference of the grouped-query / window-and-full / routed-experts
decoder that ``mellum2-12b-a2.5b-ep4`` trains: ``jax.numpy``, float32,
every matrix product at ``jax.default_matmul_precision("highest")``, no
kernel, no sort, no cache, a dense [S, S] mask; loss and gradients by
autodiff; Adam in NumPy (``reference/mla_moe.adam_step``). Independent
of ``multiverso_tpu``: it shares the parameters' names and shapes and
nothing else. The rounding control (``rounded_operands``) is
``reference/mla_moe``'s, so that one switch rounds both references.

The equations (Mellum2-12B-A2.5B's ``config.json``, ``model_type``
``mellum``). ``c`` is the configuration file's dictionary, with the
file's own keys.

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``,
  eps ``rms_norm_eps``. The layers kept are the LAST ``num_hidden_layers``
  of ``layer_types`` (a period ends with its full layer).
* Attn: ``q = u W_q`` -> ``num_attention_heads`` heads of ``head_dim``;
  ``k = u W_k``, ``v = u W_v`` -> ``num_key_value_heads`` heads; rotary on
  q and k, pairing element ``i`` with ``i + head_dim/2``, by the layer
  kind's ``rope_parameters``: ``default`` is ``theta^(-2i/d)``; ``yarn``
  (written out in :func:`frequencies`) blends each frequency between
  itself and itself over ``factor`` and multiplies cos and sin by
  ``attention_factor``. Query head ``h`` reads key-value head ``h //
  (heads / key-value heads)``. Scores over ``sqrt(head_dim)``; position
  ``i`` sees ``j`` where ``0 <= i - j`` and, in a ``sliding_attention``
  layer, ``i - j < sliding_window``; softmax; ``o W_o``.
* Experts: ``p = softmax(u W_r^T)`` over all ``published.num_experts``;
  the ``num_experts_per_tok`` largest are chosen; gates ``p_e / sum of the
  chosen`` (``norm_topk_prob``); result ``sum over the chosen experts of
  g_e (silu(u W_g,e) * (u W_u,e)) W_d,e``, the sum taken over the experts
  GIVEN (numbers ``offset`` to ``offset + n - 1``): what the others would
  add is left out. No shared expert, no bias.
* Loss: mean cross-entropy over the positions that have a next token,
  plus ``router_aux_loss_coef`` x the sum over the layers of ``E x sum_e
  f_e P_e``: ``f_e`` the share of the batch's token-to-expert assignments
  that chose ``e`` (no gradient), ``P_e`` the mean of ``p_e`` over the
  batch's tokens, both over all E experts.

Departures, each for memory alone and none of them changes a number:
with ``lean=True`` a sequence, a block and a head of attention are each
computed under ``jax.checkpoint`` and in a ``lax.map``; a sequence hands
back its counts and the sum of its probabilities, and the load-balance
term is formed from the batch's. The router is stored a row an expert
([E, D]).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mla_moe import (MARGINS, _mm, _product, _r,
                                         adam_step, rms, rounded_operands)

__all__ = ["MARGINS", "adam_step", "rounded_operands", "loss",
           "loss_and_grads"]


def frequencies(d: int, rope: dict) -> Tuple[np.ndarray, float]:
    """(the d/2 rotary frequencies, the factor on cos and sin) of one
    layer kind's ``rope_parameters``.

    YaRN (Peng et al. 2023, as the family's modelling code computes it):
    dimension ``i`` turns ``L theta^(-2i/d) / (2 pi)`` times within the
    original length ``L``; the dimension that turns ``r`` times is ``d
    ln(L / (2 pi r)) / (2 ln theta)``. With ``low`` the floor of that for
    ``beta_fast`` turns and ``high`` the ceiling for ``beta_slow`` (both
    kept inside 0 .. d - 1), ``ramp_i = clip((i - low) / (high - low), 0,
    1)``: a dimension at or under ``low`` keeps its frequency, one at or
    over ``high`` has it divided by ``factor``, those between blend."""
    theta = float(rope["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rotary scaling named {rope['rope_type']!r}")
    length = rope["original_max_position_embeddings"]

    def turning(turns):
        return d * np.log(length / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(turning(rope["beta_fast"])), 0)
    high = min(np.ceil(turning(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return (plain / rope["factor"] * ramp + plain * (1 - ramp),
            float(rope["attention_factor"]))


def rope(x, params: dict):
    """x [S, H, R]: position along axis 0."""
    s, r = x.shape[0], x.shape[-1]
    freq, factor = frequencies(r, params)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])[:, None, :]
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head(q, k, v, window):
    """One head under the dense mask: q, k, v [S, d]; ``window`` 0 for a
    full layer."""
    i = jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(q.shape[0])[None, :]
    seen = (i >= j) & ((i - j < window) | (window == 0))
    scores = jnp.where(seen, _mm(q, k.T) / np.sqrt(q.shape[-1]), -jnp.inf)
    return _mm(jax.nn.softmax(scores, -1), v)


def attention(u, p, c, kind: str, lean=False):
    """u [S, D] -> [S, D]; ``kind`` is the layer's ``layer_types`` entry."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    rp = c["rope_parameters"][kind]
    window = c["sliding_window"] if kind == "sliding_attention" else 0
    q = rope(_mm(u, p["wq"]).reshape(s, h, d), rp)
    k = rope(_mm(u, p["wk"]).reshape(s, hkv, d), rp)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    one = lambda q, k, v: _head(q, k, v, window)
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * d), p["wo"])


def route(u, router, c):
    """gates [S, E] (0 where not chosen), counts [E] (tokens that chose
    each expert), the sum over the tokens of the probabilities [E], ties
    [len(MARGINS)]: the tokens whose gap between the last chosen and the
    first unchosen probability is under each margin."""
    k = c["num_experts_per_tok"]
    p = jax.nn.softmax(u @ router.T, -1)
    top, idx = jax.lax.top_k(p, k + 1)
    chosen = jnp.zeros_like(p).at[
        jnp.arange(u.shape[0])[:, None], idx[:, :k]].set(1.0)
    picked = p * chosen
    gates = picked / picked.sum(-1, keepdims=True)
    gap = jax.lax.stop_gradient(top[:, k - 1] - top[:, k])
    ties = jnp.stack([jnp.sum(gap < m) for m in MARGINS]).astype(jnp.int32)
    return gates, chosen.sum(0).astype(jnp.int32), p.sum(0), ties


def balance_term(counts, prob_sum, tokens: int, c):
    """``E x sum_e f_e P_e`` of one layer from the batch's counts [E] and
    summed probabilities [E] over its ``tokens``."""
    e = c["published"]["num_experts"]
    share = jax.lax.stop_gradient(counts.astype(jnp.float32)) / (
        tokens * c["num_experts_per_tok"])
    return e * jnp.sum(share * prob_sum / tokens)


def routed_share(u, p, c, offset, n_given):
    """What experts ``offset .. offset + n_given - 1`` give (``p["eg"]``,
    ``p["eu"]`` [n_given, D, F], ``p["ed"]`` [n_given, F, D]): every given
    expert is applied to every token, and its result taken under its gate
    (0 where the token did not choose it). Returns (result, (counts,
    summed probabilities, ties))."""
    gates, counts, prob_sum, ties = route(u, p["router"], c)
    hidden = (jax.nn.silu(_product(
        jnp.einsum("td,edf->etf", _r(u), _r(p["eg"]))))
              * _product(jnp.einsum("td,edf->etf", _r(u), _r(p["eu"]))))
    mine = gates[:, offset:offset + n_given].T[:, :, None]          # [e, t, 1]
    out = _product(jnp.einsum("etf,efd->td", _r(hidden * mine), _r(p["ed"])))
    return out, (counts, prob_sum, ties)


def block(x, p, c, kind: str, lean=False):
    eps = c["rms_norm_eps"]
    h = x + attention(rms(x, p["attn_norm"], eps), p, c, kind, lean)
    f, aux = routed_share(rms(h, p["ffn_norm"], eps), p, c,
                          c.get("expert_offset", 0), c["num_experts"])
    return h + f, aux


def layer_kinds(c) -> Tuple[str, ...]:
    return tuple(c["layer_types"][-c["num_hidden_layers"]:])


def _sub(params, prefix):
    return {k[len(prefix) + 1:]: v for k, v in params.items()
            if k.startswith(prefix + ".")}


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f, d = c["num_experts"], c["moe_intermediate_size"], c["hidden_size"]
    return dict(p, eg=p["eg"].reshape(h, d, f), eu=p["eu"].reshape(h, d, f),
                ed=p["ed"].reshape(h, f, d))


def sequence_loss(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of CE over the positions with a
    target, counts [layers, E], summed probabilities [layers, E], ties
    [layers, len(MARGINS)])."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    s = tokens.shape[0]
    x = params["embed"][tokens]
    aux = []
    for i, kind in enumerate(layer_kinds(c)):
        x, a = wrap(lambda x, p, kind=kind: block(x, p, c, kind, lean))(
            x, _experts_3d(_sub(params, f"L{i}"), c))
        aux.append(a)

    def ce_sum(hidden, norm, head, targets):
        logp = jax.nn.log_softmax(
            _mm(rms(hidden, norm, c["rms_norm_eps"]), head.T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    main = wrap(ce_sum)(x[: s - 1], params["final_norm"], params["head"],
                        tokens[1:])
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
