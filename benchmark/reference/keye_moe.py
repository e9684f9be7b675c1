"""Plain reference of the decoder that ``keye-vl-2.0-30b-a3b-ep8`` trains:
grouped-query attention over the keys a learned indexer selects for every
query, the indexer's own term in the loss, and routed experts alone under
a softmax route. ``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no cache, whole
[S, S] arrays, ``jax.lax.top_k`` for the selection; loss and gradients by
autodiff; Adam in NumPy (``reference/mla_moe.adam_step``). Independent of
``multiverso_tpu``: it shares the parameters' names and shapes and nothing
else. The rounding control (``rounded_operands``) is
``reference/mla_moe``'s; the route, the balance term and the held experts'
partial sum are ``reference/gqa_window_moe``'s (the same family's).

The equations. ``c`` is the configuration file's dictionary, with the
file's own keys (``Qwen3MoeConfig``'s and ``sa_config``). Checked against
the installed ``transformers`` 4.57.6 where it has the code
(``models/qwen3_moe/modeling_qwen3_moe.py``: ``Qwen3MoeDecoderLayer``,
``Qwen3MoeAttention`` :147-165, ``Qwen3MoeSparseMoeBlock`` :233-236;
``models/qwen2_vl/modeling_qwen2_vl.py``:
``apply_multimodal_rotary_pos_emb`` :156-195). The indexer and its loss
are DeepSeek-V3.2-Exp's (its technical report and the class ``Indexer`` of
the ``inference/model.py`` it released) at this model's sizes; neither is
on this machine, and the points marked (+) are this repository's reading
where the configuration's keys do not decide.

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``,
  eps ``rms_norm_eps``; a final RMSNorm; an untied head.
* projections: ``q = u W_q`` -> ``num_attention_heads`` heads of
  ``head_dim``; ``k = u W_k``, ``v = u W_v`` -> ``num_key_value_heads``
  heads; no bias; an RMSNorm over ``head_dim`` on every head of q and of k,
  one gain each, BEFORE the positions (the installed class has them
  unconditionally).
* positions (:func:`rope_by_axis`): three position ids a token (time,
  height, width); of the ``head_dim / 2`` frequencies at ``rope_theta`` the
  first ``mrope_section[0]`` turn by the first id, the next
  ``mrope_section[1]`` by the second, the rest by the third; element ``i``
  pairs with ``i + head_dim / 2``. A text token's three ids are equal, and
  that is plain rotary: the cell trains on token ids alone.
* the indexer, on ``u' = stop_gradient(u)``: ``qI = u' W_qI``
  (``indexer_num_heads`` x ``indexer_head_dim``; (+) from the block's
  normed input: this model has no query latent for DSA's ``wq_b`` to
  read); ``kI = LayerNorm(u' W_kI)`` (ONE head, gain and bias, eps
  ``rms_norm_eps``; (+) the norm and its eps); (+) plain rotary at
  ``rope_theta`` over ALL ``indexer_head_dim`` dimensions of ``qI`` and
  ``kI``, half-split pairing (DSA turns 64 of its 128); ``w = u' W_w`` x
  ``indexer_num_heads^-0.5`` x ``indexer_head_dim^-0.5``; ``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])``.
* the selection: ``S_t`` = the ``min(topk, t + 1)`` positions ``s <= t``
  with the largest ``I[t, s]``, a tie to the lower ``s``
  (``jax.lax.top_k``'s rule). (+) ``q_chunk_size`` / ``kv_chunk_size`` are
  read as the tiles scores and selection are computed in: no equation has
  them.
* core: ``o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g(h)] /
  sqrt(head_dim)) v[s, g(h)]``, query head ``h`` reading key-value head
  ``g(h) = h // (heads / key-value heads)``; then ``o W_o``.
* the indexer's term: ``pbar[t, s]`` the mean over the query heads of the
  core's probabilities, held constant; ``LI = mean_t sum_{s in S_t} pbar
  (log pbar - log softmax_{S_t}(I))`` (DSA's sparse training stage). ``u'``
  and ``pbar`` are constants to it, so ``LI`` moves the indexer's tensors
  alone, and the cross-entropy, through a selection that has no
  derivative, moves none of them.
* experts: ``gqa_window_moe.routed_share`` (float32 softmax over all
  ``published.num_experts``, the ``num_experts_per_tok`` largest,
  renormalised; ``w2(silu(w1 u) * w3 u)``; the sum over the experts GIVEN).
* loss: mean cross-entropy over the positions that have a next token +
  ``router_aux_loss_coef`` x the layers' balance terms + (+)
  ``index_loss_coef`` x the layers' ``LI``.

Departures: no vision tower (the catalog's configuration is the language
model's); the program's indexer computes in bfloat16 operands where DSA's
own runs in FP8. ``selection`` [layers, B, S, S] puts a given selection in
the place of the reference's own in every layer (the comparison runs the
reference under the PROGRAM's, so that a key bfloat16 decides the other
way is no difference downstream), and the reference's own is still made
and held against it: ``differ`` counts the keys that differ, ``far`` is
the largest distance of such a key's score from its row's threshold, in
units of the row's own spread (the standard deviation of its causal
scores), and how many of them lie beyond ``BEYOND`` such units (from the
second layer on a token that bfloat16 sends to another expert arrives
with another hidden state, and its whole row of scores with it: such
rows set the largest distance, and are few).

For memory alone, and changing no number: with ``lean=True`` a sequence,
a block and ``ROW_BLOCK`` query rows of attention are each computed under
``jax.checkpoint`` and in a ``lax.map``, and the heads in a scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gqa_window_moe import (_experts_3d, _sub,
                                                balance_term, route_alone,
                                                routed_share)
from benchmark.reference.mla_moe import (MARGINS, _mm, _product, _r,
                                         adam_step, rms, rounded_operands)

__all__ = ["MARGINS", "adam_step", "rounded_operands", "loss",
           "loss_and_grads", "route_alone", "rope_by_axis", "selections"]

ROW_BLOCK = 512         # query rows at a time under ``lean``
# a differing key farther than this from its row's threshold, in units of
# the row's spread, is counted apart (``beyond``)
BEYOND = 0.1


def rope_by_axis(x, positions, sections, theta: float):
    """x [S, H, R] under ``positions`` [A, S] (one id an axis a token):
    frequency ``i`` of the R/2 turns by the id of the axis whose section
    holds ``i``; element ``i`` pairs with ``i + R/2``."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    axis = np.repeat(np.arange(len(sections)), sections)        # [R/2]
    assert axis.size == r // 2, (sections, r)
    ids = positions.astype(jnp.float32).T[:, axis]              # [S, R/2]
    ang = (ids * inv[None, :])[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def text_positions(s: int, axes: int = 3):
    """A text token's ids: its place in the sequence on every axis."""
    return jnp.broadcast_to(jnp.arange(s)[None, :], (axes, s))


def indexer(u, p, c):
    """u [S, D] -> (qI [S, Hi, Di], kI [S, Di], w [S, Hi])."""
    sa, s = c["sa_config"], u.shape[0]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    assert sa["indexer_num_kv_heads"] == 1
    qi = _mm(u, p["wq_i"]).reshape(s, hi, di)
    ki = _mm(u, p["wk_i"])
    mean = ki.mean(-1, keepdims=True)
    var = ((ki - mean) ** 2).mean(-1, keepdims=True)
    ki = ((ki - mean) / jnp.sqrt(var + c["rms_norm_eps"]) * p["k_i_norm"]
          + p["k_i_bias"])
    where = text_positions(s, 1)
    qi = rope_by_axis(qi, where, (di // 2,), c["rope_theta"])
    ki = rope_by_axis(ki[:, None, :], where, (di // 2,), c["rope_theta"])[:, 0]
    return qi, ki, _mm(u, p["ww_i"]) * hi ** -0.5 * di ** -0.5


def index_rows(qi, ki, w):
    """``I`` for the query rows given: qi [R, Hi, Di], w [R, Hi], ki [S,
    Di] -> [R, S]: the double sum, over the heads and over a head's
    dimensions."""
    dots = _product(jnp.einsum("rhd,sd->hrs", _r(qi), _r(ki)))
    return jnp.sum(w.T[:, :, None] * jax.nn.relu(dots), 0)


def own_selection(index, first, topk: int):
    """(the selection [R, S] of rows ``first ..`` from their scores
    ``index`` [R, S], each row's threshold: its ``min(topk, t + 1)``-th
    largest causal score)."""
    r, s = index.shape
    t = first + jnp.arange(r)
    causal = jnp.arange(s)[None, :] <= t[:, None]
    top, at = jax.lax.top_k(jnp.where(causal, index, -jnp.inf),
                            min(topk, s))
    mine = jnp.zeros((r, s), bool).at[jnp.arange(r)[:, None], at].set(True)
    kth = jnp.take_along_axis(
        top, (jnp.minimum(min(topk, s), t + 1) - 1)[:, None], 1)
    return mine & causal, kth


def attention_rows(first, q, k, v, qi, w, ki, given, c, keep=False):
    """Query rows ``first .. first + R - 1`` of one layer: q [R, H, d], k,
    v [S, H, d] (key-value heads repeated), the indexer's rows and keys;
    ``given`` [R, S] a selection to use, or ``None`` for the reference's
    own. Returns (o [R, H, d], the rows' part of ``sum_t KL_t``, the keys
    in which own and used selection differ, [the largest distance of such a
    key's score from its row's threshold over the row's spread, how many of
    them lie beyond ``BEYOND``], and with
    ``keep`` the reference's own selection of the rows, int8 [R, S])."""
    index = index_rows(qi, ki, w)
    mine, kth = own_selection(index, first, c["sa_config"]["topk"])
    used = mine if given is None else given != 0
    t = first + jnp.arange(index.shape[0])
    causal = jnp.arange(index.shape[1])[None, :] <= t[:, None]
    spread = jnp.sqrt(jnp.sum(jnp.where(causal, (index - jnp.sum(
        jnp.where(causal, index, 0.0), -1, keepdims=True) / (t + 1)[:, None])
        ** 2, 0.0), -1, keepdims=True) / (t + 1)[:, None]) + 1e-30
    other = mine != used
    off = jnp.where(other, jnp.abs(index - kth) / spread, 0.0)
    far = jnp.stack([jnp.max(off), jnp.sum(off > BEYOND)])

    def head(pbar, qkv):
        qh, kh, vh = qkv
        p = jax.nn.softmax(jnp.where(
            used, _mm(qh, kh.T) / np.sqrt(qh.shape[-1]), -jnp.inf), -1)
        return pbar + jax.lax.stop_gradient(p), _mm(p, vh)

    pbar, o = jax.lax.scan(head, jnp.zeros(index.shape), tuple(
        x.transpose(1, 0, 2) for x in (q, k, v)))
    pbar = pbar / q.shape[1]
    logq = jax.nn.log_softmax(jnp.where(used, index, -jnp.inf), -1)
    kl = jnp.sum(jax.scipy.special.xlogy(pbar, pbar)
                 - pbar * jnp.where(used, logq, 0.0))
    return (o.transpose(1, 0, 2), kl, jnp.sum(other).astype(jnp.int32),
            jax.lax.stop_gradient(far),
            mine.astype(jnp.int8) if keep else jnp.zeros((), jnp.int8))


def attention(u, p, c, given=None, lean=False, keep=False):
    """u [S, D] -> ([S, D], LI, differ, far, own selection or 0)."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    sections = tuple(c["rope_scaling"]["mrope_section"])
    where = text_positions(s, len(sections))
    q = rms(_mm(u, p["wq"]).reshape(s, h, d), p["q_norm"], eps)
    k = rms(_mm(u, p["wk"]).reshape(s, hkv, d), p["k_norm"], eps)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    q = rope_by_axis(q, where, sections, theta)
    k = rope_by_axis(k, where, sections, theta)
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(x, h // hkv, axis=1) for x in (k, v))
    qi, ki, w = indexer(jax.lax.stop_gradient(u), p, c)
    rows = min(ROW_BLOCK, s) if lean else s
    assert s % rows == 0
    cut = lambda x: x.reshape((s // rows, rows) + x.shape[1:])

    def block_of_rows(xs):
        n, q_r, qi_r, w_r, given_r = xs
        return attention_rows(n * rows, q_r, k, v, qi_r, w_r, ki, given_r, c,
                              keep)

    xs = (jnp.arange(s // rows), cut(q), cut(qi), cut(w),
          None if given is None else cut(given))
    if lean:
        o, kl, differ, far, mine = jax.lax.map(
            jax.checkpoint(block_of_rows), xs)
    else:
        o, kl, differ, far, mine = jax.vmap(block_of_rows)(xs)
    far = jnp.stack([far[:, 0].max(), far[:, 1].sum()])
    return (_mm(o.reshape(s, h * d), p["wo"]), kl.sum() / s, differ.sum(),
            far, mine.reshape(s, s) if keep else mine[0])


def block(x, p, c, given=None, lean=False, keep=False):
    eps = c["rms_norm_eps"]
    a, term, differ, far, mine = attention(
        rms(x, p["attn_norm"], eps), p, c, given, lean, keep)
    h = x + a
    f, aux = routed_share(rms(h, p["ffn_norm"], eps), p, c,
                          c.get("expert_offset", 0), c["num_experts"])
    return h + f, aux + (term, differ, far, mine)


def trunk(params, tokens, c, selection=None, lean=False, keep=False):
    """One sequence ``tokens`` [S] -> (the last block's output [S, D],
    the layers' (counts, summed probabilities, ties, LI, differ, far, own
    selection [S, S] with ``keep``, else 0)); ``selection`` [layers, S, S]
    or ``None``."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    x = params["embed"][tokens]
    aux = []
    for i in range(c["num_hidden_layers"]):
        given = None if selection is None else selection[i]
        x, a = wrap(lambda x, p, given: block(x, p, c, given, lean, keep))(
            x, _experts_3d(_sub(params, f"L{i}"), c), given)
        aux.append(a)
    return x, tuple(jnp.stack(t) for t in zip(*aux))


def logits(params, tokens, c, selection=None):
    """tokens [B, S] -> logits [B, S, V] (tests)."""
    with jax.default_matmul_precision("highest"):
        def one(t, given):
            x, _ = trunk(params, t, c, given)
            return _mm(rms(x, params["final_norm"], c["rms_norm_eps"]),
                       params["head"].T)
        if selection is None:
            return jax.vmap(lambda t: one(t, None))(tokens)
        return jax.vmap(one)(tokens, jnp.moveaxis(selection, 1, 0))


def selections(params, tokens, c, lean=False):
    """The reference's own selection of every layer for ``tokens`` [B, S]:
    int8 [layers, B, S, S] (under ``rounded_operands``, what a step in that
    precision would select)."""
    with jax.default_matmul_precision("highest"):
        one = lambda t: trunk(params, t, c, None, lean, keep=True)[1][-1]
        out = jax.lax.map(one, tokens) if lean else jax.vmap(one)(tokens)
        return jnp.moveaxis(out, 0, 1)


def sequence_loss(params, tokens, c, selection=None, lean=False):
    wrap = jax.checkpoint if lean else (lambda f: f)
    s = tokens.shape[0]
    x, aux = trunk(params, tokens, c, selection, lean)
    aux = aux[:-1]

    def ce_sum(hidden, norm, head, targets):
        logp = jax.nn.log_softmax(
            _mm(rms(hidden, norm, c["rms_norm_eps"]), head.T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    main = wrap(ce_sum)(x[: s - 1], params["final_norm"], params["head"],
                        tokens[1:])
    return (main,) + aux


def loss(params, tokens, c, selection=None, lean=False):
    """tokens [B, S] -> (loss, (cross-entropy, counts [layers, E], ties
    [layers, len(MARGINS)], the balance terms [layers], the indexer's
    terms [layers], differ [layers], far [layers, 2]: the farthest
    differing key and the count beyond ``BEYOND``)), float32 at the
    highest matmul precision; ``selection`` [layers, B, S, S] or
    ``None``."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        if selection is None:
            one = lambda t: sequence_loss(params, t, c, None, lean)
            xs = tokens
        else:
            one = lambda x: sequence_loss(params, x[0], c, x[1], lean)
            xs = (tokens, jnp.moveaxis(selection, 1, 0))
        if lean:
            out = jax.lax.map(jax.checkpoint(one), xs)
        else:
            out = jax.vmap(one)(xs)
        main, counts, prob_sum, ties, index, differ, far = out
        counts, prob_sum = counts.sum(0), prob_sum.sum(0)
        terms = jnp.stack([balance_term(counts[i], prob_sum[i], b * s, c)
                           for i in range(counts.shape[0])])
        index = index.mean(0)                   # a mean over B x S rows
        ce = main.sum() / (b * (s - 1))
        total = (ce + c["router_aux_loss_coef"] * terms.sum()
                 + c["index_loss_coef"] * index.sum())
        far = jnp.stack([far[..., 0].max(0), far[..., 1].sum(0)], -1)
        return total, (ce, counts, ties.sum(0), terms, index,
                       differ.sum(0), far)


def loss_and_grads(params, tokens, c, selection=None, lean=False):
    """(loss, (cross-entropy, counts, ties, balance terms, indexer's
    terms, differ, far), gradients by name)."""
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, tokens, c, selection, lean), has_aux=True)(params)
    return value, aux, grads
