"""Plain reference of the four-stream, hyper-connected MLA / routed-experts
decoder that ``xing4.0-29b-a4b-ep8`` trains: ``jax.numpy``, float32, every
matrix product at ``jax.default_matmul_precision("highest")``, no kernel,
no sort, no cache, no rematerialisation (but ``lean``, below); loss and
gradients by autodiff; Adam in NumPy (``reference/mla_moe.adam_step``).
Independent of ``multiverso_tpu``: it shares the parameters' names and
shapes and nothing else. Router, experts' share, gated MLP, norm and the
rounding control (``rounded_operands``) are ``reference/mla_moe``'s, so
that one switch rounds every reference; the stream maps, the expansion, the
reduction and the attention are this file's own.

The equations (Xing4.0-29B-A4B's ``config.json``, ``model_type``
``xing4_0``; the residual path per arXiv:2512.24880 equations 7 and 8, on
arXiv:2409.19606). ``c`` is the configuration file's dictionary, with the
file's own keys. What the keys do not say is the configuration's
``assumed``, marked (+) here. ``X`` [n, C] is one position's streams, n =
``hc_mult``.

* in and out (+): ``X_0`` = ``Emb(t)`` copied to all n streams; after the
  last block the streams are SUMMED to one vector, which goes to the final
  norm and the head.
* a sublayer ``F`` with its own ``phi`` [n^2 + 2n, nC] (a row an output:
  pre (n), post (n), res (n^2, row by row)), ``b`` [n^2 + 2n], ``alpha``
  [3]: ``x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)``; ``h = x~
  phi^T``; ``H_pre = sigmoid(alpha_0 h_pre + b_pre)``; ``H_post = 2
  sigmoid(alpha_1 h_post + b_post)``; ``M = exp(clip(alpha_2 mat(h_res) +
  b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))``, then
  ``hc_sinkhorn_iters`` times: every column over (its sum + ``hc_eps``),
  then every row over (its sum + ``hc_eps``): ``H_res``. ``u = H_pre X``;
  ``y = F(RMSNorm(u))`` with the sublayer's own gain; ``X' = H_res X +
  outer(H_post, y)``. A block is the attention sublayer, then the
  feed-forward sublayer (the gated MLP in the first
  ``first_k_dense_replace`` layers, the expert layer after).
* attention: MLA as ``reference/mla_moe`` states it, at a query and key
  head of ``qk_nope_head_dim + qk_rope_head_dim`` and a value head of
  ``v_head_dim``; rotary frequencies by YaRN (:func:`frequencies`), cos
  and sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
  (1 here), ``mscale(f, m) = 0.1 m ln(f) + 1``; scores times
  ``mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)`` (+: DeepSeek-V3's
  modelling convention).
* expert layer, loss: ``reference/mla_moe``'s.
* the prediction module (+): ``eh_proj([RMSNorm(Emb(t_{i+1})) |
  RMSNorm(h_i)])`` with ``h_i`` the trunk's SUMMED streams; the result is
  copied to all n streams, goes through one block of the expert kind under
  hyper-connections of its own, and is summed before its output norm.

Departures, for memory alone: with ``lean=True`` a sequence, a block, a
head of attention and the head's loss are each computed under
``jax.checkpoint`` and a head in a ``lax.map``. Rotary pairs element ``i``
with ``i + rope/2``.

``maps_control`` computes the maps as a faulty program would, for the
comparison's controls (``benchmark/lm_hc_control.py``).
"""

from __future__ import annotations

import contextlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mla_moe import (MARGINS, _experts_3d, _mm, _sub,
                                         adam_step, bias_rule, expert_layer,
                                         layer_names, mlp, rms, route_alone,
                                         rounded_operands, routed_share)

__all__ = ["MARGINS", "adam_step", "bias_rule", "rounded_operands",
           "route_alone", "routed_share", "loss", "loss_and_grads",
           "stream_maps", "maps_control", "CONTROLS"]

# how the maps are computed: ``None`` as the equations say, or as one of
# these faulty programs would (read when a function is TRACED)
CONTROLS = ("static_maps", "no_sinkhorn", "post_unscaled")
_MAPS = None


@contextlib.contextmanager
def maps_control(how):
    """While this holds the stream maps are computed wrongly in one way:
    ``static_maps`` (alpha = 0: the maps lose their input-dependent part),
    ``no_sinkhorn`` (``H_res`` = the row softmax of the clipped scores: one
    normalisation, rows sum to 1 and columns do not), ``post_unscaled``
    (``H_post`` without its factor 2); ``None``: as the equations say."""
    global _MAPS
    if how is not None and how not in CONTROLS:
        raise ValueError(f"no control named {how!r}")
    before, _MAPS = _MAPS, how
    try:
        yield
    finally:
        _MAPS = before


def stream_maps(x, p, c) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [S, n, C] and one sublayer's ``hc_phi``, ``hc_b``, ``hc_alpha``
    -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    s, n, _ = x.shape
    flat = x.reshape(s, -1)
    normed = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                  + c["rms_norm_eps"])
    h = normed @ p["hc_phi"].T
    alpha = p["hc_alpha"] * (0.0 if _MAPS == "static_maps" else 1.0)
    b = p["hc_b"]
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + b[:n])
    post = jax.nn.sigmoid(alpha[1] * h[:, n:2 * n] + b[n:2 * n]) * (
        1.0 if _MAPS == "post_unscaled" else 2.0)
    scores = jnp.clip(alpha[2] * h[:, 2 * n:] + b[2 * n:],
                      c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]
                      ).reshape(s, n, n)
    if _MAPS == "no_sinkhorn":
        return pre, post, jax.nn.softmax(scores, -1)
    m = jnp.exp(scores)
    for _ in range(c["hc_sinkhorn_iters"]):
        m = m / (m.sum(1, keepdims=True) + c["hc_eps"])     # columns
        m = m / (m.sum(2, keepdims=True) + c["hc_eps"])     # rows
    return pre, post, m


def sublayer(x, p, branch: str, f, c):
    """One hyper-connected sublayer on the streams x [S, n, C]: ``f`` takes
    the normed mix [S, C] and returns (y [S, C], aux)."""
    pre, post, res = stream_maps(x, _sub(p, branch), c)
    u = jnp.einsum("si,sic->sc", pre, x)
    y, aux = f(rms(u, p[branch + "_norm"], c["rms_norm_eps"]))
    return (jnp.einsum("sij,sjc->sic", res, x)
            + post[:, :, None] * y[:, None, :]), aux


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def frequencies(d: int, c) -> Tuple[np.ndarray, float]:
    """(the d/2 rotary frequencies, the factor on cos and sin) under the
    file's ``rope_scaling``.

    YaRN (Peng et al. 2023, as DeepSeek-V3's modelling code computes it):
    dimension ``i`` turns ``L theta^(-2i/d) / (2 pi)`` times within the
    original length ``L``; the dimension that turns ``r`` times is ``d
    ln(L / (2 pi r)) / (2 ln theta)``. With ``low`` the floor of that for
    ``beta_fast`` turns and ``high`` the ceiling for ``beta_slow`` (both
    kept inside 0 .. d - 1), ``ramp_i = clip((i - low) / (high - low), 0,
    1)``: a dimension at or under ``low`` keeps its frequency, one at or
    over ``high`` has it divided by ``factor``, those between blend."""
    theta, sc = float(c["rope_theta"]), c["rope_scaling"]
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if sc is None:
        return plain, 1.0
    if sc["type"] != "yarn":
        raise ValueError(f"no rotary scaling named {sc['type']!r}")
    length = sc["original_max_position_embeddings"]

    def turning(turns):
        return d * np.log(length / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(turning(sc["beta_fast"])), 0)
    high = min(np.ceil(turning(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return (plain / sc["factor"] * ramp + plain * (1 - ramp),
            _mscale(sc["factor"], sc["mscale"])
            / _mscale(sc["factor"], sc["mscale_all_dim"]))


def softmax_scale(c) -> float:
    """What the scores are multiplied by."""
    sc = c["rope_scaling"]
    m = 1.0 if sc is None else _mscale(sc["factor"], sc["mscale_all_dim"])
    return m * m / np.sqrt(c["qk_nope_head_dim"] + c["qk_rope_head_dim"])


def rope(x, c):
    """x [S, ..., R]: position along axis 0."""
    s, r = x.shape[0], x.shape[-1]
    freq, factor = frequencies(r, c)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head(q, k, v, scale):
    """One head, causal: q, k [S, dq], v [S, dv]; scores times ``scale``."""
    s = q.shape[0]
    scores = _mm(q, k.T) * scale
    scores = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :],
                       scores, -jnp.inf)
    return _mm(jax.nn.softmax(scores, -1), v)


def mla(u, p, c, lean=False):
    """u [S, D] -> [S, D]."""
    s = u.shape[0]
    h = c["num_attention_heads"]
    nope, r, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    q = _mm(rms(_mm(u, p["wdq"]), p["q_norm"], eps),
            p["wuq"]).reshape(s, h, nope + r)
    down = _mm(u, p["wdkv"].T)
    c_kv, k_r = rms(down[:, :rank], p["kv_norm"], eps), down[:, rank:]
    kv = _mm(c_kv, p["wukv"]).reshape(s, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(rope(k_r, c)[:, None, :], (s, h, r))], -1)
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, kv[..., nope:]))
    one = lambda q, k, v: _head(q, k, v, softmax_scale(c))
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * dv), p["wo"])


def block(x, p, ffn, c, lean=False):
    """x [S, n, C] -> (x', ffn's aux): the attention sublayer, then the
    feed-forward sublayer ``ffn(u, p) -> (y, aux)``."""
    x, _ = sublayer(x, p, "attn", lambda u: (mla(u, p, c, lean), None), c)
    return sublayer(x, p, "ffn", lambda u: ffn(u, p), c)


def expand(x, c):
    """[S, C] -> [S, n, C]: a copy a stream."""
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], c["hc_mult"], x.shape[1]))


def sequence_loss(params, bias, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of main CE, sum of module CE,
    counts [layers, E], ties [layers, len(MARGINS)]); the sums are over
    the positions with a target."""
    eps = c["rms_norm_eps"]
    offset, n_given = c.get("expert_offset", 0), c["n_routed_experts"]
    wrap = jax.checkpoint if lean else (lambda f: f)
    dense_ffn = lambda u, q: (mlp(u, q["wg"], q["wu"], q["wd"]), None)

    def sparse_ffn(b):
        return lambda u, q: expert_layer(u, q, b, c, offset, n_given)

    s = tokens.shape[0]
    dense, sparse = layer_names(c)
    x = expand(params["embed"][tokens], c)
    for name in dense:
        x, _ = wrap(lambda x, p: block(x, p, dense_ffn, c, lean))(
            x, _sub(params, name))
    counts = []
    for row, name in enumerate(sparse):
        if name == "mtp":
            continue
        x, cnt = wrap(lambda x, p, b: block(x, p, sparse_ffn(b), c, lean))(
            x, _experts_3d(_sub(params, name), c), bias[row])
        counts.append(cnt)
    x = x.sum(1)

    def ce_sum(hidden, norm, head, targets):
        logp = jax.nn.log_softmax(_mm(rms(hidden, norm, eps), head.T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    main = wrap(ce_sum)(x[: s - 1], params["final_norm"], params["head"],
                        tokens[1:])
    module = jnp.zeros(())
    if "mtp" in sparse:
        p = _experts_3d(_sub(params, "mtp"), c)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        joined = jnp.concatenate([rms(params["embed"][nxt], p["enorm"], eps),
                                  rms(x, p["hnorm"], eps)], -1)
        y, cnt = wrap(lambda y, p, b: block(y, p, sparse_ffn(b), c, lean))(
            expand(_mm(joined, p["eh_proj"]), c), p, bias[len(sparse) - 1])
        counts.append(cnt)
        module = wrap(ce_sum)(y.sum(1)[: s - 2], p["out_norm"],
                              params["head"], tokens[2:])
    return (main, module, jnp.stack([a for a, _ in counts]),
            jnp.stack([b for _, b in counts]))


def loss(params, bias, tokens, c, lean=False):
    """tokens [B, S] -> (loss, (counts [layers, E], ties [layers,
    len(MARGINS)])), float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        one = lambda t: sequence_loss(params, bias, t, c, lean)
        if lean:
            main, module, counts, ties = jax.lax.map(jax.checkpoint(one),
                                                     tokens)
        else:
            main, module, counts, ties = jax.vmap(one)(tokens)
        total = main.sum() / (b * (s - 1))
        if c["num_nextn_predict_layers"]:
            total = total + c["mtp_loss_weight"] * module.sum() / (b * (s - 2))
        return total, (counts.sum(0), ties.sum(0))


def loss_and_grads(params, bias, tokens, c, lean=False):
    """(loss, counts, ties, gradients by name)."""
    (value, (counts, ties)), grads = jax.value_and_grad(
        lambda p: loss(p, bias, tokens, c, lean), has_aux=True)(params)
    return value, counts, ties, grads
