"""Plain reference of the MLA / routed-experts / multi-token-prediction
decoder that ``glm-4.7-flash-ep8`` trains: ``jax.numpy``, float32, every
matrix product at ``jax.default_matmul_precision("highest")``, no kernel,
no sort, no cache; loss and gradients by autodiff; Adam in NumPy.
Independent of ``multiverso_tpu``: it shares the parameters' names and
shapes and nothing else.

The equations (GLM-4.7-Flash's ``config.json``, ``model_type``
``glm4_moe_lite``; routing and prediction module as DeepSeek-V3's report
states them, which this family follows). ``c`` is the configuration
file's dictionary, with the file's own keys.

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``, eps
  ``rms_norm_eps``; ``F`` the gated MLP ``(silu(u W_g) * (u W_u)) W_d`` in
  the first ``first_k_dense_replace`` layers, the expert layer after.
* MLA: ``c_q = RMSNorm(x W_DQ)``; ``q = c_q W_UQ`` -> heads of
  [``qk_nope_head_dim`` | ``qk_rope_head_dim``]; ``[c_kv | k_r] = x W_DKV``,
  ``c_kv = RMSNorm(c_kv)``; ``[k_n | v] = c_kv W_UKV`` per head; rotary
  (``rope_theta``, every rope dimension) on the query's rope part and on
  ``k_r``, shared by the heads; scores ``(q_n.k_n + q_r.k_r) / sqrt(nope +
  rope)``, causal softmax, ``o = P v``, output ``o W_O``.
* expert layer: ``s = sigmoid(u W_r)`` over all ``published.n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen (``n_group``
  1: no group limit); gates ``routed_scaling_factor * s_chosen /
  sum(s_chosen)``; result ``Shared(u) + sum over the chosen experts of
  g_e E_e(u)``, the sum taken over the experts GIVEN (``experts`` holds
  numbers ``offset`` to ``offset + H - 1``): what the others would add is
  left out. ``b`` takes no gradient; ``bias_rule`` is its update.
* prediction module: ``eh_proj([RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)])``,
  ``h_i`` the last layer's output before the final norm; one block of the
  expert kind; its own output norm; the same head; target ``t_{i+2}``.
  Loss ``CE(main, t_{i+1}) + mtp_loss_weight * CE(module, t_{i+2})``, means
  over the positions that have a target.

Departures, each for memory alone and none of them changes a number:
with ``lean=True`` a sequence, a block and a head of attention are each
computed under ``jax.checkpoint`` and in a ``lax.map`` (8,192 x 8,192
float32 scores of 20 heads are 5.4 GB); the module runs on every
position, and the last, which has no next token, takes the sequence's
first token in its place and has no target (so do its routing counts).
Rotary pairs element ``i`` with ``i + rope/2``. ``W_DKV`` and the router
are stored a row an output ([576, D], [E, D]).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# The dtype that every matrix product's operands are rounded to, or
# ``None`` for operands as they are (float32): see ``rounded_operands``.
_OPERANDS = None


@contextlib.contextmanager
def rounded_operands(dtype):
    """While this holds (it is read when a function is TRACED), every
    matrix product here computes as a step in ``dtype`` would: its two
    operands are rounded to ``dtype``, forward and backward (the result's
    cotangent is an operand of both backward products), each scaled as a
    whole so that its largest magnitude is the dtype's largest finite
    number, which keeps a small gradient from rounding to nothing. Sums
    stay float32; router, softmax, norms and loss stay exact. The
    comparison's control (``lm_control.py``) puts such a step in the
    program's place and has to be told apart."""
    global _OPERANDS
    before, _OPERANDS = _OPERANDS, dtype
    try:
        yield
    finally:
        _OPERANDS = before


def _rounded(x, dtype):
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(
        jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _operand(x, dtype):
    return _rounded(x, dtype)


_operand.defvjp(lambda x, dtype: (_rounded(x, dtype), None),
                lambda dtype, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _result(y, dtype):
    return y


_result.defvjp(lambda y, dtype: (y, None),
               lambda dtype, _, g: (_rounded(g, dtype),))


def _r(x):
    return x if _OPERANDS is None else _operand(x, _OPERANDS)


def _product(y):
    """A product's result: what flows back into it is rounded too."""
    return y if _OPERANDS is None else _result(y, _OPERANDS)


def _mm(a, b):
    return _product(_r(a) @ _r(b))


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, ..., R]: position along axis 0."""
    s, r = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _head(q, k, v):
    """One head, causal: q, k [S, dq], v [S, dv]."""
    s = q.shape[0]
    scores = _mm(q, k.T) / np.sqrt(q.shape[-1])
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
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c["rope_theta"])],
                        -1)
    k_r = rope(k_r, c["rope_theta"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, h, r))], -1)
    v = kv[..., nope:]
    per_head = (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2))
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(_head)(*t), per_head)
    else:
        o = jax.vmap(_head)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * dv), p["wo"])


def mlp(u, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(u, wg)) * _mm(u, wu), wd)


# a choice that hangs by less than a margin may fall otherwise in a lower
# precision: ``route`` counts the tokens under each of these
MARGINS = (1e-4, 1e-3, 1e-2)


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
    gates = c["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    gap = jax.lax.stop_gradient(top[:, k - 1] - top[:, k])
    ties = jnp.stack([jnp.sum(gap < m) for m in MARGINS]).astype(jnp.int32)
    return gates, chosen.sum(0).astype(jnp.int32), ties


def expert_layer(u, p, bias, c, offset, n_given):
    """``Shared(u)`` + the part of the routed result that experts
    ``offset .. offset + n_given - 1`` give (``p["eg"]``, ``p["eu"]``
    [n_given, D, F], ``p["ed"]`` [n_given, F, D]). Returns (result,
    (counts, ties))."""
    out, aux = routed_share(u, p, bias, c, offset, n_given)
    return mlp(u, p["sg"], p["su"], p["sd"]) + out, aux


def routed_share(u, p, bias, c, offset, n_given):
    """The routed part alone (no shared expert): what one chip of the
    deployment adds to a layer's result. Every given expert is applied to
    every token, and its result taken under its gate (0 where the token
    did not choose it)."""
    gates, counts, ties = route(u, p["router"], bias, c)
    hidden = (jax.nn.silu(_product(
        jnp.einsum("td,edf->etf", _r(u), _r(p["eg"]))))
              * _product(jnp.einsum("td,edf->etf", _r(u), _r(p["eu"]))))
    mine = gates[:, offset:offset + n_given].T[:, :, None]          # [e, t, 1]
    out = _product(jnp.einsum("etf,efd->td", _r(hidden * mine), _r(p["ed"])))
    return out, (counts, ties)


def block(x, p, ffn, c, lean=False):
    h = x + mla(rms(x, p["attn_norm"], c["rms_norm_eps"]), p, c, lean)
    f, aux = ffn(rms(h, p["ffn_norm"], c["rms_norm_eps"]), p)
    return h + f, aux


def _sub(params, prefix):
    return {k[len(prefix) + 1:]: v for k, v in params.items()
            if k.startswith(prefix + ".")}


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f = c["n_routed_experts"], c["moe_intermediate_size"]
    d = c["hidden_size"]
    return dict(p, eg=p["eg"].reshape(h, d, f), eu=p["eu"].reshape(h, d, f),
                ed=p["ed"].reshape(h, f, d))


def layer_names(c) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    dense = tuple(f"L{i}" for i in range(c["first_k_dense_replace"]))
    sparse = tuple(f"L{i}" for i in range(c["first_k_dense_replace"],
                                          c["num_hidden_layers"]))
    return dense, sparse + (("mtp",) if c["num_nextn_predict_layers"] else ())


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
    x = params["embed"][tokens]
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
            _mm(joined, p["eh_proj"]), p, bias[len(sparse) - 1])
        counts.append(cnt)
        module = wrap(ce_sum)(y[: s - 2], p["out_norm"], params["head"],
                              tokens[2:])
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


def bias_rule(bias: np.ndarray, counts: np.ndarray, speed: float
              ) -> np.ndarray:
    """``b_e += speed * sign(mean(c) - c_e)``, a row a layer."""
    counts = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float64) + speed * np.sign(
        counts.mean(-1, keepdims=True) - counts)


def adam_step(value, m, v, t, grad, lr, beta1, beta2, eps):
    """NumPy Adam with bias correction and no weight decay: returns (new
    value, m, v, t) in float64."""
    value, m, v, grad = (np.asarray(a, np.float64)
                         for a in (value, m, v, grad))
    t = t + 1
    m = beta1 * m + (1 - beta1) * grad
    v = beta2 * v + (1 - beta2) * grad * grad
    m_hat, v_hat = m / (1 - beta1 ** t), v / (1 - beta2 ** t)
    return value - lr * m_hat / (np.sqrt(v_hat) + eps), m, v, t


def route_alone(u, router, bias, c):
    """``route`` on its own, at the reference's precision: (counts [E],
    ties [len(MARGINS)]) for an input ``u`` [S, D]."""
    with jax.default_matmul_precision("highest"):
        _, counts, ties = route(u, router, bias, c)
        return counts, ties
