"""Plain reference of the two-branch hybrid decoder that
``granite-4.0-h-micro-pp4`` trains (a Mamba-2 mixer whose 64 heads read ONE
group's ``B`` and ``C``, or one layer in ten a grouped-query attention
without positions, and a dense gated MLP behind every mixer, under four
published multipliers, the head tied to the embedding): ``jax.numpy``,
float32, every matrix product at ``jax.default_matmul_precision("highest")``,
no kernel, no chunk, no cache; the state-space scan is the recurrence
itself, a position at a time (:func:`scan`, written out here as the
attention and the block are: ``reference/nemotron_h.scan``'s control rounds
by a float32 -> bfloat16 -> float32 pair of casts, which the chip's compiler
may drop, and at this cell's shapes did: a control that rounds has to use
``lax.reduce_precision``); loss and gradients by autodiff; Adam in NumPy
(``reference/mla_moe.adam_step``). Independent of ``multiverso_tpu``: it
shares the parameters' names and shapes and nothing else. The block, the
multipliers, the tied scaled head and the attention's scale are written out
HERE, from the published description; the rounding control
(``rounded_operands``) is ``reference/mla_moe``'s.

The equations (granite-4.0-h-micro's ``config.json``, ``model_type``
``granitemoehybrid``; each borne out against ``transformers`` 4.57.6,
``models/granitemoehybrid/modeling_granitemoehybrid.py``, whose line is
given). ``c`` is the configuration file's dictionary, with the file's own
keys. Tokens ``t`` [S], every product without a bias (``mamba_proj_bias``,
``attention_bias`` false).

* ``x = embedding_multiplier * Emb[t]`` (line 1347).
* block ``i`` of ``layer_types[i]``: ``h = x + residual_multiplier *
  Mixer_i(RMSNorm(x))`` (line 1197), ``x' = h + residual_multiplier *
  MLP(RMSNorm(h))`` (line 1210; ``num_local_experts`` 0: the ``shared_mlp``
  alone, line 1207); eps ``rms_norm_eps``.
* ``MLP(u) = (silu(u W_g) * (u W_u)) W_d`` (lines 904 to 909:
  ``input_linear`` is ``[W_g | W_u]``, the FIRST half the gated one).
* ``mamba`` (``GraniteMoeHybridMambaLayer.torch_forward``): ``[z | xBC | dt]
  = u W_in`` of widths ``mamba_n_heads x mamba_d_head`` | that ``+ 2
  mamba_n_groups x mamba_d_state`` | ``mamba_n_heads``; ``xBC =
  silu(conv(xBC))``, depthwise and causal over ``mamba_d_conv`` taps with a
  bias, zeros before the start (line 689); ``xBC -> x`` [heads, head], ``B``,
  ``C`` [groups, state] (line 692), every head of a group reading its ``B``
  and ``C`` (lines 764, 765: with one group, all 64 read the one); ``dt =
  softplus(dt + dt_bias)`` (line 759), the clamp to ``time_step_limit`` (0,
  inf) a no-op (lines 423, 760); ``A = -exp(A_log)`` (line 699); ``H_t =
  exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t`` from ``H = 0``, ``y_t = H_t C_t +
  D x_t``; ``y = RMSNorm(y * silu(z)) * g`` with the gate FIRST and the mean
  over the WHOLE inner width (``GraniteMoeHybridRMSNormGated``, lines 879,
  880: no groups in the norm); ``y W_out``.
* ``attention`` (``GraniteMoeHybridAttention``): q ``num_attention_heads``
  heads, k and v ``num_key_value_heads`` heads of ``hidden_size /
  num_attention_heads``; NO rotary position (``position_embedding_type``
  ``nope``: lines 1306, 198); causal ``softmax(q k^T *
  attention_multiplier)`` (line 162: the multiplier in the place of ``1 /
  sqrt(head)``); ``o W_o``.
* ``logits = RMSNorm(x) Emb^T / logits_scaling`` (line 1736;
  ``tie_word_embeddings``: the head IS the embedding's table), mean
  cross-entropy over the positions that have a next token.

Departures, each for memory alone and none of them changes a number: with
``lean=True`` a sequence and a block's two branches are each computed under
``jax.checkpoint``; the recurrence runs over stretches of ``LEAN_STEPS``
positions, each under ``jax.checkpoint`` (64 heads' states of 64 x 128
floats are 2 MB a position), and a
mixer's three stages (what feeds the scan, the scan, what follows it) are
each under ``jax.checkpoint``; attention a head and ``LEAN_ROWS`` query rows
at a time, and the cross-entropy in blocks of ``LEAN_ROWS`` positions. The
convolution is stored a row a tap.

:func:`control` computes the model as a faulty program would, for the
comparison's controls (``benchmark/lm_granite_control.py``).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import LEAN_ROWS
from benchmark.reference.mla_moe import (_mm, _sub, adam_step, rms,
                                         rounded_operands)

__all__ = ["adam_step", "rounded_operands", "control", "CONTROLS", "loss",
           "loss_and_grads"]

LEAN_STEPS = 128

# how the model is computed: ``None`` as the equations say, or as one of
# these faulty programs would (read when a function is TRACED). The first
# two are faults of the scan, as ``nemotron_h.scan_control``'s; the other
# three leave one published multiplier out
CONTROLS = ("sums_bfloat16", "no_carry", "residual_1", "softmax_sqrt",
            "logits_unscaled")
_FAULT = None


@contextlib.contextmanager
def control(how):
    """While this holds the model is computed wrongly in one way:
    ``sums_bfloat16`` (the scan's sums kept in bfloat16 as a chunked program
    would: the state is rounded after every position's update, and the
    running sum of ``dt A`` since the chunk's start is kept rounded, a
    position's decay taken from two rounded sums' difference: a slow head's
    steps of 0.001 to 0.01 are under half a last place of a sum near 2, so
    its decays come out as 0), ``no_carry`` (the scan without the state one
    ``mamba_chunk_size`` chunk hands the next), ``residual_1``
    (``residual_multiplier`` taken as 1), ``softmax_sqrt`` (the scores over
    ``sqrt(head)``, not times ``attention_multiplier``), ``logits_unscaled``
    (``logits_scaling`` left out); ``None``: as the equations say."""
    global _FAULT
    if how is not None and how not in CONTROLS:
        raise ValueError(f"no control named {how!r}")
    before, _FAULT = _FAULT, how
    try:
        yield
    finally:
        _FAULT = before


def _low(t):
    """``t`` rounded to bfloat16's eight bits of mantissa, by an operation
    the compiler keeps (a pair of casts it may drop as excess precision)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def scan(x, dt, a, b, c, chunk: int, lean=False):
    """The recurrence, a position at a time: x [S, G, K x P] (a group's K
    heads side by side), dt [S, G, K], a [G, K], b, c [S, G, N] (every head
    of a group reads its ``B`` and ``C``: with one group, all of them the
    one) -> y [S, G, K x P] (without the skip). ``chunk`` is what the two
    faults of :func:`control` count positions by."""
    s, g, k = dt.shape
    p = x.shape[-1] // k
    rounded, dropped = _FAULT == "sums_bfloat16", _FAULT == "no_carry"
    wide = lambda t: jnp.repeat(t, p, axis=-1)          # [.., K] -> [.., K P]
    a = wide(a)

    def step(carry, each):
        state, since = carry    # since: the sum of dt A from the chunk's start
        xt, dtt, bt, ct, t = each
        dtt = wide(dtt)
        log_keep = dtt * a
        if rounded:
            before = jnp.where(t % chunk == 0, 0.0, since)
            since = _low(before + log_keep)
            log_keep = since - before
        keep = jnp.exp(log_keep)[..., None]
        if dropped:
            keep = jnp.where(t % chunk == 0, 0.0, keep)
        state = keep * state + (dtt * xt)[..., None] * bt[:, None, :]
        if rounded:
            state = _low(state)
        return (state, since), jnp.sum(state * ct[:, None, :], -1)

    def stretch(carry, each):
        return jax.lax.scan(step, carry, each)

    each = (x, dt, b, c, jnp.arange(s))
    first = (jnp.zeros((g, k * p, b.shape[-1]), jnp.float32),
             jnp.zeros((g, k * p), jnp.float32))
    if not lean or s <= LEAN_STEPS or s % LEAN_STEPS:
        return stretch(first, each)[1]
    each = jax.tree.map(
        lambda t: t.reshape((-1, LEAN_STEPS) + t.shape[1:]), each)
    _, y = jax.lax.scan(jax.checkpoint(stretch), first, each)
    return y.reshape(s, g, k * p)


def mamba(u, p, c, lean=False):
    """u [S, D] -> [S, D]: the state-space mixer. With ``lean`` its three
    stages are each under ``jax.checkpoint``."""
    s = u.shape[0]
    h, hd = c["mamba_n_heads"], c["mamba_d_head"]
    g, n, taps = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"]
    inner = h * hd
    conv = inner + 2 * g * n
    wrap = jax.checkpoint if lean else (lambda f: f)
    by_group = lambda t: t.reshape(g, h // g)

    def before(u, win, conv_w, conv_b, dt_bias):
        proj = _mm(u, win)
        z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + conv],
                      proj[:, inner + conv:])
        past = jnp.concatenate([jnp.zeros((taps - 1, conv)), xbc], 0)
        xbc = jax.nn.silu(conv_b + sum(past[i:i + s] * conv_w[i]
                                       for i in range(taps)))
        # head h = (group h // (heads / groups), its h % (heads / groups)-th)
        return (xbc[:, :inner].reshape(s, g, inner // g),
                xbc[:, inner:inner + g * n].reshape(s, g, n),
                xbc[:, inner + g * n:].reshape(s, g, n),
                jax.nn.softplus(dt + dt_bias).reshape(s, g, h // g), z)

    def after(y, x, z, skip, gain, wout):
        y = (y + jnp.repeat(by_group(skip), hd, axis=-1) * x).reshape(
            s, inner)
        # the gate first, then ONE mean over the whole inner width
        y = y * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + c["rms_norm_eps"])
        return _mm(y * gain, wout)

    x, bm, cm, dt, z = wrap(before)(u, p["win"], p["conv_w"], p["conv_b"],
                                    p["dt_bias"])
    y = scan(x, dt, -jnp.exp(by_group(p["a_log"])), bm, cm,
             c["mamba_chunk_size"], lean)
    return wrap(after)(y, x, z, p["skip"], p["gate_norm"], p["wout"])


def _rows(q, k, v, first, scale):
    """Query rows ``first ..`` of one head under the causal mask: q [R, d],
    k, v [S, d]; the scores TIMES ``scale``."""
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    scores = jnp.where(i >= j, _mm(q, k.T) * scale, -jnp.inf)
    return _mm(jax.nn.softmax(scores, -1), v)


def attention(u, p, c, lean=False):
    """u [S, D] -> [S, D]: causal, grouped-query, no positions, the scores
    times ``attention_multiplier``."""
    s = u.shape[0]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // h
    scale = (1.0 / np.sqrt(d) if _FAULT == "softmax_sqrt"
             else c["attention_multiplier"])
    q = _mm(u, p["wq"]).reshape(s, h, d)
    k = _mm(u, p["wk"]).reshape(s, hkv, d)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))

    def one(q, k, v):
        if not lean or s <= LEAN_ROWS or s % LEAN_ROWS:
            return _rows(q, k, v, 0, scale)
        part = jax.checkpoint(lambda t: _rows(t[0], k, v, t[1], scale))
        return jax.lax.map(part, (q.reshape(-1, LEAN_ROWS, d),
                                  jnp.arange(0, s, LEAN_ROWS))).reshape(s, d)

    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * d), p["wo"])


def mlp(u, p):
    """``(silu(u W_g) * (u W_u)) W_d``: the gated half is the first."""
    return _mm(jax.nn.silu(_mm(u, p["wg"])) * _mm(u, p["wu"]), p["wd"])


def layer_kinds(c):
    """The first ``num_hidden_layers`` entries of ``layer_types``."""
    return tuple(c["layer_types"][:c["num_hidden_layers"]])


def block(x, p, c, kind: str, lean=False):
    """x [S, D] -> [S, D]: both branches join the stream under
    ``residual_multiplier``."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    eps = c["rms_norm_eps"]
    m = 1.0 if _FAULT == "residual_1" else c["residual_multiplier"]
    mixer = mamba if kind == "mamba" else attention
    h = wrap(lambda x, p: x + m * mixer(rms(x, p["attn_norm"], eps), p, c,
                                        lean))(x, p)
    return wrap(lambda h, p: h + m * mlp(rms(h, p["ffn_norm"], eps), p))(h, p)


def _ce_sum(hidden, norm, head, targets, weights, c, lean):
    """Sum over positions of ``weights * CE`` of the normed ``hidden``
    through the tied head, the logits over ``logits_scaling``."""
    over = 1.0 if _FAULT == "logits_unscaled" else c["logits_scaling"]

    def part(hidden, targets, weights):
        logits = _mm(rms(hidden, norm, c["rms_norm_eps"]), head.T) / over
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(weights * jnp.take_along_axis(
            logp, targets[:, None], -1)[:, 0])

    s, d = hidden.shape
    if not lean or s <= LEAN_ROWS or s % LEAN_ROWS:
        return part(hidden, targets, weights)
    return jax.lax.map(
        lambda t: jax.checkpoint(part)(*t),
        (hidden.reshape(-1, LEAN_ROWS, d), targets.reshape(-1, LEAN_ROWS),
         weights.reshape(-1, LEAN_ROWS))).sum()


def sequence_loss(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> the sum of CE over the positions that
    have a target."""
    s = tokens.shape[0]
    x = c["embedding_multiplier"] * params["embed"][tokens]
    for i, kind in enumerate(layer_kinds(c)):
        x = block(x, _sub(params, f"L{i}"), c, kind, lean)
    # the head is the embedding's table: autodiff adds the two gradients
    return _ce_sum(x, params["final_norm"], params["embed"],
                   jnp.roll(tokens, -1),
                   (jnp.arange(s) < s - 1).astype(jnp.float32), c, lean)


def loss(params, tokens, c, lean=False):
    """tokens [B, S] -> the loss, float32 at the highest matmul
    precision."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        one = lambda t: sequence_loss(params, t, c, lean)
        if lean:
            main = jax.lax.map(jax.checkpoint(one), tokens)
        else:
            main = jax.vmap(one)(tokens)
        return main.sum() / (b * (s - 1))


def loss_and_grads(params, tokens, c, lean=False):
    """(loss, gradients by name)."""
    return jax.value_and_grad(lambda p: loss(p, tokens, c, lean))(params)
