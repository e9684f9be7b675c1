"""Plain reference of the one-mixer-a-block decoder that
``nemotron-3-nano-30b-a3b-ep16`` trains (Mamba-2 mixers, relu2 experts
beside a wider shared one, grouped-query attention without positions):
``jax.numpy``, float32, every matrix product at
``jax.default_matmul_precision("highest")``, no kernel, no chunk, no sort,
no cache; the state-space scan is the recurrence itself, a position at a
time; loss and gradients by autodiff; Adam in NumPy
(``reference/mla_moe.adam_step``). Independent of ``multiverso_tpu``: it
shares the parameters' names and shapes and nothing else. The rounding
control (``rounded_operands``) is ``reference/mla_moe``'s, so that one
switch rounds every reference; :func:`scan_control` is this file's own.

The equations (Nemotron-3-Nano-30B-A3B's ``config.json``, ``model_type``
``nemotron_h``). ``c`` is the configuration file's dictionary, with the
file's own keys. What the keys do not say is marked (+) and recorded in the
configuration's ``assumed`` with the file that bore it out.

* block ``i`` is of the kind ``hybrid_override_pattern[i]``: ``y = x +
  Mixer(RMSNorm(x))``, eps ``layer_norm_epsilon``; no embedding
  multiplier; a final RMSNorm; the head untied.
* ``M``: ``[z | xBC | dt] = u W_in`` of widths ``mamba_num_heads x
  mamba_head_dim`` | that ``+ 2 n_groups x ssm_state_size`` |
  ``mamba_num_heads`` (+); ``xBC = silu(conv(xBC))``, depthwise and causal
  over ``conv_kernel`` taps with a bias, zeros before the sequence's start;
  ``xBC -> x`` [heads, head_dim], ``B``, ``C`` [groups, state], head ``h``
  reading group ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``
  from ``H = 0``, ``y_t = H_t C_t + D x_t``; ``y = y * silu(z)``, then an
  RMSNorm over each group's ``heads x head_dim / n_groups`` with one gain
  an element (+); ``y W_out``.
* ``E``: ``s = sigmoid(u W_r^T)`` over all ``published.n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` chosen (``n_group`` 1);
  gates ``s_chosen / (sum(s_chosen) + 1e-20) * routed_scaling_factor``
  (``norm_topk_prob``); result ``Shared(u)`` plus the sum over the chosen
  experts GIVEN of ``g_e relu(u W_up,e)^2 W_down,e`` (+: ``relu2``, no
  gate matrix); ``Shared`` is one such MLP of width
  ``moe_shared_expert_intermediate_size``. ``b`` takes no gradient.
* ``*``: q ``num_attention_heads`` heads of ``head_dim``, k and v
  ``num_key_value_heads``, no bias, NO positions (+), causal softmax over
  ``sqrt(head_dim)``, ``o W_o``.
* Loss: mean cross-entropy over the positions that have a next token.

Departures, each for memory alone and none of them changes a number: with
``lean=True`` a sequence and a block are each computed under
``jax.checkpoint``; the recurrence runs over stretches of ``LEAN_STEPS``
positions, each under ``jax.checkpoint`` (64 heads' states of 64 x 128
floats are 2 MB a position: 34 GB a sequence of 16,384 if every one were
kept for the backward pass), and a group's heads read its ``B`` and ``C``
by broadcasting (repeated a head they are 512 MB each); a mixer's three
stages (what feeds the scan, the scan, what follows it) are each under
``jax.checkpoint``; attention a head and ``LEAN_ROWS`` query rows at a time, the given experts one after another and the cross-entropy in
blocks, as ``reference/afmoe``'s. The router is stored a row an expert,
the convolution a row a tap.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import _ce_sum, _head, _sub
from benchmark.reference.mla_moe import (MARGINS, _mm, adam_step, bias_rule,
                                         rms, rounded_operands)

__all__ = ["MARGINS", "adam_step", "bias_rule", "rounded_operands",
           "scan_control", "loss", "loss_and_grads"]

LEAN_STEPS = 128

# What the scan is computed as: ``None`` (the recurrence as it is),
# ``"sums_bfloat16"`` or ``"no_carry"``: see ``scan_control``.
_SCAN = None


@contextlib.contextmanager
def scan_control(how):
    """While this holds (it is read when a function is TRACED), the scan
    computes as a faulty program's would, for the comparison's control
    (``lm_hybrid_control.py``), which has to tell each apart.
    ``"sums_bfloat16"`` keeps the scan's sums in bfloat16 as a chunked
    program would: the state is rounded after every position's update, and
    the running sum of ``dt A`` since the chunk's start is kept rounded,
    a position's decay taken from two rounded sums' difference (a slow
    head's steps of 0.001 to 0.01 are under half a last place of a sum near
    1, so its decays come out as 0 or twice over). Rounding the state
    alone moves a gradient by 0.003 of its norm at most, a fifteenth of
    the program's own rounding (chip runs, PR 47): no comparison of a
    bfloat16-operand step could tell it. ``"no_carry"`` drops the state at
    every ``chunk_size``-th position, as a chunked scan that leaves out
    what one chunk hands the next."""
    global _SCAN
    before, _SCAN = _SCAN, how
    try:
        yield
    finally:
        _SCAN = before


def scan(x, dt, a, b, c, chunk: int, lean=False):
    """The recurrence, a position at a time: x [S, G, K x P] (a group's K
    heads side by side, so that no array's last dimension is a head's 64,
    which the chip would pad to 128), dt [S, G, K], a [G, K], b, c [S, G,
    N] (a group's heads read its ``B`` and ``C``) -> y [S, G, K x P]
    (without the skip)."""
    s, g, k = dt.shape
    p = x.shape[-1] // k
    rounded = _SCAN == "sums_bfloat16"
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    wide = lambda t: jnp.repeat(t, p, axis=-1)          # [.., K] -> [.., K P]
    a = wide(a)

    def step(carry, each):
        state, since = carry        # since: the sum of dt A from the chunk's start
        xt, dtt, bt, ct, t = each
        dtt = wide(dtt)
        log_keep = dtt * a
        if rounded:
            before = jnp.where(t % chunk == 0, 0.0, since)
            since = low(before + log_keep)
            log_keep = since - before
        keep = jnp.exp(log_keep)[..., None]
        if _SCAN == "no_carry":
            keep = jnp.where(t % chunk == 0, 0.0, keep)
        state = (keep * state + (dtt * xt)[..., None] * bt[:, None, :])
        if rounded:
            state = low(state)
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


def mamba2(u, p, c, lean=False):
    """u [S, D] -> [S, D]. With ``lean`` the three stages (what feeds the
    scan, the scan, what follows it) are each under ``jax.checkpoint``, so
    that a backward pass holds one stage's arrays at a time."""
    s = u.shape[0]
    h, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, taps = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
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
        y = (y * jax.nn.silu(z)).reshape(s, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + c["layer_norm_epsilon"])
        return _mm(y.reshape(s, inner) * gain, wout)

    x, bm, cm, dt, z = wrap(before)(u, p["win"], p["conv_w"], p["conv_b"],
                                    p["dt_bias"])
    y = scan(x, dt, -jnp.exp(by_group(p["a_log"])), bm, cm, c["chunk_size"],
             lean)
    return wrap(after)(y, x, z, p["skip"], p["gate_norm"], p["wout"])


def attention(u, p, c, lean=False):
    """u [S, D] -> [S, D]: causal, grouped-query, no positions."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = _mm(u, p["wq"]).reshape(s, h, d)
    k = _mm(u, p["wk"]).reshape(s, hkv, d)
    v = _mm(u, p["wv"]).reshape(s, hkv, d)
    # query head i reads key-value head i // (h / hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    per_head = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    one = lambda q, k, v: _head(q, k, v, 0, lean)
    if lean:
        o = jax.lax.map(lambda t: jax.checkpoint(one)(*t), per_head)
    else:
        o = jax.vmap(one)(*per_head)
    return _mm(o.transpose(1, 0, 2).reshape(s, h * d), p["wo"])


def relu2_mlp(u, wu, wd):
    return _mm(jnp.square(jax.nn.relu(_mm(u, wu))), wd)


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
        picked.sum(-1, keepdims=True) + 1e-20)
    gap = jax.lax.stop_gradient(top[:, k - 1] - top[:, k])
    ties = jnp.stack([jnp.sum(gap < m) for m in MARGINS]).astype(jnp.int32)
    return gates, chosen.sum(0).astype(jnp.int32), ties


def routed_share(u, p, bias, c, offset, n_given, lean=False):
    """The routed part alone: what experts ``offset .. offset + n_given -
    1`` give (``p["eu"]`` [n_given, D, F], ``p["ed"]`` [n_given, F, D]).
    Every given expert is applied to every token, and its result taken
    under its gate (0 where the token did not choose it). Returns (result,
    (counts, ties))."""
    gates, counts, ties = route(u, p["router"], bias, c)
    mine = gates[:, offset:offset + n_given].T          # [e, t]

    def expert(eu, ed, gate):
        return _mm(jnp.square(jax.nn.relu(_mm(u, eu))) * gate[:, None], ed)

    each = (p["eu"], p["ed"], mine)
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
    return relu2_mlp(u, p["su"], p["sd"]) + out, aux


def layer_kinds(c) -> str:
    """A letter a block kept: the first ``num_hidden_layers`` of the
    published pattern."""
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]]


def _experts_3d(p, c):
    """The held experts' matrices as [H, D, F] / [H, F, D], however the
    caller stores them (rows of one matrix in the program's tables)."""
    h, f, d = (c["n_routed_experts"], c["moe_intermediate_size"],
               c["hidden_size"])
    return dict(p, eu=p["eu"].reshape(h, d, f), ed=p["ed"].reshape(h, f, d))


def sequence_loss(params, bias, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (sum of CE over the positions with a
    target, counts [layers, E], ties [layers, len(MARGINS)]), a row an
    expert layer."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    offset, n_given = c.get("expert_offset", 0), c["n_routed_experts"]
    eps = c["layer_norm_epsilon"]
    s = tokens.shape[0]
    x = params["embed"][tokens]
    aux = []
    for i, kind in enumerate(layer_kinds(c)):
        p = _sub(params, f"L{i}")
        if kind == "M":
            x = wrap(lambda x, p: x + mamba2(
                rms(x, p["attn_norm"], eps), p, c, lean))(x, p)
        elif kind == "*":
            x = wrap(lambda x, p: x + attention(
                rms(x, p["attn_norm"], eps), p, c, lean))(x, p)
        else:
            def sparse(x, p, b):
                f, a = expert_layer(rms(x, p["ffn_norm"], eps), p, b, c,
                                    offset, n_given, lean)
                return x + f, a

            x, a = wrap(sparse)(x, _experts_3d(p, c), bias[len(aux)])
            aux.append(a)
    # _ce_sum reads the final norm's eps under afmoe's key
    main = _ce_sum(x, params["final_norm"], params["head"],
                   jnp.roll(tokens, -1), (jnp.arange(s) < s - 1).astype(
                       jnp.float32), {"rms_norm_eps": eps}, lean)
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
