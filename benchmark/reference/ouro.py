"""Plain reference of the looped decoder that ``ouro-2.6b-pp6`` trains (a
stack of sandwich-normed dense blocks run ``total_ut_steps`` times with the
same parameters, an exit gate after every pass, a loss that is the expected
cross-entropy over the exits less an entropy term): ``jax.numpy``, float32,
every matrix product at ``jax.default_matmul_precision("highest")``, an
unrolled Python loop over passes and layers, no scan over the passes, no
checkpoint policy, no chunked loss, no kernel; loss and gradients by
autodiff; Adam in NumPy (``reference/mla_moe.adam_step``). Independent of
``multiverso_tpu``: it shares the parameters' names and shapes and nothing
else. The rounding control (``rounded_operands``) is ``reference/mla_moe``'s,
so that one switch rounds every reference.

The equations (Ouro-2.6B's ``config.json``, ``model_type`` ``ouro``;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741).
``c`` is the configuration file's dictionary, with the file's own keys;
every ``N`` is an RMSNorm with a gain of its own, eps ``rms_norm_eps``;
``T = total_ut_steps``. What the catalog's keys do not say is the
configuration's ``assumed``, marked (+) here.

* ``x_0 = Emb(tok)``.
* pass ``t = 1..T``: ``z = x_{t-1}``; for every layer in order ``h = z +
  N2(Attn(N1(z)))``, ``z = h + N4(MLP(N3(h)))`` (the sandwich); then ``x_t =
  N_f(z)``: the normed stream is the pass's exit state AND the next pass's
  input (+), under the same ``N_f``, the same layers' parameters and the same
  positions in every pass.
* Attn: ``q, k, v = u W_q, u W_k, u W_v`` -> ``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``, no bias (+); rotary
  (``rope_theta``, element ``i`` paired with ``i + head_dim/2``) over the
  whole head of q and k; causal; scores over ``sqrt(head_dim)``; softmax;
  ``o W_o``. MLP: ``(silu(u W_g) * (u W_u)) W_d``.
* exit gate, a position at a time (+): ``lambda_t = sigmoid(x_t . w_e +
  b_e)`` for ``t < T``; ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)``, ``p_T = prod_{j<T} (1 - lambda_j)``.
* ``l_t(i) = CE(x_t(i) W_head^T, tok_{i+1})``; ``loss = (1/n) sum_i [sum_t
  p_t(i) l_t(i) - exit_entropy_coef H(p(i))]``, ``H = -sum_t p_t ln p_t``,
  ``n`` the positions that have a target. Gradients flow through everything:
  through ``p`` into the gate and the streams, and back through all passes.

Departures, each for memory alone and none of them changes a number: with
``lean=True`` a sequence, a block application, a head of attention and a
block of ``LEAN_ROWS`` of a head's query rows are each computed under
``jax.checkpoint`` (plain: no policy) and in a ``lax.map``, and an exit's
cross-entropy is taken in blocks of ``LEAN_ROWS`` positions over all S
positions, the last of which has no target and weight 0.

``loop_control`` computes the loop as a faulty program would, for the
comparison's controls (``benchmark/lm_loop_control.py``). Given parameters
named ``P<t>.L<i>.*`` (a copy of every layer's for every pass) in place of
``L<i>.*``, pass ``t`` reads its own copy: the untied model whose summed
gradients a shared table's must equal (``tests/test_ouro.py``).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import LEAN_ROWS, _head
from benchmark.reference.mla_moe import (_mm, _sub, adam_step, mlp, rms,
                                         rope, rounded_operands)

__all__ = ["adam_step", "rounded_operands", "loss", "loss_and_grads",
           "exit_distribution", "loop_control", "CONTROLS"]

# how the loop is computed: ``None`` as the equations say, or as one of
# these faulty programs would (read when a function is TRACED)
CONTROLS = ("one_pass_less", "no_renorm", "untrained_weights")
_LOOP = None


@contextlib.contextmanager
def loop_control(how):
    """While this holds the loop is computed wrongly in one way:
    ``one_pass_less`` (``T - 1`` passes and exits), ``no_renorm`` (the next
    pass is fed ``z``, not ``N_f(z)``: the exit states stay normed),
    ``untrained_weights`` (the exit distribution is held constant where it
    weighs the cross-entropies: the gate learns from the entropy term
    alone); ``None``: as the equations say."""
    global _LOOP
    if how is not None and how not in CONTROLS:
        raise ValueError(f"no control named {how!r}")
    before, _LOOP = _LOOP, how
    try:
        yield
    finally:
        _LOOP = before


def attention(u, p, c, lean=False):
    """u [S, D] -> [S, D]."""
    s = u.shape[0]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = rope(_mm(u, p["wq"]).reshape(s, h, d), c["rope_theta"])
    k = rope(_mm(u, p["wk"]).reshape(s, hkv, d), c["rope_theta"])
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


def block(x, p, c, lean=False):
    eps = c["rms_norm_eps"]
    h = x + rms(attention(rms(x, p["attn_norm"], eps), p, c, lean),
                p["attn_post_norm"], eps)
    return h + rms(mlp(rms(h, p["ffn_norm"], eps), p["wg"], p["wu"], p["wd"]),
                   p["ffn_post_norm"], eps)


def passes_of(c) -> int:
    return c["total_ut_steps"] - (_LOOP == "one_pass_less")


def exit_states(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> every pass's exit state, a list of
    ``T`` arrays [S, D]."""
    wrap = jax.checkpoint if lean else (lambda f: f)
    untied = any(name.startswith("P0.") for name in params)
    run = wrap(lambda x, p: block(x, p, c, lean))
    z, exits = params["embed"][tokens], []
    for t in range(passes_of(c)):
        for i in range(c["num_hidden_layers"]):
            z = run(z, _sub(params, f"P{t}.L{i}" if untied else f"L{i}"))
        exits.append(rms(z, params["final_norm"], c["rms_norm_eps"]))
        if _LOOP != "no_renorm":
            z = exits[-1]
    return exits


def exit_distribution(exits, w, b):
    """The exit states [T, S, D] -> p [T, S]."""
    lam = jax.nn.sigmoid(exits[:-1] @ w + b)                # [T - 1, S]
    stayed = jnp.cumprod(1.0 - lam, 0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stayed[:-1]], 0)
    return jnp.concatenate([lam * before, stayed[-1:]], 0)


def _ce_each(x, head, targets, lean):
    """Each position's cross-entropy of ``x`` [S, D] through the head."""
    def part(x, targets):
        logp = jax.nn.log_softmax(_mm(x, head.T), -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    s, d = x.shape
    if not lean or s <= LEAN_ROWS or s % LEAN_ROWS:
        return part(x, targets)
    return jax.lax.map(lambda t: jax.checkpoint(part)(*t), (
        x.reshape(-1, LEAN_ROWS, d), targets.reshape(-1, LEAN_ROWS))
    ).reshape(s)


def sequence_terms(params, tokens, c, lean=False):
    """One sequence ``tokens`` [S] -> (the sum over its positions with a
    target of ``sum_t p_t l_t - coef H(p)``, and of each pass's ``l_t`` [T],
    of ``p`` [T] and of ``H``; ``p`` [T, S])."""
    s = tokens.shape[0]
    exits = jnp.stack(exit_states(params, tokens, c, lean))
    p = exit_distribution(exits, params["exit.w"], params["exit.b"][0])
    has_target = (jnp.arange(s) < s - 1).astype(jnp.float32)
    each = jnp.stack([_ce_each(x, params["head"], jnp.roll(tokens, -1), lean)
                      for x in exits])                      # [T, S]
    entropy = -jnp.sum(p * jnp.log(p), 0)
    weigh = (jax.lax.stop_gradient(p) if _LOOP == "untrained_weights" else p)
    total = jnp.sum((jnp.sum(weigh * each, 0)
                     - c["exit_entropy_coef"] * entropy) * has_target)
    return (total, jnp.sum(each * has_target, 1), jnp.sum(p * has_target, 1),
            jnp.sum(entropy * has_target), p)


def loss(params, tokens, c, lean=False):
    """tokens [B, S] -> (loss, {"loss": each pass's mean ``l_t`` [T],
    "p_mean": the mean exit distribution [T], "entropy": the mean ``H(p)``,
    "p": [T, B, S]}), float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        n = b * (s - 1)
        one = lambda t: sequence_terms(params, t, c, lean)
        if lean:
            total, each, p_sum, entropy, p = jax.lax.map(
                jax.checkpoint(one), tokens)
        else:
            total, each, p_sum, entropy, p = jax.vmap(one)(tokens)
        return total.sum() / n, {
            "loss": each.sum(0) / n, "p_mean": p_sum.sum(0) / n,
            "entropy": entropy.sum() / n, "p": p.transpose(1, 0, 2)}


def loss_and_grads(params, tokens, c, lean=False):
    """(loss, the exits' readings, gradients by name)."""
    (value, exits), grads = jax.value_and_grad(
        lambda p: loss(p, tokens, c, lean), has_aux=True)(params)
    return value, exits, grads
