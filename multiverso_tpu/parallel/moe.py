"""Expert parallelism: a mixture-of-experts layer over a mesh axis.

Rounds out the modern-strategy surface (SURVEY §2.10: the 2015 reference has
DP + parameter-storage sharding only; SP/CP live in parallel/ring.py, EP
here). Experts are sharded over the ``ep`` mesh axis — each device owns
``num_experts / ep`` expert MLPs — and tokens travel to their experts and
back via ``all_to_all`` over ICI, the TPU-native equivalent of the
dispatch/combine messaging a parameter server would do per-row.

Design choices, TPU-first:

* **Static capacity**: each device sends exactly ``capacity`` tokens to each
  expert shard (truncate-and-pad, like every production TPU MoE) so all
  shapes are static for XLA; dropped tokens fall back to the residual path.
* **Top-1 (switch) or top-k (GShard) routing** with a jittable router —
  ``top_k=1`` gates by the raw expert probability, ``top_k>1`` by the
  renormalized top-k probabilities — plus the standard auxiliary
  load-balance loss returned to the caller.
* One ``all_to_all`` out, one back; expert compute is a single batched
  einsum over the local experts — MXU-shaped, no scalar loops.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.zoo import Zoo


class MoEConfig(NamedTuple):
    num_experts: int
    dim: int
    hidden: int
    capacity_factor: float = 1.25
    axis: str = "ep"
    top_k: int = 1


def init_experts(cfg: MoEConfig, seed: int = 0, dtype=jnp.float32) -> Dict:
    """[E, ...]-stacked expert MLP params + router; shard E over the ep axis
    with :func:`shard_experts`."""
    rng = np.random.default_rng(seed)
    e, d, h = cfg.num_experts, cfg.dim, cfg.hidden
    mk = lambda *s, scale: jnp.asarray(rng.normal(0, scale, s), dtype)
    return {
        "w1": mk(e, d, h, scale=1 / np.sqrt(d)),
        "w2": mk(e, h, d, scale=1 / np.sqrt(h)),
        "router": mk(d, e, scale=1 / np.sqrt(d)),
    }


def shard_experts(params: Dict, cfg: MoEConfig,
                  mesh: Optional[Mesh] = None) -> Dict:
    """Place expert weights expert-sharded (router replicated)."""
    mesh = mesh or Zoo.get().mesh()
    shard = NamedSharding(mesh, P(cfg.axis))
    repl = NamedSharding(mesh, P())
    return {
        "w1": jax.device_put(params["w1"], shard),
        "w2": jax.device_put(params["w2"], shard),
        "router": jax.device_put(params["router"], repl),
    }


def top_k_gates(probs, kk: int):
    """Top-k expert selection with the gating convention shared by training
    (:func:`_route`) and decode (models/transformer.generate): raw top
    probability for k=1 (switch), renormalized top-k for k>1 (GShard).
    Returns (gates [T, K], topi [T, K])."""
    topv, topi = jax.lax.top_k(probs, kk)
    gates = topv if kk == 1 else topv / topv.sum(-1, keepdims=True)
    return gates, topi


def _route(probs, kk: int, capacity: int):
    """Priority routing over the [T, E] expert probabilities: assignments
    are flattened **k-major** ([all 1st choices, then all 2nd choices, ...])
    so every token's 1st choice wins the capacity race against any token's
    2nd choice — the GShard/Switch fill order. Returns (expert, gate, pos,
    keep, onehot), each over the K*T assignments; gates are the raw top
    probability for k=1 (switch) and renormalized for k>1 (GShard)."""
    t, e = probs.shape
    gates, topi = top_k_gates(probs, kk)                   # [T, K]
    expert = topi.T.reshape(-1)                            # [K*T]
    gate = gates.T.reshape(-1)
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)    # [K*T, E]
    pos = (jnp.cumsum(onehot, 0) * onehot).sum(-1) - 1     # per-expert slot
    keep = pos < capacity
    return expert, gate, pos, keep, onehot


def _local_moe(x, w1, w2, router, cfg: MoEConfig, capacity: int,
               batch_axis: Optional[str] = None):
    """Per-shard body. x: [T_local, D]; w1/w2: local experts [E_local, ...]."""
    ax = cfg.axis
    n = jax.lax.axis_size(ax)
    e = cfg.num_experts
    e_local = e // n
    t = x.shape[0]

    kk = cfg.top_k
    logits = x @ router                                    # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    expert, gate, pos, keep, onehot = _route(probs, kk, capacity)

    # dispatch buffer: [E, capacity, D] (one slice per destination expert)
    x_rep = jnp.tile(x, (kk, 1))                           # [K*T, D] k-major
    slot = jnp.where(keep, pos, capacity)                  # overflow -> pad row
    dispatch = jnp.zeros((e, capacity + 1, x.shape[1]), x.dtype)
    dispatch = dispatch.at[expert, slot].add(x_rep)
    dispatch = dispatch[:, :capacity]                      # [E, C, D]

    # all_to_all: [E, C, D] -> group by shard -> each device ends up with
    # its local experts' tokens from every peer: [n, E_local, C, D]
    dispatch = dispatch.reshape(n, e_local, capacity, -1)
    recv = jax.lax.all_to_all(dispatch, ax, split_axis=0, concat_axis=0,
                              tiled=False)                 # [n, E_local, C, D]

    # expert compute, batched over local experts: [E_local, n*C, D]
    xin = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, -1)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xin, w1))
    out = jnp.einsum("ech,ehd->ecd", h, w2)                # [E_local, n*C, D]

    # route back: inverse all_to_all
    back = out.reshape(e_local, n, capacity, -1).transpose(1, 0, 2, 3)
    combined = jax.lax.all_to_all(back, ax, split_axis=0, concat_axis=0,
                                  tiled=False)             # [n, E_local, C, D]
    combined = combined.reshape(e, capacity, -1)           # [E, C, D]

    # gather each surviving assignment's expert output (dropped -> 0) and
    # sum a token's k contributions (k-major flatten)
    y = combined[expert, jnp.minimum(pos, capacity - 1)]   # [K*T, D]
    y = jnp.where(keep[:, None], y, 0.0) * gate[:, None].astype(x.dtype)
    y = y.reshape(kk, t, -1).sum(0)                        # [T, D]

    # load-balance aux loss (switch for k=1, GShard-normalized for k>1)
    me = probs.mean(0)                                     # [E]
    ce = onehot.astype(jnp.float32).reshape(kk, t, e).sum(0).mean(0) / kk
    aux = e * jnp.sum(me * ce)
    # reduce over every axis the tokens are sharded on, so the returned
    # scalars really are replicated (out_specs=P() asserts it)
    reduce_axes = (ax,) if batch_axis is None else (ax, batch_axis)
    aux = jax.lax.pmean(aux, reduce_axes)
    # dropped = tokens whose EVERY assignment overflowed (full residual
    # fallback), matching the "dropped tokens fall back" contract
    token_dropped = 1.0 - keep.reshape(kk, t).any(axis=0)
    frac_dropped = jax.lax.pmean(token_dropped.mean(), reduce_axes)
    return y, aux, frac_dropped


def moe_layer(x: jax.Array, params: Dict, cfg: MoEConfig,
              mesh: Optional[Mesh] = None,
              batch_axis: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the expert-parallel MoE to tokens [B, T, D] sharded over
    ``cfg.axis`` on T (and optionally ``batch_axis`` on B). Returns
    (output [B, T, D], aux_loss scalar, dropped_fraction scalar —
    the fraction of tokens whose every routed choice overflowed capacity
    and that therefore fell back to the residual path with zero output)."""
    mesh = mesh or Zoo.get().mesh()
    n = mesh.shape[cfg.axis]
    if cfg.num_experts % n:
        raise ValueError(
            f"{cfg.num_experts} experts not divisible by {n} shards")
    b, t, d = x.shape
    if t % n:
        raise ValueError(f"token dim {t} not divisible by {n} {cfg.axis!r} "
                         "shards")
    if batch_axis and b % mesh.shape[batch_axis]:
        raise ValueError(f"batch dim {b} not divisible by "
                         f"{mesh.shape[batch_axis]} {batch_axis!r} shards")
    if not 1 <= cfg.top_k <= cfg.num_experts:
        raise ValueError(f"top_k={cfg.top_k} out of range for "
                         f"{cfg.num_experts} experts")
    local_tokens = b * t // n // (mesh.shape[batch_axis] if batch_axis else 1)
    capacity = max(1, int(cfg.capacity_factor * local_tokens * cfg.top_k
                          / cfg.num_experts))

    xspec = P(batch_axis, cfg.axis, None)
    espec = P(cfg.axis)

    def body(x, w1, w2, router):
        xb = x.reshape(-1, d)
        y, aux, dropped = _local_moe(xb, w1, w2, router, cfg, capacity,
                                     batch_axis)
        return y.reshape(x.shape), aux, dropped

    y, aux, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, espec, espec, P()),
        out_specs=(xspec, P(), P()), check_vma=False)(
            x, params["w1"], params["w2"], params["router"])
    return y, aux, dropped
