"""Tensor (model) parallelism: Megatron-style sharded matmuls over a mesh axis.

The reference's "model parallelism" is parameter-*storage* sharding (SURVEY
§2.10: tables row-sharded across servers, ref src/table/matrix_table.cpp:24-45
— the compute still happens whole on each worker). Here compute itself is
sharded: attention heads and MLP hidden units split over a ``tp`` axis, the
classic column-parallel -> row-parallel pairing so each layer needs exactly
one psum on its output.

Two surfaces, both TPU-first:

* **GSPMD rules** (:func:`transformer_tp_rules`, :func:`shard_params`): place
  the transformer param tree with TP layouts and let XLA insert the
  collectives — the scaling-book recipe (mesh + sharding annotations, no
  hand-written comms). :func:`constrain` is the activation-side hint.
* **Explicit primitives** (:func:`column_parallel`, :func:`row_parallel`):
  shard_map building blocks for users composing their own blocks; the psum
  placement is spelled out.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.zoo import Zoo


def transformer_tp_rules(axis: str = "tp") -> Dict[str, Any]:
    """PartitionSpec tree for models/transformer.py params (leading layer dim
    on the stacked leaves): qkv/w1 column-parallel (output dim sharded),
    wo/w2 row-parallel (input dim sharded), embeddings vocab-sharded, norms
    replicated."""
    return {
        "embed": P(axis, None),
        "pos": P(None, None),
        "layers": {
            "wqkv": P(None, None, axis),
            "wo": P(None, axis, None),
            "w1": P(None, None, axis),
            "w2": P(None, axis, None),
            "ln1": P(None, None),
            "ln2": P(None, None),
        },
        "ln_f": P(None),
    }


def transformer_fsdp_rules(axis: str = "fsdp",
                           moe: bool = False) -> Dict[str, Any]:
    """FSDP / ZeRO-3 layout for models/transformer.py params: every large
    leaf is split on one dimension over the data-parallel axis, so each
    chip STORES 1/n of the model while computing on its own batch shard
    (set ``batch_axis=axis`` too). XLA inserts the all-gather on use and
    the reduce-scatter on the gradients — the scaling-book FSDP recipe,
    no hand-written comms. Tiny norm vectors stay replicated. ``moe=True``
    matches the MoE param tree (expert stacks split on their model dim,
    leaving the expert dim free for a separate ep axis)."""
    layers = {
        "wqkv": P(None, axis, None),
        "wo": P(None, axis, None),
        "ln1": P(None, None),
        "ln2": P(None, None),
    }
    if moe:
        layers.update({
            "moe_w1": P(None, None, axis, None),
            "moe_w2": P(None, None, axis, None),
            "moe_router": P(None, axis, None),
        })
    else:
        layers.update({
            "w1": P(None, axis, None),
            "w2": P(None, axis, None),
        })
    return {
        "embed": P(axis, None),
        "pos": P(axis, None),
        "layers": layers,
        "ln_f": P(None),
    }


def shard_params(params: Any, rules: Any,
                 mesh: Optional[Mesh] = None) -> Any:
    """device_put a param pytree according to a matching PartitionSpec tree."""
    mesh = mesh or Zoo.get().mesh()
    # the whole-tree upload is a device-plane cost the scale curve
    # attributes — count it once through the devstats chokepoint
    _devstats.note_transfer(
        sum(int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree.leaves(params)), "h2d")
    # rules must mirror params' container structure with a PartitionSpec at
    # each array-leaf position (tree.map stops descending at params' leaves,
    # so the P tuples are picked up whole — but a P standing in for a whole
    # subtree is a structure mismatch)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params, rules)


def constrain(x: jax.Array, spec: P, mesh: Optional[Mesh] = None) -> jax.Array:
    """with_sharding_constraint shorthand (trace-time mesh from the Zoo)."""
    mesh = mesh or Zoo.get().mesh()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _lead_spec(x, x_spec: Optional[P]) -> tuple:
    """Sharding of x's leading (non-contracted) dims, padded to ndim-1."""
    lead = tuple(x_spec) if x_spec is not None else ()
    if len(lead) > x.ndim - 1:
        raise ValueError(f"x_spec {x_spec} longer than x's {x.ndim - 1} "
                         "leading dims")
    return lead + (None,) * (x.ndim - 1 - len(lead))


# jit-wrapped shard_map callable cache keyed on every closed-over
# parameter (the parallel/collectives.py discipline — a per-call
# closure rebuild re-lowers/recompiles every call on the legacy
# shard_map path; the devstats compiles_by_mesh counter measured it)
_MAPPED = {}


def _mapped(key, build):
    fn = _MAPPED.get(key)
    if fn is None:
        fn = _MAPPED[key] = jax.jit(build())
    return fn


def column_parallel(x: jax.Array, w: jax.Array, axis: str = "tp",
                    mesh: Optional[Mesh] = None,
                    x_spec: Optional[P] = None) -> jax.Array:
    """y = x @ w with w column-sharded [D, M/n per shard]; output stays
    sharded on its last dim (no collective — pair with :func:`row_parallel`).
    x: [..., D]; pass ``x_spec`` (a PartitionSpec over x's leading dims,
    e.g. ``P('dp')``) to keep batch-sharded activations sharded instead of
    gathering them to every device."""
    mesh = mesh or Zoo.get().mesh()
    lead = _lead_spec(x, x_spec)

    def body(x, w):
        return x @ w

    with _devstats.collective_span("column_parallel",
                                   x.nbytes + w.nbytes, mesh=mesh):
        return _mapped(
            ("col", mesh, axis, lead),
            lambda: jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(*lead, None), P(None, axis)),
                out_specs=P(*lead, axis), check_vma=False))(x, w)


def row_parallel(x: jax.Array, w: jax.Array, axis: str = "tp",
                 mesh: Optional[Mesh] = None,
                 x_spec: Optional[P] = None) -> jax.Array:
    """y = x @ w with x last-dim-sharded and w row-sharded [M/n, D]; the
    partial products psum over ``axis`` — the single collective of the
    column->row Megatron pair. ``x_spec`` shards x's leading dims as in
    :func:`column_parallel`."""
    mesh = mesh or Zoo.get().mesh()
    lead = _lead_spec(x, x_spec)

    def body(x, w):
        return jax.lax.psum(x @ w, axis)

    with _devstats.collective_span("row_parallel",
                                   x.nbytes + w.nbytes, mesh=mesh):
        return _mapped(
            ("row", mesh, axis, lead),
            lambda: jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(*lead, axis), P(axis, None)),
                out_specs=P(*lead, None), check_vma=False))(x, w)


def mlp_block(x: jax.Array, w1: jax.Array, w2: jax.Array,
              axis: str = "tp", mesh: Optional[Mesh] = None,
              x_spec: Optional[P] = None) -> jax.Array:
    """gelu(x @ w1) @ w2 with the hidden dim sharded: column_parallel ->
    local gelu -> row_parallel (one psum total). ``x_spec`` keeps
    batch-sharded inputs sharded through the pair."""
    mesh = mesh or Zoo.get().mesh()
    h = column_parallel(x, w1, axis, mesh, x_spec)
    h = jax.nn.gelu(h)
    return row_parallel(h, w2, axis, mesh, x_spec)
