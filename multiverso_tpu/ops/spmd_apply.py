"""Stacked SPMD row kernels for the mesh-sharded PS data plane.

One table's colocated :class:`~multiverso_tpu.ps.shard.RowShard`\\ s pool
their storage into ONE ``(S, R, C)`` device array sharded over a local
``("shards",)`` mesh axis (``ps/spmd.py``). These are the per-dispatch
programs over that layout: every device runs the SAME program on its own
shard slab(s) — the reference's worker-side ``Partition`` fan-out
(PAPER.md layer 5) turned server-side and mesh-placed, per the
``shard_map`` SPMD patterns in SNIPPETS.md rather than MPI-rank-style
one-array-per-process.

Bit-parity contract: each shard's slab update is EXACTLY the body of
``RowShard._row_update_fn`` (gather touched rows -> updater -> scatter),
vmapped over the stacked shard axis and partitioned with ``shard_map``.
The ops are elementwise per row (no cross-row reductions), so the
stacked program's arithmetic is bit-identical to S sequential per-shard
dispatches — asserted by tests/test_spmd_plane.py against the classic
path and by ``tools/bench_scale.py`` against a 1-shard oracle in-run.

Shape discipline: ids are padded to a shared power-of-two bucket with
each shard's OWN scratch row and zero deltas (the same trick every row
path uses, ``tables/matrix_table._bucket_size``), so there is one
compiled program per (bucket, donate) — zero steady-state recompiles.
Shards with no pending work in a wave round ride along as an all-scratch
zero-delta update, which is a no-op for every ROW_LOCAL_STATE updater on
a row that is never served.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from multiverso_tpu.updaters import AddOption

AXIS = "shards"


def _one_shard_update(updater, row_axes):
    """Per-shard update body — the exact ``RowShard._row_update_fn``
    program over one ``(R, C)`` slab. ``row_axes`` is the static tree of
    row-axis indices per updater-state leaf (-1 = row-free), computed
    once at plane build from the member shards' padded shape."""

    def _update(data, ustate, ids, vals, opt_leaves):
        opt = AddOption(*opt_leaves)
        rows = jnp.take(data, ids, axis=0)

        def gather(leaf, axis):
            return jnp.take(leaf, ids, axis=axis) if axis >= 0 else leaf

        gstate = jax.tree.map(gather, ustate, row_axes)
        new_rows, new_gstate = updater.apply(rows, gstate, vals, opt)
        data = data.at[ids].set(new_rows)

        def scatter(leaf, new_leaf, axis):
            if axis < 0:
                return new_leaf
            idx = (slice(None),) * axis + (ids,)
            return leaf.at[idx].set(new_leaf)

        ustate = jax.tree.map(scatter, ustate, new_gstate, row_axes)
        return data, ustate

    return _update


def build_apply(updater, row_axes, mesh: Optional[Any]):
    """ONE donated program applying a wave round for EVERY shard of the
    stack: ``(stack(S,R,C), ustate(S,...), ids(S,B), vals(S,B,C),
    opt_leaves((S,) each)) -> (stack, ustate)``. With a mesh, each
    device applies its local slab(s) via ``shard_map`` (no cross-device
    communication — ids are shard-local by construction); without one
    (single device) the vmap alone still makes it one dispatch."""
    inner = jax.vmap(_one_shard_update(updater, row_axes))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        spec = P(AXIS)
        inner = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec),
            out_specs=(spec, spec))
    return jax.jit(inner, donate_argnums=(0, 1))


def build_gather(mesh: Optional[Any]):
    """One program serving every shard's row gather in a single
    dispatch: ``(stack(S,R,C), ids(S,B)) -> rows(S,B,C)``."""

    def _take(data, ids):
        return jnp.take(data, ids, axis=0)

    inner = jax.vmap(_take)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        spec = P(AXIS)
        inner = jax.shard_map(inner, mesh=mesh,
                                    in_specs=(spec, spec),
                                    out_specs=spec)
    return jax.jit(inner)


def build_slice():
    """Materialize ONE shard's slab out of a stacked leaf:
    ``(stacked, slot) -> stacked[slot]``. The slot index is a traced
    scalar, so one compile serves every member (no per-slot retrace)."""

    def _slice(stacked, slot):
        return jax.lax.dynamic_index_in_dim(stacked, slot, axis=0,
                                            keepdims=False)

    return jax.jit(_slice)


def opt_leaves(opts, dtype=jnp.float32):
    """Stack a list of per-shard :class:`AddOption`\\ s into per-field
    ``(S,)`` arrays (the vmap-able form). Integer fields stay int32."""
    import numpy as np
    cols = list(zip(*[tuple(o) for o in opts]))
    out = []
    for name, vals in zip(AddOption._fields, cols):
        if name == "worker_id":
            out.append(np.asarray(vals, np.int32))
        else:
            out.append(np.asarray(vals, np.float32))
    return tuple(out)
