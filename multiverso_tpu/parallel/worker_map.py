"""Multi-worker BSP training over a worker mesh axis.

TPU-native equivalent of the reference's synchronous data parallelism: N
workers push deltas, the SyncServer's vector clocks force every i-th Get to
see the same state on all workers (ref: src/server.cpp:68-222 SyncServer,
flag -sync=true). On TPU BSP is the *hardware-native* mode: one jitted SPMD
step where each logical worker computes on its batch shard and the deltas
meet in a ``psum`` — the vector-clock machinery is replaced by the data
dependency itself (SURVEY §7 design stance).

``worker_step`` builds that step for any per-worker gradient function plus a
parameter table: grads are psum-averaged over the worker axis and applied
through the table's updater, all in one compiled program.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.updaters import AddOption
from multiverso_tpu.zoo import Zoo


def make_worker_mesh(num_workers: int, axis: str = "worker",
                     shard_axis: str = "mv") -> Mesh:
    """A (worker, shard) mesh over all local devices: batch parallel over
    ``worker``, table rows over ``shard``. num_workers must divide the device
    count."""
    devices = np.asarray(jax.devices())
    if devices.size % num_workers:
        raise ValueError(
            f"{num_workers} workers do not divide {devices.size} devices")
    return Mesh(devices.reshape(num_workers, devices.size // num_workers),
                (axis, shard_axis))


def worker_step(table, grad_fn: Callable, learning_rate: float = 0.1,
                axis: str = "worker",
                opt: Optional[AddOption] = None) -> Callable:
    """Build ``step(state, batch) -> (state, loss)`` where ``batch`` leading
    dim is sharded over the worker axis; each worker's gradient is computed
    on its shard, psum-averaged (the BSP merge), lr-premultiplied and applied
    via the table updater.

    ``grad_fn(params_flat, batch_shard) -> (loss, grad_flat)`` runs per
    worker; params are replicated across workers (each worker sees the same
    table state — the SyncServer guarantee).
    """
    mesh = Zoo.get().mesh()
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    opt = opt or AddOption(learning_rate=learning_rate)
    shard_ax = [a for a in mesh.axis_names if a != axis]

    def step(state, batch):
        data = state["data"]

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(), P(axis)), out_specs=(P(), P()),
                 check_vma=False)
        def _grads(params, local_batch):
            loss, grad = grad_fn(params, local_batch)
            # BSP merge: average the per-worker gradients over ICI
            grad = jax.lax.pmean(grad, axis)
            loss = jax.lax.pmean(loss, axis)
            return loss, grad

        loss, grad = _grads(data, batch)
        delta = learning_rate * grad
        new_state = table.functional_add(state, delta, opt)
        return new_state, loss

    return step
