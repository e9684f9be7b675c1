"""Collectives over the device mesh.

TPU-native equivalent of the reference's CPU collective engine
(ref: src/net/allreduce_engine.cpp — Bruck all-gather for small payloads,
recursive-halving reduce-scatter + Bruck for large, over point-to-point
SendRecv; src/net/allreduce_topo.cpp — the hop maps). On TPU every one of
those algorithms collapses into a single XLA collective routed on the ICI
torus by the compiler — ``psum`` / ``all_gather`` / ``psum_scatter`` inside
``shard_map``. The topology math (BruckMap/RecursiveHalvingMap) is subsumed
by hardware routing and is an explicit non-goal (SURVEY §2.2).

These helpers are host-plane conveniences: they take a host or device array,
run the collective over the Zoo mesh's table axis (or an explicit ``mesh``,
for harnesses running before/without the Zoo — the same override ring/tp
take), and hand the result back. In-graph code should call ``jax.lax.psum``
etc. directly inside its own ``shard_map``.

Observability (ISSUE 12): every entry point wraps its dispatch in
``telemetry/devstats.collective_span`` — op/bytes/duration land as
Dashboard ``coll[op]`` monitors (zoo shutdown report), flight-recorder
``coll.begin``/``coll.end`` events, one ``coll.<op>`` trace span, and the
MSG_STATS ``"devices"`` block; a compile fired inside is keyed to THIS
mesh's shape. ``tools/check_obs_surface.py`` asserts the wrapping
statically, so a future collective op cannot ship dark (the crack
MSG_SNAPSHOT once slipped through). Span durations are host
dispatch(+compile) time — jax dispatch is async, so a non-blocking
caller's span excludes device execution.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.zoo import Zoo


def _mesh_axis(axis: Optional[str], mesh: Optional[Mesh] = None):
    if mesh is not None:
        return mesh, (axis or mesh.axis_names[-1])
    zoo = Zoo.get()
    mesh = zoo.mesh()
    return mesh, (axis or zoo.shard_axis())


def process_sum(arr: np.ndarray) -> np.ndarray:
    """Sum identical-shaped per-process host arrays across the
    multi-controller world with ONE jitted device AllReduce — the
    device-side replacement for allgather-then-numpy-sum (which made
    every host download world x size bytes and reduce on CPU; the
    reference reduce-scattered for exactly this reason, ref
    src/net/allreduce_engine.cpp:39-53). Per-host transfer stays O(size)
    regardless of world size, and the reduction itself rides ICI/DCN.

    Single-process: identity. Called collectively (every process, same
    shape) like every other host-plane collective."""
    world = jax.process_count()
    if world == 1:
        return arr
    mesh, sharding, reducer = _process_sum_setup(world)
    with _devstats.collective_span("process_sum", arr.nbytes, mesh=mesh):
        rep = mesh.devices.flat[jax.process_index()]
        _devstats.note_transfer(arr.nbytes, "h2d")
        mine = jax.device_put(arr[None], rep)
        garr = jax.make_array_from_single_device_arrays(
            (world,) + arr.shape, sharding, [mine])
        out = reducer(garr)
        _devstats.note_transfer(arr.nbytes, "d2h")
        return np.asarray(out.addressable_shards[0].data).astype(arr.dtype)


_PSUM_SETUP = {}


def _process_sum_setup(world: int):
    """Mesh + jitted reducer for process_sum, built once per topology —
    a per-call jit(lambda) would re-trace every invocation (jax's
    dispatch cache keys on function identity), turning each table sync
    into a compile."""
    hit = _PSUM_SETUP.get(world)
    if hit is not None:
        return hit
    from jax.sharding import Mesh
    # one representative device per process, in process order: the
    # reduction needs each process's contribution exactly once, whatever
    # the local device count is
    rep = {}
    for d in sorted(jax.devices(), key=lambda d: d.id):
        rep.setdefault(d.process_index, d)
    mesh = Mesh(np.array([rep[p] for p in range(world)]), ("proc",))
    sharding = NamedSharding(mesh, P("proc"))
    reducer = jax.jit(lambda x: x.sum(axis=0),
                      out_shardings=NamedSharding(mesh, P()))
    _PSUM_SETUP[world] = (mesh, sharding, reducer)
    return _PSUM_SETUP[world]


# mapped-collective cache, keyed (op, mesh, axis[, root]). Two perf
# bugs the devstats compiles_by_mesh counter caught: rebuilding the
# shard_map closure per call defeated every fn-identity cache (25
# compiles for 25 all_reduce calls), and EAGER shard_map re-lowers per
# call on the legacy (jax.experimental) path even for one stable
# closure — so the cached callable is jax.jit(shard_map(...)), the
# idiom process_sum already uses: compile once per (op, mesh, shape),
# C++ fast path after. Mesh is hashable/eq by (devices, axis_names);
# bounded by the few (op, mesh) configurations a process ever builds.
_MAPPED = {}


def _mapped(key, build):
    fn = _MAPPED.get(key)
    if fn is None:
        fn = _MAPPED[key] = jax.jit(build())
    return fn


def all_reduce(x, axis: Optional[str] = None,
               mesh: Optional[Mesh] = None) -> jax.Array:
    """Sum the per-shard slices of an axis-sharded array into a replicated
    result — the reference Allreduce over per-node buffers
    (ref AllreduceEngine::Allreduce). Input: sharded [n] (n = shards * chunk);
    output: replicated [chunk] = sum of all chunks."""
    mesh, ax = _mesh_axis(axis, mesh)
    x = jnp.asarray(x)

    def build():
        @partial(jax.shard_map, mesh=mesh, in_specs=P(ax), out_specs=P(),
                 check_vma=False)
        def _psum(v):
            return jax.lax.psum(v, ax)
        return _psum

    with _devstats.collective_span("all_reduce", x.nbytes, mesh=mesh):
        return _mapped(("all_reduce", mesh, ax), build)(x)


def all_gather(x, axis: Optional[str] = None,
               mesh: Optional[Mesh] = None) -> jax.Array:
    """Concatenate the shards of an axis-sharded array on every shard
    (ref AllreduceEngine::Allgather)."""
    mesh, ax = _mesh_axis(axis, mesh)
    x = jnp.asarray(x)

    def build():
        @partial(jax.shard_map, mesh=mesh, in_specs=P(ax), out_specs=P(),
                 check_vma=False)
        def _ag(v):
            return jax.lax.all_gather(v, ax, tiled=True)
        return _ag

    with _devstats.collective_span("all_gather", x.nbytes, mesh=mesh):
        return _mapped(("all_gather", mesh, ax), build)(x)


def reduce_scatter(x, axis: Optional[str] = None,
                   mesh: Optional[Mesh] = None) -> jax.Array:
    """Sum a replicated array and leave each shard with its slice
    (ref AllreduceEngine::ReduceScatter). Input: replicated [n]; output:
    sharded [n] (each device holds n/shards)."""
    mesh, ax = _mesh_axis(axis, mesh)
    x = jnp.asarray(x)

    def build():
        @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(ax),
                 check_vma=False)
        def _rs(v):
            n = jax.lax.axis_size(ax)
            i = jax.lax.axis_index(ax)
            chunk = v.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk)
        return _rs

    with _devstats.collective_span("reduce_scatter", x.nbytes, mesh=mesh):
        return _mapped(("reduce_scatter", mesh, ax), build)(x)


def broadcast(x, root: int = 0, axis: Optional[str] = None,
              mesh: Optional[Mesh] = None) -> jax.Array:
    """Every shard adopts shard ``root``'s value (controller-broadcast
    analogue, ref src/controller.cpp membership broadcast)."""
    mesh, ax = _mesh_axis(axis, mesh)
    x = jnp.asarray(x)

    def build():
        @partial(jax.shard_map, mesh=mesh, in_specs=P(ax), out_specs=P(),
                 check_vma=False)
        def _bc(v):
            full = jax.lax.all_gather(v, ax)
            return full[root]
        return _bc

    with _devstats.collective_span("broadcast", x.nbytes, mesh=mesh):
        return _mapped(("broadcast", mesh, ax, root), build)(x)
