"""Pipeline parallelism: a GPipe-style microbatch ring over a mesh axis.

Completes the strategy surface (SURVEY §2.10: the reference's only
"pipeline" is communication/compute double-buffering; layer pipelining was
out of its scope). Stage s of a stack of identical blocks lives on device s
of the ``pp`` axis; microbatches enter at stage 0, activations hop stage to
stage over ICI via ``ppermute``, and the bubble is the classic
``(n_stages - 1) / (n_stages - 1 + n_micro)`` fraction.

TPU-first shape discipline: ONE ``lax.scan`` over ``n_micro + n_stages - 1``
ticks compiles a single pipelined body; every tick does (ingest -> stage fn
-> emit -> rotate) with static shapes, so XLA overlaps the ppermute with the
next tick's compute. Per-stage parameters are a stacked ``[n_stages, ...]``
pytree sharded over ``pp`` — the same layout `lax.scan` uses for a deep
stack on one chip, just distributed.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.zoo import Zoo


def shard_stages(stacked_params: Any, axis: str = "pp",
                 mesh: Optional[Mesh] = None) -> Any:
    """Place a [n_stages, ...]-stacked param pytree stage-sharded."""
    mesh = mesh or Zoo.get().mesh()

    def put(x):
        spec = P(axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, stacked_params)


def _check_param_specs(param_specs: Any, axis: str) -> None:
    """Shared validation for the stage-weight spec override: every spec
    must lead with the pipeline axis (the leading dim is the stage dim)."""
    if param_specs is None:
        return
    for path, spec in jax.tree_util.tree_leaves_with_path(
            param_specs, is_leaf=lambda s: isinstance(s, P)):
        if not spec or spec[0] != axis:
            raise ValueError(
                f"param_specs leaf {jax.tree_util.keystr(path)} must "
                f"lead with the pipeline axis {axis!r}, got {spec}")


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array,
                   n_micro: int, axis: str = "pp",
                   mesh: Optional[Mesh] = None,
                   batch_axis: Optional[str] = None,
                   param_specs: Any = None) -> jax.Array:
    """Run ``x`` [B, ...] through ``n_stages`` pipelined applications of
    ``stage_fn``; batch is split into ``n_micro`` microbatches on the fly.

    ``stage_params`` leaves are [n_stages, ...] (use :func:`shard_stages`);
    ``stage_fn(params_for_one_stage, act) -> act`` must preserve the
    activation shape (the identical-blocks contract of layer pipelining).
    On a multi-axis mesh pass ``batch_axis`` to shard the microbatch dim
    (each batch shard runs its own pipeline over the same stage weights).

    ``param_specs``: optional PartitionSpec pytree (same structure as
    ``stage_params``) when stage weights are sharded over ADDITIONAL mesh
    axes beyond the leading ``axis`` dim — e.g. tensor parallelism inside
    each stage, ``P('pp', None, None, 'tp')``. Each spec's first entry must
    be ``axis``; ``stage_fn`` then sees tp-local weight shards and may use
    ``jax.lax.psum`` over those axes (it runs inside this shard_map).
    """
    mesh = mesh or Zoo.get().mesh()
    n_stages = mesh.shape[axis]
    for path, leaf in jax.tree_util.tree_leaves_with_path(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leaf {jax.tree_util.keystr(path)} has leading "
                f"dim {leaf.shape[0]}, expected n_stages={n_stages} "
                f"(mesh axis {axis!r}); fold extra layers into stage_fn")
    _check_param_specs(param_specs, axis)
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    mb = b // n_micro
    xs = x.reshape(n_micro, mb, *x.shape[1:])

    def body(params, xs):
        # params: this stage's slice, leading stage-dim of 1
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        last = n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            act, outs = carry
            # stage 0 ingests microbatch t while it exists; later stages
            # keep the activation that just arrived on the ring
            inp = xs[jnp.minimum(t, n_micro - 1)]
            act = jnp.where(idx == 0, inp, act)
            act = stage_fn(params, act)
            # stage n-1 emits microbatch t-(n-1) once the fill ends
            slot = jnp.clip(t - last, 0, n_micro - 1)
            valid = (idx == last) & (t >= last)
            outs = outs.at[slot].add(jnp.where(valid, act, 0.0))
            act = jax.lax.ppermute(act, axis, fwd)
            return (act, outs), None

        act0 = jnp.zeros(xs.shape[1:], xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (act0, outs0), jnp.arange(n_micro + n_stages - 1))
        # every stage holds zeros except the last; psum replicates the result
        return jax.lax.psum(outs, axis)

    pspec = (param_specs if param_specs is not None
             else jax.tree.map(lambda _: P(axis), stage_params))
    xspec = P(None, batch_axis) if batch_axis else P()
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(pspec, xspec), out_specs=xspec,
                        check_vma=False)(stage_params, xs)
    return out.reshape(b, *x.shape[1:])


def shard_stages_interleaved(stacked_params: Any, n_stages: int,
                             axis: str = "pp",
                             mesh: Optional[Mesh] = None) -> Any:
    """Regroup a [n_total, ...] stage stack for the interleaved schedule
    and place it: global stage g runs as chunk v = g // n_stages on device
    d = g % n_stages, so the [n_total, ...] leaves become [n_stages,
    n_chunks, ...] (device-major) sharded over ``axis``."""
    mesh = mesh or Zoo.get().mesh()

    def regroup(p):
        if p.shape[0] % n_stages:
            raise ValueError(f"stage count {p.shape[0]} not divisible by "
                             f"n_stages={n_stages}")
        v = p.shape[0] // n_stages
        p = p.reshape(v, n_stages, *p.shape[1:]).swapaxes(0, 1)
        return jax.device_put(
            p, NamedSharding(mesh, P(axis, *([None] * (p.ndim - 1)))))

    return jax.tree.map(regroup, stacked_params)


def pipeline_apply_interleaved(stage_fn: Callable[[Any, jax.Array],
                                                  jax.Array],
                               stage_params: Any, x: jax.Array,
                               axis: str = "pp",
                               mesh: Optional[Mesh] = None,
                               batch_axis: Optional[str] = None,
                               param_specs: Any = None) -> jax.Array:
    """Interleaved (virtual-chunk) pipeline: each device holds ``n_chunks``
    NON-contiguous stages, Megatron's interleaved schedule adapted to the
    microbatch ring.

    vs :func:`pipeline_apply` (GPipe): with the stack split into V chunks
    per device, an activation circles the ring V times, and a device works
    on chunk v of one microbatch while later microbatches are still in its
    earlier chunks. Fill/drain cost is ``n_stages - 1`` ticks of ONE
    chunk's work instead of the whole per-device stack — the bubble
    fraction drops from (S-1)/(S-1+M) to (S-1)/(S-1+M*V) for the same
    microbatch count. The price: V times more ppermute hops (cheap on the
    ICI torus) and a fixed microbatch count of ``n_stages``.

    ``stage_params`` leaves are [n_stages, n_chunks, ...] (use
    :func:`shard_stages_interleaved`); batch must split into exactly
    ``n_stages`` microbatches; ``stage_fn(chunk_params, act) -> act``
    applies one chunk. ``param_specs`` shards chunk weights over extra
    mesh axes exactly as in :func:`pipeline_apply` (each spec must lead
    with ``axis``).
    """
    mesh = mesh or Zoo.get().mesh()
    n_stages = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves_with_path(stage_params)
    n_chunks = leaves[0][1].shape[1] if leaves else 1
    for path, leaf in leaves:
        if leaf.shape[0] != n_stages or leaf.shape[1] != n_chunks:
            raise ValueError(
                f"stage_params leaf {jax.tree_util.keystr(path)} has "
                f"leading dims {leaf.shape[:2]}, expected "
                f"({n_stages}, {n_chunks})")
    _check_param_specs(param_specs, axis)
    b = x.shape[0]
    if b % n_stages:
        raise ValueError(f"batch {b} not divisible by the interleaved "
                         f"schedule's fixed n_micro={n_stages}")
    mb = b // n_stages
    xs = x.reshape(n_stages, mb, *x.shape[1:])

    def body(params, xs):
        params = jax.tree.map(lambda p: p[0], params)  # [V, ...] local
        idx = jax.lax.axis_index(axis)
        S, V = n_stages, n_chunks
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            act, outs = carry
            u = t - idx                    # ticks since this device's first
            v = jnp.clip(u // S, 0, V - 1)  # chunk this device runs now
            # device 0 ingests microbatch t during the first S ticks; later
            # ticks it continues chunks arriving back around the ring
            act = jnp.where((idx == 0) & (t < S),
                            xs[jnp.clip(t, 0, S - 1)], act)
            pv = jax.tree.map(
                lambda q: jax.lax.dynamic_index_in_dim(
                    q, v, 0, keepdims=False), params)
            act = stage_fn(pv, act)
            # last device emits microbatch u - (V-1)S while running the
            # final chunk
            slot = jnp.clip(u - (V - 1) * S, 0, S - 1)
            valid = (idx == S - 1) & (u >= (V - 1) * S) & (u < V * S)
            outs = outs.at[slot].add(jnp.where(valid, act, 0.0))
            act = jax.lax.ppermute(act, axis, fwd)
            return (act, outs), None

        act0 = jnp.zeros(xs.shape[1:], xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (act0, outs0), jnp.arange(S * V + S - 1))
        return jax.lax.psum(outs, axis)

    pspec = (param_specs if param_specs is not None
             else jax.tree.map(lambda _: P(axis), stage_params))
    xspec = P(None, batch_axis) if batch_axis else P()
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(pspec, xspec), out_specs=xspec,
                        check_vma=False)(stage_params, xs)
    return out.reshape(b, *x.shape[1:])
