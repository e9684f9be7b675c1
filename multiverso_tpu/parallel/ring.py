"""Long-context sequence/context parallelism: ring attention + Ulysses.

The reference predates transformers — it has no sequence axis (SURVEY §5
"long-context: absent"). For this framework long context is first-class: two
standard context-parallel schemes over the mesh, built from XLA collectives
on ICI:

* **Ring attention** (blockwise attention with ``ppermute``): Q stays local,
  K/V blocks rotate around the ring; a numerically-stable online softmax
  (running max / denominator) accumulates the output, so sequence length
  scales with the number of chips at O(S_local^2) memory.
* **Ulysses-style all-to-all**: sequence-sharded -> head-sharded via
  ``all_to_all``, full attention locally, then back. Cheaper collectives when
  head count >= shard count.

Both are pure functions usable inside jit over any mesh axis.

Numerics note: on TPU the MXU's default matmul precision is bfloat16, so the
blockwise (ring) and monolithic attention orders can differ by ~5e-3 for
float32 inputs. Pass ``precision="float32"`` (or wrap the call in
``jax.default_matmul_precision("float32")``) when bit-level agreement with a
reference matters; training is fine at the default.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.zoo import Zoo

# jit-wrapped shard_map callable cache keyed on EVERY closed-over
# parameter — the parallel/collectives.py discipline: rebuilding the
# closure per call defeats every fn-identity cache, and eager legacy
# shard_map re-lowers per call (the 25-calls-=-25-compiles pathology
# the devstats compiles_by_mesh counter measured)
_MAPPED = {}


def _mapped(key, build):
    fn = _MAPPED.get(key)
    if fn is None:
        fn = _MAPPED[key] = jax.jit(build())
    return fn


def sequence_shard(x, axis_name: Optional[str] = None, seq_dim: int = 2):
    """device_put a [B, H, S, D] array sequence-sharded over the mesh."""
    zoo = Zoo.get()
    mesh = zoo.mesh()
    ax = axis_name or zoo.shard_axis()
    spec = [None] * x.ndim
    spec[seq_dim] = ax
    x = jnp.asarray(x)
    # host->device transfer through the devstats chokepoint (the sharded
    # upload is exactly the device-plane cost the scale curve attributes)
    _devstats.note_transfer(x.nbytes, "h2d")
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def _online_update(qc, kc, vc, scale, allowed, m, l, o):
    """One block of the numerically-stable online softmax: fold the scores
    of ``qc @ kc^T`` (masked where ``allowed`` is False; None = no mask)
    into the running (max, denominator, output) state. Shared by the
    contiguous and zigzag ring bodies."""
    neg_inf = jnp.asarray(-1e30, qc.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc) * scale
    if allowed is not None:
        s = jnp.where(allowed[None, None], s, neg_inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if allowed is not None:
        # fully-masked rows would otherwise get exp(neg_inf-neg_inf)=1
        p = jnp.where(allowed[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, scale: float,
                          causal: bool = False):
    """Per-shard body: local q [B,H,Sq,D] against rotating k/v blocks."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    neg_inf = jnp.asarray(-1e30, q.dtype)
    # global token positions of this shard's queries
    qpos = idx * sq + jnp.arange(sq)

    def body(carry, t):
        k_blk, v_blk, m, l, o = carry
        if causal:
            # after t rotations the visiting k/v block is block (idx - t) % n
            j = (idx - t) % n
            kpos = j * sk + jnp.arange(sk)
            allowed = qpos[:, None] >= kpos[None, :]
        else:
            allowed = None
        m, l, o = _online_update(q, k_blk, v_blk, scale, allowed, m, l, o)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, l, o), None

    m0 = jnp.full((b, h, sq), neg_inf, q.dtype)
    l0 = jnp.zeros((b, h, sq), q.dtype)
    o0 = jnp.zeros_like(q)
    (_, _, _, l, o), _ = jax.lax.scan(body, (k, v, m0, l0, o0),
                                      jnp.arange(n))
    return o / l[..., None]


def ring_attention(q, k, v, axis_name: Optional[str] = None,
                   mesh: Optional[Mesh] = None,
                   precision: Optional[str] = None,
                   causal: bool = False,
                   batch_axis: Optional[str] = None,
                   head_axis: Optional[str] = None):
    """Ring attention over sequence-sharded [B, H, S, D] arrays; causal
    masking uses global block positions so the online softmax sees exactly
    the lower-triangular scores. ``batch_axis`` additionally shards B (the
    dp x sp layout of the transformer model family) and ``head_axis``
    shards H (tensor parallelism composed with the sequence ring — heads
    are embarrassingly parallel inside the ring body). Returns the
    sequence-sharded output.

    Causal note: with contiguous block assignment shard i only has useful
    work on i+1 of its n ring steps (the rest are fully masked), so ~half
    the attention FLOPs are masked out and the ring is load-imbalanced.
    :func:`zigzag_ring_attention` is the balanced fix — every shard does
    exactly half the pairs every tick and dead pairs are skipped, not
    masked."""
    zoo = Zoo.get()
    mesh = mesh or zoo.mesh()
    ax = axis_name or zoo.shard_axis()
    if head_axis and q.shape[1] % mesh.shape[head_axis]:
        raise ValueError(f"heads {q.shape[1]} not divisible by "
                         f"{mesh.shape[head_axis]} {head_axis!r} shards")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(batch_axis, head_axis, ax, None)

    # every closed-over value is in the key: a head-dim change moves
    # `scale`, and `precision` is trace-time (the context wraps the
    # first call, which is when the cached fn traces)
    mapped = _mapped(
        ("ring", mesh, ax, scale, causal, batch_axis, head_axis,
         precision),
        lambda: jax.shard_map(
            partial(_ring_attention_local, axis_name=ax, scale=scale,
                    causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
    nbytes = q.nbytes + k.nbytes + v.nbytes
    with _devstats.collective_span("ring_attention", nbytes, mesh=mesh):
        if precision is not None:
            with jax.default_matmul_precision(precision):
                return mapped(q, k, v)
        return mapped(q, k, v)


def ulysses_attention(q, k, v, axis_name: Optional[str] = None,
                      mesh: Optional[Mesh] = None,
                      causal: bool = False,
                      batch_axis: Optional[str] = None):
    """All-to-all sequence parallelism: resharding sequence->heads, local
    full attention, heads->sequence. Head count must be divisible by the
    shard count."""
    zoo = Zoo.get()
    mesh = mesh or zoo.mesh()
    ax = axis_name or zoo.shard_axis()
    n = mesh.shape[ax]
    if q.shape[1] % n:
        raise ValueError(f"heads {q.shape[1]} not divisible by shards {n}")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(batch_axis, None, ax, None)

    def local(q, k, v):
        # [B, H, S/n, D] -> all_to_all -> [B, H/n, S, D]
        def seq2head(x):
            return jax.lax.all_to_all(x, ax, split_axis=1, concat_axis=2,
                                      tiled=True)

        def head2seq(x):
            return jax.lax.all_to_all(x, ax, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
        if causal:
            sfull = qh.shape[2]
            mask = jnp.tril(jnp.ones((sfull, sfull), bool))
            s = jnp.where(mask[None, None], s, jnp.asarray(-1e30, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
        return head2seq(o)

    nbytes = q.nbytes + k.nbytes + v.nbytes
    mapped = _mapped(
        ("ulysses", mesh, ax, scale, causal, batch_axis),
        lambda: jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec))
    with _devstats.collective_span("ulysses_attention", nbytes, mesh=mesh):
        return mapped(q, k, v)


def zigzag_shard_ids(seq_len: int, n: int) -> "jnp.ndarray":
    """Global token order for the zigzag layout: shard i owns chunks i and
    2n-1-i of the 2n equal chunks. Returns the permutation ``perm`` such
    that ``x[..., perm, :]`` is zigzag-ordered (shard-major);
    ``jnp.argsort(perm)`` inverts it."""
    if seq_len % (2 * n):
        raise ValueError(f"seq {seq_len} not divisible by 2n={2 * n} chunks")
    c = seq_len // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * c, (i + 1) * c))                    # chunk i
        j = 2 * n - 1 - i
        order.extend(range(j * c, (j + 1) * c))                    # chunk 2n-1-i
    import numpy as _np
    return jnp.asarray(_np.asarray(order, _np.int32))


def _zigzag_ring_local(q, k, v, axis_name: str, scale: float):
    """Per-shard causal body, zigzag layout. Local q/k/v are
    [B, H, 2c, D] = concat(chunk_lo=i, chunk_hi=2n-1-i). Causal liveness of
    each (q-chunk, k-chunk) pair is decided per tick with ``lax.switch`` so
    dead pairs cost nothing and every shard computes exactly 2 of 4 pairs
    every tick — balanced, ~half the FLOPs of masked contiguous ring."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s2, d = q.shape
    c = s2 // 2
    neg_inf = jnp.asarray(-1e30, q.dtype)
    ar = jnp.arange(c)

    def chunk_attn(qc, kc, vc, qpos0, kpos0, mode, m, l, o):
        """Online-softmax update of (m, l, o) for one chunk pair.
        mode: 0 dead, 1 diagonal (triangular mask), 2 fully live."""

        def dead(_):
            return m, l, o

        def live(masked):
            allowed = ((qpos0 + ar)[:, None] >= (kpos0 + ar)[None, :]
                       if masked else None)
            return _online_update(qc, kc, vc, scale, allowed, m, l, o)

        return jax.lax.switch(mode, [dead,
                                     lambda _: live(True),
                                     lambda _: live(False)], None)

    def body(carry, t):
        k_blk, v_blk, st_lo, st_hi = carry
        j = (idx - t) % n                      # owner of the visiting block
        k_lo, k_hi = k_blk[:, :, :c], k_blk[:, :, c:]
        v_lo, v_hi = v_blk[:, :, :c], v_blk[:, :, c:]
        qpos_lo = idx * c                      # chunk i
        qpos_hi = (2 * n - 1 - idx) * c        # chunk 2n-1-i
        kpos_lo = j * c
        kpos_hi = (2 * n - 1 - j) * c
        # pair liveness (see chunk algebra in ring docstring): q_lo vs k_hi
        # is always dead; q_hi vs k_lo always fully live
        m1 = jnp.where(idx > j, 2, jnp.where(idx == j, 1, 0))  # q_lo,k_lo
        m4 = jnp.where(idx < j, 2, jnp.where(idx == j, 1, 0))  # q_hi,k_hi
        st_lo = chunk_attn(q[:, :, :c], k_lo, v_lo, qpos_lo, kpos_lo,
                           m1, *st_lo)
        st_hi = chunk_attn(q[:, :, c:], k_lo, v_lo, qpos_hi, kpos_lo,
                           jnp.int32(2), *st_hi)
        st_hi = chunk_attn(q[:, :, c:], k_hi, v_hi, qpos_hi, kpos_hi,
                           m4, *st_hi)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, st_lo, st_hi), None

    def init_state():
        return (jnp.full((b, h, c), neg_inf, q.dtype),
                jnp.zeros((b, h, c), q.dtype),
                jnp.zeros((b, h, c, d), q.dtype))

    (_, _, (_, l_lo, o_lo), (_, l_hi, o_hi)), _ = jax.lax.scan(
        body, (k, v, init_state(), init_state()), jnp.arange(n))
    return jnp.concatenate([o_lo / l_lo[..., None],
                            o_hi / l_hi[..., None]], axis=2)


def zigzag_ring_attention(q, k, v, axis_name: Optional[str] = None,
                          mesh: Optional[Mesh] = None,
                          precision: Optional[str] = None,
                          batch_axis: Optional[str] = None,
                          head_axis: Optional[str] = None):
    """Causal ring attention with the balanced zigzag layout. Inputs
    [B, H, S, D] must be permuted into zigzag sequence order first
    (``x[:, :, zigzag_shard_ids(S, n), :]``); the output comes back in the
    same layout. Always causal — for non-causal use :func:`ring_attention`,
    whose contiguous ring is already balanced when nothing is masked."""
    zoo = Zoo.get()
    mesh = mesh or zoo.mesh()
    ax = axis_name or zoo.shard_axis()
    n = mesh.shape[ax]
    if q.shape[2] % (2 * n):
        raise ValueError(f"seq {q.shape[2]} not divisible by 2n={2 * n}")
    if head_axis and q.shape[1] % mesh.shape[head_axis]:
        raise ValueError(f"heads {q.shape[1]} not divisible by "
                         f"{mesh.shape[head_axis]} {head_axis!r} shards")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(batch_axis, head_axis, ax, None)
    mapped = _mapped(
        ("zigzag", mesh, ax, scale, batch_axis, head_axis, precision),
        lambda: jax.shard_map(
            partial(_zigzag_ring_local, axis_name=ax, scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
    nbytes = q.nbytes + k.nbytes + v.nbytes
    with _devstats.collective_span("zigzag_ring_attention", nbytes,
                                   mesh=mesh):
        if precision is not None:
            with jax.default_matmul_precision(precision):
                return mapped(q, k, v)
        return mapped(q, k, v)


def reference_attention(q, k, v, causal: bool = False):
    """Unsharded softmax attention (test oracle for the flash and ring
    kernels). Scores and softmax in f32 regardless of input dtype, output
    in the input dtype — the same numerics as the flash kernel."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
