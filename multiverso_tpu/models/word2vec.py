"""word2vec model math (skipgram / CBOW, negative sampling / hierarchical
softmax), pure JAX.

TPU-native re-design of the reference WordEmbedding trainer math
(ref: Applications/WordEmbedding/src/wordembedding.cpp:57-160 — per-pair
scalar FeedForward/BPOutputLayer loops, Hogwild-racy within a node). Here a
whole minibatch of (center, context) pairs trains as batched gathers + a
(B, K+1, D) einsum on the MXU, and the scatter-add of gradients replaces the
racy writes with deterministic duplicate accumulation — same algorithm, no
races, hardware-shaped.

Negative sampling uses a device-resident precomputed slot table (the
word2vec.c / reference design, sized 2^20 instead of 1e8): one uniform draw +
one gather per negative. (The inverse-CDF ``searchsorted`` variant is kept
for reference but its binary search is ~3x the whole step's cost on the VPU.)

All step functions are functional: they take and return the embedding arrays,
so the caller can run them under ``lax.scan``/``jit`` and commit to the
parameter tables at block boundaries (the PS Add/Get shows up only at the
block seam, exactly like the reference's RequestParameter/AddDeltaParameter
block pipeline, src/communicator.cpp:104-236).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from multiverso_tpu.ops import row_combine


class W2VConfig(NamedTuple):
    vocab_size: int
    embedding_dim: int = 128
    negatives: int = 5
    window: int = 5
    learning_rate: float = 0.025
    cbow: bool = False
    hierarchical_softmax: bool = False
    shared_negatives: int = 0  # >0: batch-shared negative pool (TPU-first)


def init_embeddings(cfg: W2VConfig, seed: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Input: uniform ±0.5/dim (ref communicator.cpp:20 server random init);
    output: zeros."""
    rng = np.random.default_rng(seed)
    win = ((rng.random((cfg.vocab_size, cfg.embedding_dim)) - 0.5)
           / cfg.embedding_dim).astype(np.float32)
    wout = np.zeros((cfg.vocab_size, cfg.embedding_dim), dtype=np.float32)
    return win, wout


def sample_negatives(key: jax.Array, cdf: jax.Array, batch: int,
                     k: int) -> jax.Array:
    """Inverse-CDF draw from the unigram^0.75 table. NOTE: searchsorted's
    binary search is slow on the TPU VPU (~3x the whole training step);
    prefer :func:`build_negative_table` + :func:`sample_negatives_table`,
    which is the word2vec.c design and costs one gather."""
    u = jax.random.uniform(key, (batch, k))
    return jnp.searchsorted(cdf, u).astype(jnp.int32)


def build_negative_table(unigram: np.ndarray, size: int = 1 << 20
                         ) -> np.ndarray:
    """Precomputed sampling table: word w occupies ~unigram[w]*size slots
    (the reference/word2vec.c 1e8-slot table, sized for accelerator memory).
    Sampling = uniform int + one gather — no binary search."""
    p = np.asarray(unigram, dtype=np.float64)
    p = p / p.sum()
    counts = np.maximum(np.round(p * size).astype(np.int64), 1)
    table = np.repeat(np.arange(p.size, dtype=np.int32), counts)
    if table.size >= size:
        return table[:size]
    pad = np.random.default_rng(0).choice(
        p.size, size - table.size, p=p).astype(np.int32)
    return np.concatenate([table, pad])


def sample_negatives_table(key: jax.Array, neg_table: jax.Array, batch: int,
                           k: int) -> jax.Array:
    idx = jax.random.randint(key, (batch, k), 0, neg_table.shape[0])
    return jnp.take(neg_table, idx, axis=0)


def splitmix32(x):
    """Counter-based hash (splitmix64's finalizer, 32-bit constants) that is
    BIT-IDENTICAL between numpy and jnp uint32 arrays. The PS block path
    uses it to draw the same negative-sample stream twice: once on the host
    (to know which rows to pull) and once in-graph (so the sampled ids never
    have to cross the host->device wire)."""
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def counter_negs(base, count: int, table_mask: int):
    """Slot indices into a pow2-sized negative table for counters
    [base, base+count): works on host (numpy) and in-graph (jnp, ``base``
    traced) with identical results. ``table_mask`` = table_size - 1."""
    mod = jnp if isinstance(base, jax.Array) else np
    ctr = mod.arange(count, dtype=mod.uint32) + base
    return splitmix32(ctr) & mod.uint32(table_mask)


def _ns_forward_backward(v: jax.Array, u: jax.Array, labels: jax.Array,
                         lr: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared NS math. v: (B, D); u: (B, T, D); labels: (T,) or (B, T).

    Returns (loss, dv, du) where dv/du are *ascent* deltas pre-scaled by lr
    (ref BPOutputLayer sigmoid ± label, wordembedding.cpp:100-140).
    """
    scores = jnp.einsum("bd,btd->bt", v, u)
    sig = jax.nn.sigmoid(scores)
    g = (labels - sig) * lr                     # (B, T)
    dv = jnp.einsum("bt,btd->bd", g, u)
    du = g[..., None] * v[:, None, :]
    # loss: -log sigmoid(pos) - log sigmoid(-neg)
    logsig = jax.nn.log_sigmoid(jnp.where(labels > 0, scores, -scores))
    loss = -jnp.mean(jnp.sum(logsig, axis=-1))
    return loss, dv, du


def skipgram_ns_step(win: jax.Array, wout: jax.Array, centers: jax.Array,
                     contexts: jax.Array, negatives: jax.Array,
                     lr: float, scope: str = "mv.scan", *,
                     plans: Tuple[Optional[row_combine.RowPlan],
                                  Optional[row_combine.RowPlan]] = (None, None)
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One skipgram negative-sampling minibatch.

    centers/contexts: (B,) int32; negatives: (B, K) int32. ``scope``
    prefixes the names its gathers, gradient and scatters carry in a
    device trace (``mv.scan`` in a PS block, ``mv.fused`` in a fused
    epoch). Both tables are written with duplicates summed first and every
    distinct row once (``row_combine.add_rows``): every update is computed
    at the rows the minibatch started from, so that is the same float32
    sum in another order. The output table takes its ``(K + 1) * B``
    update rows a column of ``B`` at a time, the contexts and then each
    negative (:func:`target_columns`), and not in one write: on a v5e one
    sum of 49,152 update rows into ``f32[49408,300]`` takes 1.47 ms and
    its compiler 10.5 s, six sums of 8,192 into ``f32[8448,300]`` 0.84 ms
    and 1 s, and six heads take more rows off the walks than one. The
    columns are a loop: six writes in a row run a PS block 0.25% faster
    and cost its program, which compiles on every run, 1.3 s more of the
    compiler (PERF.md, PR 40). ``plans`` is ``(plan_rows(centers, rows),
    plan_rows(target_columns(contexts, negatives), rows))`` where the
    caller made them ahead of the step; a ``None`` is made in the step.
    """
    b, k = negatives.shape
    with jax.named_scope(scope + ".gather"):
        v = jnp.take(win, centers, axis=0)                   # (B, D)
        targets = jnp.concatenate([contexts[:, None], negatives], axis=1)
        u = jnp.take(wout, targets, axis=0)                  # (B, K+1, D)
    with jax.named_scope(scope + ".grad"):
        labels = jnp.concatenate(
            [jnp.ones((b, 1), v.dtype), jnp.zeros((b, k), v.dtype)], axis=1)
        loss, dv, du = _ns_forward_backward(v, u, labels, lr)
    with jax.named_scope(scope + ".scatter"):
        win = row_combine.add_rows(win, centers, dv, plans[0])

        def column(wout, col):      # (ids [B], updates [B, D], plan or None)
            return row_combine.add_rows(wout, *col), None

        wout, _ = jax.lax.scan(
            column, wout, (targets.T, jnp.moveaxis(du, 1, 0), plans[1]))
    return win, wout, loss


def target_columns(contexts: jax.Array, negatives: jax.Array) -> jax.Array:
    """``[..., K + 1, B]``: the ids of the output rows minibatches
    ``contexts [..., B]``, ``negatives [..., B, K]`` update, in the
    columns :func:`skipgram_ns_step` writes them in."""
    return jnp.concatenate(
        [contexts[..., None, :], jnp.swapaxes(negatives, -1, -2)], axis=-2)


def _cbow_mean(win, windows, window_mask):
    """Masked mean of the window's input vectors (ref FeedForward average,
    wordembedding.cpp:57-80). Returns (v, denom, m) for the backward."""
    ctx = jnp.take(win, windows, axis=0)                     # (B, W, D)
    m = window_mask.astype(ctx.dtype)[..., None]
    denom = jnp.maximum(m.sum(axis=1), 1.0)
    return (ctx * m).sum(axis=1) / denom, denom, m


def _cbow_spread(win, windows, dv, denom, m):
    """Scatter dv back over the (masked) window, divided like the forward
    mean."""
    dctx = (dv[:, None, :] / denom[:, None, :]) * m          # (B, W, D)
    return win.at[windows.reshape(-1)].add(
        dctx.reshape(-1, dctx.shape[-1]))


def cbow_ns_step(win: jax.Array, wout: jax.Array, windows: jax.Array,
                 window_mask: jax.Array, targets_pos: jax.Array,
                 negatives: jax.Array, lr: float
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One CBOW minibatch: windows (B, W) context ids with bool mask,
    averaged input vectors predict targets_pos (B,)."""
    b, k = negatives.shape
    v, denom, m = _cbow_mean(win, windows, window_mask)
    tgt = jnp.concatenate([targets_pos[:, None], negatives], axis=1)
    u = jnp.take(wout, tgt, axis=0)
    labels = jnp.concatenate(
        [jnp.ones((b, 1), v.dtype), jnp.zeros((b, k), v.dtype)], axis=1)
    loss, dv, du = _ns_forward_backward(v, u, labels, lr)
    win = _cbow_spread(win, windows, dv, denom, m)
    wout = wout.at[tgt.reshape(-1)].add(du.reshape(-1, du.shape[-1]))
    return win, wout, loss


def _hs_forward_backward(v: jax.Array, u: jax.Array, codes: jax.Array,
                         path_mask: jax.Array, lr: float
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared hierarchical-softmax math. v: (B, D) predictor vectors;
    u: (B, L, D) inner-node vectors along each word's Huffman path.
    Returns (loss, dv, du), ascent deltas pre-scaled by lr."""
    scores = jnp.einsum("bd,bld->bl", v, u)
    sig = jax.nn.sigmoid(scores)
    # label for Huffman: predict 1 - code (word2vec.c convention)
    labels = (1.0 - codes.astype(v.dtype))
    g = (labels - sig) * path_mask.astype(v.dtype) * lr
    dv = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * v[:, None, :]
    masked = jnp.where(path_mask, scores * (1 - 2 * codes), 0.0)
    loss = -jnp.mean(jnp.sum(jax.nn.log_sigmoid(masked)
                             * path_mask.astype(v.dtype), axis=-1))
    return loss, dv, du


def skipgram_hs_step(win: jax.Array, hs_out: jax.Array, centers: jax.Array,
                     codes: jax.Array, points: jax.Array,
                     path_mask: jax.Array, lr: float
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hierarchical-softmax skipgram minibatch.

    codes/points/path_mask: (B, L) — the context word's Huffman path
    (ref huffman_encoder.cpp output consumed at wordembedding.cpp HS branch).
    hs_out has V-1 inner-node rows.
    """
    v = jnp.take(win, centers, axis=0)                       # (B, D)
    u = jnp.take(hs_out, points, axis=0)                     # (B, L, D)
    loss, dv, du = _hs_forward_backward(v, u, codes, path_mask, lr)
    win = win.at[centers].add(dv)
    hs_out = hs_out.at[points.reshape(-1)].add(
        du.reshape(-1, du.shape[-1]))
    return win, hs_out, loss


def cbow_hs_step(win: jax.Array, hs_out: jax.Array, windows: jax.Array,
                 window_mask: jax.Array, codes: jax.Array,
                 points: jax.Array, path_mask: jax.Array, lr: float
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """CBOW x hierarchical softmax: the averaged window context predicts
    the target word's Huffman path (ref wordembedding.cpp CBOW+HS branch).

    windows/window_mask: (B, W); codes/points/path_mask: (B, L), the
    TARGET word's path.
    """
    v, denom, m = _cbow_mean(win, windows, window_mask)
    u = jnp.take(hs_out, points, axis=0)                     # (B, L, D)
    loss, dv, du = _hs_forward_backward(v, u, codes, path_mask, lr)
    win = _cbow_spread(win, windows, dv, denom, m)
    hs_out = hs_out.at[points.reshape(-1)].add(
        du.reshape(-1, du.shape[-1]))
    return win, hs_out, loss


def _epoch_jit(table_formats, carried: int = 0, counts: int = 0, **jit_kw):
    """``jax.jit`` for an epoch program ``(win, wsec, ...) -> (win, wsec,
    loss, *carried, *counts)``. ``table_formats`` is the two tables'
    ``Table.format``: the tables come back laid out as they are given
    (``Table.program_state``), so a chain of calls copies no table. State
    ``carried`` from call to call comes back replicated on the tables'
    mesh, where :func:`init_lcg_state`'s caller puts it, so the second
    call finds the first call's program; ``counts`` are scalars for the
    call's span, left to the compiler like the loss. ``None`` leaves
    every result to the compiler."""
    if table_formats is not None:
        whole = table_formats[0].sharding
        if isinstance(whole, NamedSharding):
            whole = NamedSharding(whole.mesh, PartitionSpec())
        jit_kw["out_shardings"] = (tuple(table_formats) + (None,)
                                   + (whole,) * carried + (None,) * counts)
    return functools.partial(jax.jit, **jit_kw)


def make_fused_epoch(cfg: W2VConfig, unigram: np.ndarray,
                     table_formats=None,
                     slots: Optional[np.ndarray] = None):
    """Build a jitted scan over skipgram-NS pair minibatches: the whole block
    trains on device; negatives are drawn in-graph. Returns
    ``epoch_fn(win, wout, centers, contexts, key) -> (win, wout, mean_loss)``
    where centers/contexts are (num_batches, B). ``slots`` is the negative
    table where the caller has built it (:func:`build_negative_table`, a
    word's id being the row it lives in)."""
    neg_table = jnp.asarray(build_negative_table(unigram)
                            if slots is None else slots)

    @_epoch_jit(table_formats)
    def epoch_fn(win, wout, centers, contexts, key):
        def body(carry, batch):
            win, wout, key = carry
            c, ctx = batch
            key, sub = jax.random.split(key)
            neg = sample_negatives_table(sub, neg_table, c.shape[0],
                                         cfg.negatives)
            win, wout, loss = skipgram_ns_step(
                win, wout, c, ctx, neg, cfg.learning_rate, "mv.fused")
            return (win, wout, key), loss

        with jax.named_scope("mv.fused"):   # device-trace name
            (win, wout, _), losses = jax.lax.scan(
                body, (win, wout, key), (centers, contexts))
        return win, wout, jnp.mean(losses)

    return epoch_fn


_LCG_A = np.uint32(1664525)
_LCG_C = np.uint32(1013904223)


@functools.lru_cache(maxsize=8)
def _lcg_jump_consts(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Closed-form LCG jump constants: ``s_t = A^t * s_0 + C_t (mod 2^32)``
    for t = 1..n, so a whole epoch's negative-sampler states come from one
    vectorized [n, K'] expression instead of n sequential in-scan steps
    (which profiled at ~17% of the epoch). Bit-identical to stepping the
    recurrence n times."""
    At = np.empty(n, np.uint32)
    Ct = np.empty(n, np.uint32)
    # python ints masked to 32 bits: np.uint32 scalar arithmetic would wrap
    # correctly too but spews RuntimeWarnings on every overflow
    mask, A, C = 0xFFFFFFFF, int(_LCG_A), int(_LCG_C)
    a, c = A, C
    for t in range(n):
        At[t], Ct[t] = a, c
        a = (a * A) & mask
        c = (c * A + C) & mask
    return At, Ct


def shared_neg_step(win: jax.Array, wout: jax.Array, centers: jax.Array,
                    contexts: jax.Array, neg_ids: jax.Array, lr: float,
                    neg_weight: float = 1.0,
                    compute_dtype=jnp.bfloat16,
                    plans: Tuple[Optional[row_combine.RowPlan],
                                 Optional[row_combine.RowPlan]] = (None, None),
                    shardings=(None, None)
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Skipgram-NS minibatch with a batch-SHARED negative pool.

    The reference draws ``k`` fresh negatives per pair
    (wordembedding.cpp:100-140 per-pair loop). Per-pair draws on TPU cost a
    (B, K) scalar gather + a (B, K, D) row gather + a duplicate-heavy scatter
    — all latency-bound VPU work. Sharing one pool of ``K'`` negatives across
    the minibatch turns the entire negative half into two (B,D)x(D,K') MXU
    matmuls and a K'-row scatter, ~5x faster end-to-end. ``neg_weight``
    (typically k/K') rescales the negative gradient so the expected objective
    matches the reference's k-negatives-per-pair loss.

    centers/contexts: (B,) int32; neg_ids: (K',) int32: rows of the tables.
    Tables stay in their storage dtype (f32); compute runs in
    ``compute_dtype`` (bf16 on the MXU). The pairs' update rows reach the
    tables with their duplicates combined, and on row shards the pairs'
    rows are read by the shards that own them (``ops/row_combine``:
    ``add_rows``, ``take_rows``); ``plans`` is the (centers, contexts)
    pair of :func:`row_combine.plan_rows` where the caller made them ahead
    of the step; a ``None`` is made where it is used. ``shardings`` are
    the two tables', where their rows are sharded over a mesh.
    """
    cd = compute_dtype
    with jax.named_scope("mv.fused.gather"):
        # a row-sharded table's rows are read by their owners and handed
        # round (row_combine.take_rows); the pool's few stay the
        # partitioner's
        v = row_combine.take_rows(win, centers, plans[0], shardings[0],
                                  cd)                          # (B, D)
        up = row_combine.take_rows(wout, contexts, plans[1], shardings[1],
                                   cd)                         # (B, D)
        un = jnp.take(wout, neg_ids, axis=0).astype(cd)        # (K', D)
    with jax.named_scope("mv.fused.grad"):
        pos = jnp.sum(v * up, axis=-1).astype(jnp.float32)     # (B,)
        negs = jnp.dot(v, un.T).astype(jnp.float32)            # (B, K') MXU
        gp = ((1.0 - jax.nn.sigmoid(pos)) * lr).astype(cd)
        gn = (-jax.nn.sigmoid(negs) * (lr * neg_weight)).astype(cd)
        dv = gp[:, None] * up + jnp.dot(gn, un)                # (B, D) MXU
        dup = gp[:, None] * v
        dun = jnp.dot(gn.T, v)                                 # (K', D) MXU
        loss = (-jnp.mean(jax.nn.log_sigmoid(pos))
                - neg_weight * jnp.mean(
                    jnp.sum(jax.nn.log_sigmoid(-negs), axis=-1)))
    with jax.named_scope("mv.fused.scatter"):
        win = row_combine.add_rows(win, centers, dv, plans[0],
                                   shardings[0])
        # two scatters, NOT one concat'd scatter: the K'-row pool scatter
        # is nearly free while concatenation forces an extra [B+K', D]
        # materialization (measured ~30% slower per batch on-chip)
        wout = row_combine.add_rows(wout, contexts, dup, plans[1],
                                    shardings[1])
        wout = wout.at[neg_ids].add(dun.astype(wout.dtype))
    return win, wout, loss


FUSED_TABLE_BITS = 20      # the fused sampler's slot table: 2^20 slots


def lcg_epoch_states(lcg_state: np.ndarray, batches: int) -> np.ndarray:
    """On the host, the sampler states ``[batches, K']`` of the fused
    epoch that starts from ``lcg_state``: batch t draws with row t, and
    the last row is the state the epoch hands back. The epoch's own
    arithmetic (:func:`_lcg_jump_consts`), bit for bit."""
    at, ct = _lcg_jump_consts(batches)
    s = np.asarray(lcg_state, np.uint32)
    return s[None, :] * at[:, None] + ct[:, None]


def lcg_slots(lcg_states: np.ndarray,
              table_bits: int = FUSED_TABLE_BITS) -> np.ndarray:
    """The negative table's slots that the fused sampler reads with
    ``lcg_states`` (any shape): the states' top bits, as in the epoch."""
    return (np.asarray(lcg_states, np.uint32)
            >> np.uint32(32 - table_bits)).astype(np.int64)


def make_fused_shared_epoch(cfg: W2VConfig, unigram: np.ndarray,
                            compute_dtype=jnp.bfloat16,
                            table_bits: int = FUSED_TABLE_BITS,
                            table_formats=None,
                            slots: Optional[np.ndarray] = None):
    """Fused epoch with batch-shared negatives and an in-graph LCG sampler.

    The negative draw uses the reference's own RNG design — word2vec.c's
    ``next_random = next_random * A + C`` linear congruential stream (the
    reference inherits it at wordembedding.cpp SampleNegative). The whole
    epoch's (K',)-lane states come from closed-form jumps
    (:func:`_lcg_jump_consts`) + one batched table gather before the scan,
    replacing both a threefry invocation (profiled at ~55% of the epoch)
    and the earlier per-batch in-scan LCG step (~17%).
    Returns ``epoch_fn(win, wout, centers, contexts, lcg_state,
    plans=None) -> (win, wout, mean_loss, lcg_state, rows)``.
    ``centers`` and ``contexts`` are ROW ids of the tables. ``plans`` is
    ``(plan_rows(centers, rows, shards), plan_rows(contexts, rows,
    shards))`` (``ops/row_combine``; ``shards`` the tables' row shards)
    where the caller keeps them with the pairs: the sorts behind them are
    4% of an epoch of 439 x 8,192 on a v5e, so a caller that runs the same
    pairs again makes them once; without them the epoch makes its own.
    ``rows`` is ``int32[3 + shards]``, one array for one read-back
    (``row_combine.plan_counts`` of both plans): how many distinct centre
    and context rows those plans name, of ``2 * centers.size`` update rows
    (the table writes' work after and before combining); how many of them
    lay in the tables' heads, which the dense adds took and the walks did
    not (``row_combine.HEAD``); the slots each shard's walks were handed;
    and the rounds past the first that the reads of row-sharded tables
    took (``row_combine.take_rows``).
    ``slots`` is the negative table (``2^table_bits`` ids, a word's being
    the row it lives in) where the caller has built it already
    (:func:`build_negative_table`; at 12M words a fifth of a second and
    4 MB that need not be made twice).
    """
    k_shared = cfg.shared_negatives
    if k_shared <= 0:
        raise ValueError("cfg.shared_negatives must be > 0")
    if slots is None:
        slots = build_negative_table(unigram, 1 << table_bits)
    neg_table = jnp.asarray(slots)
    neg_weight = cfg.negatives / k_shared
    shift = jnp.uint32(32 - table_bits)  # top bits: LCG low bits are weak
    shardings = (tuple(f.sharding for f in table_formats)
                 if table_formats else (None, None))

    # donate the tables: epochs chain win/wout through, and without donation
    # every call pays a full-table copy before the first scatter
    @_epoch_jit(table_formats, 1, 1, donate_argnums=(0, 1))
    def epoch_fn(win, wout, centers, contexts, lcg_state, plans=None):
        # the whole epoch's sampler states in one closed-form jump + ONE
        # batched table gather (bit-identical to stepping the LCG per
        # batch, which serialized ~17% of the epoch on small VPU ops)
        At, Ct = _lcg_jump_consts(centers.shape[0])
        with jax.named_scope("mv.fused.sample"):
            s_all = (lcg_state[None, :] * jnp.asarray(At)[:, None]
                     + jnp.asarray(Ct)[:, None])
            nids = jnp.take(neg_table, (s_all >> shift).astype(jnp.int32),
                            axis=0)
        # and how every minibatch's update rows combine, for the whole
        # epoch before the scan, off the minibatch's path
        if plans is None:
            with jax.named_scope("mv.fused.plan"):
                plans = tuple(
                    row_combine.plan_rows(ids, tab.shape[0],
                                          row_combine.row_shards(sh)[1])
                    for ids, tab, sh in zip((centers, contexts),
                                            (win, wout), shardings))

        def body(carry, batch):
            win, wout, = carry
            c, x, nid, plan = batch
            win, wout, loss = shared_neg_step(
                win, wout, c, x, nid, cfg.learning_rate, neg_weight,
                compute_dtype, plan, shardings)
            return (win, wout), loss

        with jax.named_scope("mv.fused"):   # device-trace name
            (win, wout), losses = jax.lax.scan(
                body, (win, wout), (centers, contexts, nids, plans))
        rows = (row_combine.plan_counts(plans[0])
                + row_combine.plan_counts(plans[1]))
        return win, wout, jnp.mean(losses), s_all[-1], rows

    return epoch_fn


def init_lcg_state(k_shared: int, seed: int = 0) -> np.ndarray:
    """Independent per-lane LCG seeds for :func:`make_fused_shared_epoch`."""
    return np.random.default_rng(seed).integers(
        0, np.iinfo(np.uint32).max, size=(k_shared,), dtype=np.uint32)


def make_fused_cbow_epoch(cfg: W2VConfig, unigram: np.ndarray,
                          table_formats=None,
                          slots: Optional[np.ndarray] = None):
    """CBOW-NS variant: scans (windows, masks, targets) batches; ``slots``
    as :func:`make_fused_epoch` takes them."""
    neg_table = jnp.asarray(build_negative_table(unigram)
                            if slots is None else slots)

    @_epoch_jit(table_formats)
    def epoch_fn(win, wout, windows, masks, targets, key):
        def body(carry, batch):
            win, wout, key = carry
            w, m, t = batch
            key, sub = jax.random.split(key)
            neg = sample_negatives_table(sub, neg_table, t.shape[0],
                                         cfg.negatives)
            win, wout, loss = cbow_ns_step(win, wout, w, m, t, neg,
                                           cfg.learning_rate)
            return (win, wout, key), loss

        with jax.named_scope("mv.fused"):   # device-trace name
            (win, wout, _), losses = jax.lax.scan(
                body, (win, wout, key), (windows, masks, targets))
        return win, wout, jnp.mean(losses)

    return epoch_fn


def _make_path_gather(codes: np.ndarray, points: np.ndarray,
                      lengths: np.ndarray):
    """Closure gathering words' Huffman paths in-graph: the path tables
    live on device once; ``gather(ids) -> (code, point, mask)``."""
    codes_d = jnp.asarray(codes)
    points_d = jnp.asarray(points)
    lengths_d = jnp.asarray(lengths)
    max_len = codes.shape[1]

    def gather(ids):
        code = jnp.take(codes_d, ids, axis=0)
        point = jnp.take(points_d, ids, axis=0)
        mask = (jnp.arange(max_len)[None, :]
                < jnp.take(lengths_d, ids)[:, None])
        return code, point, mask

    return gather


def make_fused_hs_epoch(cfg: W2VConfig, codes: np.ndarray, points: np.ndarray,
                        lengths: np.ndarray, table_formats=None):
    """Hierarchical-softmax skipgram variant: each batch gathers its
    contexts' Huffman paths in-graph."""
    path = _make_path_gather(codes, points, lengths)

    @_epoch_jit(table_formats)
    def epoch_fn(win, hs_out, centers, contexts, key):
        def body(carry, batch):
            win, hs_out = carry
            c, ctx = batch
            code, point, mask = path(ctx)
            win, hs_out, loss = skipgram_hs_step(
                win, hs_out, c, code, point, mask, cfg.learning_rate)
            return (win, hs_out), loss

        with jax.named_scope("mv.fused"):   # device-trace name
            (win, hs_out), losses = jax.lax.scan(
                body, (win, hs_out), (centers, contexts))
        return win, hs_out, jnp.mean(losses)

    return epoch_fn


def make_fused_cbow_hs_epoch(cfg: W2VConfig, codes: np.ndarray,
                             points: np.ndarray, lengths: np.ndarray,
                             table_formats=None):
    """CBOW x HS variant: scans (windows, masks, targets) batches; each
    batch gathers its TARGETS' Huffman paths in-graph."""
    path = _make_path_gather(codes, points, lengths)

    @_epoch_jit(table_formats)
    def epoch_fn(win, hs_out, windows, masks, targets, key):
        del key  # HS draws no negatives; kept for dispatch uniformity

        def body(carry, batch):
            win, hs_out = carry
            w, m, t = batch
            code, point, pmask = path(t)
            win, hs_out, loss = cbow_hs_step(
                win, hs_out, w, m, code, point, pmask, cfg.learning_rate)
            return (win, hs_out), loss

        with jax.named_scope("mv.fused"):   # device-trace name
            (win, hs_out), losses = jax.lax.scan(
                body, (win, hs_out), (windows, masks, targets))
        return win, hs_out, jnp.mean(losses)

    return epoch_fn


def generate_cbow_batches(ids: np.ndarray, window: int
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(windows, mask, targets) for CBOW: each position is a target predicted
    from its masked +-window context."""
    n = ids.size
    pad = np.concatenate([np.full(window, -1, ids.dtype), ids,
                          np.full(window, -1, ids.dtype)])
    view = np.lib.stride_tricks.sliding_window_view(pad, 2 * window + 1)
    ctx = np.delete(view, window, axis=1)        # (n, 2*window)
    mask = ctx >= 0
    windows = np.where(mask, ctx, 0).astype(np.int32)
    return windows, mask, ids.astype(np.int32)


def nearest_neighbors(win: np.ndarray, word_id: int, k: int = 10) -> np.ndarray:
    """Cosine-similarity neighbors (analogy/eval helper)."""
    w = win / (np.linalg.norm(win, axis=1, keepdims=True) + 1e-8)
    sims = w @ w[word_id]
    return np.argsort(-sims)[1: k + 1]


def generate_pairs(ids: np.ndarray, window: int, seed: int = 0,
                   dynamic: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Sliding-window (center, context) pairs with the reference's random
    window shrink (word2vec 'b = rand % window'). Vectorized: one pass per
    offset instead of a Python loop per token."""
    n = ids.size
    if n < 2:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32))
    rng = np.random.default_rng(seed)
    win_sizes = (rng.integers(1, window + 1, size=n) if dynamic
                 else np.full(n, window))
    centers_parts, contexts_parts = [], []
    idx = np.arange(n)
    for d in range(1, window + 1):
        ok = win_sizes >= d
        fwd = ok & (idx + d < n)
        bwd = ok & (idx - d >= 0)
        i_f = idx[fwd]
        i_b = idx[bwd]
        centers_parts.append(ids[i_f])
        contexts_parts.append(ids[i_f + d])
        centers_parts.append(ids[i_b])
        contexts_parts.append(ids[i_b - d])
    centers = np.concatenate(centers_parts).astype(np.int32)
    contexts = np.concatenate(contexts_parts).astype(np.int32)
    # shuffle so minibatches mix offsets (the per-token order of the scalar
    # version isn't load-bearing; SGD prefers shuffled pairs)
    perm = rng.permutation(centers.size)
    return centers[perm], contexts[perm]
