"""WordEmbedding application (distributed word2vec).

TPU-native re-build of the reference WordEmbedding app
(ref: Applications/WordEmbedding/src/distributed_wordembedding.cpp — block
pipeline driver; src/communicator.cpp — PS glue pulling rows per block and
pushing (new-old)/workers deltas; src/trainer.cpp — words/sec reporting;
src/util.cpp — argv config). Capability parity:

* skipgram / CBOW, negative sampling / hierarchical softmax
* min_count vocab pruning, frequent-word subsampling, dynamic window
* block pipeline: per data block, pull the block's vocabulary rows from the
  parameter tables, train the block as ONE packed ``lax.scan``, push deltas
  — block N+1's prep/pull overlaps training block N (ref :178-227 OMP
  overlap; here prefetch threads on the device plane, the async-dispatch
  pull on the host plane)
* KVTable word-count aggregation across workers (ref communicator.cpp:17-31)
* stopword filtering (-stopwords 1 -sw_file; ref reader.cpp:11-47) and
  binary vector output (-binary 1; ref util.h:26 + the WriteToFile .bin
  layout), with a round-tripping loader (``load_embeddings``)
* words/sec per chip reporting

Two execution paths:
* ``train_fused``: the whole corpus trains on device via a jitted scan — the
  TPU-first path used for the headline words/sec benchmark.
* ``train_ps_blocks``: the reference's block Get/Add flow — the
  semantics-parity path. Single-worker sync runs fuse each block's
  pull/train/push into one device program (``ps_device_plane``);
  multi-worker and async runs pull/push through the table wire with the
  same packed-scan compute.

Usage: ``python -m multiverso_tpu.apps.word_embedding -train_file f.txt
-output vec.txt -size 128 ...`` (argv keys mirror ref util.cpp ParseArgs).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import multiverso_tpu as mv
from multiverso_tpu import native
from multiverso_tpu.data.dictionary import Dictionary, build_huffman
from multiverso_tpu.io.sample_reader import BlockPrepareQueue
from multiverso_tpu.models import word2vec as w2v
from multiverso_tpu.ops import row_assemble as _rowasm
from multiverso_tpu.ops import row_combine
from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.telemetry import memstats as _memstats
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.utils import config, log
from multiverso_tpu.tables.matrix_table import _bucket_size
from multiverso_tpu.utils.async_buffer import AsyncBuffer

config.define_int(
    "we_prepare_depth", 4,
    "WordEmbedding prepared-block queue depth (blocks produced but not "
    "yet trained, BOTH PS planes): bounds host prep memory while letting "
    "producers run ahead of the consumer — the ISSUE-11 pipeline's K")
config.define_int(
    "we_prepare_threads", 2,
    "producer threads feeding the WordEmbedding prepared-block queue "
    "(pair generation, negative sampling, remap/pack run here, OFF the "
    "training thread's critical path)")
config.define_int(
    "we_pair_cache_corpora", 4,
    "bounded LRU capacity (corpora) of the fused path's device-resident "
    "pair-batch cache — multi-corpus alternating epochs used to thrash "
    "the old keep-one cache every epoch")


def _gen_pairs(ids: np.ndarray, window: int, seed: int):
    """Prefer the native C++ pair generator (mv_data.cpp); fall back to the
    vectorized numpy path."""
    if native.available():
        return native.generate_pairs(ids, window, seed=seed)
    return w2v.generate_pairs(ids, window, seed=seed)


def prepare_ids(dictionary: Dictionary, ids: np.ndarray,
                cfg: "WEConfig") -> np.ndarray:
    """THE training-stream policy — one implementation shared by every
    entry point (app method, load_corpus, bench) so id streams can't
    diverge. Order matches the reference reader (reader.cpp:36-57
    GetSentence): stopword drop first, then frequency subsampling."""
    if getattr(cfg, "stopwords", False):
        # O(|sw|) id lookup, not an O(V) scan: the banned set resolves
        # against word2id once per call (the stopword list is small)
        banned = np.array(
            [dictionary.word2id[w] for w in _load_stopwords(cfg.sw_file)
             if w in dictionary.word2id], np.int64)
        if banned.size:
            ids = ids[~np.isin(ids, banned)]
    if cfg.sample <= 0:
        return ids
    if native.available():
        return native.subsample(ids, dictionary.counts, cfg.sample,
                                seed=cfg.seed).astype(np.int64)
    return dictionary.subsample(ids, cfg.sample, seed=cfg.seed)


def _load_stopwords(path: str) -> set:
    """Whitespace-separated stopword list (ref reader.cpp:11-23 — the
    table the Reader loads from ``sw_file``)."""
    with open(path, "rb") as f:
        return {t.decode("utf-8", errors="replace")
                for t in f.read().split()}


class WEConfig:
    """ref util.cpp ParseArgs keys (-size -window -negative -hs -cbow -alpha
    -epoch -min_count -sample -batch_size -data_block_size)."""

    def __init__(self, **kw):
        self.size = int(kw.get("size", 128))
        self.window = int(kw.get("window", 5))
        self.negative = int(kw.get("negative", 5))
        # TPU-first extension: >0 = batch-shared negative pool of this size
        # in the fused path (gradients rescaled to the -negative objective);
        # 0 = reference per-pair semantics.
        self.shared_negatives = int(kw.get("shared_negatives", 64))
        self.hs = str(kw.get("hs", "0")) in ("1", "true", "True")
        self.cbow = str(kw.get("cbow", "0")) in ("1", "true", "True")
        self.alpha = float(kw.get("alpha", 0.025))
        self.epoch = int(kw.get("epoch", 1))
        self.min_count = int(kw.get("min_count", 5))
        self.sample = float(kw.get("sample", 1e-4))
        self.batch_size = int(kw.get("batch_size", 1024))
        self.data_block_size = int(kw.get("data_block_size", 100_000))
        # reference-shaped PS block pipeline (pull rows / train / push
        # deltas, ref ps_model-style use_ps) instead of the fused path
        self.use_ps = str(kw.get("use_ps", "0")) in ("1", "true", "True")
        # uncoordinated async tables (multiverso_tpu.ps): workers trade
        # rows at independent rates — the reference's default Server mode
        self.async_ps = str(kw.get("async_ps", "0")) in ("1", "true", "True")
        # PS-block execution plane: "auto" fuses pull+train+push into one
        # device program when this process is the only worker (the sync
        # single-controller case); "0" forces the host Get/Add plane (the
        # multi-worker wire path); "1" asserts the device plane.
        self.ps_device_plane = str(kw.get("ps_device_plane", "auto"))
        # compute dtype INSIDE the block scan (both planes): "bf16" casts
        # the pulled rows for the scan (the table stays f32; deltas are
        # measured against the bf16-rounded baseline so untrained rows get
        # exactly-zero deltas). Default f32 — the block step is
        # gather-bound; measured bf16 gain on-chip is ~2%.
        self.ps_block_dtype = str(kw.get("ps_block_dtype", "f32"))
        if self.ps_block_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"unknown ps_block_dtype {self.ps_block_dtype!r}")
        # ISSUE-11 pipelined prepare: "1" (default) produces blocks on a
        # bounded K-deep queue of producer threads and dispatches the row
        # pulls at dequeue (same program-order point as inline, so results
        # stay bit-identical); "0" = the legacy inline one-lookahead path
        # (the parity oracle)
        self.pipeline = str(kw.get("pipeline", "1")) in ("1", "true",
                                                         "True")
        self.data_presplit = str(kw.get("data_presplit", "0")) in (
            "1", "true", "True")
        self.max_vocab = kw.get("max_vocab")
        self.train_file = kw.get("train_file", "")
        # pre-counted vocabulary file ("word count" lines, the
        # tools/word_count.py output; ref -read_vocab consuming the
        # preprocess/word_count.cpp output) and its writer twin
        self.read_vocab = kw.get("read_vocab", "")
        self.save_vocab = kw.get("save_vocab", "")
        self.output = kw.get("output", "")
        # -binary 1: classic word2vec .bin output (ref util.h:26
        # output_binary, writer distributed_wordembedding.cpp:310-325)
        self.output_binary = str(kw.get("binary", "0")) in ("1", "true",
                                                            "True")
        # -stopwords 1 -sw_file <path>: drop listed words from the
        # TRAINING stream; the dictionary keeps them (ref reader.cpp:11-47
        # — stopwords count toward word_count and stay in the vocab, they
        # are only skipped when building sentences; option defaults
        # util.cpp:10,24)
        self.stopwords = str(kw.get("stopwords", "0")) in ("1", "true",
                                                           "True")
        self.sw_file = kw.get("sw_file", "")
        if self.stopwords and not self.sw_file:
            raise ValueError("-stopwords 1 needs -sw_file (ref util.cpp:75)")
        self.seed = int(kw.get("seed", 0))

    @classmethod
    def from_argv(cls, argv: List[str]) -> "WEConfig":
        kw = {}
        i = 0
        while i < len(argv):
            a = argv[i]
            if a.startswith("-") and "=" in a:
                i += 1   # "-key=value" runtime flag: mv.init's to parse
            elif a.startswith("-") and i + 1 < len(argv):
                kw[a.lstrip("-")] = argv[i + 1]
                i += 2
            else:
                i += 1
        return cls(**kw)


class WordEmbedding:
    def __init__(self, cfg: WEConfig, dictionary: Dictionary):
        if not mv.Zoo.get().started:
            mv.init()
        self.cfg = cfg
        self.dict = dictionary
        v, d = len(dictionary), cfg.size
        if v < 2:
            raise ValueError("vocabulary too small; lower min_count")
        # input/output embedding tables (ref communicator.cpp:17-31: two
        # MatrixTables; input randomly initialized server-side). async_ps
        # swaps in the uncoordinated tables — same client API, no lockstep.
        if cfg.async_ps:
            matrix, kv, shards = mv.AsyncMatrixTable, mv.AsyncKVTable, 1
        else:
            matrix, kv = mv.MatrixTable, mv.KVTable
            shards = mv.mesh().shape[mv.Zoo.get().shard_axis()]
        # on row shards the words are dealt round them (_rows), and the
        # tables have a row for the last word of every shard
        rows = row_combine.striped_table_rows(v, shards)
        self.table_in = matrix(rows, d, name="embed_in", updater="default",
                               seed=cfg.seed + 17, init_scale=0.5 / d)
        self.table_out = matrix(rows, d, name="embed_out", updater="default")
        self.word_count = kv(name="word_count")
        self.unigram = dictionary.unigram_table()
        self._trained_words = 0
        self._calls = 0     # training calls so far: the call spans' request
        # closes a dispatched program's device span when the device is
        # done with it; no thread unless a capture or trace_ids reads it.
        # train_fused leaves it running between calls (a thread's start
        # and join cost 0.8 ms of host a call, my chip run, PR 37);
        # train_ps_blocks closes it with its one long call
        self._watcher = _trace.DeviceWatcher()
        # caller already sharded the corpus (skip the blocks[wid::nw] split;
        # we_async_worker-style drivers that feed per-rank shards set it via
        # -data_presplit 1)
        self._data_presplit = cfg.data_presplit
        self._neg_host: Optional[np.ndarray] = None
        self._neg_dev = None
        # device-plane in-graph negative re-derivation pays one remap upload
        # of V ids per block; worth it unless the vocab dwarfs the block's
        # negative traffic (a 21M-vocab run keeps the packed-negs upload)
        self._dev_negs = (not cfg.hs and cfg.negative > 0
                          and 4 * v <= cfg.data_block_size * cfg.negative)
        self._fused_cache: Dict[str, object] = {}
        # the programs whose map has been recorded (xla.program)
        self._described: set = set()
        # matmul dtype of the shared-negatives fused epoch, set when that
        # program is first built (bf16 on TPU, f32 elsewhere)
        self.fused_compute_dtype = None
        # bounded LRU of device-resident pair batches, keyed by corpus
        # fingerprint (flag we_pair_cache_corpora): multi-corpus
        # alternating epochs no longer thrash it every epoch, and its
        # device bytes ride the PR-10 ledger
        self._pair_cache: "OrderedDict[object, object]" = OrderedDict()
        self._plan_fn = None    # the pair cache's plan program, on first use
        # guards the LRU against the memstats sampler thread's gauge
        # pull (mutation is per corpus-epoch — the lock is never hot)
        self._pair_cache_lock = threading.Lock()
        _memstats.register(f"we.pair_cache[{self.table_in.name}]", self,
                           attr="pair_cache_memory_stats")
        if cfg.hs:
            codes, points, lengths = build_huffman(dictionary.counts)
            self._hs = (codes, points, lengths)
            self.table_hs = matrix(max(v - 1, 1), d, name="embed_hs",
                                   updater="default")
        else:
            self._hs = None

    # ------------------------------------------------------------------ #
    # corpus -> id stream
    # ------------------------------------------------------------------ #
    def prepare_ids(self, tokens) -> np.ndarray:
        return prepare_ids(self.dict, self.dict.encode(tokens), self.cfg)

    def _stripes(self) -> Tuple[int, int]:
        """The word tables' row shards and the rows of each (an
        uncoordinated table has none of its own on the mesh: one)."""
        t = self.table_in
        shards = getattr(t, "num_shards", 1)
        return shards, (t.rows_per_shard if shards > 1 else 0)

    def _rows(self, words):
        """The rows of ``embed_in`` and ``embed_out`` that the words
        (dictionary ids: frequency ranks) live in: THE place a word becomes
        a row. On row shards the ranks are dealt round the shards
        (``row_combine.striped_row``), so that every shard owns its share
        of the hot rows; on one shard a word's row is its id, and
        ``words`` comes back as it is."""
        return row_combine.striped_row(words, *self._stripes())

    def _words(self, rows):
        """:meth:`_rows`'s inverse."""
        return row_combine.striped_word(rows, *self._stripes())

    def _batches(self, centers: np.ndarray, contexts: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
        b = self.cfg.batch_size
        n = (centers.size // b) * b
        if n == 0:
            raise ValueError(
                f"corpus too small: {centers.size} pairs < batch {b}")
        return (centers[:n].reshape(-1, b), contexts[:n].reshape(-1, b))

    def _device_pairs(self, ids: np.ndarray):
        """Batched (centers, contexts) pair arrays, resident on device,
        and their pair count: the arrays the fused epoch scans, so ROW
        ids of ``table_in.raw()`` and ``table_out.raw()`` (:meth:`_rows`;
        under ``-hs`` the contexts stay words: they name Huffman paths,
        not rows)."""
        return self._cached_pairs(ids)[0]

    def _cached_pairs(self, ids: np.ndarray):
        """The pair cache's entry for ``ids``: :meth:`_device_pairs`'s
        triple; how many of those centres and contexts each row shard
        of the tables owns (``[shards]``, counted once here, so that a
        call which finds its pairs cached counts nothing); and, for the
        shared-negatives epoch, how every minibatch's update rows combine
        (:meth:`_pair_plans`, made once here for the same reason), else
        ``None``.

        Pair generation is one-time corpus preprocessing; caching the
        device-resident batches (keyed by a corpus fingerprint) keeps repeat
        epochs off the host->device path entirely.
        """
        with _trace.span("we.fused.pairs", cache_hit=1) as sp:
            key = (ids.shape, hash(ids.tobytes()),
                   self.cfg.window, self.cfg.seed, self.cfg.batch_size)
            with self._pair_cache_lock:
                hit = self._pair_cache.get(key)
                if hit is not None:
                    self._pair_cache.move_to_end(key)
                    sp.set(pairs=hit[0][2])
                    return hit
            # pair gen + device put happen OFF the lock (one-time corpus
            # preprocessing — a concurrent gauge pull must not stall on
            # it); a racing duplicate build just overwrites with equal
            # content
            with _trace.span("we.pairs.generate"):
                centers, contexts = _gen_pairs(ids, self.cfg.window,
                                               self.cfg.seed)
                # words become rows here, once a corpus, off every
                # minibatch's path
                cb, xb = self._batches(
                    self._rows(centers),
                    contexts if self.cfg.hs else self._rows(contexts))
                by_shard = self._rows_by_shard(cb) + self._rows_by_shard(xb)
            with _trace.span("we.pairs.upload"):
                cbd, xbd = jnp.asarray(cb), jnp.asarray(xb)
                hit = ((cbd, xbd, cb.size), by_shard,
                       self._pair_plans(cbd, xbd))
                jax.block_until_ready(hit)
            sp.set(cache_hit=0, pairs=cb.size,
                   h2d_bytes=cb.nbytes + xb.nbytes)
            with self._pair_cache_lock:
                self._pair_cache[key] = hit
                cap = max(1, int(config.get_flag("we_pair_cache_corpora")))
                while len(self._pair_cache) > cap:   # bounded LRU
                    self._pair_cache.popitem(last=False)
            return hit

    def _pair_plans(self, centers: jax.Array, contexts: jax.Array):
        """``ops/row_combine.plan_rows`` of every minibatch of the pair
        batches ``[batches, B]``, for the input and the output table: one
        jitted program, run once a corpus and table (three sorts a
        minibatch, 14 ms of a v5e at 439 x 8,192: two of them would be
        4% of every call), replicated on the tables' mesh, where the
        epoch program reads them: how the update rows combine for the
        table writes and, on row shards, where the reads find each row
        among the rows the shards hand round (``plan.place``: one more
        ``int32[batches, B]`` a table, 29 MB a 600,000-word chunk).
        ``None`` unless the epoch is the shared-negatives one: only it
        combines its update rows."""
        if not self._shared_epoch:
            return None
        if self._plan_fn is None:
            self._plan_fn = jax.jit(
                row_combine.plan_rows, static_argnums=(1, 2),
                out_shardings=jax.sharding.NamedSharding(
                    mv.mesh(), jax.sharding.PartitionSpec()))
        return tuple(self._plan_fn(ids, t.padded_shape[0], t.num_shards)
                     for ids, t in ((centers, self.table_in),
                                    (contexts, self.table_out)))

    def _rows_by_shard(self, ids: np.ndarray) -> np.ndarray:
        """How many of the row ids ``ids`` each row shard of the
        embedding tables owns (``[shards]`` int64)."""
        t = self.table_in
        return np.bincount(ids.reshape(-1) // t.rows_per_shard,
                           minlength=t.num_shards)

    def pair_cache_memory_stats(self) -> Dict[str, int]:
        """PR-10 ledger gauges for the pair-batch LRU (pull-only)."""
        with self._pair_cache_lock:   # vs the training thread's insert
            entries = list(self._pair_cache.values())
        dev = sum(int(getattr(a, "nbytes", 0) or 0)
                  for (cb, xb, _n), _rows, plans in entries
                  for a in (cb, xb, *jax.tree.leaves(plans)))
        return {"corpora": len(entries), "device_bytes": dev}

    # ------------------------------------------------------------------ #
    # fused path (device-resident training)
    # ------------------------------------------------------------------ #
    @property
    def _shared_epoch(self) -> bool:
        """Whether the fused epoch is the shared-negatives skip-gram one."""
        cfg = self.cfg
        return not (cfg.cbow or cfg.hs) and cfg.shared_negatives > 0

    def _fused_epoch_fn(self):
        """The jitted epoch program of the active (cbow, hs,
        shared-negatives) mode, built once; it hands both tables back in
        their own formats. ``shared`` programs donate the tables and
        thread the LCG sampler state through instead of a PRNG key."""
        cfg, shared = self.cfg, self._shared_epoch
        name = (("cbow_hs" if cfg.hs else "cbow") if cfg.cbow else
                "hs" if cfg.hs else "sg_shared" if shared else "sg")
        fn = self._fused_cache.get(name)
        if fn is not None:
            return fn, shared
        w2v_cfg = w2v.W2VConfig(len(self.dict), cfg.size, cfg.negative,
                                cfg.window, cfg.alpha, cfg.cbow, cfg.hs,
                                cfg.shared_negatives)
        formats = (self.table_in.format, self._sec_table().format)
        # the sampler's slot table names the rows its words live in, as
        # the pairs do
        slots = None if cfg.hs else self._rows(w2v.build_negative_table(
            self.unigram, 1 << w2v.FUSED_TABLE_BITS))
        if cfg.hs:
            make = (w2v.make_fused_cbow_hs_epoch if cfg.cbow
                    else w2v.make_fused_hs_epoch)
            fn = make(w2v_cfg, *self._hs, table_formats=formats)
        elif shared:
            # TPU-first fast path: batch-shared negatives on the MXU
            cd = self.fused_compute_dtype = (
                jnp.bfloat16 if jax.devices()[0].platform == "tpu"
                else jnp.float32)
            # the slot table is kept on the host too: which rows a
            # call's pools held is read from it (fused_pool)
            self._fused_slots = slots
            fn = w2v.make_fused_shared_epoch(w2v_cfg, self.unigram,
                                             compute_dtype=cd,
                                             table_formats=formats,
                                             slots=slots)
            # replicated on the mesh, as the epoch hands it back
            self._lcg = jax.device_put(
                w2v.init_lcg_state(cfg.shared_negatives, cfg.seed),
                jax.sharding.NamedSharding(
                    mv.mesh(), jax.sharding.PartitionSpec()))
        else:
            make = (w2v.make_fused_cbow_epoch if cfg.cbow
                    else w2v.make_fused_epoch)
            fn = make(w2v_cfg, self.unigram, table_formats=formats,
                      slots=slots)
        self._fused_cache[name] = fn
        return fn, shared

    def fused_pool(self, next_batches: Optional[int] = None) -> np.ndarray:
        """The shared negative pools of the fused epoch as ROW ids of
        ``table_out.raw()`` (:meth:`_rows` of the words drawn), read
        from the program's own slot table and sampler state: with no
        argument the pool ``[K']`` that the last batch of the last
        :meth:`train_fused` call drew; with ``next_batches`` the pools
        ``[next_batches, K']`` that the next call's batches will draw, in
        order. A check that holds a call to a reference asks here and
        knows nothing of the sampler."""
        if not self._fused_epoch_fn()[1]:
            raise ValueError("only the shared-negatives skip-gram epoch "
                             "(shared_negatives > 0, no -cbow, no -hs) "
                             "draws a pool")
        state = np.asarray(self._lcg)
        if next_batches is not None:
            state = w2v.lcg_epoch_states(state, next_batches)
        return self._fused_slots[w2v.lcg_slots(state)]

    def _sharded_call_counts(self, pair_rows: np.ndarray, lcg_state,
                             batches: int, epochs: int) -> Dict[str, object]:
        """What a shared-negatives :meth:`train_fused` call does to
        row-sharded tables, as counts for its span: the update rows each
        shard owns (``pair_rows``: the pairs' centres and contexts, from
        the pair cache; plus every batch's pool, drawn here on the host
        from the sampler state the call started with, while the device
        runs the call), and ``allreduce_bytes``: each batch's gathered
        rows (B centres, B contexts, K' pool rows) in the compute dtype,
        none on one shard. That is what every chip is handed, and what the
        partitioner's all-reduce carried; since the pairs' rows are read
        by their owners (``row_combine.take_rows``) only the pool's go by
        an all-reduce, and the count keeps its name and rule for the
        benchmark's facts. What the all-gathers hand every chip is
        ``gather_cap(B, shards) * shards`` rows a table and a round, in
        the compute dtype: on four shards of the benchmark's cell 2 x
        6,144 rows of 600 B a minibatch where this counts 2 x 8,192."""
        cfg, t = self.cfg, self.table_in
        per_batch = 2 * cfg.batch_size + cfg.shared_negatives
        if t.num_shards == 1:       # whole tables: nothing to draw again
            return {"update_rows_by_shard": [epochs * batches * per_batch],
                    "allreduce_bytes": 0}
        rows, state = epochs * pair_rows, np.asarray(lcg_state)
        for _ in range(epochs):
            states = w2v.lcg_epoch_states(state, batches)
            rows = rows + self._rows_by_shard(
                self._fused_slots[w2v.lcg_slots(states)])
            state = states[-1]
        return {"update_rows_by_shard": [int(n) for n in rows],
                "allreduce_bytes": int(
                    epochs * batches * per_batch * cfg.size
                    * jnp.dtype(self.fused_compute_dtype).itemsize)}

    def _device_cbow_batches(self, ids: np.ndarray):
        """Batched (windows, masks, targets) arrays on the device and
        their example count; generated per call (no cache)."""
        with _trace.span("we.fused.pairs", cache_hit=0) as sp:
            with _trace.span("we.pairs.generate"):
                windows, masks, targets = w2v.generate_cbow_batches(
                    ids, self.cfg.window)
                b = self.cfg.batch_size
                windows = self._rows(windows)    # a masked slot: row 0
                if not self.cfg.hs:     # -hs: targets name Huffman paths
                    targets = self._rows(targets)
                n = (targets.size // b) * b
                if n == 0:
                    raise ValueError("corpus too small for batch size")
                host = (windows[:n].reshape(-1, b, windows.shape[1]),
                        masks[:n].reshape(-1, b, masks.shape[1]),
                        targets[:n].reshape(-1, b))
            with _trace.span("we.pairs.upload"):
                batches = jax.block_until_ready(
                    tuple(jnp.asarray(a) for a in host))
            sp.set(pairs=n, h2d_bytes=sum(a.nbytes for a in host))
        return batches, n

    def train_fused(self, ids: np.ndarray,
                    epochs: Optional[int] = None) -> Dict[str, float]:
        """Train ``epochs`` passes over ``ids`` on the device, one program
        a pass, and leave the result in the tables.

        The programs run on the tables' own buffers (the shared-negatives
        epoch donates them), as a block of :meth:`train_ps_blocks` does:
        under both tables' dispatch locks each pass takes the tables'
        ``program_state()`` and the tables adopt what it returns. An
        error raised before a program runs (trace, compile, allocation at
        dispatch) leaves both tables as the last completed dispatch left
        them: readable, and before the first pass unchanged. A device
        fault in the middle of a pass loses the tables, here as in the
        block path; a checkpoint is the remedy in both."""
        cfg = self.cfg
        epochs = epochs or cfg.epoch
        self._calls += 1
        with _trace.span("we.fused", request=self._calls, epochs=epochs,
                         words=epochs * int(ids.size)) as call:
            t0, loss = time.perf_counter(), None
            if cfg.cbow:
                batches, pairs = self._device_cbow_batches(ids)
            else:
                (cbd, xbd, pairs), pair_rows, plans = self._cached_pairs(ids)
                batches = (cbd, xbd)
            epoch_fn, shared = self._fused_epoch_fn()
            t_in, t_sec = self.table_in, self._sec_table()
            n_batches = int(batches[0].shape[0])
            call.set(pairs=int(pairs), batches=n_batches,
                     shards=t_in.num_shards)
            lcg_before = self._lcg if shared else None
            rows = []   # a pass's distinct update rows, on the device
            t0_ns = time.time_ns()
            with _trace.span("we.fused.dispatch", programs=epochs) as disp, \
                    t_in._dispatch_lock, t_sec._dispatch_lock:
                key = None if shared else jax.random.key(cfg.seed)
                for _ in range(epochs):
                    si, ss = t_in.program_state(), t_sec.program_state()
                    if shared:
                        win, wsec, loss, self._lcg, r = epoch_fn(
                            si["data"], ss["data"], *batches, self._lcg,
                            plans)
                        rows.append(r)
                    else:
                        key, sub = jax.random.split(key)
                        win, wsec, loss = epoch_fn(
                            si["data"], ss["data"], *batches, sub)
                    t_in.adopt({"data": win, "ustate": si["ustate"]})
                    t_sec.adopt({"data": wsec, "ustate": ss["ustate"]})
            # the call's programs are in flight from here until the last
            # pass's loss is ready (it waits for the chain)
            self._watcher.watch("we.fused.device", loss, t0_ns,
                                request=self._calls, cause=disp.id)
            if "we.fused" not in self._described:
                # the epoch program's map, once, on what the last pass
                # handed back (the tables' buffers now)
                self._described.add("we.fused")
                _devstats.describe_program(
                    "we.fused", epoch_fn, win, wsec, *batches,
                    *((self._lcg, plans) if shared else (sub,)))
            if shared:
                call.set(**self._sharded_call_counts(
                    pair_rows, lcg_before, n_batches, epochs))
            with _trace.span("we.fused.wait"):
                # fetch the scalar loss BEFORE stopping the clock: the
                # readback waits for the whole epoch chain; the passes'
                # row counts come with it, one round trip for all
                loss_f, rows = jax.device_get((loss, rows))
                loss_f = float(loss_f)
            if shared:
                # the pairs' update rows before combining, the distinct
                # ones after, those of them that the dense adds of the
                # tables' heads took, the slots every shard's walks were
                # handed for the others, and the rounds past the first
                # that the reads of row-sharded tables took
                unique, head, *walk, rounds = np.sum(rows, axis=0)
                call.set(update_rows=2 * epochs * int(pairs),
                         unique_rows=int(unique), head_rows=int(head),
                         walk_slots_by_shard=[int(n) for n in walk],
                         kernel_rows=row_combine.kernel_rows(
                             jax.ShapeDtypeStruct(t_in.padded_shape,
                                                  t_in.dtype),
                             t_in.format.sharding, unique, head),
                         gather_rounds=int(rounds))
            with _trace.span("we.fused.count"):
                dt = time.perf_counter() - t0
                # words/sec follows the word2vec convention: corpus
                # *tokens* consumed per second (ref trainer.cpp
                # words/sec), not training pairs.
                words = epochs * int(ids.size)
                self._trained_words += words
                self.word_count.add([0], [words])
        return {"loss": loss_f, "words_per_sec": words / dt,
                "seconds": dt, "pairs": int(pairs),
                "pairs_per_sec": epochs * pairs / dt}

    # ------------------------------------------------------------------ #
    # PS block path (reference block pipeline; multi-worker capable)
    # ------------------------------------------------------------------ #
    def _use_device_plane(self, num_workers: int) -> bool:
        """The single-worker sync case fuses each block's pull+train+push
        into ONE device program (see :meth:`_fused_block_fn`); multi-worker
        and uncoordinated runs keep the host Get/Add wire."""
        mode = self.cfg.ps_device_plane
        eligible = num_workers == 1 and not self.cfg.async_ps
        if mode == "1":
            if not eligible:
                raise ValueError(
                    "ps_device_plane=1 requires a single worker on the sync "
                    "plane; multi-worker runs exchange deltas over the "
                    "Get/Add wire")
            return True
        if mode == "0":
            return False
        return eligible

    def train_ps_blocks(self, ids: np.ndarray,
                        epochs: Optional[int] = None) -> Dict[str, float]:
        """ref distributed_wordembedding.cpp:147-252: per block pull rows,
        train locally, push (new - old) deltas. The pull for block N+1 is
        dispatched before block N trains (ref :202-223 OMP overlap thread) —
        its device gather + host transfer proceed while block N computes, at
        the cost of the same one-block staleness the reference accepts.

        Single-worker sync runs take the *device plane*: the worker's pull /
        local-train / push collapses into one jitted program per block, so
        block traffic never crosses the host boundary (the reference's
        worker and server are separate address spaces; here both live on
        the same chip, so the Get/Add hop is a device gather/scatter — the
        semantics, not the message flow, is the parity surface)."""
        device_plane = self._use_device_plane(self._ps_topology()[0])
        self._calls += 1
        # the watcher (device plane) closes after the drain, when every
        # block it waits on is done
        with _trace.span("we.blocks", request=self._calls,
                         plane="device" if device_plane else "host"
                         ) as call, self._watcher:
            return self._run_ps_blocks(ids, epochs or self.cfg.epoch,
                                       device_plane, call)

    def _run_ps_blocks(self, ids: np.ndarray, epochs: int,
                       device_plane: bool, call) -> Dict[str, float]:
        """The body of :meth:`train_ps_blocks`, inside its ``we.blocks``
        span (``call``, which takes the block and word counts)."""
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        nw, wid = self._ps_topology()
        t0, losses, words = time.perf_counter(), [], 0
        dev_losses: List[jax.Array] = []
        # what each block's table writes were handed (_block_ahead's
        # counts), where its mode combines them
        row_counts = []
        blocks = [ids[lo: lo + cfg.data_block_size]
                  for lo in range(0, ids.size, cfg.data_block_size)]
        blocks = [b for b in blocks if b.size >= 2]
        # Delta scaling is ALWAYS 1/nw on the multi-worker planes
        # (ref communicator.cpp:154). Note the convergence consequence,
        # measured at np4/1M tokens: with each worker sweeping the FULL
        # corpus (reference layout; set -data_presplit 1 and feed every
        # rank all the data), N sweeps x 1/N deltas net one epoch's
        # learning and the loss tracks the sync plane; with the
        # partitioned split below, each token contributes only 1/N of a
        # gradient per epoch (undertrains, loss 2.55 vs sync 0.70), and
        # dropping the divide instead makes zipf-hot rows absorb ~N
        # concurrent full-alpha pushes (diverges, loss 5.5). Partitioned
        # mode is the throughput/liveness fixture; reference-comparable
        # CONVERGENCE numbers come from the full-sweep layout.
        if nw > 1 and cfg.async_ps and not self._data_presplit:
            # data split evenly per worker (ref BENCHMARK.md common
            # settings). ONLY on the uncoordinated plane: sync-table
            # add_rows is a collective, so unequal per-worker block counts
            # would leave the worker with more blocks waiting forever.
            blocks = blocks[wid::nw]
        # one flat schedule across all epochs so the pull of the next block
        # overlaps training of the current one at every step, including
        # across epoch boundaries (ref :202-223 keeps its overlap thread
        # alive for the whole multi-epoch run)
        schedule = [b for _ in range(epochs) for b in blocks]
        # per-block child rngs: identical draws whether blocks are prepped
        # serially (host plane) or by prefetch threads (device plane) — the
        # two planes must stay bit-comparable
        child_rngs = rng.spawn(len(schedule)) if schedule else []
        if device_plane and schedule:
            if self._neg_host is None and not cfg.hs:
                self._host_negs(1, 1, np.random.default_rng(0))  # build once
            # K-deep ordered producer queue (io/sample_reader): replaces
            # the PR-5 fixed pool — same 2-thread default, but depth and
            # threads are now the shared we_prepare_* knobs; the
            # producers' we.prepare spans and the consumer's wait below
            # are what the queue leaves in the ring
            with BlockPrepareQueue(
                    list(range(len(schedule))),
                    lambda idx, _i: self._prepare_block_device(
                        schedule[idx], child_rngs[idx], idx),
                    depth=int(config.get_flag("we_prepare_depth")),
                    threads=int(config.get_flag("we_prepare_threads"))
                    ) as q:
                for i, block in enumerate(schedule):
                    with _trace.span("we.block.wait_prepared", request=i,
                                     queue_depth=q.ready()):
                        prepared = q.next()
                    if prepared is not None:
                        loss, counts = self._train_block_device(prepared, i)
                        dev_losses.append(loss)
                        row_counts.append(counts)
                    words += block.size
        elif schedule and cfg.pipeline and len(schedule) > 1:
            # ISSUE-11 pipelined host plane: producers run the CPU-heavy
            # prepare (pair gen, negative sampling, remap/pack) K blocks
            # ahead; the consumer dispatches each block's row pulls at
            # DEQUEUE — the same point in program order (before the
            # previous block's push) the inline path dispatches them, so
            # the pulled rows, and therefore the training results, are
            # bit-identical to pipeline=0
            if self._neg_host is None and not cfg.hs:
                self._host_negs(1, 1, np.random.default_rng(0))  # build once
            with BlockPrepareQueue(
                    list(range(len(schedule))),
                    lambda idx, _i: self._produce_block(
                        schedule[idx], child_rngs[idx]),
                    depth=int(config.get_flag("we_prepare_depth")),
                    threads=int(config.get_flag("we_prepare_threads"))
                    ) as q:
                prepared = self._dispatch_pulls(q.next())
                for i, block in enumerate(schedule):
                    with _trace.span("we.step", request=i, step=1):
                        nxt = None
                        if i + 1 < len(schedule):
                            with _trace.span("we.block.wait_prepared",
                                             request=i + 1,
                                             phase="io_wait"):
                                produced = q.next()
                            with _trace.span("we.pipeline"):
                                nxt = self._dispatch_pulls(produced)
                        loss, counts = self._train_prepared(prepared, nw)
                        losses.append(loss)
                        row_counts.append(counts)
                    words += block.size
                    prepared = nxt
        else:
            # legacy inline one-lookahead path (-pipeline 0): the parity
            # oracle the pipelined path is asserted bit-identical to.
            # Pipeline-fill prepare happens outside any step: steady-
            # state steps each cover ONE (prepare of block N+1, train of
            # block N) pair — the overlap a step's report measures
            prepared = (self._prepare_block(schedule[0], child_rngs[0])
                        if schedule else None)
            for i, block in enumerate(schedule):
                with _trace.span("we.step", request=i, step=1):
                    nxt = (self._prepare_block(schedule[i + 1],
                                               child_rngs[i + 1])
                           if i + 1 < len(schedule) else None)
                    loss, counts = self._train_prepared(prepared, nw)
                    losses.append(loss)
                    row_counts.append(counts)
                words += block.size
                prepared = nxt
        call.set(blocks=len(schedule), words=words)
        with _trace.span("we.blocks.drain", blocks=len(schedule)):
            if dev_losses:
                # ONE host readback for the whole run: materializing the
                # stacked per-block losses drains the device program chain,
                # so the trained state is durable when the clock stops
                losses = [float(x)
                          for x in np.asarray(jnp.stack(dev_losses))]
            row_counts = [np.asarray(c, np.int64) for c in row_counts
                          if c is not None]
            if row_counts:
                update, unique, head, walk, _ = np.sum(row_counts, axis=0)
                bucket = jax.ShapeDtypeStruct(
                    (row_combine.TILE, row_combine.lane_wide(cfg.size)),
                    self._compute_dtype() or self.table_in.dtype)
                call.set(update_rows=int(update), unique_rows=int(unique),
                         head_rows=int(head), walk_slots=int(walk),
                         kernel_rows=row_combine.kernel_rows(
                             bucket, None, unique, head))
            # drain in-flight async pushes so the trained state is durable
            # before the caller reads embeddings (sync tables order by
            # program order; async tables need the explicit flush)
            for t in (self.table_in, self.table_out,
                      getattr(self, "table_hs", None)):
                if t is not None and hasattr(t, "flush"):
                    t.flush()
        dt = time.perf_counter() - t0
        self._trained_words += words
        self.word_count.add([0], [words])
        return {"loss": float(np.mean(losses)) if losses else 0.0,
                "words_per_sec": words / dt, "seconds": dt}

    def _host_negs(self, n: int, k: int, rng) -> Tuple[np.ndarray, np.uint32]:
        """Negative draws from a precomputed unigram^0.75 slot table
        (word2vec.c's 1e8-slot design, ref wordembedding NS branch). Slot
        indices come from a counter-based hash (w2v.splitmix32) seeded per
        block, so the device plane can RE-DERIVE the identical draws
        in-graph from just the 4-byte seed instead of shipping the
        (nb, B, K) id array across the host->device wire."""
        if self._neg_host is None:
            self._neg_host = w2v.build_negative_table(self.unigram)
        seed = np.uint32(rng.integers(0, 1 << 32))
        idx = w2v.counter_negs(seed, max(n, 1) * k, self._neg_host.size - 1)
        return (self._neg_host[idx].reshape(max(n, 1), k).astype(np.int32),
                seed)

    def _block_arrays(self, block: np.ndarray, rng) -> Dict:
        """Host-side block prep shared by both PS planes: the mode-specific
        training arrays, the block's input-vocab set/remap, and — for HS
        modes — the block's Huffman inner-node set/remap
        (ref RequestParameter's needed-row collection,
        communicator.cpp:104-142)."""
        cfg = self.cfg
        prep: Dict = {}
        if cfg.cbow:
            windows, masks, targets = w2v.generate_cbow_batches(
                block, cfg.window)
            prep.update(windows=windows, masks=masks, targets=targets)
            used = [windows.reshape(-1), targets, np.zeros(1, np.int64)]
            examples = targets   # the word whose path/negs are scored
        else:
            centers, contexts = _gen_pairs(block, cfg.window,
                                           int(rng.integers(1 << 31)))
            prep.update(centers=centers, contexts=contexts)
            used = [centers, contexts]
            examples = contexts
        prep["examples"] = examples
        if cfg.hs:
            codes, points, lengths = self._hs
            t = np.asarray(examples, np.int64)
            pmask = (np.arange(codes.shape[1])[None, :]
                     < lengths[t][:, None])
            prep.update(codes=codes[t], points=points[t], pmask=pmask)
            prep["hs_rows"] = self._used_ids(
                self.table_hs.shape[0], [prep["points"][pmask]])
        else:
            negs, neg_seed = self._host_negs(examples.size, cfg.negative, rng)
            prep.update(negs=negs, neg_seed=neg_seed)
            used.append(negs.reshape(-1))
        vocab = self._used_ids(len(self.dict), used)
        # the rows they live in, ascending as the pushes promise: on row
        # shards the words follow their rows' order
        rows = self._rows(vocab)
        if rows is not vocab:
            rows = np.sort(rows)
            vocab = self._words(rows)
        prep.update(vocab=vocab, rows=rows)
        return prep

    @staticmethod
    def _used_ids(limit: int, arrays) -> np.ndarray:
        """Sorted unique ids across ``arrays`` via a presence mask — O(n + V)
        instead of np.unique's O(n log n) sort (block prep is on the
        words/sec critical path)."""
        seen = np.zeros(limit, bool)
        for a in arrays:
            seen[np.asarray(a).reshape(-1)] = True
        return np.flatnonzero(seen)

    def _produce_block(self, block: np.ndarray, rng,
                       dispatch_early: bool = False) -> Optional[Dict]:
        """The PURE host-CPU half of host-plane block prep — pair/negative
        generation, remap, packing — safe on a producer thread: it reads
        no table state, so K-deep production cannot reorder the wire.
        The pulls are dispatched separately (:meth:`_dispatch_pulls`) on
        the consumer thread, in program order — EXCEPT the inline path
        (``dispatch_early``, consumer thread by definition), which
        dispatches them before the ~35 ms packing work so the
        wire/gather latency hides under it (packing makes no wire ops,
        so the dispatch point within prepare never changes results)."""
        cfg = self.cfg
        b = cfg.batch_size
        with _trace.span("we.prepare", phase="prepare"):
            prep = self._block_arrays(block, rng)
            n = (prep["examples"].size // b) * b
            if n == 0:
                return None
            nbb = -(-(n // b) // 8) * 8
            vocab = prep["vocab"]
            k = vocab.size
            # bucket the pulled-row count so the jitted scan compiles once
            # per bucket, not once per block's distinct vocab size (the
            # device plane buckets for the same reason); the pulled rows
            # are zero-padded to the bucket before the scan
            kb = _bucket_size(k, 1 << 30)
            remap_hs, hkb = None, 0
            if cfg.hs:
                hs_rows = prep["hs_rows"]
                hkb = _bucket_size(hs_rows.size, 1 << 30)
                # remap path points into the pulled hs block; padded path
                # slots route to a dummy extra row (their grads are masked
                # to zero, the scatter just needs a valid index)
                remap_hs = np.full(self.table_hs.shape[0] + 1, hkb, np.int64)
                remap_hs[hs_rows] = np.arange(hs_rows.size)
            remap = np.full(len(self.dict), kb, np.int64)   # default: dummy
            remap[vocab] = np.arange(k)
            prep.update(kb=kb, hkb=hkb)
            if dispatch_early:
                self._dispatch_pulls(prep)
            batch, valid = self._pack_batches(prep, n, nbb, remap, kb,
                                              remap_hs, hkb)
            prep.update(batch=batch, valid=valid)
            return prep

    def _dispatch_pulls(self, prep: Optional[Dict]) -> Optional[Dict]:
        """Dispatch a produced block's row pulls (ref RequestParameter,
        communicator.cpp:104-142) — the one ordered step kept on the
        consumer thread: a pull must enter the conn FIFO before the
        PREVIOUS block's push, exactly where the inline path dispatches
        it, or the pulled rows (hence the results) would change. Tables
        with a warm training cache serve a fully-covered block as a
        device-resident (bucket, D) array instead — one fused gather/pad
        program (ops/row_assemble), nothing crossing the host boundary —
        and cold/partial blocks fall back to get_rows_async, whose cache
        split fetches only the residual cold rows over the wire."""
        if prep is None:
            return None
        if "dev_in" in prep or "pull_in" in prep:
            return prep   # already dispatched (the inline early path)
        cfg = self.cfg

        def pull(table, ids, bucket, k_dev, k_pull):
            f = getattr(table, "train_cache_device_block", None)
            blk = f(ids, bucket) if f is not None else None
            if blk is not None:
                prep[k_dev] = blk
            else:
                prep[k_pull] = table.get_rows_async(ids)

        pull(self.table_in, prep["rows"], prep["kb"], "dev_in", "pull_in")
        if cfg.hs:
            pull(self.table_hs, prep["hs_rows"], prep["hkb"],
                 "dev_sec", "pull_hs")
        else:
            pull(self.table_out, prep["rows"], prep["kb"],
                 "dev_sec", "pull_out")
        return prep

    def _prepare_block(self, block: np.ndarray, rng) -> Optional[Dict]:
        """Inline host-plane block prep (-pipeline 0, the parity oracle):
        produce + dispatch on the calling thread, the step's ``prepare``
        phase (``we.prepare``). Compute is the SAME packed ``lax.scan``
        as the device plane — only pull/push differ (table Get/Add over
        the wire here, in-graph gather/scatter there)."""
        return self._produce_block(block, rng, dispatch_early=True)

    def _train_prepared(self, prep: Optional[Dict], num_workers: int
                        ) -> Tuple[float, Optional[jax.Array]]:
        """Consume the pulls, run the block's packed scan, push the
        (new - old)/workers deltas ASYNC like the reference
        (ref communicator.cpp:144-236 AddAsync) — the push overlaps the
        next block's prep/compute. Ordering is safe: sync tables dispatch
        in program order; on the async plane arrival-order accumulation
        is the semantics. Returns the block's loss and its plans' counts
        (:meth:`_block_ahead`)."""
        cfg = self.cfg
        if prep is None:
            return 0.0, None
        with _trace.span("we.block"):
            # device pad (ops/row_assemble): ONE transfer of the real
            # rows, the zero padding materializes in-graph — the old
            # np.pad + jnp.asarray paid a host copy of the padded block
            padded = _rowasm.pad_rows

            sec_t = self._sec_table()
            # ps_wait: the residual of the pulls dispatched during
            # prepare — the part the prefetch overlap did NOT hide.
            # Cache-served blocks (dev_in/dev_sec) already sit on device.
            with _trace.span("we.block.ps_wait", phase="ps_wait"):
                rows_in = (None if "dev_in" in prep
                           else self.table_in.wait(prep["pull_in"]))
                rows_sec = (None if "dev_sec" in prep
                            else sec_t.wait(
                                prep["pull_hs" if cfg.hs else "pull_out"]))
            with _trace.span("we.block.compute", phase="compute"):
                win_l = (prep["dev_in"] if rows_in is None
                         else padded(rows_in, prep["kb"]))
                wsec_l = (prep["dev_sec"] if rows_sec is None
                          else padded(rows_sec,
                                      prep["hkb"] if cfg.hs
                                      else prep["kb"]))
                # batch upload through the devstats chokepoint (the
                # per-direction device-plane counters)
                _devstats.note_transfer(sum(
                    int(np.asarray(a).nbytes)
                    for a in prep["batch"]), "h2d")
                d_in, d_sec, loss, counts = self._local_train_fn()(
                    win_l, wsec_l, jnp.asarray(prep["valid"]),
                    jax.device_put(prep["batch"]))
                # materialize the deltas HERE: np.asarray is the device
                # sync, so the scan's runtime lands in `compute`, not in
                # the push's enqueue accounting (the push itself is the
                # client's send-to-reply span, with trace_ids on)
                d_in = np.asarray(d_in)
                d_sec = np.asarray(d_sec)
                _devstats.note_transfer(d_in.nbytes + d_sec.nbytes, "d2h")
            with _trace.span("we.push", phase="push"):
                k = prep["rows"].size
                self.table_in.add_rows_async(
                    prep["rows"], d_in[:k] / num_workers)
                ids_sec = prep["hs_rows"] if cfg.hs else prep["rows"]
                sec_t.add_rows_async(
                    ids_sec, d_sec[:ids_sec.size] / num_workers)
            return float(loss), counts

    # ------------------------------------------------------------------ #
    # PS block path: shared packed-scan compute, two pull/push planes
    # ------------------------------------------------------------------ #
    def _sec_table(self):
        return self.table_hs if self.cfg.hs else self.table_out

    @staticmethod
    def _idt(limit: int):
        """Smallest index dtype covering [0, limit] — the packed batches
        cross the host->device wire; int16 halves the bytes."""
        return np.int16 if limit < (1 << 15) else np.int32

    def _pack_batches(self, prep: Dict, n: int, nbb: int,
                      remap: np.ndarray, dummy_in: int,
                      remap_hs: Optional[np.ndarray], dummy_hs: int,
                      dev_negs: bool = False
                      ) -> Tuple[Tuple[np.ndarray, ...], np.ndarray]:
        """Remap + pack the block's training arrays into the (nbb, B, ...)
        scan layout shared by BOTH planes. Index spaces: ids are remapped
        into the pulled-row array; pad slots and padded minibatches point
        at the dummy extra row appended after the pulled rows, so their
        (masked) garbage never touches real rows."""
        cfg = self.cfg
        b = cfg.batch_size
        nb = n // b

        def pack(x, fill, dtype):
            out = np.full((nbb, b) + x.shape[1:], fill, dtype)
            out[:nb] = x[:n].reshape((nb, b) + x.shape[1:])
            return out

        din = self._idt(dummy_in)
        if cfg.hs:
            dhs = self._idt(dummy_hs)
            points = remap_hs[prep["points"][:n]]
            points[~prep["pmask"][:n]] = dummy_hs  # mask off-path garbage
            sec_batch = (pack(prep["codes"][:n], 0, np.int8),
                         pack(points, dummy_hs, dhs),
                         pack(prep["pmask"][:n], False, bool))
        elif dev_negs:
            sec_batch = ()  # negatives re-derived in-graph from the seed
        else:
            sec_batch = (pack(remap[prep["negs"][:n]], dummy_in, din),)
        if cfg.cbow:
            head = (pack(remap[prep["windows"][:n]], dummy_in, din),
                    pack(prep["masks"][:n], False, bool))
            if cfg.hs:          # cbow_hs_step(w, m, codes, points, pmask)
                batch = head + sec_batch
            else:               # cbow_ns_step(w, m, targets, negs)
                batch = head + (pack(remap[prep["targets"][:n]],
                                     dummy_in, din),) + sec_batch
        else:
            centers = pack(remap[prep["centers"][:n]], dummy_in, din)
            if cfg.hs:          # skipgram_hs_step(c, codes, points, pmask)
                batch = (centers,) + sec_batch
            else:               # skipgram_ns_step(c, contexts, negs)
                batch = (centers,
                         pack(remap[prep["contexts"][:n]], dummy_in, din),
                         ) + sec_batch
        valid = np.zeros(nbb, np.float32)
        valid[:nb] = 1.0
        return batch, valid

    def _step_fn_raw(self):
        """Unjitted per-minibatch step for the active (cbow, hs) mode —
        all four reference variants (ref wordembedding.cpp FeedForward/
        HS/NS branches); scanned by both PS planes."""
        cfg = self.cfg
        alpha = cfg.alpha
        if cfg.cbow and cfg.hs:
            return lambda a, s, w, m, c, p, pm: w2v.cbow_hs_step(
                a, s, w, m, c, p, pm, alpha)
        if cfg.cbow:
            return lambda a, s, w, m, t, g: w2v.cbow_ns_step(
                a, s, w, m, t, g, alpha)
        if cfg.hs:
            return lambda a, s, c, cd, p, pm: w2v.skipgram_hs_step(
                a, s, c, cd, p, pm, alpha)
        return lambda a, s, c, x, g, plans=(None, None): (
            w2v.skipgram_ns_step(a, s, c, x, g, alpha, plans=plans))

    def _compute_dtype(self):
        return jnp.bfloat16 if self.cfg.ps_block_dtype == "bf16" else None

    def _block_ahead(self, batch, valid, rows: int, remap=None,
                     neg_seed=None, neg_table=None):
        """What a block's scan is handed that is made before it, traced
        ahead of the scan in both planes: ``(batch, plans, counts)``.

        Where the negatives are re-derived in the graph (the device
        plane's ``_dev_negs``: ``remap`` is the block's word -> local row
        map) the whole block's are drawn here, with the counters the host
        drew them with when it built the pull set, so only the 4-byte
        seed crossed the wire; a padded minibatch's counters were not in
        the host's pass, so its negatives are the dummy row ``rows - 1``.

        For skip-gram with negative sampling every id of the block is
        then known, so ``plans`` is the pair :func:`row_combine.plan_rows`
        of every minibatch's centres and of its targets a column at a time
        (``w2v.target_columns``) for the ``rows`` local rows (the bucket
        and the dummy row), off the minibatch's path, and ``counts`` what
        the table writes are handed, as ``we.fused`` says it: the update
        rows of the real minibatches' pairs (a pair's centre, its context
        and its negatives), then :func:`row_combine.plan_counts` of both
        plans, summed (a padded minibatch adds the one distinct row of
        each of its writes, the dummy). The other modes keep their raw
        scatters: ``None``, ``None``."""
        cfg = self.cfg
        if remap is not None:
            nbb, bsz, k = valid.shape[0], cfg.batch_size, cfg.negative
            slots = w2v.counter_negs(neg_seed, nbb * bsz * k,
                                     neg_table.shape[0] - 1)
            negs = jnp.take(remap, jnp.take(neg_table, slots)).astype(
                jnp.int32).reshape(nbb, bsz, k)
            batch = batch + (jnp.where(valid[:, None, None] > 0, negs,
                                       jnp.int32(rows - 1)),)
        if cfg.cbow or cfg.hs:
            return batch, None, None
        c, x, g = (a.astype(jnp.int32) for a in batch)
        with jax.named_scope("mv.scan.plan"):
            plans = (row_combine.plan_rows(c, rows),
                     row_combine.plan_rows(w2v.target_columns(x, g), rows))
        update_rows = valid.sum().astype(jnp.int32) * (
            c.shape[-1] * (2 + cfg.negative))
        return batch, plans, jnp.concatenate([
            update_rows[None],
            row_combine.plan_counts(plans[0])
            + row_combine.plan_counts(plans[1])])

    def _run_block_scan(self, step, rows_in, rows_sec, valid, batch,
                        plans=None):
        """THE block-train scan, traced inside both planes' jits: pulled
        rows in, (new - old) deltas + mean loss out. ``plans`` is
        :meth:`_block_ahead`'s, a minibatch's slice of which goes to its
        step. The scan runs on buckets of whole lanes (``dummy``) and the
        deltas are their first columns. Deltas are measured against the
        SAME baseline the scan started from — in bf16 mode the rounded
        rows — so a pulled-but-untrained row gets an exactly-zero
        delta."""
        cdtype = self._compute_dtype()

        def dummy(r):
            # padded slots train against an extra row; in the same pass
            # the bucket is made whole lanes wide with zero columns, which
            # every step leaves zero, so that its walks take the tile
            # kernel (row_combine.tile_walk)
            r = r.astype(cdtype) if cdtype is not None else r
            return jnp.pad(r, ((0, 1), (
                0, row_combine.lane_wide(r.shape[1]) - r.shape[1])))

        def body(carry, xs):
            ri, rs = carry
            w, arrs, plan = xs
            arrs = tuple(a.astype(jnp.int32)
                         if a.dtype == jnp.int16 else a for a in arrs)
            kw = {} if plan is None else {"plans": plan}
            ri, rs, loss = step(ri, rs, *arrs, **kw)
            return (ri, rs), loss * w

        (ri, rs), losses = jax.lax.scan(
            body, (dummy(rows_in), dummy(rows_sec)), (valid, batch, plans))
        loss = losses.sum().astype(jnp.float32) / jnp.maximum(
            valid.sum(), 1.0)

        def base(old):
            if cdtype is None:
                return old
            return old.astype(cdtype).astype(old.dtype)

        d_in = ri[:-1, :rows_in.shape[1]].astype(
            rows_in.dtype) - base(rows_in)
        d_sec = rs[:-1, :rows_sec.shape[1]].astype(
            rows_sec.dtype) - base(rows_sec)
        return d_in, d_sec, loss

    def _local_train_fn(self):
        """Jitted local-train scan for the host plane — the packed
        equivalent of the reference's per-block OMP train loop
        (ref distributed_wordembedding.cpp:178-227), minus the per-
        minibatch dispatch round-trips. Returns the deltas, the loss and
        :meth:`_block_ahead`'s counts."""
        fn = self._fused_cache.get("ps_local")
        if fn is not None:
            return fn
        step = self._step_fn_raw()

        def local_train(ri, rs, v, b):
            b, plans, counts = self._block_ahead(b, v, ri.shape[0] + 1)
            with jax.named_scope("mv.scan"):    # device-trace name
                return self._run_block_scan(step, ri, rs, v, b,
                                            plans) + (counts,)

        fn = self._fused_cache["ps_local"] = jax.jit(local_train)
        return fn

    def _prepare_block_device(self, block: np.ndarray, rng, index: int
                              ) -> Optional[Tuple[Dict, int]]:
        """Device-plane block prep: bucketed table-id lists + packed
        batches, shipped in ONE pytree device_put per block (overlapped
        with the previous block's compute by JAX async dispatch).
        Returns the payload and the id of its ``we.prepare`` span, which
        the consumer names as the cause of the block's dispatch."""
        cfg = self.cfg
        b = cfg.batch_size
        with _trace.span("we.prepare", request=index) as sp:
            with _trace.span("we.prepare.arrays", request=index):
                prep = self._block_arrays(block, rng)
            n = (prep["examples"].size // b) * b
            if n == 0:
                return None
            with _trace.span("we.prepare.pack", request=index):
                # multiple-of-8 bucket: pair counts per fixed-size block
                # jitter by << 8 minibatches, so this stays on one compiled
                # program while wasting far less upload padding than pow2
                nbb = -(-(n // b) // 8) * 8
                vocab = prep["vocab"]
                k = vocab.size
                vbb = _bucket_size(k, self.table_in.padded_shape[0])
                # bucket the pulled-row count; pad ids gather the table's
                # scratch row (zero delta scatters back into it, a no-op)
                ids_in = np.full(vbb, self.table_in.scratch_row, np.int32)
                ids_in[:k] = prep["rows"]
                remap = np.full(len(self.dict), vbb, np.int64)  # dummy
                remap[vocab] = np.arange(k)
                remap_hs, hsb = None, 0
                if cfg.hs:
                    hs_rows = prep["hs_rows"]
                    hk = hs_rows.size
                    hsb = _bucket_size(hk,
                                       self._sec_table().padded_shape[0])
                    ids_sec = np.full(hsb, self._sec_table().scratch_row,
                                      np.int32)
                    ids_sec[:hk] = hs_rows
                    remap_hs = np.full(self.table_hs.shape[0] + 1, hsb,
                                       np.int64)
                    remap_hs[hs_rows] = np.arange(hk)
                else:
                    ids_sec = ids_in
                batch, valid = self._pack_batches(prep, n, nbb, remap, vbb,
                                                  remap_hs, hsb,
                                                  dev_negs=self._dev_negs)
                payload = {"ids_in": ids_in, "ids_sec": ids_sec,
                           "valid": valid, "batch": batch, "remap": None,
                           "neg_seed": None}
                if self._dev_negs:
                    # in-graph negatives need the global->local remap (V
                    # small ids) and the block's 4-byte draw seed
                    payload["remap"] = remap.astype(self._idt(vbb))
                    payload["neg_seed"] = np.uint32(prep["neg_seed"])
            sp.set(rows_touched=int(k), rows_bucket=int(vbb),
                   minibatches=int(nbb), pairs=int(n),
                   h2d_bytes=sum(int(np.asarray(a).nbytes)
                                 for a in jax.tree.leaves(payload)))
            with _trace.span("we.prepare.put", request=index):
                return jax.device_put(
                    payload,
                    jax.sharding.NamedSharding(
                        mv.mesh(), jax.sharding.PartitionSpec())), sp.id

    def _block_ahead_fn(self):
        """:meth:`_block_ahead` as the device plane's own program,
        dispatched right before the block's: it returns nothing in a
        layout of its own, so the persistent compile cache serves it and
        the sorts behind the plans (1.8 s of the v5e's compiler) stay off
        every run's set-up, which the block program's compile is on
        (``utils/platform.compile_result_layouts_in_process``). ``None``
        where there is nothing to make ahead."""
        if "ps_ahead" not in self._fused_cache:
            fn = None
            if self._dev_negs or not (self.cfg.cbow or self.cfg.hs):
                fn = jax.jit(
                    self._block_ahead, static_argnums=(2,),
                    out_shardings=jax.sharding.NamedSharding(
                        mv.mesh(), jax.sharding.PartitionSpec()))
            self._fused_cache["ps_ahead"] = fn
        return self._fused_cache["ps_ahead"]

    def _fused_block_fn(self):
        """One jitted program = the whole reference block cycle: pull
        (device gather of the block's rows), local train (lax.scan over
        minibatches, with :meth:`_block_ahead_fn`'s plans), push (new -
        old deltas through the table updater, functional_add_rows).
        Donates both tables' buffers and hands them back in the tables'
        own formats — the block chain re-uses device memory like the
        reference's in-place server shard
        (ref distributed_wordembedding.cpp:147-252 collapsed into
        XLA)."""
        fn = self._fused_cache.get("ps_block")
        if fn is not None:
            return fn
        t_in, t_sec = self.table_in, self._sec_table()
        step = self._step_fn_raw()

        def fused(din, uin, dsec, usec, ids_in, ids_sec, valid, batch,
                  plans):
            # mv.pull / mv.scan / mv.push: the names the block's three
            # regions carry in a device trace (metadata only)
            with jax.named_scope("mv.pull"):
                old_in = jnp.take(din, ids_in, axis=0)
                old_sec = jnp.take(dsec, ids_sec, axis=0)
            with jax.named_scope("mv.scan"):
                d_in, d_sec, loss = self._run_block_scan(
                    step, old_in, old_sec, valid, batch, plans)
            with jax.named_scope("mv.push"):
                # _prepare_block_device: sorted ids, then scratch rows
                s_in = t_in.functional_add_rows(
                    {"data": din, "ustate": uin}, ids_in, d_in,
                    sorted_ids=True)
                s_sec = t_sec.functional_add_rows(
                    {"data": dsec, "ustate": usec}, ids_sec, d_sec,
                    sorted_ids=True)
            return (s_in["data"], s_in["ustate"],
                    s_sec["data"], s_sec["ustate"], loss)

        f_in, f_sec = t_in.state_format, t_sec.state_format
        fn = jax.jit(fused, donate_argnums=(0, 1, 2, 3),
                     out_shardings=(f_in["data"], f_in["ustate"],
                                    f_sec["data"], f_sec["ustate"], None))
        self._fused_cache["ps_block"] = fn
        return fn

    def _train_block_device(self, prepared: Tuple[Dict, int],
                            index: int
                            ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Dispatch one block: what is made ahead of its scan, then the
        fused block program. Returns the block loss as a DEVICE scalar
        (readback deferred to end of run) and the plans' counts, on their
        way to the host behind the device's work. The dispatch is the
        ``we.block.dispatch`` span; the block itself ends when the device
        is done, which the app's watcher records as ``we.block.device``
        while a profiler trace or ``trace_ids`` can read it."""
        prep, prepare_span = prepared
        t_in, t_sec = self.table_in, self._sec_table()
        fn, ahead = self._fused_block_fn(), self._block_ahead_fn()
        if self._dev_negs and self._neg_dev is None:
            self._neg_dev = jax.device_put(
                self._neg_host, jax.sharding.NamedSharding(
                    mv.mesh(), jax.sharding.PartitionSpec()))
        t0_ns = time.time_ns()
        with _trace.span("we.block.dispatch", request=index,
                         cause=prepare_span) as sp:
            batch, plans, counts = prep["batch"], None, None
            if ahead is not None:
                batch, plans, counts = ahead(
                    batch, prep["valid"], prep["ids_in"].shape[0] + 1,
                    prep["remap"], prep["neg_seed"], self._neg_dev)
            if counts is not None:
                counts.copy_to_host_async()
            with t_in._dispatch_lock, t_sec._dispatch_lock:
                si, ss = t_in.program_state(), t_sec.program_state()
                din, uin, dsec, usec, loss = fn(
                    si["data"], si["ustate"], ss["data"], ss["ustate"],
                    prep["ids_in"], prep["ids_sec"], prep["valid"], batch,
                    plans)
                t_in.adopt({"data": din, "ustate": uin})
                t_sec.adopt({"data": dsec, "ustate": usec})
        self._watcher.watch("we.block.device", loss, t0_ns, request=index,
                            cause=sp.id)
        if "we.blocks" not in self._described:
            # the block program's map, once, on the buffers it handed
            # back, and the map of the program that made its plans
            self._described.add("we.blocks")
            _devstats.describe_program(
                "we.blocks", fn, din, uin, dsec, usec, prep["ids_in"],
                prep["ids_sec"], prep["valid"], batch, plans)
            if ahead is not None:
                _devstats.describe_program(
                    "we.blocks.ahead", ahead, prep["batch"], prep["valid"],
                    prep["ids_in"].shape[0] + 1, prep["remap"],
                    prep["neg_seed"], self._neg_dev)
        return loss, counts

    def _ps_topology(self) -> Tuple[int, int]:
        """(num_workers, worker_id) of the PS plane in use: the async
        context's world for uncoordinated tables, the collective runtime's
        otherwise."""
        if self.cfg.async_ps:
            ctx = self.table_in.ctx
            return max(ctx.world, 1), ctx.rank
        return max(mv.num_workers(), 1), mv.rank()

    def total_word_count(self) -> int:
        """Global trained-word count across all workers — the reference reads
        the server-aggregated KV value (ref communicator.cpp:17-31 +
        kv_table.h:44-99), so this uses the aggregated Get, not the local
        view. Async tables aggregate on every get (uncoordinated); the sync
        KVTable needs the collective global_=True read."""
        return int(self.word_count.get([0], global_=True)[0])

    # ------------------------------------------------------------------ #
    def embeddings(self) -> np.ndarray:
        """The input embeddings ``[words, size]`` in WORD order, wherever
        the rows live (:meth:`_rows`)."""
        emb = self.table_in.get()
        if self._stripes()[0] == 1:
            return emb
        return emb[self._rows(np.arange(len(self.dict)))]

    def nearest(self, word: str, k: int = 10) -> List[str]:
        wid = self.dict.word2id[word]
        ids = w2v.nearest_neighbors(self.embeddings(), wid, k)
        return [self.dict.words[i] for i in ids]

    def save_embeddings(self, path: Optional[str] = None,
                        binary: Optional[bool] = None) -> None:
        """ref SaveEmbedding (distributed_wordembedding.cpp:263-306):
        word2vec text format, or the classic .bin layout with -binary 1
        (ref util.h:26 output_binary; writer WriteToFile
        distributed_wordembedding.cpp:310-325 — header line, then per row
        ``word `` + embedding_size raw float32 + newline)."""
        path = path or self.cfg.output
        if not path:
            return
        binary = self.cfg.output_binary if binary is None else binary
        emb = self.embeddings()
        if binary:
            with open(path, "wb") as f:
                f.write(f"{len(self.dict)} {self.cfg.size}\n".encode())
                for w, row in zip(self.dict.words, emb):
                    f.write(w.encode() + b" "
                            + np.asarray(row, np.float32).tobytes() + b"\n")
            return
        with open(path, "w") as f:
            f.write(f"{len(self.dict)} {self.cfg.size}\n")
            for w, row in zip(self.dict.words, emb):
                f.write(w + " " + " ".join(f"{v:.6f}" for v in row) + "\n")


def load_embeddings(path: str) -> Tuple[List[str], np.ndarray]:
    """Read embeddings written by :meth:`WordEmbedding.save_embeddings`,
    auto-detecting text vs binary (both carry the same ``"V D\\n"``
    header; the binary body is the classic word2vec .bin row layout).
    Returns (words, (V, D) float32 matrix) — binary round-trips
    bit-exact."""
    with open(path, "rb") as f:
        head = f.readline().split()
        v, d = int(head[0]), int(head[1])
        rest = f.read()
    # text rows are pure ASCII floats; binary rows embed raw float bytes.
    # Decide ONCE from the first row (the reference had no marker either);
    # after that, parse errors mean a malformed file and must propagate —
    # falling back would silently reinterpret broken text as binary.
    def _first_row_is_text() -> bool:
        try:   # probe ONLY the first line — no full-file decode
            nl = rest.find(b"\n")
            row = rest[: nl if nl >= 0 else len(rest)].decode(
                "utf-8", errors="strict")
            vals = np.asarray(row.split()[1:], np.float32)
            return vals.size == d
        except (ValueError, UnicodeDecodeError, IndexError):
            return False

    if _first_row_is_text():
        rows = rest.decode("utf-8").splitlines()
        if len(rows) != v:
            raise ValueError(
                f"{path}: malformed text embeddings (header says {v} "
                f"rows, file has {len(rows)})")
        twords: List[str] = []
        emb = np.empty((v, d), np.float32)
        for i, row in enumerate(rows):
            parts = row.split()
            twords.append(parts[0])
            emb[i] = np.asarray(parts[1:], np.float32)
        return twords, emb
    words: List[str] = []
    emb = np.empty((v, d), np.float32)
    off = 0
    for i in range(v):
        sp = rest.index(b" ", off)
        words.append(rest[off:sp].decode("utf-8", errors="replace"))
        start = sp + 1
        emb[i] = np.frombuffer(rest, np.float32, count=d, offset=start)
        off = start + 4 * d + 1   # skip the trailing newline
    return words, emb


def synthetic_corpus(num_tokens: int = 200_000, vocab: int = 2000,
                     seed: int = 0) -> List[str]:
    """Zipf-distributed token stream with local co-occurrence structure
    (bench/test stand-in for text8 in a zero-egress environment): tokens are
    drawn in correlated runs so that nearby words share topics."""
    rng = np.random.default_rng(seed)
    base = rng.zipf(1.3, size=num_tokens) % vocab
    # topic runs: overwrite stretches with a narrow band of ids
    out = base.copy()
    pos = 0
    while pos < num_tokens:
        run = int(rng.integers(5, 50))
        topic = int(rng.integers(0, max(vocab - 50, 1)))
        out[pos: pos + run] = topic + (base[pos: pos + run] % 50)
        pos += run
    return [f"w{t}" for t in out]


def read_vocab_file(path: str, min_count: int,
                    max_vocab: Optional[int] = None) -> Dictionary:
    """Adopt a pre-counted vocabulary ("word count" lines, any order —
    re-sorted count-desc like the reference's loader, capped at
    ``max_vocab`` like Dictionary.build; ref
    distributed_wordembedding.cpp:415-446 consuming the
    preprocess/word_count.cpp output)."""
    items = []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) < 2:
                continue
            c = int(parts[-1])
            if c >= min_count:
                items.append((" ".join(parts[:-1]), c))
    if not items:
        raise ValueError(f"vocab file {path} has no words >= min_count")
    items.sort(key=lambda wc: (-wc[1], wc[0]))
    if max_vocab is not None:
        items = items[:max_vocab]
    return Dictionary.from_counts([w for w, _ in items],
                                  np.array([c for _, c in items], np.int64),
                                  min_count)


def load_corpus(cfg: WEConfig):
    """Build (Dictionary, encoded ids) for cfg.train_file, preferring the
    native C++ loader (mv_data.cpp: tokenize+count+prune+encode in one
    pass); -read_vocab adopts a pre-counted vocabulary instead of
    re-scanning, -save_vocab writes one (ref word_count preprocess)."""
    max_vocab = int(cfg.max_vocab) if cfg.max_vocab else None
    dictionary = None
    if cfg.read_vocab:
        dictionary = read_vocab_file(cfg.read_vocab, cfg.min_count,
                                     max_vocab)
        if cfg.train_file and native.available():
            # keep the native one-pass tokenizer: encode under ITS vocab,
            # then remap native ids onto the adopted vocabulary (ids not
            # in it drop, same OOV rule as Dictionary.encode)
            corpus = native.NativeCorpus(cfg.train_file, 1, None)
            remap = np.array(
                [dictionary.word2id.get(w, -1) for w in corpus.words()],
                np.int64)
            ids = remap[corpus.ids().astype(np.int64)]
            _maybe_save_vocab(cfg, dictionary)
            return dictionary, prepare_ids(dictionary, ids[ids >= 0], cfg)
    if cfg.train_file and dictionary is None and native.available():
        corpus = native.NativeCorpus(cfg.train_file, cfg.min_count,
                                     max_vocab)
        dictionary = Dictionary.from_counts(corpus.words(), corpus.counts(),
                                            cfg.min_count)
        _maybe_save_vocab(cfg, dictionary)
        return dictionary, prepare_ids(dictionary,
                                       corpus.ids().astype(np.int64), cfg)
    if cfg.train_file:
        # byte-level ASCII-whitespace split, matching the native tokenizer
        # exactly (mv_data.cpp is_space) so results don't depend on whether
        # the C++ build is available
        with open(cfg.train_file, "rb") as f:
            tokens = [t.decode("utf-8", errors="replace")
                      for t in f.read().split()]
    else:
        log.info("no -train_file given; using synthetic corpus")
        tokens = synthetic_corpus()
    if dictionary is None:
        dictionary = Dictionary.build(tokens, cfg.min_count, max_vocab)
    _maybe_save_vocab(cfg, dictionary)
    return dictionary, prepare_ids(dictionary, dictionary.encode(tokens), cfg)


def _maybe_save_vocab(cfg: WEConfig, dictionary: Dictionary) -> None:
    if not cfg.save_vocab:
        return
    with open(cfg.save_vocab, "w") as f:
        for w, c in zip(dictionary.words, dictionary.counts.tolist()):
            f.write(f"{w} {c}\n")


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    # "-key=value" entries flow into the runtime flag registry exactly like
    # the reference's MV_Init(&argc, argv) (ref src/multiverso.cpp:10) —
    # e.g. -ps_rank=0 -ps_world=4 -ps_rendezvous=/dir launches the
    # uncoordinated plane straight from the app command line
    from multiverso_tpu.utils import config as config_lib
    argv = config_lib.consume_runtime_flags(argv)
    cfg = WEConfig.from_argv(argv)
    mv.init()
    dictionary, ids = load_corpus(cfg)
    log.info("vocab %d words, %d training tokens (native=%s)",
             len(dictionary), ids.size, native.available())
    we = WordEmbedding(cfg, dictionary)
    if cfg.use_ps:
        stats = we.train_ps_blocks(ids)
    else:
        stats = we.train_fused(ids)
    log.info("trained: %s", stats)
    we.save_embeddings()
    mv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
