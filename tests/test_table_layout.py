"""A table's own row programs keep it in the layout they run in
(``table.py:row_program_layout``) and hand it back so; everyone else gets
the device's default layout, and each change of hands is one counted
copy (``table.relayout``).

On the CPU the two are one layout, so the first tests hold that nothing
moved: no re-layout is ever counted and results equal what the
copy-chained path gave, bit for bit. Two layouts are then driven for
real on the CPU with a stand-in (column-major against the default
row-major). The rule itself, and the whole-table copies it removes, are
checked against the v5e's compiler, which this image has without the
chip (the ``topo`` fixture; skipped where the topology cannot be
described).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

import multiverso_tpu as mv
from multiverso_tpu import table as table_lib
from multiverso_tpu.models import word2vec as w2v
from multiverso_tpu.ops import row_combine
from multiverso_tpu.telemetry import trace as ttrace

RELAYOUT = "table.relayout"


def _relayouts(since: int = 0, to=None):
    return [e for e in ttrace.events()[since:] if e["name"] == RELAYOUT
            and to in (None, e["args"]["to"])]


def _order(x):
    return x.format.layout.major_to_minor


def _we(vocab=60, tokens=6_000, **kw):
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    mv.init()
    corpus = synthetic_corpus(tokens, vocab=vocab, seed=0)
    cfg = WEConfig(**{**dict(size=8, min_count=1, batch_size=64, negative=2,
                             window=2, epoch=1, sample=0), **kw})
    we = WordEmbedding(cfg, Dictionary.build(corpus, 1))
    return we, we.prepare_ids(corpus)


@pytest.fixture
def column_major_rows(monkeypatch):
    """Stand-in for a v5e on the CPU: tables of two dimensions whose row
    programs run in another layout (column-major) than the device's
    default (row-major), as a 300-wide table's do there the other way
    round. The CPU backend keeps both layouts for real."""
    monkeypatch.setattr(
        table_lib, "row_program_layout",
        lambda shape, dtype, sharding:
            Layout(major_to_minor=(1, 0)) if len(shape) == 2 else None)


# ---------------------------------------------------------------------- #
# (a) on the CPU: one layout, no re-layout, the same bits
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("width", [300, 128])
def test_cpu_table_has_one_layout_and_never_relayouts(width):
    mv.init()
    start = len(ttrace.events())
    t = mv.MatrixTable(50, width, updater="adagrad", name=f"lay{width}")
    assert t.format == Format(None, t.sharding)
    fmt = t.state_format
    assert fmt["data"] is t.format
    assert jax.tree.structure(fmt) == jax.tree.structure(
        jax.tree.map(lambda x: 0, t.state))
    [init] = [e for e in ttrace.events()[start:] if e["name"] == "table.init"
              and e["args"]["table"] == f"lay{width}"]
    assert init["args"]["row_major"] == 0
    # the logical surface is what it was
    assert t.padded_shape == t.raw().shape == (56, width)
    ids = jnp.asarray([3, 7, t.scratch_row], jnp.int32)
    vals = jnp.ones((3, width), jnp.float32)
    t.adopt(jax.jit(t.functional_add_rows)(t.state, ids, vals))
    t.adopt(jax.jit(t.functional_add_rows)(t.program_state(), ids, vals))
    t.add_rows([1, 3], np.ones((2, width), np.float32))
    t.add(np.ones((50, width), np.float32))
    assert t.get().shape == (50, width)
    assert _relayouts(start) == []


def test_train_fused_equals_the_copy_chained_epoch_bit_for_bit(same_floats):
    """What ``train_fused`` did until PR 26: the donated epoch chained from
    ``jnp.copy`` of both tables, its results adopted afterwards. That
    epoch is not told the tables' placement, so on the eight row shards of
    the tests' mesh its reads and writes are the partitioner's: since
    ISSUE 38 another program than ``train_fused``'s, bit for bit where
    floats are computed as written (``same_floats``)."""
    start = len(ttrace.events())
    we, ids = _we()
    ref, _ = _we()
    for _ in range(2):
        out = we.train_fused(ids, epochs=2)
    cb, xb, _n = ref._device_pairs(ids)
    cfg = ref.cfg
    epoch = w2v.make_fused_shared_epoch(
        w2v.W2VConfig(len(ref.dict), cfg.size, cfg.negative, cfg.window,
                      cfg.alpha, False, False, cfg.shared_negatives),
        ref.unigram, compute_dtype=jnp.float32,
        slots=we._fused_slots)     # the pools as rows, as the pairs are
    lcg = jnp.asarray(w2v.init_lcg_state(cfg.shared_negatives, cfg.seed))
    win, wout = jnp.copy(ref.table_in.raw()), jnp.copy(ref.table_out.raw())
    for _ in range(4):
        win, wout, loss, lcg, _ = epoch(win, wout, cb, xb, lcg)
    same_floats(we.table_in.raw(), win)
    same_floats(we.table_out.raw(), wout)
    same_floats(out["loss"], loss)
    # one program serves every call: the sampler state goes in as it
    # comes back
    assert we._fused_epoch_fn()[0]._cache_size() == 1
    assert _relayouts(start) == []


def test_a_ps_block_on_the_device_plane_never_relayouts():
    start = len(ttrace.events())
    we, ids = _we(use_ps=1, data_block_size=1500)
    before = we.table_in.get()
    we.train_ps_blocks(ids[:3000], epochs=1)
    assert not np.array_equal(before, we.table_in.get())
    assert _relayouts(start) == []


# ---------------------------------------------------------------------- #
# (b) two layouts: the table's own row programs keep theirs, everyone
#     else gets the default, and every change of hands is counted
# ---------------------------------------------------------------------- #
def test_own_programs_and_the_outside_each_get_their_layout(
        column_major_rows):
    mv.init()
    start = len(ttrace.events())
    t = mv.MatrixTable(50, 300, updater="adagrad", name="lay_two")
    assert t.format == Format(Layout(major_to_minor=(1, 0)), t.sharding)
    [init] = [e for e in ttrace.events()[start:] if e["name"] == "table.init"]
    assert init["args"]["row_major"] == 1
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 50, 16)
    vals = rng.normal(size=(16, 300)).astype(np.float32)
    opt = mv.AddOption(learning_rate=0.1, rho=0.1)
    # built, and handed out, in the default layout: nothing to count
    assert _order(t.raw()) == (0, 1) and _relayouts(start) == []
    # a row program lays data and history out, once
    t.add_rows(ids, vals, opt)
    events = _relayouts(start)
    assert [(e["args"]["table"], e["args"]["to"]) for e in events] == [
        ("lay_two", "rows")] * 2
    assert all(e["args"]["relayouts"] == 1
               and e["args"]["bytes"] == 56 * 300 * 4 for e in events)
    assert _order(t._data) == _order(t._ustate["g_sqr"]) == (1, 0)
    # ... and the next finds them so, as does a host-plane row read
    t.add_rows(ids, vals, opt)
    rows = t.get_rows(ids)
    state = t.program_state()
    assert _order(state["data"]) == (1, 0) and len(_relayouts(start)) == 2
    # the outside gets the default layout back, at a copy each
    assert _order(t.state["ustate"]["g_sqr"]) == _order(t.raw()) == (0, 1)
    assert len(_relayouts(start, "default")) == 2
    assert len(_relayouts(start)) == 4
    # state comes back as it is given; the next row program lays it out
    t.adopt(t.state)
    assert _order(t._data) == (0, 1) and len(_relayouts(start)) == 4
    # same numbers as a table that never left the default layout
    plain = mv.MatrixTable(50, 300, updater="adagrad", name="lay_one")
    plain._format = Format(None, plain.sharding)
    plain.add_rows(ids, vals, opt)
    plain.add_rows(ids, vals, opt)
    np.testing.assert_array_equal(rows, plain.get_rows(ids))
    np.testing.assert_array_equal(t.get(), plain.get())


def test_training_calls_change_hands_once_and_then_never(column_major_rows):
    start = len(ttrace.events())
    we, ids = _we(use_ps=1, data_block_size=1500)
    assert we.table_in.format.layout.major_to_minor == (1, 0)
    we.train_fused(ids, epochs=1)
    assert [(e["args"]["table"], e["args"]["to"])
            for e in _relayouts(start)] == [("embed_in", "rows"),
                                            ("embed_out", "rows")]
    mark = len(ttrace.events())
    # the measured windows: call after call, block after block
    for _ in range(3):
        we.train_fused(ids, epochs=2)
    we.train_ps_blocks(ids, epochs=1)
    we.train_fused(ids, epochs=1)
    assert _relayouts(mark) == []
    assert _order(we.table_in._data) == _order(we.table_out._data) == (1, 0)
    # a reader from outside costs a copy each way
    assert np.isfinite(np.asarray(we.table_in.raw())).all()
    we.train_fused(ids, epochs=1)
    assert [(e["args"]["table"], e["args"]["to"])
            for e in _relayouts(mark)] == [("embed_in", "default"),
                                           ("embed_in", "rows")]


def test_two_layouts_train_to_the_same_tables(column_major_rows,
                                              monkeypatch):
    we, ids = _we(use_ps=1, data_block_size=1500)
    we.train_fused(ids, epochs=2)
    we.train_ps_blocks(ids[:3000], epochs=1)
    monkeypatch.undo()
    ref, _ = _we(use_ps=1, data_block_size=1500)
    assert ref.table_in.format.layout is None
    ref.train_fused(ids, epochs=2)
    ref.train_ps_blocks(ids[:3000], epochs=1)
    for got, want in ((we.table_in, ref.table_in),
                      (we.table_out, ref.table_out)):
        np.testing.assert_allclose(got.get(), want.get(), rtol=1e-5,
                                   atol=1e-7)


def test_leading_axes_of_updater_state_stay_major(column_major_rows):
    mv.init()
    t = mv.MatrixTable(20, 300, name="lay_lead")
    per_worker = np.zeros((3,) + t.padded_shape, np.float32)
    fmt = t._leaf_format(per_worker)
    assert fmt.layout.major_to_minor == (0, 2, 1)
    assert fmt.sharding.spec == jax.sharding.PartitionSpec(
        None, t._axis, None)
    assert t._leaf_format(np.zeros((), np.int32)) == Format(
        None, t._replicated)


# ---------------------------------------------------------------------- #
# the build's host draw, by every core, is the draw it was
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(5,), (7, 3), (1001, 300), (70_001, 31)])
def test_parallel_uniform_draw_is_the_sequential_draw(shape):
    seed, scale = 2 ** 40 + 17, 0.5 / 300
    want = np.random.default_rng(seed).uniform(-scale, scale, shape).astype(
        np.float32)
    np.testing.assert_array_equal(
        table_lib._uniform(seed, scale, shape, np.float32), want)


def test_seeded_table_build_draws_what_it_drew():
    mv.init()
    t = mv.MatrixTable(1000, 300, seed=17, init_scale=0.5 / 300)
    want = np.random.default_rng(17).uniform(
        -0.5 / 300, 0.5 / 300, t.padded_shape).astype(np.float32)[:1000]
    np.testing.assert_array_equal(t.get(), want)
    assert not np.asarray(t.raw())[1000:].any()


# ---------------------------------------------------------------------- #
# (c) an epoch that fails before it runs leaves the tables as they were
# ---------------------------------------------------------------------- #
def test_train_fused_failing_before_the_program_runs_keeps_the_tables(
        monkeypatch):
    we, ids = _we()
    we.train_fused(ids, epochs=1)
    before = (we.table_in.get(), we.table_out.get())
    held = (we.table_in.raw(), we.table_out.raw())

    def refuses(*_a, **_k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory at dispatch")

    monkeypatch.setattr(we, "_fused_epoch_fn", lambda: (refuses, True))
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        we.train_fused(ids, epochs=2)
    monkeypatch.undo()
    np.testing.assert_array_equal(we.table_in.get(), before[0])
    np.testing.assert_array_equal(we.table_out.get(), before[1])
    # nothing was adopted: the tables hold the very arrays they held
    assert we.table_in.raw() is held[0] and we.table_out.raw() is held[1]
    # and the locks were let go: the next call trains
    assert np.isfinite(we.train_fused(ids, epochs=1)["loss"])
    assert not np.array_equal(we.table_in.get(), before[0])


# ---------------------------------------------------------------------- #
# programs that return a chosen layout stay out of the compile cache
# ---------------------------------------------------------------------- #
def test_result_layout_programs_are_compiled_in_process(monkeypatch):
    from jax._src import compiler
    from multiverso_tpu.utils import platform

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    column = Format(Layout(major_to_minor=(1, 0)), sharding)
    x = jax.device_put(np.arange(12, dtype=np.float32).reshape(3, 4),
                       sharding)

    def module(**kw):
        return jax.jit(lambda a: a * 2, **kw).lower(x).compiler_ir()

    assert platform._asks_for_result_layout(module(out_shardings=column))
    assert not platform._asks_for_result_layout(module())
    assert not platform._asks_for_result_layout(module(in_shardings=sharding,
                                                       out_shardings=sharding))
    through_cache = []
    real = compiler.compile_or_get_cached

    def spy(backend, computation, *rest, **kw):
        through_cache.append(platform._asks_for_result_layout(computation))
        return real(backend, computation, *rest, **kw)

    monkeypatch.setattr(compiler, "compile_or_get_cached", spy)
    assert platform.compile_result_layouts_in_process()
    guarded = compiler.compile_or_get_cached
    assert platform.compile_result_layouts_in_process()      # idempotent
    assert compiler.compile_or_get_cached is guarded
    y = jax.jit(lambda a: a * 3, out_shardings=column)(x)    # in process
    assert _order(y) == (1, 0) and through_cache == []
    z = jax.jit(lambda a: a.sum())(y)       # takes a layout, returns none
    assert float(z) == 3 * 66 and through_cache == [False]


# ---------------------------------------------------------------------- #
# (d) against the v5e's compiler, without the chip
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:   # no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


VOCAB = 240_000       # far more rows than a block of the test pulls
ROWS = 240_008        # that table's padded rows on the 8-device CPU mesh


def test_the_rule_on_a_v5e(one_chip):
    """Row-major where the chip's row programs run row-major and its
    default is not; the default wherever it serves or row-major tiles
    would pad a narrow row many times over."""
    f32 = jnp.dtype(jnp.float32)
    for width in (300, 100, 64):
        layout = table_lib.row_program_layout((ROWS, width), f32, one_chip)
        assert layout is not None and layout.major_to_minor == (0, 1), width
    for width in (128, 256):
        assert table_lib.row_program_layout((ROWS, width), f32,
                                            one_chip) is None, width
    # the compiler weighs the padding: a million 10-wide rows stay as the
    # device stores them (64 MB; row-major tiles would take 512 MB)
    for width in (32, 10, 2):
        assert table_lib.row_program_layout((1_000_001, width), f32,
                                            one_chip) is None, width
    assert table_lib.row_program_layout((ROWS,), f32, one_chip) is None


def _table_copies(compiled, shape):
    """Names of the ``copy`` ops of ``compiled`` whose result has a
    table's shape: the whole-table layout conversions."""
    dims = ",".join(str(d) for d in shape)
    return re.findall(r"^\s*(\S*copy\S*) = f32\[%s\]" % re.escape(dims),
                      compiled.as_text(), re.M)


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), tree)


@pytest.fixture(scope="module")
def we300():
    from multiverso_tpu.apps.word_embedding import WEConfig, WordEmbedding
    from multiverso_tpu.data.dictionary import Dictionary

    mv.init()
    counts = np.maximum(1_000_000 // np.arange(1, VOCAB + 1), 5)
    cfg = WEConfig(size=300, min_count=1, batch_size=256, negative=5,
                   window=5, epoch=1, sample=0, shared_negatives=64,
                   use_ps=1, data_block_size=1_000)
    return WordEmbedding(cfg, Dictionary.from_counts(
        [str(i) for i in range(VOCAB)], counts, 1))


@pytest.mark.parametrize("kept", [True, False])
def test_fused_epoch_on_a_v5e_copies_no_table(one_chip, kept):
    shape = (ROWS, 300)
    layout = table_lib.row_program_layout(shape, jnp.dtype(jnp.float32),
                                          one_chip)
    fmt = Format(layout if kept else None, one_chip)
    cfg = w2v.W2VConfig(VOCAB, 300, 5, 5, 0.025, False, False, 64)
    fn = w2v.make_fused_shared_epoch(
        cfg, np.full(VOCAB, 1 / VOCAB), jnp.bfloat16,
        table_formats=(fmt, fmt))
    table = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=fmt)
    batch = jax.ShapeDtypeStruct((4, 256), jnp.int32, sharding=one_chip)
    lcg = jax.ShapeDtypeStruct((64,), jnp.uint32, sharding=one_chip)
    compiled = fn.lower(table, table, batch, batch, lcg).compile()
    copies = _table_copies(compiled, shape)
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_table = ROWS * 384 * 4          # a row is three lane tiles
    if kept:
        assert copies == [] and temp < one_table
        assert compiled.output_formats[0].layout.major_to_minor == (0, 1)
    else:
        # the defect, as the compiler shows it for a default-layout table:
        # two copies in, two out, and both tables again as temporaries
        assert len(copies) == 4 and temp > 2 * one_table


def _compiled_epoch(rows, tables, whole, batch):
    """The shared-negatives epoch over four minibatches of ``batch``
    pairs, compiled for two ``f32[rows, 300]`` tables placed as
    ``tables`` says and laid out as their row programs run; ids and
    sampler state lie ``whole`` on every device."""
    fmt = Format(table_lib.row_program_layout(
        (rows, 300), jnp.dtype(jnp.float32), tables), tables)
    cfg = w2v.W2VConfig(rows - 8, 300, 5, 5, 0.025, False, False, 64)
    fn = w2v.make_fused_shared_epoch(
        cfg, np.full(rows - 8, 1 / (rows - 8)), jnp.bfloat16,
        table_formats=(fmt, fmt))
    table = jax.ShapeDtypeStruct((rows, 300), jnp.float32, sharding=fmt)
    pairs = jax.ShapeDtypeStruct((4, batch), jnp.int32, sharding=whole)
    lcg = jax.ShapeDtypeStruct((64,), jnp.uint32, sharding=whole)
    return fn.lower(table, table, pairs, pairs, lcg).compile()


def _pair_scatters_promise_distinct_rows_only(compiled, rows):
    """Three table scatters: the pairs' two promise distinct rows, the
    pool's does not, and none is told its ids are sorted."""
    scatters = re.findall(
        r"= f32\[%d,300\]\S* scatter\(.*" % rows, compiled.as_text())
    return (len(scatters) == 3
            and sum("unique_indices=true" in s for s in scatters) == 2
            and not any("indices_are_sorted=true" in s for s in scatters))


@pytest.mark.parametrize("batch", [256, 1024])
def test_fused_epoch_on_a_v5e_scatters_distinct_rows_in_place(one_chip,
                                                              batch):
    """ISSUE 28: the epoch combines a minibatch's duplicate update rows.
    A batch of 256 is one table scatter a table, 1,024 the walk over
    chunks of ``row_combine.CHUNK`` slots: either way no table is copied,
    the plans and the combined rows are small beside a table, and the two
    table scatters of the pairs promise distinct rows (the pool's, whose
    rows may repeat, does not). None is told its ids are sorted: the v5e
    then streams the whole table (PERF.md, PR 28). ISSUE 31: the first
    ``row_combine.HEAD`` rows of each table take a dense add, written
    into the table where it lies."""
    shape = (ROWS, 300)
    compiled = _compiled_epoch(ROWS, one_chip, one_chip, batch)
    assert _table_copies(compiled, shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < ROWS * 384 * 4 / 50
    assert compiled.output_formats[0].layout.major_to_minor == (0, 1)
    assert _pair_scatters_promise_distinct_rows_only(compiled, ROWS)
    assert "tpu_custom_call" not in compiled.as_text()
    assert len(_head_adds(compiled, shape)) == 2


def _head_adds(compiled, shape):
    """The dense adds of a table's head in ``compiled``: updates of a
    slice of a buffer of the table's (a shard's) shape."""
    return re.findall(r"= f32\[%d,%d\]\S* dynamic-update-slice\(" % shape,
                      compiled.as_text())


@pytest.mark.parametrize("rows", [ROWS, 16_008])
def test_fused_epoch_on_four_v5e_row_shards_adds_the_head_in_place(topo,
                                                                   rows):
    """The epoch on tables row-sharded over a four-chip host (ISSUE 27),
    with the head's dense add (ISSUE 31): every chip adds to its own
    rows. ISSUE 38: every chip reads its own rows and an all-gather hands
    them round, ``[4 * cap, 300]`` in the compute type a table, once in
    the first round and once in the loop of later rounds; the one
    all-reduce left is the pool's. No chip copies its shard or casts it
    whole (left alone, the compiler casts the shard ahead of the later
    rounds' loop: half a shard of temporaries), and the walk's scatters
    are what they are on one chip. At 60,002 rows a shard the head lies
    in shard 0; at 4,002 it ends in shard 2."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("mv",))
    sharded = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("mv", None))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    per = (rows // 4, 300)
    compiled = _compiled_epoch(rows, sharded, whole, 1024)
    text = compiled.as_text()
    assert "collective-permute" not in text and "all-to-all" not in text
    handed = 4 * row_combine.gather_cap(1024, 4)    # 768: a round of four
    assert len(re.findall(r"= bf16\[%d,300\]\S* all-gather\(" % handed,
                          text)) == 4
    assert len(re.findall(r" all-gather\(", text)) == 4
    assert len(re.findall(r"= bf16\[64,300\]\S* all-reduce\(", text)) == 1
    assert len(re.findall(r" all-reduce\(", text)) == 1
    assert "bf16[%d,300]" % per[0] not in text
    assert _pair_scatters_promise_distinct_rows_only(compiled, per[0])
    if rows == ROWS:    # a shard of 6 MB is all head, and moved about whole
        assert _table_copies(compiled, per) == []
        assert (compiled.memory_analysis().temp_size_in_bytes
                < per[0] * 384 * 4 / 2)
        assert len(_head_adds(compiled, per)) == 2


def test_fused_block_program_on_a_v5e_copies_no_table(one_chip, we300,
                                                      monkeypatch):
    we = we300
    layout = table_lib.row_program_layout(
        we.table_in.padded_shape, jnp.dtype(jnp.float32), one_chip)
    assert we.table_in.padded_shape == (ROWS, 300)
    fmt = Format(layout, one_chip)
    for t in (we.table_in, we.table_out):
        monkeypatch.setattr(t, "_format", fmt)
    we._fused_cache.pop("ps_block", None)
    ids = (np.random.default_rng(0).zipf(1.3, 1_000) % VOCAB).astype(
        np.int64)
    we._host_negs(1, 1, np.random.default_rng(0))     # the sampling table
    prep, _ = we._prepare_block_device(ids, np.random.default_rng(0), 0)
    table = jax.ShapeDtypeStruct(we.table_in.padded_shape, jnp.float32,
                                 sharding=fmt)
    # the shapes of what is made ahead of the scan (negatives, plans)
    batch, plans, _ = jax.eval_shape(
        lambda *a: we._block_ahead(a[0], a[1], prep["ids_in"].shape[0] + 1,
                                   *a[2:]),
        prep["batch"], prep["valid"], prep["remap"], prep["neg_seed"],
        jnp.asarray(we._neg_host))
    rest = _on_chip((prep["ids_in"], prep["ids_sec"], prep["valid"],
                     batch, plans), one_chip)
    try:
        compiled = we._fused_block_fn().lower(
            table, (), table, (), *rest).compile()
    finally:
        we._fused_cache.pop("ps_block", None)
    assert _table_copies(compiled, we.table_in.padded_shape) == []
    assert (compiled.memory_analysis().temp_size_in_bytes
            < ROWS * 384 * 4)
    assert [f.layout.major_to_minor for f in
            (compiled.output_formats[0], compiled.output_formats[2])] == [
                (0, 1), (0, 1)]


def test_fused_block_program_on_a_v5e_walks_its_buckets_in_tiles(
        one_chip, we300, monkeypatch):
    """ISSUE 45: at the published shapes of ``we-psblock`` (a bucket of
    2^19 rows and the dummy row, 40 minibatches of 8,192 pairs, 5
    negatives) the block's scan runs on ``f32[524289,384]`` buckets and
    its walks are the tile kernel: one kernel shape, called for the
    centres and in the columns' loop, written in place; no scatter is
    left on a bucket, no bucket is copied, and the kernel adds under 1/50
    of a bucket to what the program holds. (The tables here have 240,008
    rows, not 1,800,001: the pull and the push, not the scan.)"""
    from multiverso_tpu.ops import row_combine
    we = we300
    fmt = Format(table_lib.row_program_layout(
        we.table_in.padded_shape, jnp.dtype(jnp.float32), one_chip), one_chip)
    for t in (we.table_in, we.table_out):
        monkeypatch.setattr(t, "_format", fmt)
    monkeypatch.setattr(row_combine, "_kernel_interpret", lambda: False)
    we._fused_cache.pop("ps_block", None)
    bucket, nb, b, k = 2 ** 19, 40, 8192, we.cfg.negative
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    batch = (ints(nb, b), ints(nb, b), ints(nb, b, k))
    valid = jax.ShapeDtypeStruct((nb,), jnp.float32)
    _, plans, _ = jax.eval_shape(
        lambda bt, v: we._block_ahead(bt, v, bucket + 1), batch, valid)
    table = jax.ShapeDtypeStruct(we.table_in.padded_shape, jnp.float32,
                                 sharding=fmt)
    rest = _on_chip((ints(bucket), ints(bucket), valid, batch, plans),
                    one_chip)
    try:
        compiled = we._fused_block_fn().lower(
            table, (), table, (), *rest).compile()
    finally:
        we._fused_cache.pop("ps_block", None)
    text, wide = compiled.as_text(), (bucket + 1, 384)
    kernels = re.findall(r"^\s*\S+ = (\S+) custom-call\((.*?)\), "
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    assert len(kernels) == 2 and len(set(
        re.sub(r"%\S+", "", operands) for _, operands in kernels)) == 1
    assert all(result.startswith("f32[%d,%d]" % wide)
               for result, _ in kernels)
    assert text.count("row_walk_tiles") >= 2
    assert not re.findall(r"= f32\[%d,\d+\]\S* scatter\(" % wide[0], text)
    assert _table_copies(compiled, wide) == []
    assert _table_copies(compiled, (bucket + 1, 300)) == []
    # the two buckets, the pulled rows they are measured against and a
    # delta are five buckets' worth, as at width 300 before the kernel
    # (4,037,229,056 B): the kernel's ring and lists are under 1/50 more
    assert (compiled.memory_analysis().temp_size_in_bytes
            < (5 + 1 / 50) * wide[0] * wide[1] * 4)


def test_language_model_kernels_compile_for_a_v5e_at_published_widths(
        one_chip):
    """The flash kernel at head size 256 over 8,192 positions, forward
    and backward, at 512 x 512 blocks and at the 512 x 1,024 that
    ``models/mla_moe.attn_blocks`` gives the cell, and the held experts'
    grouped products over a 16,384-row buffer (2,048 x 1,536, eight
    groups), as ``glm47f-train-8k`` calls them: the chip's compiler takes
    them, and the kernels are in the program."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.ops import attention_kernels
    from multiverso_tpu.parallel import moe

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    qkv = shape((2, 20, 8192, 256), jnp.bfloat16)
    cell = mla_moe.attn_blocks(mla_moe.MLAMoEConfig(v_head_dim=256), 8192)
    assert cell == (512, 1024)
    for bq, bk in ((512, 512), cell):
        def core(q, k, v):
            return attention_kernels.flash_attention(
                q, k, v, True, bq, bk, False).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(core, argnums=(0, 1, 2))).lower(
            qkv, qkv, qkv).compile().as_text()
        # forward, dQ, dK with dV
        assert text.count("tpu_custom_call") >= 3, (bq, bk)

    held = moe.HeldExperts(num_experts=64, experts_held=8, top_k=4,
                           routed_scale=1.8, buffer_rows=16384,
                           tile=(512, 512, 512))

    def experts(u, router, wg, wu, wd):
        out, counts, overflow, _ = moe.held_expert_layer(
            u, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            jnp.zeros((64,)), held, kernel="pallas")
        return out.sum(), (counts, overflow)

    args = (shape((16384, 2048), jnp.float32), shape((64, 2048), jnp.float32),
            shape((8, 2048, 1536), jnp.float32),
            shape((8, 2048, 1536), jnp.float32),
            shape((8, 1536, 2048), jnp.float32))
    compiled = jax.jit(jax.grad(experts, argnums=(0, 2, 3, 4),
                                has_aux=True)).lower(*args).compile()
    # three products forward, and for each the input's and the weight's
    assert compiled.as_text().count("tpu_custom_call") >= 9


@pytest.mark.parametrize("window", [1024, None])
def test_grouped_query_kernels_compile_for_a_v5e_at_published_widths(
        one_chip, window):
    """The banded and the causal flash kernels as ``mellum2-train-8k``
    calls them: 32 query heads of 128 over 4 key-value heads, 8,192
    positions, at the blocks ``models/mla_moe.attn_blocks`` gives a head
    of 128. K and V go in at four heads and dK and dV come out at four: no
    operand of the program has them repeated."""
    from multiverso_tpu.models import gqa_moe, mla_moe
    from multiverso_tpu.ops import attention_kernels

    cfg = gqa_moe.GQAMoEConfig(n_heads=32, n_kv_heads=4, head_dim=128,
                               window=1024)
    blocks = mla_moe.attn_blocks(cfg, 8192)
    assert blocks == (1024, 1024)
    shape = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                           sharding=one_chip)

    def core(q, k, v):
        return attention_kernels.flash_attention(
            q, k, v, True, *blocks, False, window).astype(jnp.float32).sum()

    args = (shape((2, 32, 8192, 128)), shape((2, 4, 8192, 128)),
            shape((2, 4, 8192, 128)))
    grads = jax.grad(core, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # forward, dQ, dK with dV
    assert [g.shape for g in jax.eval_shape(grads, *args)] == [
        a.shape for a in args]
    # a key-value operand at 32 heads would be bf16[2,32,8192,128] beside
    # q, its gradient and the output (and the same flattened: [64, ...]);
    # the kernels' own k-shaped operands and results are [8, 8192, 128]
    assert "bf16[8,8192,128]" in text


def test_softmax_expert_layer_compiles_for_a_v5e_at_published_widths(
        one_chip):
    """The held experts' grouped products over the 65,536-row buffer at
    the tile ``moe.product_tile`` reads off 2,304 and 896 (sixteen
    groups), under the softmax route, forward and backward."""
    from multiverso_tpu.parallel import moe

    tile = moe.product_tile(2304, 896)
    assert tile == (512, 768, 896)
    held = moe.HeldExperts(num_experts=64, experts_held=16, top_k=8,
                           buffer_rows=65536, tile=tile, route="softmax")
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def experts(u, router, wg, wu, wd):
        out, counts, overflow, balance = moe.held_expert_layer(
            u, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            None, held, kernel="pallas")
        return out.sum() + balance, (counts, overflow)

    compiled = jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4),
                                has_aux=True)).lower(
        shape(16384, 2304), shape(64, 2304), shape(16, 2304, 896),
        shape(16, 2304, 896), shape(16, 896, 2304)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 9


@pytest.mark.parametrize("window", [2048, None])
def test_gated_attention_compiles_for_a_v5e_at_16k_positions(
        one_chip, window, monkeypatch):
    """``models/gqa_moe.gqa`` under ``models/afmoe.py``'s switches as
    ``trinity-train-16k`` calls it: 32 query heads of 128 over 4 key-value
    heads, ONE sequence of 16,384 positions at 1,024 x 1,024 blocks, a
    window of two k blocks (a band three blocks wide) or none, with the q/k
    norms and the gate in XLA around the kernels, forward and backward."""
    from multiverso_tpu.models import afmoe, mla_moe
    from multiverso_tpu.ops import attention_kernels

    # the process's devices are the CPU's: the kernels would be interpreted
    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = afmoe.AFMoEConfig(dim=2048, n_heads=32, n_kv_heads=4, head_dim=128,
                            window=2048, attn="flash")
    assert mla_moe.attn_blocks(cfg, 16384) == (1024, 1024)
    kind = "full" if window is None else "window"
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes(kind).items()}

    def attend(u, p):
        return cfg.attend(u, p, kind).sum()

    text = jax.jit(jax.grad(attend, argnums=(0, 1))).lower(
        f32(1, 16384, 2048), p).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # forward, dQ, dK with dV
    assert "bf16[4,16384,128]" in text and "bf16[32,16384,128]" in text


def test_attention_under_a_selection_compiles_for_a_v5e_at_16k_positions(
        one_chip, monkeypatch):
    """``models/keye_moe.sparse_gqa`` as ``keye-train-16k`` calls it: 32
    query heads of 128 over 4 key-value heads, ONE sequence of 16,384
    positions, the indexer's 16 heads of 64 and a selection of 2,048 keys a
    query in chunks of 512 rows, the three flash kernels with the int8
    selection's 1,024 x 1,024 tile beside q, k and v (1 MB more a pair: it
    fits), forward and backward with the indexer's term, whose index scores
    and their gradients are ``ops/index_kernels.py``'s two kernels a chunk
    (16 heads' blocks of [512, 64] resident beside a key tile of 512: their
    VMEM limit is set from the shapes), under names of their own."""
    from multiverso_tpu.models import keye_moe, mla_moe
    from multiverso_tpu.ops import attention_kernels, index_kernels

    # the process's devices are the CPU's: the kernels would be interpreted
    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = keye_moe.KeyeMoEConfig(
        dim=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        mrope_section=(16, 24, 24), index_heads=16, index_dim=64,
        index_topk=2048, index_chunk=512, attn="flash")
    assert mla_moe.attn_blocks(cfg, 16384) == (1024, 1024)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("sparse").items()}

    def attend(u, p):
        out, term = cfg.attend(u, p, "sparse")
        return out.sum() + term

    text = jax.jit(jax.grad(attend, argnums=(0, 1))).lower(
        f32(1, 16384, 2048), p).compile().as_text()
    # forward, dQ, dK with dV, and the term's two
    assert text.count("tpu_custom_call") >= 5
    for name in (index_kernels.STATS, index_kernels.GRADS):
        assert re.search(rf"%{name}[.\d]* = ", text), name
    assert not re.search(r"%mv\.lm\.attn\.(index|target)[.\d]* = ", text)
    # the sixteen heads' dots over every key are the selection's alone
    # (inside its fusion), the term's none
    whole = [line for line in text.splitlines()
             if "f32[1,16,512,16384]" in line]
    assert whole and not any("mv.lm.attn.target" in line for line in whole)
    assert "s8[1,16384,16384]" in text              # the selection, whole
    assert "bf16[4,16384,128]" in text and "bf16[32,16384,128]" in text


def test_sigmoid_expert_layer_over_128_compiles_for_a_v5e(one_chip):
    """The held experts' grouped products as ``trinity-train-16k`` calls
    them: a 32,768-row buffer in sixteen groups of 2,048 x 1,024 at GLM's
    tile, under the sigmoid route over 128 outputs with 8 a token."""
    from multiverso_tpu.parallel import moe

    tile = moe.product_tile(2048, 1024)
    assert tile == (512, 512, 512)
    held = moe.HeldExperts(num_experts=128, experts_held=16, top_k=8,
                           routed_scale=2.826, buffer_rows=32768, tile=tile)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def experts(u, router, wg, wu, wd):
        out, counts, overflow, _ = moe.held_expert_layer(
            u, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            jnp.zeros((128,)), held, kernel="pallas")
        return out.sum(), (counts, overflow)

    compiled = jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4),
                                has_aux=True)).lower(
        shape(16384, 2048), shape(128, 2048), shape(16, 2048, 1024),
        shape(16, 2048, 1024), shape(16, 1024, 2048)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 9


def test_relu2_expert_layer_at_rows_of_1856_compiles_for_a_v5e(one_chip):
    """The held experts' grouped products as ``nemotron3n-train-16k`` calls
    them: TWO products an expert (no gate matrix), a 12,288-row buffer in
    eight groups of 2,688 x 1,856. 1,856 is 14.5 lanes of 128: no multiple
    of 128 divides it, so the products take the WHOLE width as one tile
    (PR 67; a group's weight block of 896 x 1,856 bfloat16 is 3.3 MB, held
    twice), 896 over 2,688 and rows by 256; the matrices' float32 gradient
    blocks would be 20 MB of VMEM at that tile and take 896 x 1,024."""
    from multiverso_tpu.parallel import moe

    tile = moe.product_tile(2688, 1856)
    assert tile == (256, 896, 1856)
    assert moe.weights_tile(tile) == (256, 896, 1024)
    assert moe.weights_tile((256, 1856, 896)) == (256, 1024, 896)
    held = moe.HeldExperts(num_experts=128, experts_held=8, top_k=6,
                           routed_scale=2.5, buffer_rows=12288, tile=tile,
                           form="relu2")
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def experts(u, router, wu, wd):
        out, counts, overflow, _ = moe.held_expert_layer(
            u, {"router": router, "w_up": wu, "w_down": wd},
            jnp.zeros((128,)), held, kernel="pallas")
        return out.sum(), (counts, overflow)

    compiled = jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3),
                                has_aux=True)).lower(
        shape(16384, 2688), shape(128, 2688), shape(8, 2688, 1856),
        shape(8, 1856, 2688)).compile()
    # forward, the input's and the weight's gradient, for two matrices
    assert compiled.as_text().count("tpu_custom_call") >= 6


def test_attention_at_sixteen_query_heads_a_key_value_head_compiles_for_a_v5e(
        one_chip, monkeypatch):
    """``models/gqa_moe.gqa`` under ``models/nemotron_h.py``'s switches (all
    off, no positions) as ``nemotron3n-train-16k`` calls it: 32 query heads
    of 128 over TWO key-value heads, one sequence of 16,384 positions at
    1,024 x 1,024 blocks, forward and backward."""
    from multiverso_tpu.models import mla_moe, nemotron_h
    from multiverso_tpu.ops import attention_kernels

    # the process's devices are the CPU's: the kernels would be interpreted
    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = nemotron_h.NemotronHConfig(dim=2688, n_heads=32, n_kv_heads=2,
                                     head_dim=128, attn="flash")
    assert mla_moe.attn_blocks(cfg, 16384) == (1024, 1024)
    assert cfg.kv_group == 16
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("full").items()}

    def attend(u, p):
        return cfg.attend(u, p, "full").sum()

    text = jax.jit(jax.grad(attend, argnums=(0, 1))).lower(
        f32(1, 16384, 2688), p).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # forward, dQ, dK with dV
    assert "bf16[2,16384,128]" in text and "bf16[32,16384,128]" in text


def test_attention_at_heads_of_64_compiles_for_a_v5e(one_chip, monkeypatch):
    """``models/gqa_moe.gqa`` under ``models/lfm2_moe.py``'s switches (q/k
    norms, rotary positions, no gate) as ``lfm2-train-8k`` calls it: 32
    query heads of 64 over 8 key-value heads, two sequences of 8,192
    positions at 1,024 x 1,024 blocks. A block's last dimension is half a
    lane tile, and Mosaic takes it as it is: no operand is padded to 128."""
    from multiverso_tpu.models import lfm2_moe, mla_moe
    from multiverso_tpu.ops import attention_kernels

    # the process's devices are the CPU's: the kernels would be interpreted
    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = lfm2_moe.LFM2MoEConfig(dim=2048, n_heads=32, n_kv_heads=8,
                                 head_dim=64, attn="flash")
    assert mla_moe.attn_blocks(cfg, 8192) == (1024, 1024)
    assert attention_kernels.sub_tile(1024, 1024, 64) == 256
    assert cfg.kv_group == 4
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("full").items()}

    def attend(u, p):
        return cfg.attend(u, p, "full").sum()

    text = jax.jit(jax.grad(attend, argnums=(0, 1))).lower(
        f32(2, 8192, 2048), p).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # forward, dQ, dK with dV
    assert "bf16[16,8192,64]" in text and "bf16[64,8192,64]" in text
    assert "8192,128]" not in text


def test_sigmoid_expert_layer_at_rows_of_1792_compiles_for_a_v5e(one_chip):
    """The held experts' grouped products as ``lfm2-train-8k`` calls them:
    a 32,768-row buffer in eight groups of 2,048 x 1,792 (1,792 = 2 x 896:
    the tile over it is 896), under the sigmoid route over 32 outputs with
    4 a token at scale 1 and no shared expert beside it."""
    from multiverso_tpu.parallel import moe

    tile = moe.product_tile(2048, 1792)
    assert tile == (512, 512, 896)
    held = moe.HeldExperts(num_experts=32, experts_held=8, top_k=4,
                           routed_scale=1.0, buffer_rows=32768, tile=tile)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def experts(u, router, wg, wu, wd):
        out, counts, overflow, _ = moe.held_expert_layer(
            u, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            jnp.zeros((32,)), held, kernel="pallas")
        return out.sum(), (counts, overflow)

    compiled = jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4),
                                has_aux=True)).lower(
        shape(16384, 2048), shape(32, 2048), shape(8, 2048, 1792),
        shape(8, 2048, 1792), shape(8, 1792, 2048)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 9


@pytest.mark.parametrize("model,gmm,tgmm", [
    ("lfm2", 7, 3), ("lfm2_keeping_nothing", 9, 3), ("nemotron", 6, 2)])
def test_a_rematerialised_expert_block_on_a_v5e_keeps_its_up_products(
        one_chip, model, gmm, tgmm):
    """An expert block of ``lfm2-train-8k`` (gated, 2,048 x 1,792) under
    ``mla_moe._run_block`` as a training step rematerialises it: the
    compiled backward and forward hold THREE grouped kernels a matrix
    (``gmm`` forward and for the buffer's gradient, ``tgmm`` for the
    matrix's) and the forward one out of the experts' width once more,
    the results of those into it being kept by name; with nothing kept
    every forward ``gmm`` call is there a second time, as it is in a
    block of ``nemotron3n-train-16k`` (``relu2``, 2,688 x 1,856), whose
    configuration keeps nothing."""
    import chip_smoke
    from multiverso_tpu.models import lfm2_moe, mla_moe, nemotron_h

    lfm2 = lfm2_moe.LFM2MoEConfig(
        dim=2048, moe_ffn=1792, n_experts=32, experts_held=8, top_k=4,
        expert_kernel="pallas")
    cfg = {"lfm2": lfm2,
           "lfm2_keeping_nothing": type(
               "Bare", (lfm2_moe.LFM2MoEConfig,),
               {"keeps_products": False})(*lfm2),
           "nemotron": nemotron_h.NemotronHConfig(
               dim=2688, moe_ffn=1856, n_experts=128, experts_held=8,
               top_k=6, expert_kernel="pallas")}[model]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in dict(mla_moe._ffn_shapes(cfg, "experts"),
                                     ffn_norm=(cfg.dim,)).items()}
    layer = mla_moe.Layer("L0", None, "experts")

    def loss(x, p, bias):
        y, _ = mla_moe._run_block(x, p, layer, bias, cfg)
        return y.sum()

    # the value as well: a gradient alone needs no forward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        f32(1, 4096, cfg.dim), p, f32(cfg.n_experts)).compile().as_text()
    assert chip_smoke.grouped_kernels(text) == {"gmm": gmm, "tgmm": tgmm}


def test_short_convolution_mixer_compiles_for_a_v5e_at_published_widths(
        one_chip):
    """``models/lfm2_moe.short_conv`` on two sequences of 8,192 at width
    2,048, forward and backward: two products forward (and two a product
    backward), no kernel of the repo's own between them."""
    from multiverso_tpu.models import lfm2_moe

    cfg = lfm2_moe.LFM2MoEConfig(dim=2048)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in lfm2_moe.short_conv_shapes(cfg).items()}

    def mixer(u, p):
        return lfm2_moe.short_conv(u, p, cfg).sum()

    compiled = jax.jit(jax.grad(mixer, argnums=(0, 1))).lower(
        f32(2, 8192, 2048), p).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # the step's temporaries of one mixer stay far under the chip's memory
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_state_space_scan_compiles_for_a_v5e_at_published_widths(
        one_chip, monkeypatch):
    """``nemotron_h.mamba2`` as ``nemotron3n-train-16k`` calls it (one
    sequence of 16,384 positions, 64 heads of 64 in 8 groups, a state of
    128), forward and backward: Mosaic compiles ``ops/ssd.py``'s two
    kernels at a group's [128, 512] blocks, and they read ``x``, ``B`` and
    ``C`` out of the convolution kernel's ONE result as it lies; no copy
    and no transpose of ``x``, ``y`` or their gradients round the calls
    (the plain form's group-major walk made four)."""
    from multiverso_tpu.models import nemotron_h
    from multiverso_tpu.ops import short_conv, ssd

    # the process's devices are the CPU's: the rules would take the plain
    # forms
    monkeypatch.setattr(short_conv, "kernel_tiles",
                        lambda s, c, dtype=None: (512, 512))
    monkeypatch.setattr(ssd, "kernel_heads",
                        lambda s, h, p, g, n, chunk: h // g)
    cfg = nemotron_h.NemotronHConfig(
        dim=2688, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state=128, chunk=128)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("ssm").items()}
    compiled = jax.jit(jax.grad(lambda u, p: cfg.attend(u, p, "ssm").sum(),
                                argnums=(0, 1))).lower(
        f32(1, 16384, 2688), p).compile()
    text = compiled.as_text()
    calls = {name: re.findall(
        rf"%{name}[.\d]* = ([^\n]*?) custom-call\(([^)]*)\)", text)
        for name in (ssd.FWD, ssd.BWD)}
    assert len(calls[ssd.FWD]) == len(calls[ssd.BWD]) == 1
    for name, ((results, operands),) in calls.items():
        first = [o.strip() for o in operands.split(",")[:3]]
        # [x | B | C] whole, three times: the convolution kernel's result
        assert len(set(first)) == 1 and first[0].startswith(
            f"%{short_conv.FWD}"), (name, first)
    # y as the gated norm takes it and the chunk-start states, float32;
    # [dx | dB | dC] ONE array, as the convolution's backward kernel takes
    # it: its operand is the scan's own result
    assert "f32[1,16384,4096]" in calls[ssd.FWD][0][0]
    assert "f32[1,8,128,128,512]" in calls[ssd.FWD][0][0]
    assert "f32[1,16384,6144]" in calls[ssd.BWD][0][0]
    taps, = re.findall(rf"%{short_conv.BWD}[.\d]* = [^\n]*? custom-call\("
                       r"([^)]*)\)", text)
    made = taps.split(",")[2].strip()
    assert re.search(rf"{re.escape(made)} = [^\n]*get-tuple-element\("
                     rf"[^\n]*%{ssd.BWD}", text), made
    assert not re.search(
        r"= f32\[1,16384,(4096|6144)\]\S* (copy|transpose|concatenate)\(",
        text)
    # one mixer's backward pass, its 0.27 GB of states among it (3.3 GB;
    # 3.1 with the plain form, which keeps no states but four copies)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.6 * (1 << 30)


def test_one_group_of_64_heads_compiles_for_a_v5e_in_blocks_of_heads(
        one_chip, monkeypatch):
    """``nemotron_h.mamba2`` as ``granite4h-train-8k`` calls it (one
    sequence of 8,192 positions, 64 heads of 64 in ONE group, a state of
    128, the configuration's chunk 256), forward and backward: Mosaic
    compiles ``ops/ssd.py``'s two kernels at blocks of 16 heads, [128,
    1024], four a group and 64 chunks of a lane tile, and they still read
    ``x``, ``B`` and ``C`` out of the convolution kernel's one result and
    write ONE ``[dx | dB | dC]`` array."""
    from multiverso_tpu.models import granite_h
    from multiverso_tpu.ops import short_conv, ssd

    monkeypatch.setattr(short_conv, "kernel_tiles",
                        lambda s, c, dtype=None: (512, 256))
    monkeypatch.setattr(ssd, "kernel_refusal", lambda *shape: None)
    cfg = granite_h.GraniteHConfig(
        dim=2048, ssm_heads=64, ssm_head_dim=64, ssm_groups=1,
        ssm_state=128, chunk=256)
    assert ssd.kernel_heads(8192, 64, 64, 1, 128, 256) == 16
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("ssm").items()}
    compiled = jax.jit(jax.grad(lambda u, p: cfg.attend(u, p, "ssm").sum(),
                                argnums=(0, 1))).lower(
        f32(1, 8192, 2048), p).compile()
    text = compiled.as_text()
    calls = {name: re.findall(
        rf"%{name}[.\d]* = ([^\n]*?) custom-call\(([^)]*)\)", text)
        for name in (ssd.FWD, ssd.BWD)}
    assert len(calls[ssd.FWD]) == len(calls[ssd.BWD]) == 1
    for name, ((results, operands),) in calls.items():
        first = [o.strip() for o in operands.split(",")[:3]]
        assert len(set(first)) == 1 and first[0].startswith(
            f"%{short_conv.FWD}"), (name, first)
    # y, the chunk-start states of four units x 64 chunks, and the one
    # gradient array [x | B | C] of 4,096 + 2 x 128
    assert "f32[1,8192,4096]" in calls[ssd.FWD][0][0]
    assert "f32[1,4,64,128,1024]" in calls[ssd.FWD][0][0]
    assert "f32[1,8192,4352]" in calls[ssd.BWD][0][0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * (1 << 30)


def _qwen3next():
    """``qwen3next-train-16k``'s configuration, kernels on."""
    import json

    from benchmark.drivers import lm_train_delta

    class _Cell:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmark", "configs",
                "qwen3-next-80b-a3b-ep16.json")) as f:
            config = json.load(f)

    return lm_train_delta._model_config(_Cell)._replace(
        attn="flash", expert_kernel="pallas")


def test_attention_at_heads_of_256_in_groups_of_eight_compiles_for_a_v5e(
        one_chip, monkeypatch):
    """``models/gqa_moe.gqa`` under ``models/qwen3_next.py``'s switches (q/k
    norms, the gate, rotary over the first 64 of a head) as
    ``qwen3next-train-16k`` calls it: 16 query heads of 256 over 2
    key-value heads, one sequence of 16,384 positions at 512 x 1,024
    blocks: the dK-with-dV kernel holds a group's 8 query heads against one
    k block in VMEM."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.ops import attention_kernels

    # the process's devices are the CPU's: the kernels would be interpreted
    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = _qwen3next()
    assert mla_moe.attn_blocks(cfg, 16384) == (512, 1024)
    assert (cfg.kv_group, cfg.head_size, cfg.rope_dim) == (8, 256, 64)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("full").items()}

    def attend(u, p):
        return cfg.attend(u, p, "full").sum()

    text = jax.jit(jax.grad(attend, argnums=(0, 1))).lower(
        f32(1, 16384, 2048), p).compile().as_text()
    assert text.count("tpu_custom_call") >= 3       # forward, dQ, dK with dV
    assert "bf16[2,16384,256]" in text and "bf16[16,16384,256]" in text


def test_softmax_route_over_512_with_32_small_groups_compiles_for_a_v5e(
        one_chip):
    """The expert layer as ``qwen3next-train-16k`` calls it: a softmax
    route over 512 outputs, 10 a token, 32 held experts of 2,048 x 512 that
    see 320 rows each of a 20,480-row buffer (under a row tile of 512), a
    gated shared expert beside them, forward and backward."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.parallel import moe

    cfg = _qwen3next()
    held = mla_moe.held(cfg, 16384)
    assert (held.tile, held.buffer_rows, held.num_experts, held.top_k) == (
        (512, 512, 512), 20480, 512, 10)
    assert moe.even_rows(held, 16384) == 10240
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*mla_moe.table_shape(s))
         for n, s in mla_moe._ffn_shapes(cfg, "shared+experts").items()}
    assert p["sgate"].shape == (2048,) and p["eg"].shape == (32 * 2048, 512)

    def ffn(u, p):
        out, (counts, overflow, balance) = mla_moe.expert_ffn(u, p, None, cfg)
        return out.sum() + balance, (counts, overflow)

    compiled = jax.jit(jax.grad(ffn, argnums=(0, 1), has_aux=True)).lower(
        f32(1, 16384, 2048), p).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 9


def test_the_delta_mixer_compiles_for_a_v5e_at_16k_positions(one_chip):
    """``models/qwen3_next.gated_delta_net`` as ``qwen3next-train-16k``
    calls it (16 key and 32 value heads of 128, chunks of 64, one sequence
    of 16,384 positions), forward and backward: the mixer's temporaries
    stay under 4 GB (with its float32 stages kept for the backward pass
    they were 7.0 GB, and the cell's tables and gradients take 10 of the
    chip's 16)."""
    cfg = _qwen3next()
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes("delta").items()}

    def mix(u, p):
        return cfg.attend(u, p, "delta").sum()

    compiled = jax.jit(jax.grad(mix, argnums=(0, 1))).lower(
        f32(1, 16384, 2048), p).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
    assert "while" in compiled.as_text()        # the scan over chunks


@pytest.mark.parametrize("kind", ["delta", "ssm"])
def test_the_mixers_short_convolution_kernels_compile_for_a_v5e(
        one_chip, monkeypatch, kind):
    """``ops/short_conv.causal_taps`` inside ``qwen3_next.gated_delta_net``
    ([1, 16384, 8192], no bias) and ``nemotron_h.mamba2`` ([1, 16384, 6144],
    a bias) as the two cells call them, forward and backward: Mosaic
    compiles both kernels at tiles of 512 x 512, and the convolution's
    operand reaches them as the in-projection's own product leaves it (the
    mixers slice the WEIGHT: a column window of one product's result would
    be copied out before a custom call, 0.5 and 0.4 GB a pass)."""
    from multiverso_tpu.models import nemotron_h
    from multiverso_tpu.ops import short_conv

    # the process's devices are the CPU's: the rule would take the plain form
    monkeypatch.setattr(short_conv, "kernel_tiles",
                        lambda s, c, dtype=None: (512, 512))
    cfg, dim, channels = {
        "delta": (_qwen3next(), 2048, 8192),
        "ssm": (nemotron_h.NemotronHConfig(
            dim=2688, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
            ssm_state=128, chunk=128), 2688, 6144)}[kind]
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {n: f32(*s) for n, s in cfg.attn_shapes(kind).items()}
    assert p["conv_w"].shape == (4, channels)
    text = jax.jit(jax.grad(lambda u, p: cfg.attend(u, p, kind).sum(),
                            argnums=(0, 1))).lower(
        f32(1, 16384, dim), p).compile().as_text()
    calls = {name: re.findall(rf"%{name}[.\d]* = [^\n]*? custom-call\(([^)]*)\)",
                              text)
             for name in (short_conv.FWD, short_conv.BWD)}
    # the delta mixer's feed is rematerialised: its forward runs again
    assert len(calls[short_conv.FWD]) == 1 + (kind == "delta")
    assert len(calls[short_conv.BWD]) == 1
    shape = f"f32[1,16384,{channels}]"
    for name, found in calls.items():
        for operands in found:
            x = operands.split(",")[0].strip()
            # the operand is the product's own result: a fusion that ends
            # in the convolution, no copy and no slice of it
            assert x.startswith("%convolution"), (name, x)
            made = re.search(rf"{re.escape(x)} = {re.escape(shape)}[^\n]*",
                             text).group(0)
            assert "dot_general" in made, made
    assert not re.search(rf"= {re.escape(shape)}\S* (copy|slice)\(", text)


def _xing4():
    from multiverso_tpu.models import xing4

    return xing4.Xing4Config(
        vocab=16384, dim=3584, n_heads=32, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, dense_ffn=9216,
        n_dense_layers=1, n_moe_layers=4, moe_ffn=1024, n_experts=64,
        experts_held=8, top_k=4, attn="flash", expert_kernel="pallas")


def test_a_hyper_connections_projection_lies_a_row_an_output_on_a_v5e(
        one_chip):
    """The 24-wide table's case: ``xing4-train-4k``'s projection is stored
    [24, 14,336], a row an output. The chip tiles a float32 array in 8 x
    128: the paper's [14,336, 24] would lie in 128 lanes a row, 5.3 times
    the bytes for the table, its moments and its gradient, and its row
    programs would keep it in a layout of their own (row-major, where the
    chip's default for so narrow an array is not: ``row_program_layout``),
    compiled in the process on every run. As stored it is 24 whole rows of
    whole lanes in the device's default layout, padded row and all."""
    from multiverso_tpu.models import mla_moe

    cfg = _xing4()
    shape = mla_moe.param_shapes(cfg)["L0.attn.hc_phi"]
    assert shape == mla_moe.table_shape(shape) == (24, 4 * 3584)
    f32 = jnp.dtype(jnp.float32)
    for s in (shape, (shape[0] + 1, shape[1])):
        assert table_lib.row_program_layout(s, f32, one_chip) is None, s
    own = table_lib.row_program_layout(shape[::-1], f32, one_chip)
    assert own is not None and own.major_to_minor == (0, 1)
    size = lambda s: jax.jit(lambda a: a + 1.0).lower(
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    ).compile().memory_analysis().output_size_in_bytes
    assert size(shape) == 4 * 24 * 14336


def test_latent_attention_at_two_head_sizes_compiles_for_a_v5e(
        one_chip, monkeypatch):
    """``xing4-train-4k``'s attention core: 32 heads of 192 for queries and
    keys and of 128 for values over 4,096 positions under the
    configuration's scale, forward and backward, at the blocks
    ``attn_blocks`` gives the cell: Mosaic takes a block of a lane tile
    and a half, the three kernels are in the program, and no operand of
    theirs is padded to 256 in HBM (no [.., 4096, 256] array anywhere)."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.ops import attention_kernels

    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    cfg = _xing4()
    assert cfg.head_size == 192
    blocks = mla_moe.attn_blocks(cfg, 4096)
    assert blocks == (1024, 1024)
    assert attention_kernels.sub_tile(*blocks, cfg.head_size) == 256
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                            sharding=one_chip)
    qk, v = shape(1, 32, 4096, 192), shape(1, 32, 4096, 128)

    def core(q, k, v):
        return attention_kernels.flash_attention(
            q, k, v, True, *blocks, scale=cfg.softmax_scale).astype(
                jnp.float32).sum()

    text = jax.jit(jax.grad(core, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 3      # forward, dQ, dK with dV
    assert not re.search(r"bf16\[(1,)?32,4096,256\]", text)
    assert re.search(r"bf16\[(1,)?32,4096,128\]", text)


def test_a_hyper_connected_sublayers_maps_on_a_v5e_keep_a_position_a_lane(
        one_chip):
    """One sublayer's stream maps at ``xing4-train-4k``'s shapes (four
    streams of 3,584 over 4,096 positions, 20 Sinkhorn rounds), forward
    and backward: the compiler's temporaries stay near the streams' own
    size (235 MB an array). A [4096, 4, 4] array would lie in 8 x 128
    tiles, 16 MB for 262 KB, and Sinkhorn's backward pass keeps two a
    round: the maps are [.., 4096], a position a lane."""
    from multiverso_tpu.models import mla_moe

    cfg = _xing4()
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {"attn_norm": f32(3584), "attn.hc_phi": f32(24, 14336),
         "attn.hc_b": f32(24), "attn.hc_alpha": f32(3)}

    def loss(x, p):
        return mla_moe.block(x, p, lambda u, p: u, None, cfg)[0].sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        f32(1, 4096, 4, 3584), p).compile()
    stream = 4 * 4096 * 4 * 3584
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * stream
    assert "f32[4096,4,4]" not in compiled.as_text()


def test_a_hyper_connected_blocks_backward_walks_compile_for_a_v5e(
        one_chip, monkeypatch):
    """``xing4-train-4k``'s backward walks (``ops/stream_walks.py``) at the
    cell's shapes, under the sublayer's one rule in a rematerialised block
    of two sublayers: Mosaic takes the three kernels at the tiles
    ``tiles_for`` gives (blocks of 512 channels x 128 positions a stream,
    inside the default scoped VMEM), each
    is in the program once a sublayer, and the kernels read and write the
    streams a stream at a time without a pass of their own for it: the
    transposes round a call are layout (XLA lays the streams out [B, n, S,
    C] between its own fusions), and none is left as an operation."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.ops import stream_walks

    monkeypatch.setattr(
        stream_walks, "walk_tiles",
        lambda t, n, c, *dtypes: stream_walks.tiles_for(t, n, c))
    cfg = _xing4()
    assert stream_walks.tiles_for(4096, 4, 3584) == (512, 512, 512)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    p = {f"{b}{k}": f32(*s) for b in ("attn", "ffn") for k, s in (
        ("_norm", (3584,)), (".hc_phi", (24, 14336)), (".hc_b", (24,)),
        (".hc_alpha", (3,)))}

    def loss(x, p, weight):
        run = lambda x, p: mla_moe.block(
            x, p, lambda u, p: jnp.tanh(u), lambda u, p: (jnp.tanh(u), None),
            cfg)[0]
        return (jax.checkpoint(run)(x, p) * weight).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        f32(1, 4096, 4, 3584), p, f32(1, 4096, 4, 3584)).compile()
    text = compiled.as_text()
    for name in (stream_walks.GATHER, stream_walks.DOTS, stream_walks.SPREAD):
        assert len(re.findall(rf'custom-call\(.*"{name}"|{name}', text)) >= 2
    assert text.count("tpu_custom_call") == 6
    assert not re.search(
        r"= f32\[(1,)?4(096)?,4(096)?,3584\]\S* transpose\(", text)
    stream = 4 * 4096 * 4 * 3584
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * stream


def test_sigmoid_expert_layer_at_row_tiles_of_128_compiles_for_a_v5e(one_chip):
    """The held experts' grouped products as ``xing4-train-4k`` calls them:
    a 4,096-row buffer in eight groups of 3,584 x 1,024 at a row tile of 128
    (``Xing4Config.product_rows``: a group is about 256 rows), under the
    sigmoid route over 64 outputs with 4 a token."""
    from multiverso_tpu.models import mla_moe

    cfg = _xing4()
    held = mla_moe.held(cfg, 4096)
    assert held.tile == (128, 512, 512) and held.buffer_rows == 4096
    assert mla_moe.held(mla_moe.MLAMoEConfig(dim=2048, moe_ffn=1536),
                        16384).tile == (512, 512, 512)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    def experts(u, router, wg, wu, wd):
        out, counts, overflow, _ = moe.held_expert_layer(
            u, {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            jnp.zeros((64,)), held, kernel="pallas")
        return out.sum(), (counts, overflow)

    from multiverso_tpu.parallel import moe
    compiled = jax.jit(jax.grad(experts, argnums=(0, 1, 2, 3, 4),
                                has_aux=True)).lower(
        shape(4096, 3584), shape(64, 3584), shape(8, 3584, 1024),
        shape(8, 3584, 1024), shape(8, 1024, 3584)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 9


@pytest.mark.parametrize("cell,passes,share", [
    ("glm", 1, 2 / 3), ("mellum_full", 2, 2 / 3), ("trinity", 2, 2 / 3),
    ("xing", 1, 0.85), ("lfm2", 2, 2 / 3)])
def test_the_pass_between_projection_and_core_compiles_for_a_v5e(
        one_chip, monkeypatch, cell, passes, share):
    """ONE attention layer with every gradient as five cells run it
    (``chip_smoke._heads_cells``), ``ops/head_turns.py``'s kernels where
    the chip would run them: Mosaic takes a tile at heads of 256 (the last
    64 turning), 128, 192 and 64; each turned part is one kernel forward
    and one backward beside the three flash kernels; no float32 array of
    the core's shape crosses a copy, and what the program moves outside
    its kernels is under two thirds of what the formulation before PR 63
    moved (the compiler's own bytes: ``chip_smoke.bytes_outside_kernels``;
    under 85% at ``xing4``'s 4,096 positions, where the latents' products
    over a width of 3,584 weigh more than the heads)."""
    import chip_smoke
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.ops import attention_kernels, head_turns

    monkeypatch.setattr(attention_kernels, "_resolve_interpret",
                        lambda interpret: False)
    monkeypatch.setattr(head_turns, "kernel_tile", head_turns.tile_of)
    _, cfg, kind, b, s = next(c for c in chip_smoke._heads_cells()
                              if c[0] == cell)
    cfg = cfg._replace(attn="flash")
    f32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)
    p = {n: f32(*sh) for n, sh in cfg.attn_shapes(kind).items()}
    core = lambda q, k, v: attention_kernels.flash_attention(
        q, k, v, True, *mla_moe.attn_blocks(cfg, s), None,
        cfg.window if kind == "window" else None,
        scale=getattr(cfg, "softmax_scale", None))
    parent = ((lambda u, p: chip_smoke.parent_mla(u, p, cfg, core))
              if kind == "latent" else
              (lambda u, p: chip_smoke.parent_gqa(u, p, cfg, kind, core)))
    moved = {}
    for form, layer in (("parent", parent),
                        ("new", lambda u, p: cfg.attend(u, p, kind))):
        text = jax.jit(jax.grad(lambda u, p: jnp.sum(layer(u, p) ** 2),
                                (0, 1))).lower(f32(b, s, cfg.dim),
                                               p).compile().as_text()
        moved[form] = chip_smoke.bytes_outside_kernels(text)
        if form == "new":
            assert not re.search(
                r"%%?copy(\.\d+)? = f32\[%d,\d+,%d,\d+\]" % (b, s), text)
    assert moved["parent"]["kernels"] == 3
    assert moved["new"]["kernels"] == 3 + 2 * passes
    assert moved["new"]["outside_gb"] < share * moved["parent"]["outside_gb"], \
        moved
