"""FTRL updater, sparse LR push/pull path, compression filters
(ref: LR FTRL objective + SparseTable, quantization_util filters)."""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.apps.logistic_regression import LogReg, LogRegConfig
from multiverso_tpu.models import logreg as model_lib
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils.filters import SparseFilter


@pytest.fixture(autouse=True)
def _init():
    mv.init()
    yield
    mv.shutdown()


class TestFTRL:
    def test_zero_small_weights(self):
        # lambda1 drives small-|z| weights to exactly 0 (the sparsity FTRL
        # exists for)
        t = mv.ArrayTable(8, updater="ftrl", name="ftrl")
        t.add(np.full(8, 1e-4, np.float32))
        np.testing.assert_allclose(t.get(), 0.0)

    def test_descends_against_gradient(self):
        t = mv.ArrayTable(4, updater="ftrl", name="ftrl2")
        for _ in range(10):
            t.add(np.full(4, 1.0, np.float32))
        w = t.get()
        assert np.all(w < 0)  # persistent positive gradient -> negative w

    def test_state_roundtrip(self):
        import io
        t = mv.ArrayTable(16, updater="ftrl", name="ftrl3")
        t.add(np.random.default_rng(0).normal(size=16).astype(np.float32))
        buf = io.BytesIO()
        t.store(buf)
        snap = t.get().copy()
        t.add(np.ones(16, np.float32))
        buf.seek(0)
        t.load(buf)
        np.testing.assert_allclose(t.get(), snap)


def _write_svm(path, x, y):
    with open(path, "w") as f:
        for xi, yi in zip(x, y):
            nz = np.nonzero(xi)[0]
            feats = " ".join(f"{j}:{xi[j]:.5f}" for j in nz)
            f.write(f"{yi} {feats}\n")


class TestSparseLR:
    def _sparse_data(self, n=800, d=50, seed=0):
        # 10 informative features at fixed columns, randomly dropped per
        # sample (sparse but consistent layout)
        x, y = model_lib.synthetic_dataset(n, 10, 2, seed=seed)
        rng = np.random.default_rng(seed)
        cols = rng.choice(d, size=10, replace=False)
        full = np.zeros((n, d), np.float32)
        full[:, cols] = x
        drop = rng.random((n, d)) < 0.3
        full[drop] = 0.0
        return full, y

    def test_sparse_path_converges(self, tmp_path):
        x, y = self._sparse_data()
        train = tmp_path / "s.svm"
        _write_svm(train, x, y)
        cfg = LogRegConfig(dict(input_size="50", output_size="2",
                                sparse="true", updater_type="sgd",
                                minibatch_size="64", learning_rate="0.5",
                                train_epoch="4",
                                train_file=str(train),
                                test_file=str(train)))
        lr = LogReg(cfg)
        assert lr.sparse_table is not None and lr.table is None
        stats = lr.train_file()
        acc = lr.test_file()
        assert acc > 0.8, f"sparse LR acc {acc}, stats {stats}"

    def test_sparse_ftrl(self, tmp_path):
        x, y = self._sparse_data(seed=3)
        train = tmp_path / "f.svm"
        _write_svm(train, x, y)
        cfg = LogRegConfig(dict(input_size="50", output_size="2",
                                sparse="true", updater_type="ftrl",
                                objective_type="sigmoid",
                                minibatch_size="64", train_epoch="3",
                                train_file=str(train),
                                test_file=str(train)))
        lr = LogReg(cfg)
        lr.train_file()
        acc = lr.test_file()
        assert acc > 0.7, f"ftrl acc {acc}"
        # FTRL produces exact zeros somewhere (sparsity)
        w = lr.sparse_table.get()
        assert np.any(w == 0.0)


class TestFilters:
    def test_sparse_filter_roundtrip(self):
        f = SparseFilter(clip=0.1)
        data = np.zeros(100, np.float32)
        data[[3, 50, 99]] = [1.0, -2.0, 0.5]
        header, payload = f.filter_in(data)
        assert header["sparse"] and header["nnz"] == 3
        assert payload.size == 6  # (idx, val) pairs
        out = f.filter_out(header, payload)
        np.testing.assert_allclose(out, data)

    def test_sparse_filter_dense_passthrough(self):
        f = SparseFilter(clip=0.0)
        data = np.arange(1, 11, dtype=np.float32)
        header, payload = f.filter_in(data)
        assert not header["sparse"]
        np.testing.assert_allclose(f.filter_out(header, payload), data)


class TestWireFilteredTables:
    """The sync tables have no link filter (PR 46: the bf16 cast tied the
    plain path on Add and doubled the Get on the chip's own host link);
    what compresses a wire is the PS plane's ``wire=``."""

    def test_unknown_filter_raises(self):
        import multiverso_tpu as mv
        from multiverso_tpu.table import Table
        for mode in ("none", "bf16", "zstd", "1bit", "topk"):
            with pytest.raises(TypeError, match="wire_filter"):
                mv.ArrayTable(16, name="wf_bad", wire_filter=mode)
            with pytest.raises(TypeError, match="wire_filter"):
                Table((16,), name="wf_bad", wire_filter=mode)
