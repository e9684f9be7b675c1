"""Native C++ data pipeline vs the Python reference implementations
(mv_data.cpp; ref reader.cpp/dictionary.cpp territory)."""

import os

import numpy as np
import pytest

from multiverso_tpu import native
from multiverso_tpu.data.dictionary import Dictionary
from multiverso_tpu.models import word2vec as w2v

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


@pytest.fixture
def corpus_file(tmp_path):
    text = ("the quick brown fox jumps over the lazy dog " * 200 +
            "pack my box with five dozen liquor jugs " * 100)
    p = tmp_path / "c.txt"
    p.write_text(text)
    return str(p), text


class TestNativeCorpus:
    def test_matches_python_dictionary(self, corpus_file):
        path, text = corpus_file
        nc = native.NativeCorpus(path, min_count=5)
        pd = Dictionary.build(text.split(), min_count=5)
        assert nc.vocab_size == len(pd)
        assert nc.words() == pd.words
        np.testing.assert_array_equal(nc.counts(), pd.counts)
        np.testing.assert_array_equal(nc.ids(), pd.encode(text.split()))
        assert nc.total_tokens == len(text.split())

    def test_min_count_prunes(self, corpus_file):
        path, text = corpus_file
        nc = native.NativeCorpus(path, min_count=150)
        # only the 'the' (400) and the 9-word *200 sentence words (200 each)
        assert nc.vocab_size == 8  # 'the' + 7 other words at 200; dog/fox...
        assert all(c >= 150 for c in nc.counts())

    def test_max_vocab(self, corpus_file):
        path, _ = corpus_file
        nc = native.NativeCorpus(path, min_count=1, max_vocab=3)
        assert nc.vocab_size == 3

    def test_missing_file(self):
        with pytest.raises(IOError):
            native.NativeCorpus("/nonexistent/file.txt")


class TestNativeSubsample:
    def test_distribution_matches_python(self):
        rng = np.random.default_rng(0)
        counts = np.array([50_000, 5_000, 50], dtype=np.int64)
        ids = rng.choice(3, p=counts / counts.sum(), size=30_000)
        native_kept = native.subsample(ids, counts, t=1e-3, seed=1)
        d = Dictionary(min_count=1)
        d.counts = counts
        py_kept = d.subsample(ids.astype(np.int64), t=1e-3, seed=1)
        # independent RNGs: compare survival rates, not exact sets
        for w in range(3):
            n_nat = np.sum(native_kept == w)
            n_py = np.sum(py_kept == w)
            denom = max(np.sum(ids == w), 1)
            assert abs(n_nat - n_py) / denom < 0.05


class TestNativePairs:
    def test_pair_multiset_matches_python(self):
        ids = np.arange(50, dtype=np.int64) % 7
        nc, nx = native.generate_pairs(ids, window=2, dynamic=False)
        pc, px = w2v.generate_pairs(ids, window=2, dynamic=False)
        assert nc.size == pc.size
        nat = sorted(zip(nc.tolist(), nx.tolist()))
        py = sorted(zip(pc.tolist(), px.tolist()))
        assert nat == py

    def test_dynamic_window_bounds(self):
        ids = np.arange(200, dtype=np.int64)
        c, x = native.generate_pairs(ids, window=5, seed=3, dynamic=True)
        assert 0 < c.size <= 2 * 5 * 200
        assert np.all(np.abs(c - x) <= 5)

    def test_deterministic_given_seed(self):
        ids = np.arange(100, dtype=np.int64)
        a = native.generate_pairs(ids, 3, seed=7)
        b = native.generate_pairs(ids, 3, seed=7)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


class TestNativeLibsvm:
    def test_parse(self):
        out = native.parse_libsvm_line(b"2 0:1.5 4:-2.0", 6)
        assert out is not None
        label, x = out
        assert label == 2
        np.testing.assert_allclose(x, [1.5, 0, 0, 0, -2.0, 0])

    def test_comment_and_empty(self):
        assert native.parse_libsvm_line(b"# hi", 4) is None
        assert native.parse_libsvm_line(b"   ", 4) is None


class TestBuildKey:
    """native.build_and_load loads only the build whose name carries the
    hash of the current source, flags and host."""

    @pytest.fixture
    def native_dir(self, tmp_path, monkeypatch):
        monkeypatch.setattr(native, "_DIR", str(tmp_path))
        return tmp_path

    @staticmethod
    def _write(native_dir, value):
        (native_dir / "t.cpp").write_text(
            'extern "C" int answer() { return %d; }\n' % value)

    def test_rebuilds_when_the_source_changes(self, native_dir):
        self._write(native_dir, 1)
        assert native.build_and_load("libt.so", "t.cpp").answer() == 1
        first = native.artefact_path("libt.so", "t.cpp")
        self._write(native_dir, 2)
        second = native.artefact_path("libt.so", "t.cpp")
        assert second != first
        assert native.build_and_load("libt.so", "t.cpp").answer() == 2
        assert sorted(p.name for p in native_dir.glob("*.so")) == sorted(
            os.path.basename(p) for p in (first, second))

    def test_refuses_a_library_without_the_stamp(self, native_dir):
        # a library somebody left behind, under the plain name and under
        # another build's key: neither may load (these bytes could not)
        self._write(native_dir, 3)
        (native_dir / "libt.so").write_bytes(b"not a library")
        (native_dir / "libt.0123456789abcdef.so").write_bytes(b"stale")
        assert native.build_and_load("libt.so", "t.cpp").answer() == 3

    def test_failed_build_keeps_the_compiler_output(self, native_dir):
        (native_dir / "t.cpp").write_text("this is not C++\n")
        assert native.build_and_load("libt.so", "t.cpp") is None
        assert "error" in native.build_failure("libt.so")
        assert not list(native_dir.glob("*.so*"))   # no half-built file
