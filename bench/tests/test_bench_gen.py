"""The seeded corpus generator (bench/lib/gen.py) at a tiny size."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import gen, harness  # noqa: E402


def shape(name, **kw):
    return gen.Shape.from_config(
        harness.load_json(ROOT, "bench", "configs", name + ".json")["corpus"], **kw)


@pytest.mark.parametrize("name", ["nytimes", "pubmed"])
def test_nnz_and_tokens_per_doc_match_the_published_shape(name):
    sh = shape(name, docs=12000)
    csr = gen.concat(list(gen.Generator(sh, 2**31 + 5).shards()))
    assert csr.n_rows == 12000
    assert csr.nnz / csr.n_rows == pytest.approx(sh.nnz_per_doc, rel=0.03)
    assert csr.values.sum() / csr.n_rows == pytest.approx(sh.tokens_per_doc, rel=0.03)
    lens = np.diff(csr.row_ptr)
    assert lens.max() > 4 * lens.mean()             # heavy-tailed lengths
    assert lens.max() <= 16384                      # a row fits one chunk


def test_rows_are_sets_of_words_and_the_seed_fixes_the_corpus():
    sh = shape("pubmed", docs=3000)
    a = gen.concat(list(gen.Generator(sh, 123).shards()))
    b = gen.concat(list(gen.Generator(sh, 123).shards()))
    c = gen.concat(list(gen.Generator(sh, 124).shards()))
    assert np.array_equal(a.cols, b.cols) and np.array_equal(a.values, b.values)
    assert not np.array_equal(a.cols[:1000], c.cols[:1000])
    for r in range(0, 3000, 97):
        row = a.cols[a.row_ptr[r]:a.row_ptr[r + 1]]
        assert np.all(np.diff(row) > 0)            # distinct, sorted words
    assert np.all(a.values >= 1) and np.all(a.values == np.round(a.values))


def test_zipf_slope_of_word_frequencies():
    sh = shape("nytimes", docs=20000)
    g = gen.Generator(sh, 99)
    csr = gen.concat(list(g.shards()))
    freq = np.bincount(csr.cols, weights=csr.values, minlength=sh.words)
    by_rank = freq[g.word_of_rank]
    ranks = np.arange(200, 5000)
    topics = np.concatenate(list(gen.topic_ranks(sh).values()))
    keep = ~np.isin(ranks, topics)
    slope = np.polyfit(np.log(ranks[keep] + 1), np.log(by_rank[ranks[keep]]), 1)[0]
    assert slope == pytest.approx(-sh.zipf, abs=0.05)


def test_calibration_recovers_the_exponent_that_made_the_corpus():
    sh = shape("pubmed", words=5000, zipf=1.2)
    csr = gen.concat(list(gen.Generator(sh, 3).block(1, 20000) for _ in [0]))
    fitted = gen.calibrate_zipf(gen.Shape.from_config(
        {**sh.__dict__, "nnz_per_doc": csr.nnz / csr.n_rows}))
    assert fitted == pytest.approx(1.2, abs=0.01)


def test_fit_corpus_seed_only_orders_documents_and_labels_words():
    sh = shape("pubmed", docs=4000)
    a, b = gen.corpus(sh, 11), gen.corpus(sh, 12)
    assert a.nnz == b.nnz and a.n_rows == b.n_rows
    assert not np.array_equal(a.cols, b.cols)

    def signature(c):                 # per-document sorted counts: label-free
        return sorted(tuple(sorted(c.values[c.row_ptr[i]:c.row_ptr[i + 1]]))
                      for i in range(c.n_rows))

    assert signature(a) == signature(b)
    fa = np.sort(np.bincount(a.cols, weights=a.values, minlength=sh.words))
    fb = np.sort(np.bincount(b.cols, weights=b.values, minlength=sh.words))
    assert np.array_equal(fa, fb)


def test_store_round_trip(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.sparse import SparseCorpus

    sh = shape("pubmed", docs=2000, words=3000)
    csr = gen.corpus(sh, 5)
    gen.write_store(csr, str(tmp_path / "s"), sh.words, block_rows=300)
    store = SparseCorpus.open(str(tmp_path / "s"))
    assert (store.n_rows, store.n_cols, store.nnz) == (2000, 3000, csr.nnz)
    dense = np.zeros((2000, 3000), np.float32)
    rows = np.repeat(np.arange(2000), np.diff(csr.row_ptr))
    dense[rows, csr.cols] = csr.values
    assert np.array_equal(store.to_dense(), dense)
