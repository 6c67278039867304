"""Seeded bag-of-words generator at a published corpus shape.

The idea is that of ``repro.data.corpus.make_corpus`` (Zipf word
frequencies plus planted topics whose words co-occur in a slice of the
documents), rebuilt so that a 70M-entry corpus is made in seconds and
never held as one COO array:

* a document is a multinomial bag: ``T_d`` tokens drawn from its group's
  word distribution, counted per distinct word;
* ``T_d`` is heavy-tailed (log-normal) with the published mean of tokens
  per document, and the Zipf exponent is the one at which the mean number
  of DISTINCT words per document is the published nnz/doc
  (``calibrate_zipf``);
* word ranks map to word ids through a permutation drawn from the seed, so
  frequency is not ordered by column id (as in an alphabetical vocabulary);
* documents are made shard by shard (``shard_docs`` rows), each shard from
  its own stream of the seed, on a thread pool, and handed back as CSR.

Everything is a function of (shape, seed): the same seed gives the same
corpus.  A fit cell makes its documents from the configuration's
``corpus_seed`` and lets the run's seed order the documents and label the
words (``corpus``), so that every seed gives the fit the same documents,
passes and sizes; the order changes float32 rounding, and with it the
path the lambda searches take.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Shape:
    docs: int
    words: int
    nnz_per_doc: float
    tokens_per_doc: float
    zipf: float
    length_sigma: float
    length_cap: int
    topics: dict
    topic_boost: float
    topic_doc_frac: float
    topic_rank: int
    topic_stride: int
    shard_docs: int
    corpus_seed: int = 0

    @classmethod
    def from_config(cls, corpus: dict, **override) -> "Shape":
        kw = {f: corpus[f] for f in cls.__dataclass_fields__}
        kw.update(override)
        return cls(**kw)


@dataclass
class CSR:
    """Row block: ``values`` f32 counts, ``cols`` i32 word ids, ``row_ptr``."""

    values: np.ndarray
    cols: np.ndarray
    row_ptr: np.ndarray

    @property
    def n_rows(self) -> int:
        return int(self.row_ptr.size - 1)

    @property
    def nnz(self) -> int:
        return int(self.values.size)


def concat(blocks: list[CSR]) -> CSR:
    lens = np.concatenate([np.diff(b.row_ptr) for b in blocks])
    row_ptr = np.zeros(lens.size + 1, np.int64)
    np.cumsum(lens, out=row_ptr[1:])
    return CSR(np.concatenate([b.values for b in blocks]),
               np.concatenate([b.cols for b in blocks]), row_ptr)


def zipf_probs(words: int, alpha: float) -> np.ndarray:
    """Zipf word distribution by rank: p_i ~ i^-alpha."""
    p = 1.0 / np.arange(1, words + 1, dtype=np.float64) ** alpha
    return p / p.sum()


def topic_ranks(shape: Shape) -> dict[str, np.ndarray]:
    """Rank of every planted topic word: ``topic_rank`` onwards, one every
    ``topic_stride`` ranks, topics in file order (as ``make_corpus``)."""
    out, rank = {}, shape.topic_rank
    for name, words in shape.topics.items():
        out[name] = rank + shape.topic_stride * np.arange(len(words))
        rank += shape.topic_stride * len(words)
    return out


def group_probs(shape: Shape, alpha: float | None = None) -> list[np.ndarray]:
    """Word distribution by rank of group 0 (background) and of each topic
    group: a topic's words keep the frequency of their ranks and are
    boosted ``topic_boost`` times in their own group's documents."""
    base = zipf_probs(shape.words, shape.zipf if alpha is None else alpha)
    ranks = topic_ranks(shape)
    groups = [base]
    for r in ranks.values():
        g = base.copy()
        g[r] *= shape.topic_boost
        groups.append(g / g.sum())
    return groups


_QUANTILES = 2048


def _lognormal_quantiles(sigma: float) -> np.ndarray:
    """Deterministic stand-in for the unit-median log-normal: its values at
    the midpoints of ``_QUANTILES`` equal-probability bins."""
    from statistics import NormalDist

    nd = NormalDist()
    q = (np.arange(_QUANTILES) + 0.5) / _QUANTILES
    return np.exp(sigma * np.array([nd.inv_cdf(x) for x in q]))


def token_lengths(shape: Shape) -> tuple[float, np.ndarray]:
    """Median tokens per document giving the published mean of
    ``tokens_per_doc``, and the quantile grid of the length distribution."""
    q = _lognormal_quantiles(shape.length_sigma)
    lo, hi = 1.0, float(shape.length_cap)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        t = np.clip(np.round(mid * q), 1, shape.length_cap)
        lo, hi = (mid, hi) if t.mean() < shape.tokens_per_doc else (lo, mid)
    scale = 0.5 * (lo + hi)
    return scale, np.clip(np.round(scale * q), 1, shape.length_cap)


def calibrate_zipf(shape: Shape) -> float:
    """The Zipf exponent at which documents of the published token count
    hold the published mean of DISTINCT words (the configurations store
    the result as ``zipf``).  A T-token multinomial bag holds
    E[D | T] = sum_i 1 - (1 - p_i)^T distinct words, averaged here over the
    document groups in their proportions and over the length law."""
    _, lengths = token_lengths(shape)
    t_grid = np.unique(np.round(np.geomspace(1, shape.length_cap, 64)))
    n_groups = len(shape.topics) + 1
    weights = np.full(n_groups, shape.topic_doc_frac)
    weights[0] = 1.0 - shape.topic_doc_frac * (n_groups - 1)

    def mean_distinct(alpha: float) -> float:
        groups = group_probs(shape, alpha)
        d_grid = sum(w * np.array([-np.expm1(t * np.log1p(-p)).sum()
                                   for t in t_grid])
                     for w, p in zip(weights, groups))
        return float(np.interp(lengths, t_grid, d_grid).mean())

    lo, hi = 0.5, 2.0          # a steeper law holds fewer distinct words
    for _ in range(24):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean_distinct(mid) > shape.nnz_per_doc else (lo, mid)
    return 0.5 * (lo + hi)


class Generator:
    """Documents of one shape from one seed."""

    def __init__(self, shape: Shape, seed: int):
        self.shape = shape
        self.seed = int(seed)
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0x5EED]))
        # rank -> word id: a seeded relabelling of the vocabulary
        self.word_of_rank = rng.permutation(shape.words).astype(np.int32)
        self.scale, _ = token_lengths(shape)
        self.cdfs = [np.cumsum(p) for p in group_probs(shape)]
        for c in self.cdfs:
            c[-1] = 1.0

    # -------------------------------------------------------------- docs
    def block(self, stream: int, n_docs: int) -> CSR:
        """``n_docs`` documents from sub-stream ``stream`` of the seed."""
        sh = self.shape
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, stream]))
        n_groups = len(self.cdfs) - 1
        u = rng.random(n_docs)
        group = np.where(u < sh.topic_doc_frac * n_groups,
                         1 + np.minimum((u / sh.topic_doc_frac).astype(np.int64),
                                        n_groups - 1), 0)
        z = rng.standard_normal(n_docs)
        tokens = np.clip(np.round(self.scale * np.exp(sh.length_sigma * z)),
                         1, sh.length_cap).astype(np.int64)
        doc_of_tok = np.repeat(np.arange(n_docs, dtype=np.int64), tokens)
        grp_of_tok = group[doc_of_tok]
        draws = rng.random(doc_of_tok.size)
        rank = np.empty(doc_of_tok.size, np.int64)
        for g, cdf in enumerate(self.cdfs):
            sel = grp_of_tok == g
            rank[sel] = np.searchsorted(cdf, draws[sel], side="right")
        np.minimum(rank, sh.words - 1, out=rank)
        key = doc_of_tok * sh.words + self.word_of_rank[rank]
        key, counts = np.unique(key, return_counts=True)
        doc = key // sh.words
        row_ptr = np.zeros(n_docs + 1, np.int64)
        np.cumsum(np.bincount(doc, minlength=n_docs), out=row_ptr[1:])
        return CSR(counts.astype(np.float32),
                   (key - doc * sh.words).astype(np.int32), row_ptr)

    def shards(self, n_docs: int | None = None, *, stream0: int = 1,
               threads: int | None = None):
        """Yield the corpus shard by shard, in order, made on a pool."""
        n_docs = self.shape.docs if n_docs is None else int(n_docs)
        step = self.shape.shard_docs
        sizes = [min(step, n_docs - lo) for lo in range(0, n_docs, step)]
        threads = threads or min(8, os.cpu_count() or 1)
        with cf.ThreadPoolExecutor(threads) as pool:
            futs = [pool.submit(self.block, stream0 + i, n)
                    for i, n in enumerate(sizes)]
            for f in futs:
                yield f.result()


def permuted(csr: CSR, seed: int, words: int) -> CSR:
    """The same documents in another order, under another labelling of the
    vocabulary, both drawn from ``seed``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x9E27]))
    relabel = rng.permutation(words).astype(np.int32)
    order = rng.permutation(csr.n_rows)
    lens = np.diff(csr.row_ptr)[order]
    row_ptr = np.zeros(order.size + 1, np.int64)
    np.cumsum(lens, out=row_ptr[1:])
    src = (np.repeat(csr.row_ptr[:-1][order] - row_ptr[:-1], lens)
           + np.arange(row_ptr[-1]))
    return CSR(csr.values[src], relabel[csr.cols[src]], row_ptr)


def corpus(shape: Shape, seed: int) -> CSR:
    """A fit cell's corpus: the documents are made from the configuration's
    ``corpus_seed``, and the run's ``seed`` only orders the documents and
    labels the words: every seed gives the fit the same documents."""
    base = concat(list(Generator(shape, shape.corpus_seed).shards()))
    return permuted(base, seed, shape.words)


def write_store(csr: CSR, path: str, n_cols: int, *,
                block_rows: int = 65_536) -> float:
    """Write ``csr`` through the program's store writer in row blocks;
    returns the seconds it took."""
    import time

    from repro.sparse import CSRStoreWriter

    t0 = time.perf_counter()
    writer = CSRStoreWriter(path, n_cols)
    for lo in range(0, csr.n_rows, block_rows):
        hi = min(lo + block_rows, csr.n_rows)
        a, b = csr.row_ptr[lo], csr.row_ptr[hi]
        writer.append_csr(csr.values[a:b], csr.cols[a:b],
                          csr.row_ptr[lo:hi + 1] - a)
    writer.finish()
    return time.perf_counter() - t0
