"""PR-5 ingestion pipeline: megabatch packing, async prefetch semantics,
multi-chunk grid=(C,) kernel parity, and the 1+1-pass fit economics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import SPCAConfig, fit_components
from repro.data.bow import StreamingGram, StreamingStats, SupportRemap
from repro.data.pipeline import prefetch
from repro.data import make_corpus
from repro.kernels import ops, ref
from repro.kernels.csr_gram import csr_gram_batched_pallas
from repro.kernels.csr_stats import csr_column_stats_pallas
from repro.obs import metrics
from repro.sparse import write_corpus
from repro.sparse.engine import sparse_feature_variances, sparse_stats
from repro.sparse.store import CSRMegaBatch


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    corpus = make_corpus(900, 2500, topics={"t": ["a", "b", "c"]}, seed=3)
    path = str(tmp_path_factory.mktemp("store") / "csr")
    store = write_corpus(corpus, path, shard_nnz=20_000)
    return corpus, store


# ------------------------------------------------------------- megabatches

def test_megabatch_packs_chunks_exactly(setup):
    """Megabatch slot i must equal chunk C*b + i, ragged tail padded with
    empty slots — the (C, E) arrays are just the chunk stream restacked."""
    _, store = setup
    kw = dict(chunk_nnz=1024, chunk_rows=64)
    chunks = list(store.iter_chunks(**kw))
    C = 4
    seen = 0
    for mb in store.iter_megabatches(**kw, megabatch=C, reuse_buffers=False):
        for i in range(C):
            if i < mb.n_chunks:
                ch = chunks[seen]
                np.testing.assert_array_equal(mb.values[i], ch.values)
                np.testing.assert_array_equal(mb.col_ids[i], ch.col_ids)
                np.testing.assert_array_equal(mb.seg_ids[i], ch.seg_ids)
                assert mb.n_rows[i] == ch.n_rows
                assert mb.nnz[i] == ch.nnz
                assert mb.row_offset[i] == ch.row_offset
                seen += 1
            else:            # ragged tail: empty, padding-contract clean
                assert mb.n_rows[i] == 0 and mb.nnz[i] == 0
                assert not mb.values[i].any()
                assert not mb.col_ids[i].any()
                assert not mb.seg_ids[i].any()
    assert seen == len(chunks)
    assert len(chunks) % C != 0   # the fixture really exercises a ragged tail


def test_megabatch_buffer_ring_reuse_is_safe_under_prefetch(setup):
    """With reuse_buffers, a depth-2 prefetch must still see every batch's
    own content (ring > in-flight items) — accumulate through the kernels
    and compare against the fresh-buffer path."""
    _, store = setup
    kw = dict(chunk_nnz=1024, chunk_rows=64, megabatch=3)
    acc_a = StreamingStats(store.n_cols)
    for mb in prefetch(store.iter_megabatches(**kw, ring=4), size=2):
        acc_a.update_csr_batch(mb)
    acc_b = StreamingStats(store.n_cols)
    for mb in store.iter_megabatches(**kw, reuse_buffers=False):
        acc_b.update_csr_batch(mb)
    a, b = acc_a.finalize(), acc_b.finalize()
    np.testing.assert_array_equal(np.asarray(a.variances),
                                  np.asarray(b.variances))
    assert int(a.count) == int(b.count)


def test_chunk_plan_cached_once(setup):
    _, store = setup
    p1 = store.chunk_plan(1024, 64)
    p2 = store.chunk_plan(1024, 64)
    assert all(a is b for a, b in zip(p1, p2))   # same cached arrays
    assert store.n_chunks(1024, 64) == sum(b.size - 1 for b in p1)
    assert store.n_chunks(1024, 64) == len(
        list(store.iter_chunks(chunk_nnz=1024, chunk_rows=64))
    )


# --------------------------------------------------------------- prefetch

def test_prefetch_order_matches_synchronous_iterator(setup):
    """Chunk order through the prefetch thread is deterministic and equal
    to the synchronous pass (single FIFO worker)."""
    _, store = setup
    kw = dict(chunk_nnz=1024, chunk_rows=64)
    sync = sparse_feature_variances(store, prefetch_depth=0, **kw)
    pre = sparse_feature_variances(store, prefetch_depth=2, **kw)
    np.testing.assert_array_equal(np.asarray(sync.variances),
                                  np.asarray(pre.variances))
    np.testing.assert_array_equal(np.asarray(sync.means),
                                  np.asarray(pre.means))
    assert int(sync.count) == int(pre.count)


def test_prefetch_stall_accounting_read_bound():
    """A slow producer (read-bound pass) must show up as CONSUMER stall:
    the consumer blocks on an empty queue — the blindness this PR fixes
    (before, a stalled pipeline and a saturated one looked identical)."""
    import time as _time

    stats = {}

    def slow_src():
        for i in range(5):
            _time.sleep(0.02)
            yield i

    assert list(prefetch(slow_src(), size=2, stats=stats)) == list(range(5))
    assert stats["items"] == 5
    assert stats["consumer_stall_s"] > 0.0
    assert stats.get("producer_stall_s", 0.0) < stats["consumer_stall_s"]


def test_prefetch_stall_accounting_reduce_bound():
    """A slow consumer (reduce-bound pass) must show up as PRODUCER stall:
    the worker blocks on a full queue."""
    import time as _time

    stats = {}
    out = []
    for x in prefetch(iter(range(6)), size=1, stats=stats):
        _time.sleep(0.02)
        out.append(x)
    assert out == list(range(6))
    assert stats["items"] == 6
    assert stats["producer_stall_s"] > 0.0
    assert stats.get("consumer_stall_s", 0.0) < stats["producer_stall_s"]
    # queue stayed warm: mean occupancy near the buffer size
    assert stats["occupancy_sum"] / stats["items"] > 0.5


def test_prefetch_abandonment_reaps_worker_and_closes_source():
    """A consumer that stops early (break/close) must not leave the worker
    parked on a full queue forever: the cancellation event unblocks it,
    the thread is joined, and the SOURCE generator's finally runs — so
    ring-buffered megabatch arrays/mmaps are released, not pinned."""
    import threading
    import time

    released = threading.Event()
    before = threading.active_count()

    def src():
        try:
            for i in range(1000):
                yield i
        finally:
            released.set()

    g = prefetch(src(), size=2)
    assert next(g) == 0
    g.close()                      # abandon with the queue full
    assert released.wait(timeout=5.0), "source generator never closed"
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, "worker thread leaked"


def test_prefetch_break_mid_stream_reaps_worker():
    """Same contract via a plain ``break`` (GeneratorExit at gc/scope
    exit) and via a consumer-side exception."""
    import threading
    import time

    before = threading.active_count()
    for stop in ("break", "raise"):
        try:
            for x in prefetch(iter(range(1000)), size=1):
                if x == 3:
                    if stop == "break":
                        break
                    raise RuntimeError("consumer bailed")
        except RuntimeError:
            pass
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, "worker thread leaked"


def test_prefetch_propagates_reader_exception(setup):
    """A reader-thread failure (row too wide for chunk_nnz, detected while
    building the chunk plan) must surface in the consumer, not truncate
    the stream silently."""
    _, store = setup
    with pytest.raises(ValueError, match="chunk_nnz"):
        sparse_feature_variances(store, chunk_nnz=8, chunk_rows=64,
                                 prefetch_depth=2)


# ------------------------------------------- multi-chunk kernels (grid=(C,))

@pytest.mark.parametrize("C,E,n", [
    (1, 64, 50),       # E < 128: block sizing must not overrun the chunk
    (3, 128, 130),     # exactly one lane row per chunk
    (2, 1000, 200),    # E not a multiple of 128
    (4, 4096, 300),    # multiple (8, 128) tiles per block
])
def test_multi_chunk_stats_kernel_parity(C, E, n):
    rng = np.random.default_rng(C * E + n)
    vals = rng.normal(size=(C, E)).astype(np.float32)
    cols = rng.integers(0, n, (C, E)).astype(np.int32)
    s, ss = csr_column_stats_pallas(
        jnp.asarray(vals), jnp.asarray(cols), n, interpret=True
    )
    s_r, ss_r = ref.csr_column_stats_batched_ref(
        jnp.asarray(vals), jnp.asarray(cols), n
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ss_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("C,E,R,n_hat", [
    (1, 128, 8, 7),        # tiny support, single chunk batch
    (3, 256, 16, 100),     # off-support sentinels dropped per chunk
    (4, 512, 32, 130),     # n_hat straddles a 128 tile boundary
    (2, 64, 5, 200),       # E < 128 and R not a multiple of 8
])
def test_multi_chunk_gram_kernel_parity(C, E, R, n_hat):
    rng = np.random.default_rng(C + E + R + n_hat)
    vals = rng.normal(size=(C, E)).astype(np.float32)
    cols = rng.integers(0, n_hat + 25, (C, E)).astype(np.int32)
    segs = rng.integers(0, R, (C, E)).astype(np.int32)
    G = csr_gram_batched_pallas(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat,
        interpret=True,
    )
    G_r = ref.csr_gram_batched_ref(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat
    )
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_r),
                               rtol=1e-5, atol=1e-5)
    # and the batched oracle really is the sum of per-chunk grams
    G_s = sum(
        np.asarray(ref.csr_gram_ref(
            jnp.asarray(vals[c]), jnp.asarray(cols[c]), jnp.asarray(segs[c]),
            R, n_hat,
        ), np.float64)
        for c in range(C)
    )
    np.testing.assert_allclose(np.asarray(G), G_s, rtol=1e-4, atol=1e-4)


def test_ops_padding_contract_asserted():
    """The ops wrappers enforce the `value 0` padding contract on concrete
    chunks (the satellite fix: a nonzero slot past nnz must fail loudly,
    not silently corrupt the screen)."""
    v = np.zeros((2, 64), np.float32)
    c = np.zeros((2, 64), np.int32)
    s = np.zeros((2, 64), np.int32)
    v[1, 7] = 3.0                       # slot past nnz[1] = 0
    with pytest.raises(ValueError, match="padding contract"):
        ops.csr_column_stats(v, c, n=10, nnz=np.array([64, 0]))
    with pytest.raises(ValueError, match="padding contract"):
        ops.csr_gram_batched(v, c, s, n_rows=4, n_hat=10,
                             nnz=np.array([64, 0]))
    # a clean batch passes and computes
    v[1, 7] = 0.0
    v[0, :5] = 1.0
    s_out, _ = ops.csr_column_stats(v, c, n=10, nnz=np.array([5, 0]))
    assert float(s_out[0]) == 5.0


# ------------------------------------------------------- device-side gram

def test_streaming_gram_merge_is_device_side(setup):
    """StreamingGram accumulates and merges as jnp adds — one host
    transfer at finalize — and the multi-host partial pool still matches
    the single-host pass."""
    _, store = setup
    support = np.arange(0, 40, 2)
    accs = []
    for h in range(3):
        acc = StreamingGram(support, chunk_rows=64)
        for mb in store.iter_megabatches(chunk_nnz=1024, chunk_rows=64,
                                         megabatch=4, host_id=h,
                                         num_hosts=3):
            acc.update_csr_batch(mb)
        assert isinstance(acc.g, jax.Array)
        accs.append(acc)
    pooled = accs[0]
    for other in accs[1:]:
        pooled.merge(other)
    assert isinstance(pooled.g, jax.Array)
    one = StreamingGram(support, chunk_rows=64)
    for ch in store.iter_chunks(chunk_nnz=1024, chunk_rows=64):
        one.update_csr(ch)
    np.testing.assert_allclose(pooled.finalize(), one.finalize(),
                               rtol=1e-10, atol=1e-12)
    assert pooled.count == one.count


def test_streaming_gram_f32_accumulation_is_compensated():
    """With an f32 accumulator (the x64-off production config) the
    Neumaier compensation must keep the fold exact where a plain f32
    running sum loses every small addend."""
    support = np.arange(3)
    acc = StreamingGram(support, acc_dtype=np.float32)
    big = np.full((3, 3), 1e8, np.float32)
    small = np.full((3, 3), 1.0, np.float32)
    acc._acc(big)
    for _ in range(1000):
        acc._acc(small)         # each add is below f32 resolution of 1e8
    acc.count = 1
    got = acc.finalize()
    np.testing.assert_allclose(got, 1e8 + 1000.0, rtol=1e-9)
    # plain f32 (what the uncompensated sum would give) is exactly 1e8
    assert float(np.asarray(acc.g)[0, 0]) == 1e8


def test_streaming_gram_f32_merge_keeps_compensation():
    support = np.arange(2)
    parts = []
    for h in range(3):
        a = StreamingGram(support, acc_dtype=np.float32)
        a._acc(np.full((2, 2), 1e8 if h == 0 else 0.0, np.float32))
        for _ in range(500):
            a._acc(np.full((2, 2), 1.0, np.float32))
        a.count = 1 if h == 0 else 0
        parts.append(a)
    pooled = parts[0]
    for other in parts[1:]:
        pooled.merge(other)
    np.testing.assert_allclose(pooled.finalize(), 1e8 + 1500.0, rtol=1e-9)


# ---------------------------------------------------------- support remap

def _searchsorted_cols(support, col_ids):
    """The remap before `SupportRemap`: one searchsorted of every entry
    into the sorted support, kept as the table's oracle."""
    support = np.asarray(support)
    k = support.size
    pos = np.searchsorted(support, col_ids)
    pos_c = np.minimum(pos, max(k - 1, 0))
    return np.where(support[pos_c] == col_ids, pos_c, k).astype(np.int32)


_N_WORDS = 2500
_SUPPORTS = {
    "empty": np.array([], np.int64),
    "one": np.array([7]),
    "one_word0": np.array([0]),
    "220": np.sort(np.random.default_rng(1).choice(
        np.arange(1, _N_WORDS), 220, replace=False)),
    "220_word0": np.sort(np.concatenate([[0], np.random.default_rng(2).choice(
        np.arange(1, _N_WORDS), 219, replace=False)])),
}


@pytest.mark.parametrize("shape", [(1024,), (4, 256), (2, 3, 128)],
                         ids=["chunk", "megabatch", "superbatch"])
@pytest.mark.parametrize("kind", list(_SUPPORTS))
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_support_remap_matches_searchsorted(kind, shape, seed):
    """The table gather equals the searchsorted remap for every int32 id:
    negative ids, 0, the last support word and the one past it, ids at
    and past ``n``; an empty support sends everything to the sentinel 0."""
    support = _SUPPORTS[kind]
    rng = np.random.default_rng(seed)
    ids = np.where(rng.random(int(np.prod(shape))) < 0.5,
                   rng.zipf(1.2, int(np.prod(shape))) - 1,
                   rng.integers(-2**31, 2**31, int(np.prod(shape))))
    top = int(support[-1]) if support.size else 0
    edges = [-2**31, -1, 0, 1, top, top + 1, _N_WORDS, _N_WORDS + 1,
             2**31 - 1]
    ids[:len(edges)] = edges
    ids = np.clip(ids, -2**31, 2**31 - 1).astype(np.int32).reshape(shape)
    got = SupportRemap(support).cols(ids)
    want = (_searchsorted_cols(support, ids) if support.size
            else np.zeros(shape, np.int32))
    assert got.dtype == np.int32 and got.shape == shape
    np.testing.assert_array_equal(got, want)


def _oracle_cols(self, col_ids):
    """`SupportRemap.cols` through the oracle (the table's finite entries
    are the sorted support)."""
    return _searchsorted_cols(np.flatnonzero(self.table < self.n_hat),
                              col_ids)


@pytest.mark.parametrize("leg", ["batch", "chunk"])
@pytest.mark.parametrize("word0", [False, True])
def test_streaming_gram_table_remap_matches_oracle_cols(setup, monkeypatch,
                                                        leg, word0):
    """Both Gram legs hand the kernel the same local columns as the
    searchsorted remap did, so ``g`` is equal bit for bit."""
    _, store = setup
    support = np.arange(0 if word0 else 1, 80, 3)
    kw = dict(chunk_nnz=1024, chunk_rows=64)

    def run():
        acc = StreamingGram(support, chunk_rows=64)
        if leg == "batch":
            for mb in store.iter_megabatches(**kw, megabatch=4):
                acc.update_csr_batch(mb)
        else:
            for ch in store.iter_chunks(**kw):
                acc.update_csr(ch)
        return np.asarray(acc.g), acc.count

    g_table, n_table = run()
    monkeypatch.setattr(SupportRemap, "cols", _oracle_cols)
    g_oracle, n_oracle = run()
    assert np.any(g_table != 0)
    np.testing.assert_array_equal(g_table, g_oracle)
    assert n_table == n_oracle


@pytest.mark.parametrize("word0", [False, True])
def test_gram_remap_counters_count_live_and_support_entries(setup, word0):
    """``ingest.gram_entries`` counts live entries and
    ``ingest.gram_support_entries`` those on the support — padded slots
    (col 0) count in neither, even when word 0 is on the support."""
    support = np.array([0, 3, 5]) if word0 else np.array([3, 5])
    col = np.zeros((3, 8), np.int32)         # slot 2 is an empty tail slot
    col[0, :5] = [0, 3, 4, 5, 9]             # 3 padded slots after them
    col[1, :8] = [3, 3, 7, 0, 5, 2, 1, 6]
    nnz = np.array([5, 8, 0])
    vals = (col != 0).astype(np.float32)
    vals[0, 0] = vals[1, 3] = 1.0            # the live word-0 entries
    mb = CSRMegaBatch(values=vals, col_ids=col, seg_ids=np.zeros_like(col),
                      row_offset=np.zeros(3, np.int64),
                      n_rows=np.array([1, 1, 0], np.int32), nnz=nnz,
                      n_chunks=2)
    with metrics.use_registry() as reg:
        StreamingGram(support, chunk_rows=64).update_csr_batch(mb)
        assert reg.value("ingest.gram_entries") == 13
        on = 2 + 3 + 2 * word0               # 3,5 | 3,3,5 | the two 0s
        assert reg.value("ingest.gram_support_entries") == on
    # a whole pass counts every stored entry, whatever the geometry
    _, store = setup
    with metrics.use_registry() as reg:
        acc = StreamingGram(support, chunk_rows=64)
        for b in store.iter_megabatches(chunk_nnz=1024, chunk_rows=64,
                                        megabatch=4):
            acc.update_csr_batch(b)
        assert reg.value("ingest.gram_entries") == store.nnz
        assert 0 < reg.value("ingest.gram_support_entries") < store.nnz


# ------------------------------------------------------- pass economics

def test_fit_components_streaming_is_two_passes(setup):
    """The PR-5 acceptance counter: a 3-component streaming fit makes
    exactly 2 corpus passes (screen + ONE shared Gram on the union
    support) with one ingest dispatch per pass-megabatch."""
    _, store = setup
    cfg = SPCAConfig(max_sweeps=6, lam_search_evals=5,
                     chunk_nnz=1024, chunk_rows=64, megabatch_chunks=4)
    diag = {}
    rs = fit_components(store, 3, target_card=4, cfg=cfg, diagnostics=diag)
    assert len(rs) == 3
    assert diag["corpus_passes"] == 2
    assert diag["cov_builds"] == 1          # ONE Gram pass serves all K
    n_chunks = store.n_chunks(1024, 64)
    per_pass = -(-n_chunks // 4)            # one launch per megabatch
    assert diag["ingest"]["screen_launches"] == per_pass
    assert diag["ingest"]["gram_launches"] == per_pass
    assert diag["ingest"]["chunks"] == 2 * n_chunks
    # stall accounting rides along on every prefetched pass (>= 0; which
    # side stalls depends on machine load, presence is the contract)
    assert diag["ingest"]["prefetch_consumer_stall_s"] >= 0.0
    assert diag["ingest"]["prefetch_producer_stall_s"] >= 0.0
    # deflated components stay disjoint (paper-style word sets)
    sup = [set(r.support.tolist()) for r in rs]
    assert not (sup[0] & sup[1]) and not (sup[0] & sup[2])


def test_sparse_stats_counters_tally_build_passes(setup):
    _, store = setup
    counters = {}
    var, build = sparse_stats(store, chunk_nnz=1024, chunk_rows=64,
                              megabatch=4, counters=counters)
    assert counters["screen_passes"] == 1 and "gram_passes" not in counters
    build(np.argsort(var)[::-1][:8])
    build(np.argsort(var)[::-1][:4])
    assert counters["gram_passes"] == 2
