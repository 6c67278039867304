"""Online serving subsystem: projector/kernel equivalence, registry
hot-swap under concurrent lookups, batcher shape stability, drift trigger."""
import tempfile
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.elimination import Screen, feature_variances
from repro.core.spca import PCResult
from repro.data.corpus import make_corpus
from repro.data.pipeline import prefetch
from repro.kernels import ops, ref
from repro.obs import metrics
from repro.serve import (
    BatcherConfig, DriftMonitor, MicroBatcher, ModelRegistry, TopicProjector,
    pack_components,
)
from repro.serve.batcher import SparseBatch


def _fake_components(n, k, card, seed=0, lam=1.0):
    rng = np.random.default_rng(seed)
    results = []
    used = rng.permutation(n)
    for c in range(k):
        sup = np.sort(used[c * card:(c + 1) * card])
        x = np.zeros(n)
        x[sup] = rng.normal(size=card)
        x /= np.linalg.norm(x)
        results.append(PCResult(
            x=x, support=sup, lam=lam + 0.1 * c, variance=1.0,
            cardinality=card, reduced_n=card, gap=0.0,
        ))
    return results


# --------------------------------------------------------------- projector
@pytest.mark.parametrize("B,n,k,card", [
    (16, 200, 3, 5), (100, 1000, 5, 7), (8, 300, 1, 3), (130, 513, 4, 9),
])
def test_projector_kernel_matches_dense_reference(B, n, k, card):
    """Pallas gather kernel (interpret) == gather oracle == dense matmul."""
    rng = np.random.default_rng(B * n)
    pack = pack_components(_fake_components(n, k, card, seed=n), n_features=n)
    X = jnp.asarray(rng.poisson(0.5, size=(B, n)).astype(np.float32))

    # Fully dense ground truth: scatter loadings into W (n, k), X @ W.
    W = np.zeros((n, k), np.float32)
    for c in range(k):
        W[pack.support_idx[c], c] += pack.values[c]
    dense = np.asarray(X) @ W

    oracle = ref.sparse_project_ref(
        X, jnp.asarray(pack.support_idx), jnp.asarray(pack.values))
    np.testing.assert_allclose(oracle, dense, rtol=1e-5, atol=1e-5)

    # impl='pallas' off-TPU runs the gather kernel in interpret mode.
    out = ops.sparse_project(
        X, jnp.asarray(pack.support_idx), jnp.asarray(pack.values),
        impl="pallas",
    )
    np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-5, atol=1e-5)


def test_projector_sparse_doc_path_matches_dense():
    n, k = 400, 3
    pack = pack_components(_fake_components(n, k, 6), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    rng = np.random.default_rng(0)
    X = rng.poisson(0.4, size=(12, n)).astype(np.float32)
    docs = [(np.flatnonzero(x), x[np.flatnonzero(x)]) for x in X]
    np.testing.assert_allclose(
        proj.project_docs(docs), np.asarray(proj.project(X)),
        rtol=1e-5, atol=1e-5,
    )


def test_projector_sparse_doc_path_with_overlapping_supports():
    """'project' (Hotelling) deflation can give overlapping supports: a
    shared word must contribute to EVERY component that loads on it."""
    n, card = 100, 4
    rng = np.random.default_rng(5)
    shared = np.array([7, 42])
    results = []
    for c in range(3):
        extra = 50 + c * card + np.arange(card - shared.size)
        sup = np.sort(np.concatenate([shared, extra]))
        x = np.zeros(n)
        x[sup] = rng.normal(size=card)
        results.append(PCResult(x=x, support=sup, lam=1.0, variance=1.0,
                                cardinality=card, reduced_n=card, gap=0.0))
    proj = TopicProjector(pack_components(results, n_features=n), impl="ref")
    X = rng.poisson(1.0, size=(10, n)).astype(np.float32)
    X[:, shared] += 3.0  # make the shared words matter
    docs = [(np.flatnonzero(x), x[np.flatnonzero(x)]) for x in X]
    np.testing.assert_allclose(
        proj.project_docs(docs), np.asarray(proj.project(X)),
        rtol=1e-5, atol=1e-5,
    )


def _sparse_batch(docs, rows, n):
    """The SparseBatch the collector builds from (word_ids, counts) docs."""
    sizes = [len(w) for w, _ in docs]
    return SparseBatch(
        rows=rows, n=n,
        row_ids=np.repeat(np.arange(len(docs), dtype=np.int32), sizes),
        word_ids=np.concatenate([np.asarray(w, np.int64) for w, _ in docs]),
        counts=np.concatenate([np.asarray(c, np.float32) for _, c in docs]),
        live=len(docs))


def _dense_rows(docs, rows, n):
    """The dense batch as the collector used to build it, row by row."""
    X = np.zeros((rows, n), np.float32)
    for i, (w, c) in enumerate(docs):
        np.add.at(X[i], np.asarray(w, np.int64), np.asarray(c, np.float32))
    return X


def _overlapping_components(n, card=4, seed=5):
    rng = np.random.default_rng(seed)
    shared = np.array([7, 42])
    results = []
    for c in range(3):
        extra = 50 + c * card + np.arange(card - shared.size)
        sup = np.sort(np.concatenate([shared, extra]))
        x = np.zeros(n)
        x[sup] = rng.normal(size=card)
        results.append(PCResult(x=x, support=sup, lam=1.0, variance=1.0,
                                cardinality=card, reduced_n=card, gap=0.0))
    return results


@pytest.mark.parametrize("case", [
    "disjoint", "disjoint_pallas", "overlapping", "no_support_word",
    "repeated_ids", "wide_support", "partial_rows",
])
def test_projector_sparse_batch_matches_dense(case):
    """project(SparseBatch) folds entries into the support columns; its
    scores equal project(dense) bit for bit: same products, same slot
    order."""
    n, rows = 600, 16
    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "overlapping":
        results = _overlapping_components(n)
    elif case == "wide_support":             # 150 support words: 2 x 128
        results = _fake_components(n, 30, 5, seed=3)
    else:
        results = _fake_components(n, 4, 5, seed=1)
    pack = pack_components(results, n_features=n)
    proj = TopicProjector(pack, impl="pallas" if case.endswith("pallas")
                          else "ref")
    support = np.unique(pack.support_idx[pack.values != 0])
    off = np.setdiff1d(np.arange(n), support)
    live = 5 if case == "partial_rows" else rows
    docs = []
    for d in range(live):
        if case == "no_support_word" and d % 2:
            w = rng.choice(off, size=30, replace=False)
        else:
            w = np.concatenate([rng.choice(off, size=30, replace=False),
                                rng.choice(support, size=4, replace=False)])
            if case == "repeated_ids":
                w = np.concatenate([w, w[-3:], w[:2]])
            rng.shuffle(w)
        docs.append((w, rng.integers(1, 5, size=w.size).astype(np.float32)))
    X = _dense_rows(docs, rows, n)
    batch = _sparse_batch(docs, rows, n)
    np.testing.assert_array_equal(batch.dense(), X)
    got = np.asarray(proj.project(batch))
    want = np.asarray(proj.project(X))
    assert got.shape == (rows, pack.k)
    np.testing.assert_array_equal(got, want)
    assert want[: live].any() and not got[live:].any()
    if case == "no_support_word":
        assert not got[1:live:2].any()
    assert proj._ncols == (256 if case == "wide_support" else 128)


def test_pack_components_shape_stable_across_cardinality_wobble():
    n = 300
    p1 = pack_components(_fake_components(n, 3, 5), n_features=n)
    p2 = pack_components(_fake_components(n, 3, 7, seed=1), n_features=n)
    assert p1.cap == p2.cap == 8  # both round up to the same padded cap


# ---------------------------------------------------------------- registry
def test_registry_persist_and_reload():
    n = 250
    res = _fake_components(n, 2, 4)
    screen = Screen(variances=jnp.ones(n), means=jnp.zeros(n),
                    count=jnp.asarray(100))
    with tempfile.TemporaryDirectory() as d:
        reg = ModelRegistry(d, impl="ref")
        mv = reg.register(res, screen, n_features=n,
                          meta={"corpus": "unit", "note": 7})
        assert mv.version == 0
        mv2 = reg.register(res, screen, n_features=n)
        assert mv2.version == 1
        assert reg.active().version == 1
        reg.rollback(0)
        assert reg.active().version == 0

        fresh = ModelRegistry(d, impl="ref")
        assert fresh.load_all() == [0, 1]
        assert fresh.active().version == 1
        np.testing.assert_array_equal(
            fresh.get(0).pack.support_idx, mv.pack.support_idx)
        np.testing.assert_allclose(
            fresh.get(0).pack.values, mv.pack.values, rtol=1e-6)
        assert fresh.get(0).lam == pytest.approx(mv.lam)
        np.testing.assert_allclose(fresh.get(0).lams, mv.lams)
        assert fresh.get(0).meta == {"corpus": "unit", "note": 7}


def test_registry_hot_swap_under_concurrent_lookups():
    """Readers hammering active() during swaps must always see a complete,
    internally consistent version (pack matches projector), never a torn
    or missing one."""
    n = 200
    screen = Screen(variances=jnp.ones(n), means=jnp.zeros(n),
                    count=jnp.asarray(10))
    reg = ModelRegistry(None, impl="ref")
    reg.register(_fake_components(n, 2, 4, seed=0), screen, n_features=n)

    stop = threading.Event()
    errors: list[Exception] = []

    def reader():
        X = np.ones((4, n), np.float32)
        try:
            while not stop.is_set():
                mv = reg.active()
                # internal consistency: projector serves ITS OWN pack
                s = np.asarray(mv.projector.project(X))
                assert s.shape == (4, mv.pack.k)
                assert mv.pack.values is mv.projector.pack.values
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for v in range(1, 6):
        reg.register(_fake_components(n, 2 + v % 2, 4, seed=v), screen,
                     n_features=n, persist=False)
        time.sleep(0.02)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    assert reg.active().version == 5
    assert reg.versions() == [0, 1, 2, 3, 4, 5]


# ----------------------------------------------------------------- batcher
def test_batcher_shape_stability_across_ragged_requests():
    """Ragged request sizes must never retrace the jitted projector: the
    batcher always presents the one padded (max_batch, n) shape."""
    n = 300
    pack = pack_components(_fake_components(n, 3, 5), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    rng = np.random.default_rng(1)
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=8, max_wait_ms=1.0))
    with mb:
        futs = []
        for sz in rng.integers(1, 60, size=100):  # ragged doc lengths
            wi = rng.choice(n, size=sz, replace=False)
            futs.append(mb.submit(wi, np.ones(sz, np.float32)))
        scores = [f.result(timeout=30) for f in futs]
    assert proj.trace_count == 1, "projector retraced on ragged traffic"
    assert all(s.shape == (3,) for s in scores)
    assert mb.batches_served >= 100 // 8
    snap = mb.stats.snapshot()
    assert snap["count"] == 100
    assert snap["p99_ms"] >= snap["p50_ms"] >= 0.0


def _queued_docs(mb, n, count, seed):
    """Submit ``count`` ragged docs to a not-yet-started batcher (so the
    batches it forms are the queue in order, ``max_batch`` at a time), one
    with repeated word ids and one malformed; return the well-formed
    docs in submit order."""
    rng = np.random.default_rng(seed)
    docs = []
    for i in range(count):
        w = rng.choice(n, size=int(rng.integers(1, 40)), replace=False)
        if i == 3:
            w = np.concatenate([w, w[:2]])
        c = rng.integers(1, 4, size=w.size).astype(np.float32)
        docs.append((w, c))
    futs = [mb.submit(w, c) for w, c in docs]
    bad = mb.submit([n + 1], [1.0])          # fails alone, takes no row
    return docs, futs, bad


def test_batcher_hands_projector_a_sparse_batch():
    """The projector receives SparseBatch entries of max_batch rows: no
    (max_batch, n) buffer is built anywhere on the way."""
    n, k, max_batch = 500, 3, 8

    class Recorder:
        def __init__(self):
            self.batches = []

        def project(self, X):
            self.batches.append(X)
            return np.zeros((X.rows, k), np.float32)

    rec = Recorder()
    mb = MicroBatcher(rec, n, BatcherConfig(max_batch=max_batch,
                                            max_wait_ms=1.0))
    with metrics.use_registry() as reg:
        docs, futs, bad = _queued_docs(mb, n, 20, seed=4)
        with mb:
            for f in futs:
                assert f.result(timeout=30).shape == (k,)
            with pytest.raises(IndexError):
                bad.result(timeout=30)
        assert reg.value("serve.dense_batches", 0) == 0
    warm, served = rec.batches[0], rec.batches[1:]
    assert isinstance(warm, SparseBatch) and warm.live == 0
    assert warm.rows == max_batch and warm.word_ids.size == 0
    assert [b.live for b in served] == [8, 8, 4]
    for i, b in enumerate(served):
        assert isinstance(b, SparseBatch)
        assert (b.rows, b.n) == (max_batch, n)
        chunk = docs[i * max_batch:(i + 1) * max_batch]
        nnz = sum(w.size for w, _ in chunk)
        for a in (b.row_ids, b.word_ids, b.counts):
            assert a.shape == (nnz,)
        np.testing.assert_array_equal(
            b.dense(), _dense_rows(chunk, max_batch, n))


def test_batcher_densifies_for_array_only_projector():
    """A projector that takes arrays gets the same zero-padded
    (max_batch, n) float32 matrix as before, and serve.dense_batches
    counts each densification (the warm-up's included)."""
    n, max_batch = 300, 4
    W = np.random.default_rng(0).normal(size=(n, 2)).astype(np.float32)

    class ArrayProjector:
        def __init__(self):
            self.seen = []

        def project(self, X):
            X = np.asarray(X)
            self.seen.append(X)
            return X @ W

    ap = ArrayProjector()
    mb = MicroBatcher(ap, n, BatcherConfig(max_batch=max_batch,
                                           max_wait_ms=1.0))
    with metrics.use_registry() as reg:
        docs, futs, _ = _queued_docs(mb, n, 10, seed=6)
        with mb:
            got = np.stack([f.result(timeout=30) for f in futs])
        assert reg.value("serve.dense_batches") == len(ap.seen) == 4
    assert all(X.shape == (max_batch, n) and X.dtype == np.float32
               for X in ap.seen)
    assert not ap.seen[0].any()                      # the warm-up
    for i, X in enumerate(ap.seen[1:]):
        np.testing.assert_array_equal(
            X, _dense_rows(docs[i * max_batch:(i + 1) * max_batch],
                           max_batch, n))
    want = [(_dense_rows(docs[i:i + max_batch], max_batch, n) @ W)
            [:len(docs[i:i + max_batch])] for i in range(0, 10, max_batch)]
    np.testing.assert_array_equal(got, np.concatenate(want))


def test_batcher_observer_receives_dense_live_rows():
    n = 250
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    seen = []
    mb = MicroBatcher(TopicProjector(pack, impl="ref"), n,
                      BatcherConfig(max_batch=4, max_wait_ms=1.0),
                      observer=seen.append)
    docs, futs, _ = _queued_docs(mb, n, 10, seed=8)
    with mb:
        for f in futs:
            f.result(timeout=30)
    assert [X.shape for X in seen] == [(4, n), (4, n), (2, n)]
    np.testing.assert_array_equal(np.concatenate(seen),
                                  _dense_rows(docs, len(docs), n))


def test_batcher_serves_topic_projector_from_support_columns():
    """Every batch a TopicProjector serves takes the compact path (no
    densification), with one trace under ragged traffic, and the entry
    counters see every entry."""
    n = 400
    pack = pack_components(_fake_components(n, 3, 5), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=8, max_wait_ms=1.0))
    docs, futs, _ = _queued_docs(mb, n, 30, seed=9)
    with metrics.use_registry() as reg:
        mb.start()
        try:
            got = np.stack([f.result(timeout=30) for f in futs])
        finally:
            mb.stop()
        batches = reg.value("serve.batches")
        assert batches == 4
        assert reg.value("serve.compact_batches") == batches + 1  # warm-up
        assert reg.value("serve.dense_batches", 0) == 0
        assert reg.value("serve.batch_entries") == sum(w.size
                                                       for w, _ in docs)
        support = set(pack.support_idx[pack.values != 0].tolist())
        assert reg.value("serve.support_entries") == sum(
            int(np.isin(w, list(support)).sum()) for w, _ in docs)
    assert proj.trace_count == 1
    want = [np.asarray(proj.project(_dense_rows(docs[i:i + 8], 8, n)))
            [:len(docs[i:i + 8])] for i in range(0, 30, 8)]
    np.testing.assert_array_equal(got, np.concatenate(want))


def test_batcher_scores_match_direct_projection():
    n = 150
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    rng = np.random.default_rng(2)
    X = rng.poisson(0.5, size=(20, n)).astype(np.float32)
    direct = np.asarray(proj.project(X))
    with MicroBatcher(proj, n, BatcherConfig(max_batch=4)) as mb:
        futs = [mb.submit(np.flatnonzero(x), x[np.flatnonzero(x)]) for x in X]
        got = np.stack([f.result(timeout=30) for f in futs])
    np.testing.assert_allclose(got, direct, rtol=1e-5, atol=1e-5)


def test_batcher_propagates_projection_errors_to_futures():
    class Boom:
        def project(self, X):
            raise RuntimeError("kernel exploded")

    mb = MicroBatcher(Boom(), 50, BatcherConfig(max_batch=2, max_wait_ms=0.5))
    mb._thread = threading.Thread(target=mb._serve_loop, daemon=True)
    mb._thread.start()  # bypass start()'s warm-up (it would raise here)
    f = mb.submit([1, 2], [1.0, 1.0])
    with pytest.raises(RuntimeError, match="kernel exploded"):
        f.result(timeout=30)
    mb.stop()


def test_batcher_survives_malformed_request():
    """An out-of-range word id fails ITS request's future; the serve loop
    keeps running and later requests still resolve."""
    n = 120
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    with MicroBatcher(proj, n, BatcherConfig(max_batch=4,
                                             max_wait_ms=0.5)) as mb:
        bad = mb.submit([n + 5], [1.0])       # word id beyond the vocab
        with pytest.raises(IndexError):
            bad.result(timeout=30)
        neg = mb.submit([-1], [1.0])          # would alias to column n-1
        with pytest.raises(IndexError):
            neg.result(timeout=30)
        good = mb.submit([3, 4], [1.0, 2.0])
        assert good.result(timeout=30).shape == (2,)


def test_batcher_sheds_over_capacity_submits():
    """Submits past cfg.max_queue fail fast with RequestShed instead of
    growing an unbounded backlog; the shed tally lands in snapshot() and
    the serve.shed registry counter."""
    from repro.obs import metrics
    from repro.serve.batcher import RequestShed

    n = 60
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    # not started: the queue holds exactly what we submit (deterministic)
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=4, max_queue=2))
    with metrics.use_registry() as reg:
        f1 = mb.submit([1], [1.0])
        f2 = mb.submit([2], [1.0])
        f3 = mb.submit([3], [1.0])     # queue at capacity: shed at the door
        assert not f1.done() and not f2.done()
        with pytest.raises(RequestShed):
            f3.result(timeout=1)
        assert reg.value("serve.shed") == 1
    assert mb.snapshot()["shed"] == 1
    with mb:                            # drain the two queued requests
        assert f1.result(timeout=30).shape == (2,)
        assert f2.result(timeout=30).shape == (2,)
    assert mb.snapshot()["shed"] == 1 and mb.snapshot()["timeouts"] == 0


def test_batcher_expires_requests_past_deadline():
    """Requests that overstay cfg.deadline_ms in the queue fail with
    RequestTimeout at pop time and never occupy a batch slot; fresh
    requests still resolve."""
    from repro.obs import metrics
    from repro.serve.batcher import RequestTimeout

    n = 60
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=4, max_wait_ms=0.5,
                                             deadline_ms=50.0))
    with metrics.use_registry() as reg:
        stale1 = mb.submit([1], [1.0])
        stale2 = mb.submit([2], [1.0])
        time.sleep(0.1)                 # both are now past their deadline
        with mb:                        # serve loop starts popping
            with pytest.raises(RequestTimeout):
                stale1.result(timeout=30)
            with pytest.raises(RequestTimeout):
                stale2.result(timeout=30)
            fresh = mb.submit([3, 4], [1.0, 1.0])
            assert fresh.result(timeout=30).shape == (2,)
        assert reg.value("serve.timeouts") == 2
    snap = mb.snapshot()
    assert snap["timeouts"] == 2 and snap["shed"] == 0
    assert snap["count"] == 1           # only the fresh request was served


def test_registry_skips_corrupt_version_and_rolls_back(tmp_path):
    """A truncated checkpoint must not crash server startup: load_all
    skips it with a warning + serve.registry.corrupt count, newest
    LOADABLE version becomes active, and rollback_to_last_good() steps
    back one more version."""
    import os

    from repro.obs import metrics

    n = 150
    screen = Screen(variances=jnp.ones(n), means=jnp.zeros(n),
                    count=jnp.asarray(50))
    reg = ModelRegistry(str(tmp_path), impl="ref")
    for seed in range(3):
        reg.register(_fake_components(n, 2, 4, seed=seed), screen,
                     n_features=n)
    # corrupt the NEWEST version's data file (what a torn copy leaves)
    npz = str(tmp_path / "step_000000002" / "host_00000.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 3)

    fresh = ModelRegistry(str(tmp_path), impl="ref")
    with metrics.use_registry() as mreg:
        with pytest.warns(RuntimeWarning, match="corrupt version 2"):
            assert fresh.load_all() == [0, 1]
        assert mreg.value("serve.registry.corrupt") == 1
    assert fresh.active().version == 1

    mv = fresh.rollback_to_last_good()
    assert mv.version == 0 and fresh.active().version == 0
    with pytest.raises(LookupError, match="no version older"):
        fresh.rollback_to_last_good()


def test_rollback_to_last_good_requires_active():
    reg = ModelRegistry(None, impl="ref")
    with pytest.raises(LookupError, match="no active model"):
        reg.rollback_to_last_good()


def test_batcher_stop_fails_stranded_requests():
    """A request that races in behind the shutdown sentinel is failed by
    stop()'s queue drain rather than hanging its future forever."""
    from repro.serve.batcher import _Request

    n = 80
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=4)).start()
    mb.stop()
    r = _Request([1], [1.0])   # enqueue directly: submit() already rejects
    mb._q.put(r)
    mb.stop()                  # second stop drains and fails it
    with pytest.raises(RuntimeError, match="batcher stopped"):
        r.future.result(timeout=5)


def test_prefetch_reraises_worker_exception():
    """Satellite: producer-side exceptions must surface in the consumer,
    not silently end the stream."""
    def boom():
        yield 1
        yield 2
        raise ValueError("worker died")

    got = []
    with pytest.raises(ValueError, match="worker died"):
        for x in prefetch(boom(), size=2):
            got.append(x)
    assert got == [1, 2]


def test_drift_watches_every_components_threshold():
    """A feature eliminated only from the higher-lambda solves must still
    trip the flag when traffic crosses THAT component's threshold."""
    n = 50
    train = np.full(n, 0.1)
    train[7] = 1.0                    # kept at lam=0.5, eliminated at lam=2.0
    screen = Screen(variances=jnp.asarray(train), means=jnp.zeros(n),
                    count=jnp.asarray(1000))
    mon = DriftMonitor(screen, np.array([0.5, 2.0]), min_docs=1)
    rng = np.random.default_rng(11)
    X = (rng.normal(scale=np.sqrt(0.05), size=(4000, n))
         .astype(np.float32))
    X[:, 7] = rng.normal(scale=np.sqrt(10.0), size=4000)  # var 10 >> 2.0
    mon.observe(X)
    rep = mon.check()
    assert rep.triggered
    assert 7 in rep.offending.tolist()
    # scalar-lam monitor at the min threshold would have missed it:
    mon_min = DriftMonitor(screen, 0.5, min_docs=1)
    mon_min.observe(X)
    assert 7 not in mon_min.check().offending.tolist()


# ------------------------------------------------------------------- drift
def _zipf_fit_screen(n_docs=600, n_words=800, seed=0):
    corpus = make_corpus(n_docs, n_words, topics=None, seed=seed)
    mean, var = corpus.column_stats_exact()
    screen = Screen(variances=jnp.asarray(var), means=jnp.asarray(mean),
                    count=jnp.asarray(n_docs))
    return corpus, screen


def test_drift_quiet_on_training_distribution():
    corpus, screen = _zipf_fit_screen()
    lam = float(np.sort(np.asarray(screen.variances))[::-1][30])  # keep ~30
    mon = DriftMonitor(screen, lam, min_docs=100)
    fresh = make_corpus(400, corpus.n_words, topics=None, seed=99)
    for X in fresh.batches(128):
        mon.observe(X)
    rep = mon.check()
    assert rep.docs_seen == 400
    assert not rep.triggered, (
        f"false drift alarm: ratio={rep.max_ratio} ids={rep.offending[:5]}")


def test_drift_fires_on_shifted_tail_words():
    """Boosting tail-word rates pushes eliminated-feature variance past the
    fitted lambda — the certificate is stale and the flag must fire."""
    corpus, screen = _zipf_fit_screen()
    n = corpus.n_words
    lam = float(np.sort(np.asarray(screen.variances))[::-1][30])
    mon = DriftMonitor(screen, lam, min_docs=100)
    rng = np.random.default_rng(7)
    fresh = make_corpus(400, n, topics=None, seed=98)
    hot = np.arange(n - 4, n)
    for X in fresh.batches(128):
        X = X.copy()
        X[:, hot] += rng.poisson(3.0, size=(X.shape[0], hot.size))
        mon.observe(X)
    rep = mon.check()
    assert rep.triggered
    assert set(hot) <= set(rep.offending.tolist())
    assert rep.max_ratio > 1.5


def test_drift_respects_min_docs():
    _, screen = _zipf_fit_screen(n_docs=200, n_words=300)
    lam = float(np.sort(np.asarray(screen.variances))[::-1][10])
    mon = DriftMonitor(screen, lam, min_docs=500)
    X = np.zeros((100, 300), np.float32)
    X[:, 299] = 50.0 * np.arange(100)  # wild drift, but below min_docs
    mon.observe(X)
    assert not mon.check().triggered
    mon.observe(X)
    mon.observe(X)
    mon.observe(X)
    mon.observe(X)
    assert mon.check().triggered


def test_drift_fold_matches_single_screen():
    """Batch-wise folding via combine_screens must equal one global
    screen over the concatenated traffic."""
    rng = np.random.default_rng(3)
    X = rng.poisson(0.7, size=(300, 120)).astype(np.float32)
    whole = feature_variances(jnp.asarray(X), center=True)
    _, screen = _zipf_fit_screen(n_docs=100, n_words=120)
    mon = DriftMonitor(screen, lam=1e9, min_docs=1)
    for lo in range(0, 300, 77):
        mon.observe(X[lo:lo + 77])
    np.testing.assert_allclose(
        np.asarray(mon._running.variances), np.asarray(whole.variances),
        rtol=1e-5, atol=1e-7,
    )
    assert int(mon._running.count) == 300


# ------------------------------------------------------------- end-to-end
@pytest.mark.slow
def test_end_to_end_fit_register_serve_drift():
    """The full serve_topics story on a real (small) fitted model."""
    from repro.core import fit_components
    from repro.core.spca import SPCAConfig

    corpus = make_corpus(1200, 900, topics={"t": ["alpha", "beta", "gamma"]},
                         seed=0)
    A = corpus.dense()
    res = fit_components(A, 2, target_card=3,
                         cfg=SPCAConfig(max_sweeps=6, lam_search_evals=6))
    screen = feature_variances(jnp.asarray(A), center=True)
    with tempfile.TemporaryDirectory() as d:
        reg = ModelRegistry(d, impl="ref")
        mv = reg.register(res, screen, n_features=corpus.n_words)
        mon = DriftMonitor(mv.screen, mv.lam, min_docs=64)
        mb = MicroBatcher(mv.projector, corpus.n_words,
                          BatcherConfig(max_batch=32, max_wait_ms=1.0),
                          observer=mon.observe)
        fresh = make_corpus(600, 900,
                            topics={"t": ["alpha", "beta", "gamma"]}, seed=5)
        with mb:
            futs = []
            rows = fresh.dense()
            for x in rows:
                nz = np.flatnonzero(x)
                futs.append(mb.submit(nz, x[nz]))
            for f in futs:
                f.result(timeout=60)
        assert mb.stats.snapshot()["count"] == 600
        assert mv.projector.trace_count == 1
        assert not mon.check().triggered


def test_batcher_snapshot_carries_live_queue_picture():
    """snapshot() is what /varz serves for the batcher, so it must hold
    the complete overload picture: degradation tallies, queue depth, and
    the configured limits — not just latency percentiles."""
    n = 60
    pack = pack_components(_fake_components(n, 2, 4), n_features=n)
    proj = TopicProjector(pack, impl="ref")
    mb = MicroBatcher(proj, n, BatcherConfig(max_batch=4, max_wait_ms=0.5,
                                             deadline_ms=75.0, max_queue=16))
    snap = mb.snapshot()
    assert snap["queue_depth"] == 0
    assert snap["max_queue"] == 16 and snap["deadline_ms"] == 75.0
    assert {"timeouts", "shed", "batches", "count"} <= set(snap)
    mb._q.put(object())                    # un-popped backlog is visible
    assert mb.snapshot()["queue_depth"] == 1
    mb._q.get_nowait()
    from repro.obs import metrics
    with metrics.use_registry() as reg:
        with mb:
            assert mb.submit([1, 2], [1.0, 1.0]).result(timeout=30).shape \
                == (2,)
        # the serve loop mirrors the depth into the live gauge
        assert reg.value("serve.queue_depth", default=None) == 0


def test_drift_check_mirrors_verdict_into_gauges():
    """DriftMonitor.check() sets the serve.drift.* gauges the exporter's
    serve_drift health rule watches — both verdict polarities."""
    from repro.obs import metrics

    corpus, screen = _zipf_fit_screen()
    n = corpus.n_words
    lam = float(np.sort(np.asarray(screen.variances))[::-1][30])
    with metrics.use_registry() as reg:
        mon = DriftMonitor(screen, lam, min_docs=100)
        fresh = make_corpus(400, n, topics=None, seed=99)
        for X in fresh.batches(128):
            mon.observe(X)
        rep = mon.check()
        assert not rep.triggered
        assert reg.value("serve.drift.triggered") == 0.0
        assert reg.value("serve.drift.docs_seen") == 400
        rng = np.random.default_rng(7)
        hot = np.arange(n - 4, n)
        for X in fresh.batches(128):
            X = X.copy()
            X[:, hot] += rng.poisson(3.0, size=(X.shape[0], hot.size))
            mon.observe(X)
        rep = mon.check()
        assert rep.triggered
        assert reg.value("serve.drift.triggered") == 1.0
        assert reg.value("serve.drift.max_ratio") == pytest.approx(
            rep.max_ratio)
        assert reg.value("serve.drift.offending") == rep.n_offending
