"""Streaming screen/Gram over a sharded CSR store — the out-of-core leg
of the SPCA preprocessing pipeline.

Mirrors the dense streaming pipeline (`data/bow.py`) chunk-for-batch:

  pass 1  sparse_feature_variances — per-column sum/sumsq through the
          csr_stats kernel, one partial `Screen` per host slice, pooled
          with `core.elimination.combine_screens` (the same merge a real
          multi-host run finishes with one psum — see core.distributed);
  pass 2  sparse_reduced_covariance — gather-Gram on the post-elimination
          support through the csr_gram kernel, O(nnz_S + n_hat^2) per
          chunk, never materialising an (m, n) dense array.

Pass pipelining (PR 5): each pass drains the store's *megabatch* iterator
(C chunks packed into reusable (C, chunk_nnz) host buffers off the cached
chunk plan) through `data.pipeline.prefetch`, so mmap read + pad of batch
i+1 overlaps device compute on batch i — the producer/consumer idiom the
serve microbatcher uses, with the same worker-exception propagation and
deterministic chunk order (single reader thread, FIFO queue).  Each
megabatch is ONE kernel dispatch (`update_csr_batch`), so a pass costs
ceil(chunks / C) launches instead of `chunks`.

``counters`` (a plain dict) tallies the pass economics the driver surfaces
via `fit_components(diagnostics=...)`: ``screen_passes`` / ``gram_passes``
(corpus passes), ``screen_launches`` / ``gram_launches`` (ingest
dispatches), and ``chunks`` streamed.

`sparse_stats` packages the two passes as the ``(variances, build)`` pair
`core.spca._as_stats` hands to the lambda search; the driver's
cross-component covariance cache calls ``build`` ONCE per fit in the
common case — 1 + 1 corpus passes for K components (see
`core.spca.fit_components`).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.elimination import Screen, combine_screens, select_support
from repro.data.bow import StreamingGram, StreamingStats
from repro.data.pipeline import prefetch
from repro.obs import metrics, trace

from .resume import DEFAULT_CHECKPOINT_EVERY, PassCheckpointer, pass_fingerprint
from .store import DEFAULT_CHUNK_NNZ, DEFAULT_CHUNK_ROWS, SparseCorpus

DEFAULT_MEGABATCH = 8
DEFAULT_PREFETCH = 2


def _bump(counters: dict | None, **deltas) -> None:
    for k, d in deltas.items():
        metrics.counter(f"ingest.{k}").inc(d)
    if counters is None:
        return
    for k, d in deltas.items():
        counters[k] = counters.get(k, 0) + d


def _count(counters: dict | None, key: str, delta) -> None:
    """Diagnostics-dict side only (for registry names that don't follow
    the flat ``ingest.<key>`` scheme, e.g. ``ingest.resume.*``)."""
    if counters is not None:
        counters[key] = counters.get(key, 0) + delta


def _stream_prefetch_stats(pstats: dict, prev: dict) -> None:
    """Push the prefetch pipeline's stall/occupancy accounting into the
    registry *incrementally* (delta since the previous megabatch), so a
    scrape mid-pass sees live read-bound/reduce-bound attribution instead
    of zeros until the pass ends.  ``pstats`` is written concurrently by
    the producer/consumer threads; reading monotone floats under the GIL
    is safe, and deltas make double counting impossible."""
    if not pstats:
        return
    dc = pstats.get("consumer_stall_s", 0.0) - prev.get("consumer_stall_s", 0.0)
    dp = pstats.get("producer_stall_s", 0.0) - prev.get("producer_stall_s", 0.0)
    if dc > 0:
        metrics.counter("ingest.prefetch.consumer_stall_s").inc(dc)
        prev["consumer_stall_s"] = pstats.get("consumer_stall_s", 0.0)
    if dp > 0:
        metrics.counter("ingest.prefetch.producer_stall_s").inc(dp)
        prev["producer_stall_s"] = pstats.get("producer_stall_s", 0.0)
    items = pstats.get("items", 0)
    di = items - prev.get("items", 0)
    if di > 0:
        occ = (pstats.get("occupancy_sum", 0)
               - prev.get("occupancy_sum", 0)) / di
        metrics.histogram("ingest.prefetch.occupancy").observe(occ)
        metrics.gauge("ingest.prefetch.queue_depth").set(occ)
        prev["items"] = items
        prev["occupancy_sum"] = pstats.get("occupancy_sum", 0)


def _timed_next(it, span, b: int):
    """``it`` with each ``next`` inside ``span(b)``, ``b`` being the index
    of the megabatch that comes out next (a superbatch of ``lanes``
    megabatches moves it on by ``lanes``), so spans on the reader thread
    and on the pass thread join on ``b``.  Closes ``it`` when done or
    abandoned."""
    try:
        while True:
            with span(b):
                item = next(it, None)
            if item is None:
                return
            yield item
            b = item.index + getattr(item, "lanes", 1)
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def _feed(it, *, start_batch: int, prefetch_depth: int, stats: dict):
    """The pass thread's view of the store iterator ``it``: each item's
    read and packing is an ``ingest.read`` span (on the prefetch reader
    thread when ``prefetch_depth`` > 0), and each wait for the next item
    an ``ingest.feed_wait`` span on the pass thread."""
    it = _timed_next(it, lambda b: trace.span("ingest.read", b=b),
                     start_batch)
    if prefetch_depth > 0:
        it = prefetch(it, size=prefetch_depth, stats=stats)
    return _timed_next(it, lambda b: trace.span("ingest.feed_wait", b=b),
                       start_batch)


def _drain(store: SparseCorpus, acc, *, chunk_nnz, chunk_rows, megabatch,
           prefetch_depth, host_id, num_hosts, counters, launch_key,
           checkpointer: PassCheckpointer | None = None, kind: str = "",
           pass_deadline_s: float | None = None):
    """One streaming pass of ``acc`` over this host's shard slice: packed
    megabatches, prefetched one batch ahead, one dispatch per batch.

    Resume (``checkpointer``): the pass loads the newest checkpoint whose
    fingerprint matches (store identity + chunk geometry + host slice +
    accumulator signature), restores the summed moments, and starts the
    iterator at the saved megabatch boundary — completed megabatches are
    never re-streamed (whole shards before the boundary are skipped
    without a read).  The accumulator state + cursor are re-published
    atomically every ``checkpointer.every`` megabatches and once more with
    ``complete=True`` when the pass finishes, so a kill *between* passes
    resumes the finished pass with zero streaming.

    Observability: each megabatch gets an ``ingest.megabatch`` span (the
    pass thread's own work on it: prep, copy, dispatch and, in the screen
    pass, the readback; the pass spans end on completed device work), and
    the feed's ``ingest.read`` / ``feed_wait`` / ``prep`` / ``h2d`` /
    ``readback`` spans, all carrying the megabatch index ``b``
    (``_feed``, `data.bow`, `kernels.ops`); transient-read retries absorbed
    by the store land in ``counters['io_retries']`` (registry:
    ``ingest.retries``); resume events land in ``ingest.resume.*`` and
    ``counters['resumed_megabatches']``; and the prefetch queue's stall
    accounting lands in ``counters`` (``prefetch_consumer_stall_s`` /
    ``prefetch_producer_stall_s``) and the ``ingest.prefetch.*`` registry
    instruments — consumer stall means the pass is read-bound, producer
    stall means it is reduce-bound.

    ``pass_deadline_s`` arms a cooperative wall-clock watchdog checked at
    every megabatch boundary (AFTER the checkpoint cadence runs, so an
    expired pass is resumable at the boundary it died on); expiry raises
    the typed `obs.health.PassDeadlineError`."""
    wd = None
    if pass_deadline_s is not None:
        from repro.obs import health as _health
        wd = _health.Watchdog(pass_deadline_s, what=f"{kind or launch_key} pass",
                              exc=_health.PassDeadlineError)
    start_batch = 0
    fp = None
    if checkpointer is not None:
        fp = pass_fingerprint(
            kind or launch_key, store, chunk_nnz=chunk_nnz,
            chunk_rows=chunk_rows, megabatch=megabatch, host_id=host_id,
            num_hosts=num_hosts, signature=acc.state_signature(),
        )
        hit = checkpointer.load(fp)
        if hit is not None:
            cursor, state, _complete = hit
            acc.load_state(state)
            start_batch = cursor
            metrics.counter("ingest.resume.loads").inc()
            metrics.counter("ingest.resume.megabatches_skipped").inc(cursor)
            _count(counters, "resumed_megabatches", cursor)
    retries0 = getattr(store, "io_retry_count", 0)
    pstats: dict = {}
    pprev: dict = {}
    it = _feed(store.iter_megabatches(
        chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
        host_id=host_id, num_hosts=num_hosts,
        ring=max(2, prefetch_depth + 2),
        start_batch=start_batch,
    ), start_batch=start_batch, prefetch_depth=prefetch_depth, stats=pstats)
    done = start_batch
    for mb in it:
        with trace.span("ingest.megabatch", kind=launch_key,
                        chunks=int(mb.n_chunks), b=mb.index):
            acc.update_csr_batch(mb)
        _bump(counters, **{launch_key: 1, "chunks": mb.n_chunks})
        # Stream prefetch stall/occupancy into the registry NOW, not at
        # pass end: a multi-hour Gram pass scraped over /metrics shows its
        # read-vs-reduce attribution mid-flight instead of zeros.
        _stream_prefetch_stats(pstats, pprev)
        done += 1
        if checkpointer is not None and done % checkpointer.every == 0:
            with trace.span("ingest.resume.checkpoint", kind=launch_key,
                            cursor=done):
                checkpointer.save(fp, done, acc.state_dict())
            metrics.counter("ingest.resume.checkpoints").inc()
            _count(counters, "resume_checkpoints", 1)
        if wd is not None:
            wd.check()
    if checkpointer is not None:
        checkpointer.save(fp, done, acc.state_dict(), complete=True)
        metrics.counter("ingest.resume.checkpoints").inc()
        _count(counters, "resume_checkpoints", 1)
    dr = getattr(store, "io_retry_count", 0) - retries0
    if dr:
        _count(counters, "io_retries", dr)
    if pstats:
        # Registry got its share incrementally above; flush whatever the
        # producer thread recorded after the last megabatch, then write
        # the pass TOTALS into the diagnostics dict (which, unlike the
        # registry, is per-call and so wants totals, not deltas).
        _stream_prefetch_stats(pstats, pprev)
        if counters is not None:
            counters["prefetch_consumer_stall_s"] = (
                counters.get("prefetch_consumer_stall_s", 0.0)
                + pstats.get("consumer_stall_s", 0.0))
            counters["prefetch_producer_stall_s"] = (
                counters.get("prefetch_producer_stall_s", 0.0)
                + pstats.get("producer_stall_s", 0.0))
    return acc


def _reliability(store: SparseCorpus, io_retries, io_backoff_s,
                 resume_dir, checkpoint_every) -> PassCheckpointer | None:
    """Apply the pass-level reliability knobs: retry policy onto the store
    handle, and a `PassCheckpointer` when a resume root is given."""
    if io_retries is not None or io_backoff_s is not None:
        store.set_io_policy(io_retries=io_retries, io_backoff_s=io_backoff_s)
    if not resume_dir:
        return None
    return PassCheckpointer(resume_dir, every=checkpoint_every)


def sparse_feature_variances(
    store: SparseCorpus,
    *,
    center: bool = True,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    pass_deadline_s: float | None = None,
) -> Screen:
    """One streaming pass: the Thm 2.1 screen input from CSR chunks.

    ``num_hosts > 1`` emulates the multi-host layout on one process: each
    host slice reduces its own shards into a partial Screen and the pool
    goes through `combine_screens` — byte-identical to what H real hosts
    would produce and merge.
    """
    ckpt = _reliability(store, io_retries, io_backoff_s,
                        resume_dir, checkpoint_every)
    partials = []
    with trace.span("ingest.screen_pass", nnz=int(store.nnz),
                    num_hosts=num_hosts, megabatch=megabatch):
        for h in range(num_hosts):
            acc = StreamingStats(store.n_cols, impl=impl)
            _drain(
                store, acc, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
                megabatch=megabatch, prefetch_depth=prefetch_depth,
                host_id=h, num_hosts=num_hosts, counters=counters,
                launch_key="screen_launches",
                checkpointer=ckpt, kind="screen",
                pass_deadline_s=pass_deadline_s,
            )
            partials.append(acc.finalize(center=center))
        _bump(counters, screen_passes=1)
        if len(partials) == 1:
            return partials[0]
        return combine_screens(partials)


def sparse_reduced_covariance(
    store: SparseCorpus,
    support: np.ndarray,
    *,
    means: np.ndarray | None = None,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    pass_deadline_s: float | None = None,
):
    """One streaming pass: Sigma_hat = A_S^T A_S / m (centred when
    ``means`` is given) on the surviving columns, straight from chunks.
    The partial accumulators pool DEVICE-side (`StreamingGram.merge` is a
    jnp add) — one host transfer at finalize."""
    ckpt = _reliability(store, io_retries, io_backoff_s,
                        resume_dir, checkpoint_every)
    support = np.asarray(support)
    accs = []
    with trace.span("ingest.gram_pass", n_hat=int(support.size),
                    num_hosts=num_hosts, megabatch=megabatch):
        for h in range(num_hosts):
            acc = StreamingGram(support, impl=impl, chunk_rows=chunk_rows)
            _drain(
                store, acc, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
                megabatch=megabatch, prefetch_depth=prefetch_depth,
                host_id=h, num_hosts=num_hosts, counters=counters,
                launch_key="gram_launches",
                checkpointer=ckpt, kind="gram",
                pass_deadline_s=pass_deadline_s,
            )
            accs.append(acc)
        _bump(counters, gram_passes=1)
        acc = accs[0]
        for other in accs[1:]:
            acc.merge(other)
        out = jnp.asarray(acc.finalize(means=means))
        trace.device_sync(out)
    return out


def sparse_stats(
    store: SparseCorpus,
    *,
    center: bool = True,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    pass_deadline_s: float | None = None,
):
    """The ``(variances, build)`` pair `core.spca` drives the lambda
    search with, computed out-of-core.  ``build(support)`` is one more
    streaming pass; the driver's covariance cache calls it ONCE per fit
    (cross-component slicing), so a K-component fit costs 1 + 1 passes.

    With ``resume_dir`` both passes checkpoint accumulator state + cursor
    every ``checkpoint_every`` megabatches; a killed fit restarted with
    the same arguments resumes each pass from its last completed boundary
    (a pass that had finished re-streams NOTHING — its final moments are
    reloaded from the ``complete`` checkpoint)."""
    screen = sparse_feature_variances(
        store, center=center, impl=impl,
        chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
        prefetch_depth=prefetch_depth, num_hosts=num_hosts,
        counters=counters, io_retries=io_retries, io_backoff_s=io_backoff_s,
        resume_dir=resume_dir, checkpoint_every=checkpoint_every,
        pass_deadline_s=pass_deadline_s,
    )
    means = np.asarray(screen.means) if center else None

    def build(support):
        return sparse_reduced_covariance(
            store, np.asarray(support), means=means,
            impl=impl, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
            megabatch=megabatch, prefetch_depth=prefetch_depth,
            num_hosts=num_hosts, counters=counters,
            io_retries=io_retries, io_backoff_s=io_backoff_s,
            resume_dir=resume_dir, checkpoint_every=checkpoint_every,
            pass_deadline_s=pass_deadline_s,
        )

    return np.asarray(screen.variances), build


def screen_and_gram_sparse(
    store: SparseCorpus,
    lam: float,
    *,
    center: bool = True,
    impl: str = "auto",
    max_reduced: int = 2048,
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    pass_deadline_s: float | None = None,
):
    """Two-pass out-of-core pipeline at a fixed lambda — the sparse twin
    of `data.bow.screen_and_gram_streaming`.  Returns
    (Sigma_hat, support, screen)."""
    screen = sparse_feature_variances(
        store, center=center, impl=impl,
        chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
        prefetch_depth=prefetch_depth, num_hosts=num_hosts,
        counters=counters, io_retries=io_retries, io_backoff_s=io_backoff_s,
        resume_dir=resume_dir, checkpoint_every=checkpoint_every,
        pass_deadline_s=pass_deadline_s,
    )
    support = select_support(screen.variances, lam, max_reduced)
    Sigma_hat = sparse_reduced_covariance(
        store, support,
        means=np.asarray(screen.means) if center else None,
        impl=impl, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
        megabatch=megabatch, prefetch_depth=prefetch_depth,
        num_hosts=num_hosts, counters=counters,
        io_retries=io_retries, io_backoff_s=io_backoff_s,
        resume_dir=resume_dir, checkpoint_every=checkpoint_every,
        pass_deadline_s=pass_deadline_s,
    )
    return Sigma_hat, support, screen
