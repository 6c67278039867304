"""Serving launcher: fit sparse topics, register, serve a live query stream.

    PYTHONPATH=src python -m repro.launch.serve_topics --smoke

Serving
-------
The paper fits components offline; this launcher exercises the *online*
half of the system (``repro.serve``):

  1. fit     — the paper's pipeline (screen -> eliminate -> BCD) on a
               Zipf corpus with planted topics, exactly as spca_run does;
  2. register— pack the components and hot-swap them into a versioned,
               checkpointed ``ModelRegistry``;
  3. serve   — a synthetic query stream (fresh draws from the training
               distribution) flows through the ``MicroBatcher`` into the
               jitted gather-matvec projector; per-request latency and
               throughput are reported (p50/p99, docs/s);
  4. monitor — a ``DriftMonitor`` folds the served traffic into a running
               variance screen and is then shown a *shifted* stream (tail
               words boosted) to demonstrate the refit flag firing when
               the Thm 2.1 elimination certificate goes stale.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from repro.core import SPCAConfig, search_lambda
from repro.core.elimination import Screen
from repro.data.corpus import NYTIMES_TOPICS, make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import metrics, trace
from repro.serve import BatcherConfig, DriftMonitor, MicroBatcher, ModelRegistry

_EXAMPLES = """\
observability examples:
  # span timeline (fit + per-batch serve spans on the server thread's own
  # Perfetto track) and a serve.* / solver.* metrics snapshot
  python -m repro.launch.serve_topics --smoke \\
      --trace serve_trace.json --metrics serve_metrics.jsonl

live telemetry examples:
  # background exporter: samples the registry every 2s into the --metrics
  # JSONL (a TIME SERIES of delta snapshots: per-interval request rate,
  # window latency percentiles) and serves, while the process runs,
  #   /metrics   Prometheus text exposition (point a scraper at it)
  #   /healthz   200 ok / 503 unhealthy from the serving rule pack
  #              (p99 latency, shed/timeout bursts, drift flag, solver
  #              nonfinite objectives)
  #   /varz      registry + live MicroBatcher snapshot (queue depth,
  #              timeouts, shed) as JSON
  #   /tracez    recently completed span trees (with --trace)
  python -m repro.launch.serve_topics --smoke --export-port 9100 \\
      --export-interval 2 --metrics serve_metrics.jsonl --trace t.json
  # while it serves:  curl -s localhost:9100/healthz
  # --export-port 0 picks a free port (printed at startup)
"""


def iter_docs(corpus):
    """Yield each document as a sparse (word_ids, counts) pair."""
    order = np.argsort(corpus.doc_idx, kind="stable")
    di = corpus.doc_idx[order]
    wi = corpus.word_idx[order]
    ct = corpus.counts[order]
    starts = np.searchsorted(di, np.arange(corpus.n_docs + 1))
    for d in range(corpus.n_docs):
        lo, hi = starts[d], starts[d + 1]
        yield wi[lo:hi], ct[lo:hi]


def shifted_docs(docs, n_words: int, *, n_shift: int = 8, rate: float = 4.0,
                 seed: int = 0):
    """Traffic-drift injector: boost ``n_shift`` tail words in every doc.

    Tail words (the last Zipf ranks) had training variance far below lambda
    — exactly the features safe elimination removed — so this is the drift
    the certificate cannot absorb."""
    rng = np.random.default_rng(seed)
    hot = np.arange(n_words - n_shift, n_words, dtype=np.int64)
    for wi, ct in docs:
        extra = 1.0 + rng.poisson(rate, size=n_shift)
        yield (np.concatenate([np.asarray(wi, np.int64), hot]),
               np.concatenate([np.asarray(ct, np.float32),
                               extra.astype(np.float32)]))


def fit_topics(corpus, n_components: int, target_card: int):
    """The spca_run fit loop, returning (results, training screen)."""
    import jax.numpy as jnp

    mean, var = corpus.column_stats_exact()

    def build(support):
        A = corpus.columns_dense(np.asarray(support))
        A = A - A.mean(0, keepdims=True)
        return jnp.asarray((A.T @ A) / corpus.n_docs)

    mask = np.ones(corpus.n_words, bool)
    cfg = SPCAConfig(max_sweeps=8, lam_search_evals=8)
    results = []
    for c in range(n_components):
        t0 = time.time()
        r = search_lambda(None, target_card, cfg=cfg,
                          active_mask=mask, stats=(var, build))
        results.append(r)
        mask[r.support] = False
        words = [corpus.vocab[i] for i in r.support]
        print(f"PC{c + 1}: card={r.cardinality} n_hat={r.reduced_n} "
              f"lam={r.lam:.3f} var={r.variance:.2f} "
              f"({time.time() - t0:.1f}s)  " + ", ".join(words[:8]))
    screen = Screen(variances=jnp.asarray(var), means=jnp.asarray(mean),
                    count=jnp.asarray(corpus.n_docs))
    return results, screen


def serve_stream(batcher, docs, *, inflight: int = 256):
    """Closed-loop client: keeps at most ``inflight`` requests outstanding
    (an open loop would just measure queue depth, not the server)."""
    pending = []
    served = 0
    topics = []
    for wi, ct in docs:
        pending.append(batcher.submit(wi, ct))
        if len(pending) >= inflight:
            for f in pending:
                topics.append(int(np.argmax(np.abs(f.result(timeout=60)))))
            served += len(pending)
            pending = []
    for f in pending:
        topics.append(int(np.argmax(np.abs(f.result(timeout=60)))))
    served += len(pending)
    return served, np.bincount(topics, minlength=batcher.projector.pack.k)


def main(argv=None):
    """Run fit -> register -> serve -> drift; returns what `_run` returns."""
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        epilog=_EXAMPLES,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus, fast end-to-end run")
    ap.add_argument("--docs", type=int, default=8000)
    ap.add_argument("--words", type=int, default=10_000)
    ap.add_argument("--components", type=int, default=5)
    ap.add_argument("--target-card", type=int, default=5)
    ap.add_argument("--queries", type=int, default=4000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--registry", default=None,
                    help="persistence dir (default: a temp dir)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write the host span timeline as Chrome "
                         "trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--metrics", default="", metavar="PATH",
                    help="append one metrics-registry snapshot (JSON line) "
                         "at exit (with --export-port: a time series, one "
                         "line per exporter interval)")
    ap.add_argument("--export-port", type=int, default=None, metavar="PORT",
                    help="start the background telemetry exporter and serve "
                         "/metrics /healthz /varz /tracez on this port "
                         "(0 = ephemeral; see the live telemetry examples)")
    ap.add_argument("--export-interval", type=float, default=2.0,
                    metavar="S",
                    help="seconds between exporter samples (with "
                         "--export-port)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.docs = min(args.docs, 3000)
        args.words = min(args.words, 2500)
        args.components = min(args.components, 3)
        args.queries = max(min(args.queries, 1500), 1000)

    exporter = None
    if args.export_port is not None:
        from repro.obs import health
        from repro.obs.export import TelemetryExporter

        exporter = TelemetryExporter(
            interval_s=args.export_interval,
            port=args.export_port,
            jsonl_path=args.metrics or None,
            rules=health.serving_rules() + health.solver_rules(),
            extra={"run": "serve_topics"},
        )

    prev_tracer = trace.active()
    tracer = trace.install(trace.Tracer()) if args.trace else None
    try:
        if exporter is not None:
            exporter.start()
            print(f"telemetry: http://127.0.0.1:{exporter.port}"
                  "/{metrics,healthz,varz,tracez} "
                  f"(sampling every {args.export_interval:g}s)")
        out = _run(args, exporter)
    finally:
        if exporter is not None:
            exporter.stop()
        trace.install(prev_tracer)
    if tracer is not None:
        tracer.dump_chrome_trace(args.trace)
        print(f"trace: {args.trace} (load at ui.perfetto.dev)")
    if exporter is not None:
        print(exporter.health().describe())
    if args.metrics:
        if exporter is None:
            # One exit snapshot; with the exporter the file is already a
            # time series (final flush included by exporter.stop()).
            metrics.get_registry().dump_jsonl(
                args.metrics, extra={"run": "serve_topics"}
            )
        print(f"metrics: {args.metrics}")
    return out


def _run(args, exporter=None):
    """Returns a dict: ``results`` (fitted PCs), ``model`` (the registered
    version, projector included), ``queries`` (the served corpus),
    ``served``, ``stats`` (batcher latency snapshot) and the two drift
    reports ``drift`` / ``drift_shifted``."""
    # 1. fit ---------------------------------------------------------------
    print(f"corpus: {args.docs} docs x {args.words} words")
    corpus = make_corpus(args.docs, args.words, topics=NYTIMES_TOPICS, seed=0)
    results, screen = fit_topics(corpus, args.components, args.target_card)

    # 2. register ----------------------------------------------------------
    root = args.registry or tempfile.mkdtemp(prefix="topic_registry_")
    registry = ModelRegistry(root)
    prior = registry.load_all()   # a re-run extends the version history
    if prior:
        print(f"registry at {root} already holds versions {prior}")
    mv = registry.register(results, screen, n_features=args.words,
                           meta={"corpus": "nytimes-like"})
    print(f"registered v{mv.version} -> {root}  "
          f"(k={mv.pack.k} cap={mv.pack.cap} nnz={mv.pack.nnz} "
          f"lam={mv.lam:.3f})")

    # 3. serve -------------------------------------------------------------
    queries = make_corpus(args.queries, args.words, topics=NYTIMES_TOPICS,
                          seed=1)
    monitor = DriftMonitor(mv.screen, mv.lams, min_docs=args.batch * 4)
    batcher = MicroBatcher(
        mv.projector, args.words,
        BatcherConfig(max_batch=args.batch, max_wait_ms=2.0),
        observer=monitor.observe,
    )
    if exporter is not None:
        # /varz now shows the live batcher picture (queue depth, timeouts,
        # shed, p50/p99) next to the registry snapshot.
        exporter.add_snapshot_provider("serve.batcher", batcher.snapshot)
    with batcher:
        t0 = time.perf_counter()
        served, hist = serve_stream(batcher, iter_docs(queries))
        wall = time.perf_counter() - t0
    s = batcher.stats.snapshot()
    print(f"served {served} docs in {wall:.2f}s: "
          f"{served / wall:.0f} docs/s  "
          f"p50={s['p50_ms']:.2f}ms p99={s['p99_ms']:.2f}ms  "
          f"({batcher.batches_served} batches, "
          f"{mv.projector.trace_count} trace(s))")
    print("topic histogram:", hist.tolist())

    # 4. drift -------------------------------------------------------------
    rep = monitor.check()
    print(f"drift on in-distribution traffic: triggered={rep.triggered} "
          f"max_ratio={rep.max_ratio:.2f} docs={rep.docs_seen}")
    shifted = DriftMonitor(mv.screen, mv.lams, min_docs=args.batch * 4)
    batcher2 = MicroBatcher(
        mv.projector, args.words,
        BatcherConfig(max_batch=args.batch, max_wait_ms=2.0),
        observer=shifted.observe,
    )
    with batcher2:
        serve_stream(
            batcher2,
            shifted_docs(iter_docs(queries), args.words, seed=2),
        )
    rep2 = shifted.check()
    print(f"drift on shifted traffic:          triggered={rep2.triggered} "
          f"max_ratio={rep2.max_ratio:.2f} "
          f"offending={rep2.offending[:8].tolist()}")
    if rep.triggered or not rep2.triggered:
        raise SystemExit("drift monitor misbehaved")
    print("ok: certificate quiet in-distribution, refit flag on drift")
    return dict(results=results, model=mv, queries=queries, served=served,
                stats=s, drift=rep, drift_shifted=rep2)


if __name__ == "__main__":
    main()
