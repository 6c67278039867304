"""The paper's own pipeline as a launcher: corpus -> streaming variance
screen -> safe elimination -> reduced gram -> BCD -> topic tables.

    PYTHONPATH=src python -m repro.launch.spca_run --corpus nytimes \
        --docs 8000 --components 5 --target-card 5

With ``--streaming`` the corpus is first written to a sharded CSR store on
disk (``--store-dir``, default a temp dir) and the whole fit runs
out-of-core from the store through the CSR kernels (``repro.sparse``):
prefetched megabatch chunk passes, 1 + 1 passes for ALL components
(screen + one union-support Gram shared across the deflation rounds via
the covariance cache), never an (m, n) dense array — the paper's "cannot
even load them into memory" regime.  The per-component lines and the
final total report the solve-launch AND corpus-pass/ingest-launch
economics.

With ``--devices D`` (and, off-TPU, ``XLA_FLAGS=
--xla_force_host_platform_device_count=D`` set before launch — the device
topology is locked at first jax init) the fit goes data-parallel over a
1-D device mesh (``repro.sparse.mesh_engine`` + the batched solver's
``devices=`` leg): each corpus pass drains superbatches of D megabatches
in ceil(B/D) sharded dispatches with per-device resident accumulators
merged once at finalize, and every lambda-search round solves
batch_evals·D evaluations in one launch.  Pass economics stay 1 + 1.

Serving
-------
This launcher stops at fitted components.  The online half — packing the
sparse PCs into a gather representation, registering them in a versioned
hot-swappable registry, projecting live document streams through the
Pallas gather-matvec, and watching the Thm 2.1 elimination certificate for
traffic drift — lives in ``repro.serve`` and is exercised end-to-end by
``python -m repro.launch.serve_topics --smoke``.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from repro.configs.spca_experiments import NYTIMES, PUBMED
from repro.core import SPCAConfig, fit_components
from repro.data.corpus import NYTIMES_TOPICS, PUBMED_TOPICS, make_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import metrics, profile, trace

_EXAMPLES = """\
reliability examples:
  # resumable streaming fit: pass checkpoints (accumulator state + the
  # megabatch cursor) land in ckpt/ every 8 megabatches; if the fit is
  # killed, re-running the SAME command restarts each screen/Gram pass
  # from its last completed boundary instead of re-streaming the corpus
  # ("resumed N megabatch(es)" in the final report shows the skip)
  python -m repro.launch.spca_run --streaming --components 3 \\
      --store-dir store/ --resume ckpt/ --checkpoint-every 8
  # NOTE: resume needs a persistent --store-dir; checkpoints are keyed to
  #       the store identity + chunk geometry, so changing --chunk-nnz /
  #       --megabatch (or the corpus) safely falls back to a clean pass

  # flaky storage: retry transient shard-read OSErrors up to 5 times with
  # exponential backoff before giving up (absorbed retries are counted as
  # ingest.retries in --metrics output; corrupt shards are NEVER retried
  # — they raise ShardCorruptionError naming the shard)
  python -m repro.launch.spca_run --streaming --io-retries 5 \\
      --metrics m.jsonl

observability examples:
  # span timeline of the whole fit (Perfetto-loadable) + metrics snapshot
  python -m repro.launch.spca_run --streaming --components 3 \\
      --trace out.json --metrics m.jsonl
  #   out.json  -> load at https://ui.perfetto.dev (or chrome://tracing);
  #                the span tree (also printed) shows the 2 corpus passes
  #                (ingest.screen_pass / ingest.gram_pass), per-megabatch
  #                dispatches, and the solve-launch structure
  #   m.jsonl   -> one JSON line: solver.*, cov.*, search.*, ingest.*
  #                (incl. ingest.prefetch.* stall time), kernel.launches.*

  # device-level jax.profiler trace; the program spans land on its host
  # planes beside the device ops
  python -m repro.launch.spca_run --profile-dir /tmp/jaxtrace

live telemetry examples:
  # background exporter: samples the registry every 2s into m.jsonl (a
  # TIME SERIES of delta-aware snapshots, not one exit line) and serves
  #   http://127.0.0.1:9100/metrics   Prometheus text (scrapeable)
  #   http://127.0.0.1:9100/healthz   200/503 from the solver+ingestion
  #                                   rule pack (nonfinite objectives,
  #                                   sweep stalls, prefetch starvation)
  #   http://127.0.0.1:9100/varz      full registry snapshot as JSON
  #   http://127.0.0.1:9100/tracez    recent span trees (with --trace)
  python -m repro.launch.spca_run --streaming --components 3 \\
      --export-port 9100 --export-interval 2 --metrics m.jsonl
  # --export-port 0 picks a free ephemeral port (printed at startup);
  # watch a long fit live:  curl -s localhost:9100/metrics | grep ingest
"""


def main(argv=None):
    """Run the fit; returns what `_run` returns (results + diagnostics)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        epilog=_EXAMPLES,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--corpus", choices=("nytimes", "pubmed"), default="nytimes")
    ap.add_argument("--docs", type=int, default=8000)
    ap.add_argument("--words", type=int, default=0,
                    help="0 = the corpus's real vocabulary width")
    ap.add_argument("--components", type=int, default=5)
    ap.add_argument("--target-card", type=int, default=5)
    ap.add_argument("--streaming", action="store_true",
                    help="run out-of-core from a sharded CSR store on disk")
    ap.add_argument("--store-dir", default="",
                    help="where to write the CSR store (default: temp dir)")
    ap.add_argument("--chunk-nnz", type=int, default=16_384)
    ap.add_argument("--chunk-rows", type=int, default=512)
    ap.add_argument("--megabatch", type=int, default=8,
                    help="chunks per ingest launch (grid=(C,) batch)")
    ap.add_argument("--resume", default="", metavar="DIR",
                    help="checkpoint the fit into DIR and resume a killed "
                         "run: streaming passes restart at the last "
                         "completed megabatch boundary AND the solver "
                         "phase restarts at the last completed "
                         "component/eval boundary (see the reliability "
                         "examples below)")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="megabatches between pass checkpoints (with "
                         "--resume)")
    ap.add_argument("--io-retries", type=int, default=2,
                    help="transient shard-read OSError retries before "
                         "giving up (exponential backoff; corruption is "
                         "never retried)")
    ap.add_argument("--pass-deadline-s", type=float, default=None,
                    metavar="S",
                    help="wall-clock budget per streaming corpus pass; "
                         "expiry raises PassDeadlineError at a resumable "
                         "megabatch boundary")
    ap.add_argument("--solve-deadline-s", type=float, default=None,
                    metavar="S",
                    help="wall-clock budget per lambda-search solve round; "
                         "expiry raises SolveDeadlineError at a "
                         "checkpointed eval boundary")
    ap.add_argument("--no-solver-fallback", action="store_true",
                    help="disable the fused->oracle solver fallback ladder "
                         "(an unhealthy fused solve then raises instead of "
                         "re-solving on the jnp path)")
    ap.add_argument("--batch-evals", type=int, default=0,
                    help=">1: run each lambda-search round as ONE batched "
                         "solve launch of this many evaluations")
    ap.add_argument("--devices", type=int, default=0,
                    help=">1: partition the streaming passes and the "
                         "batched solves across the first D local devices "
                         "(1-D data mesh; off-TPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=D before "
                         "launching); fewer than D local devices is an "
                         "error")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write the host span timeline as Chrome "
                         "trace-event JSON (Perfetto-loadable) and print "
                         "the span tree")
    ap.add_argument("--metrics", default="", metavar="PATH",
                    help="append one metrics-registry snapshot (JSON line) "
                         "after the fit")
    ap.add_argument("--profile-dir", default="", metavar="DIR",
                    help="run a jax.profiler device trace into DIR, with "
                         "the program spans on its host planes")
    ap.add_argument("--export-port", type=int, default=None, metavar="PORT",
                    help="start the background telemetry exporter and serve "
                         "/metrics /healthz /varz /tracez on this port "
                         "(0 = ephemeral; see the live telemetry examples)")
    ap.add_argument("--export-interval", type=float, default=2.0,
                    metavar="S",
                    help="seconds between exporter samples (with "
                         "--export-port; each interval appends one delta "
                         "snapshot to --metrics)")
    args = ap.parse_args(argv)

    exporter = None
    if args.export_port is not None:
        from repro.obs import health
        from repro.obs.export import TelemetryExporter

        exporter = TelemetryExporter(
            interval_s=args.export_interval,
            port=args.export_port,
            jsonl_path=args.metrics or None,
            rules=(health.solver_rules() + health.ingestion_rules()
                   + health.runtime_rules()),
            extra={"run": "spca_run", "corpus": args.corpus},
        )

    prev_tracer = trace.active()
    tracer = trace.install(trace.Tracer()) if args.trace else None
    try:
        if exporter is not None:
            exporter.start()
            print(f"telemetry: http://127.0.0.1:{exporter.port}"
                  "/{metrics,healthz,varz,tracez} "
                  f"(sampling every {args.export_interval:g}s)")
        with profile.trace_device(args.profile_dir or None):
            out = _run(args)
    finally:
        if exporter is not None:
            exporter.stop()
        trace.install(prev_tracer)
    if tracer is not None:
        tracer.dump_chrome_trace(args.trace)
        print(f"trace: {args.trace} (load at ui.perfetto.dev)")
        print(tracer.tree_str(min_s=0.005))
    if exporter is not None:
        print(exporter.health().describe())
    if args.metrics:
        if exporter is None:
            # One exit snapshot.  (With the exporter the file is already a
            # time series of interval samples, final flush included.)
            metrics.get_registry().dump_jsonl(
                args.metrics,
                extra={"run": "spca_run", "corpus": args.corpus},
            )
        print(f"metrics: {args.metrics}")
    return out


def _run(args):
    """The fit itself.  Returns a dict: ``results`` (PCResults),
    ``diagnostics`` (the `fit_components` dict), ``ingest`` (pass/launch
    counters; empty without --streaming), ``corpus``, ``variances`` (the
    screen the fit used), ``store`` (the CSR store handle or None),
    ``cfg`` and ``fit_s``."""
    exp = NYTIMES if args.corpus == "nytimes" else PUBMED
    topics = NYTIMES_TOPICS if args.corpus == "nytimes" else PUBMED_TOPICS
    n_words = args.words or exp.n_words
    print(f"generating {args.corpus}-like corpus: {args.docs} docs x "
          f"{n_words} words ...")
    t0 = time.time()
    corpus = make_corpus(args.docs, n_words, topics=topics, alpha=exp.alpha,
                         seed=exp.seed)
    print(f"  nnz={corpus.nnz} ({time.time() - t0:.1f}s)")

    devices = max(0, args.devices)
    if devices > 1:
        import jax

        avail = jax.local_device_count()
        if avail < devices:
            raise SystemExit(
                f"--devices {devices} requested but only {avail} local "
                "device(s) exist (off-TPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={devices} before "
                "launching)")

    cfg = SPCAConfig(max_sweeps=8, lam_search_evals=8,
                     chunk_nnz=args.chunk_nnz, chunk_rows=args.chunk_rows,
                     megabatch_chunks=args.megabatch,
                     batch_evals=args.batch_evals,
                     io_retries=args.io_retries,
                     resume_dir=args.resume or None,
                     checkpoint_every=args.checkpoint_every,
                     mesh_devices=devices,
                     solver_fallback=not args.no_solver_fallback,
                     pass_deadline_s=args.pass_deadline_s,
                     solve_deadline_s=args.solve_deadline_s)

    ingest: dict = {}
    store = None
    if args.streaming:
        from repro.sparse import write_corpus
        from repro.sparse.engine import sparse_stats
        from repro.sparse.mesh_engine import mesh_sparse_stats

        store_dir = args.store_dir or tempfile.mkdtemp(prefix="csr_store_")
        t0 = time.time()
        store = write_corpus(corpus, store_dir)
        mb = store.nnz * (4 + 4) / 1e6 + 8 * (store.n_rows + store.n_shards) / 1e6
        print(f"  wrote CSR store: {store.n_shards} shard(s), {mb:.1f} MB "
              f"at {store_dir} ({time.time() - t0:.1f}s)")
        t0 = time.time()
        pass_kw = dict(
            chunk_nnz=cfg.chunk_nnz, chunk_rows=cfg.chunk_rows,
            megabatch=cfg.megabatch_chunks,
            prefetch_depth=cfg.ingest_prefetch,
            impl=cfg.csr_impl, counters=ingest,
            io_retries=cfg.io_retries, io_backoff_s=cfg.io_backoff_s,
            resume_dir=cfg.resume_dir,
            checkpoint_every=cfg.checkpoint_every,
            pass_deadline_s=cfg.pass_deadline_s,
        )
        if devices > 1 and cfg.data_parallel:
            print(f"  sharding passes across {devices} device(s) "
                  "(1-D data mesh)")
            var, build = mesh_sparse_stats(store, devices=devices,
                                           min_devices=cfg.mesh_min_devices,
                                           **pass_kw)
        else:
            var, build = sparse_stats(store, **pass_kw)
        resumed = ingest.get("resumed_megabatches", 0)
        print(f"  out-of-core variance screen: {time.time() - t0:.1f}s "
              f"(one pass over {store.nnz} nnz, "
              f"{ingest.get('screen_launches', 0)} megabatch launch(es)"
              + (f", resumed {resumed} megabatch(es)" if resumed else "")
              + ")")
    else:
        mean, var = corpus.column_stats_exact()

        def build(support):
            import jax.numpy as jnp

            A = corpus.columns_dense(np.asarray(support))
            A = A - A.mean(0, keepdims=True)
            return jnp.asarray((A.T @ A) / corpus.n_docs)

    # The driver owns the cross-component pass economics (PR 5): ONE
    # eager Gram build on the union support serves every deflated search
    # via principal-submatrix slices — with --streaming that is ONE more
    # corpus pass for ALL components instead of one per component.
    t0 = time.time()
    diag: dict = {}
    results = fit_components(
        None, args.components, target_card=args.target_card, cfg=cfg,
        stats=(np.asarray(var), build), diagnostics=diag,
    )
    fit_s = time.time() - t0
    for c, (r, d) in enumerate(zip(results, diag["components"])):
        words = [corpus.vocab[i] for i in r.support]
        print(f"PC{c + 1}: card={r.cardinality} n_hat={r.reduced_n} "
              f"lam={r.lam:.3f} var={r.variance:.2f} gap={r.gap:.1e} "
              f"launches={d['solve_launches']} evals={d['evals']} "
              f"cov_builds={d['cov_builds']}")
        print("   " + ", ".join(words))
    print(f"total: {diag['solve_launches']} solve launch(es) across "
          f"{args.components} components in {fit_s:.1f}s; gram builds: "
          f"{diag['cov_builds']}")
    if args.streaming:
        passes = ingest.get("screen_passes", 0) + ingest.get("gram_passes", 0)
        print(f"corpus passes: {passes} "
              f"(screen={ingest.get('screen_passes', 0)} "
              f"gram={ingest.get('gram_passes', 0)}; old scheme: "
              f"{1 + args.components}), ingest launches: "
              f"{ingest.get('screen_launches', 0) + ingest.get('gram_launches', 0)} "
              f"over {ingest.get('chunks', 0)} chunk(s)")
    extras = []
    if ingest.get("resumed_megabatches"):
        extras.append(f"resumed {ingest['resumed_megabatches']} "
                      "megabatch(es) from checkpoint")
    fr = diag.get("fit_resume") or {}
    if fr.get("components_restored"):
        extras.append(f"restored {fr['components_restored']} completed "
                      "component(s) from fit checkpoint")
    if fr.get("evals_skipped"):
        extras.append(f"skipped {fr['evals_skipped']} already-solved "
                      "lambda eval(s)")
    if diag.get("solver_fallbacks"):
        extras.append(f"took {diag['solver_fallbacks']} solver "
                      "fallback(s) to the oracle path")
    if diag.get("mesh_degraded"):
        extras.append(f"degraded the device mesh {diag['mesh_degraded']} "
                      "time(s)")
    if ingest.get("io_retries"):
        extras.append(f"absorbed {ingest['io_retries']} transient "
                      "read error(s)")
    if extras:
        print("reliability: " + "; ".join(extras))
    return dict(results=results, diagnostics=diag, ingest=ingest,
                corpus=corpus, variances=np.asarray(var), store=store,
                cfg=cfg, fit_s=fit_s)


if __name__ == "__main__":
    main()
