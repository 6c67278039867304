"""Serve cells: an open-loop Poisson stream of documents through the topic
server, at a rate fixed in the traffic file, with ``serve_topics``'
settings: ``MicroBatcher(TopicProjector(pack_components(...)), n,
BatcherConfig(max_batch, max_wait_ms))``.

The model is made from the seed: ``topics`` sparse components of
``card`` words each over the configuration's vocabulary, on supports drawn
from its ``rank_pool`` most frequent words, with unit-norm random
loadings.  The documents come from the configuration's generator.

Set-up makes the model and every document, starts the batcher (which
compiles its one batch shape) and sends ``warmup_s`` of traffic.  The
window sends the requests due in [0, seconds) on their schedule;
``serve_p95_ms`` is the 95th percentile of ALL of them, each timed from
its due time, a failed or unanswered one counting as missing, and
``served_docs_per_s`` the answers that arrived inside the window over its
length.  Every answer is then checked against the reference projection.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import gen as genlib
from bench.lib import harness, openloop, reference, xplane


def make_model(gen: genlib.Generator, traffic: dict, rng: np.random.Generator):
    """PCResult-shaped components: (support word ids, loadings) per topic."""
    from repro.core.spca import PCResult

    k, card = traffic["topics"], traffic["card"]
    ranks = rng.choice(traffic["rank_pool"], size=k * card, replace=False)
    words = gen.word_of_rank[ranks].reshape(k, card)
    out = []
    for c in range(k):
        sup = np.sort(words[c]).astype(np.int64)
        load = rng.standard_normal(card)
        load /= np.linalg.norm(load)
        x = np.zeros(gen.shape.words)
        x[sup] = load
        out.append(PCResult(x=x, support=sup, lam=0.0, variance=0.0,
                            cardinality=card, reduced_n=card, gap=0.0))
    return out


class _Annotated:
    """The projector with each batch inside a profiler annotation, so the
    device trace attributes the projection kernel to serving batches.  The
    batch is waited for inside the annotation: the projection is
    dispatched asynchronously, and a kernel that starts after the
    annotation closed would not be attributed."""

    def __init__(self, projector):
        self._p = projector

    def project(self, X):
        import jax

        with jax.profiler.TraceAnnotation("bench.batch"):
            return jax.block_until_ready(self._p.project(X))


def _requests(csr: genlib.CSR):
    rp = csr.row_ptr
    return [(csr.cols[rp[i]:rp[i + 1]], csr.values[rp[i]:rp[i + 1]])
            for i in range(csr.n_rows)]


def readings(docs: genlib.CSR, pack, n_words: int, ok: list, scores,
             unanswered: int) -> dict:
    """The numbers compared for ``correct``: the answered requests' scores
    against the reference projection, and the requests never answered."""
    ref, mags = reference.project(docs.values, docs.cols, docs.row_ptr,
                                  pack.support_idx, pack.values, n_words)
    return {"score_rel_err": reference.score_rel_err(scores, ref[ok], mags[ok]),
            "unanswered": unanswered}


def control_scores(docs: genlib.CSR, pack, n_words: int):
    """The control: the reference projection in bfloat16 in the program's
    place."""
    return reference.project(docs.values, docs.cols, docs.row_ptr,
                             pack.support_idx, pack.values, n_words,
                             precision="bf16")[0]


def run(cell, *, seed: int, seconds: float, trace: bool, clog,
        t_start: float) -> dict:
    from repro.obs import metrics
    from repro.obs import trace as otrace
    from repro.serve import (BatcherConfig, MicroBatcher, TopicProjector,
                             pack_components)

    config, traffic = cell.config, cell.traffic
    shape = genlib.Shape.from_config(config["corpus"])
    g = genlib.Generator(shape, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5E7E]))
    results = make_model(g, traffic, rng)
    pack = pack_components(results, n_features=shape.words)
    rate = float(traffic["rate_per_s"])
    warm_due = openloop.poisson_schedule(rng, rate, traffic["warmup_s"])
    due = openloop.poisson_schedule(rng, rate, seconds)
    t0 = time.perf_counter()
    warm_docs = g.block(1, warm_due.size)
    docs = g.block(2, due.size)
    harness.log(f"setup: model {pack.k} topics x {pack.cap} slots; "
                f"{due.size} requests at {rate:g}/s "
                f"({docs.nnz / max(docs.n_rows, 1):.1f} words per doc), "
                f"made in {time.perf_counter() - t0:.2f}s")

    projector = TopicProjector(pack)
    served = _Annotated(projector) if trace else projector
    cfg = BatcherConfig(max_batch=traffic["max_batch"],
                        max_wait_ms=traffic["max_wait_ms"])
    batcher = MicroBatcher(served, shape.words, cfg).start()
    try:
        warm = openloop.drive(lambda r: batcher.submit(*r), _requests(warm_docs),
                              warm_due, time.perf_counter())
        openloop.wait(warm, time.perf_counter() + 60)
        setup = clog.snapshot()
        metrics.reset()
        tracer = otrace.install(otrace.Tracer()) if trace else None
        trace_dir = None
        if trace:
            import tempfile

            import jax

            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
        reqs = _requests(docs)
        w0 = time.perf_counter() + 0.01
        setup_s = w0 - t_start
        with harness.annotate(trace, "bench.window"):
            res = openloop.drive(lambda r: batcher.submit(*r), reqs, due, w0)
            w1 = w0 + seconds
            openloop.wait(res, w1 + 60)
        if trace:
            jax.profiler.stop_trace()
            otrace.install(None)
        registry = metrics.get_registry()
    finally:
        batcher.stop()
    in_window = clog.snapshot()
    lat = res.latencies()
    attempted = len(lat)
    failed = int(sum(1 for v in lat if v is None))
    answered_in = int(np.sum(~res.failed & (res.done <= w1)))
    late = res.lateness()
    p95 = openloop.percentile(lat, 95)
    harness.log(f"window: {attempted} requests, {failed} failed or "
                f"unanswered, p50 {openloop.percentile(lat, 50) * 1e3:.3f}ms "
                f"p95 {p95 * 1e3:.3f}ms p99 "
                f"{openloop.percentile(lat, 99) * 1e3:.3f}ms; generator late "
                f"by p50 {np.median(late) * 1e3:.3f}ms, max "
                f"{np.max(late, initial=0) * 1e3:.3f}ms; compiles inside the "
                f"window {in_window['compiles'] - setup['compiles']}")
    harness.log(f"setup: {setup_s:.2f}s; compile {setup['compile_s']:.2f}s "
                f"over {setup['compiles']} compile(s), persistent cache "
                f"{setup['cache_hits']} hit(s) / {setup['cache_misses']} "
                "miss(es)")
    device = harness.device_info(cell.chips)
    del projector, served, batcher

    t0 = time.perf_counter()
    ok = [i for i, f in enumerate(res.futures)
          if f is not None and f.done() and f.exception() is None]
    scores = np.stack([np.asarray(res.futures[i].result(), np.float64)
                       for i in ok]) if ok else np.zeros((0, pack.k))
    read = readings(docs, pack, shape.words, ok, scores, failed)
    harness.log(f"reference: {len(ok)} answers in "
                f"{time.perf_counter() - t0:.2f}s")
    out = {"attempted": attempted, "failed": failed, "readings": read,
           "device": device,
           "e2e": {"setup_s": setup_s, "serve_p95_ms": p95 * 1e3,
                   "served_docs_per_s": answered_in / seconds}}
    if trace:
        out.update(_layer(cell, registry, tracer, trace_dir, w0, w1 + 60,
                          setup, pack, device["kind"]))
    return out


def _layer(cell, registry, tracer, trace_dir, w0, w1, setup, pack,
           device_kind) -> dict:
    import shutil

    dev = None
    path = xplane.find_xplane(trace_dir)
    if path:
        tr = xplane.load(path)
        win = xplane.annotations(tr["host"], "bench.window")
        if win:
            _, lo, hi = win[0]
            dev = xplane.reduce(tr, lo, hi, "bench.")
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans = harness.span_table(tracer, w0, w1)
    h = registry.get("serve.batch_size")
    rows = int(h.snapshot()["sum"]) if h is not None else 0
    f, b = cell.work("project")(rows=rows, slots=pack.k * pack.cap, k=pack.k)
    ctx = harness.layer_context(
        cell, spans=spans, registry=registry,
        units=len(spans.get("serve.batch", [])), setup=setup, device=dev,
        work={"project": (f, b)}, device_kind=device_kind,
        extra={"max_batch": cell.traffic["max_batch"]})
    return {"layer_ctx": ctx, "trace": dev}
