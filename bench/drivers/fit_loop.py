"""Fit cells: whole streaming fits back to back, as the launcher's
``--streaming`` branch runs them (``spca_run._run``), through the library:

  1. ``SparseCorpus.open(store)``;
  2. ``sparse_stats(...)`` (or ``mesh_sparse_stats(..., devices=D)``): the
     screen pass;
  3. ``fit_components(None, K, target_card, cfg, stats=(var, build))``: the
     lambda searches, whose covariance cache calls ``build`` for the Gram
     pass on the union support.

Each fit reopens the store, so nothing carries over between fits.  Set-up
generates the corpus from the seed, writes the store (in a temporary
directory), and runs one whole warm-up fit.  The window runs fits until
``seconds`` have passed; the fit in progress then is finished and counted.
``fit_s`` is the window's fit wall time over its fit count.

The check compares the window's LAST fit with the reference
(bench/lib/reference.py): its screen variances, the Gram pass's output on
its support, and each accepted component: its explained variance, unit
loadings, a variance no less than about the strongest word left to it,
and no word shared with another component.  Each fit's search
path (cardinality, lambda, evaluations, launches, sweeps per component)
goes to stderr.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench.lib import gen as genlib
from bench.lib import harness, reference, xplane

SOLVER_SPANS = ("solver.solve", "solver.solve_many", "solver.device_grid",
                "solver.fallback")


class FitRunner:
    """Runs whole fits of one store with the cell's settings, and keeps
    what the last fit produced."""

    def __init__(self, store_path: str, config: dict, traffic: dict, *,
                 annotate: bool = False):
        from repro.core import SPCAConfig

        spca = {**config["spca"], **traffic.get("spca", {})}
        self.cfg = SPCAConfig(**spca)
        self.store_path = store_path
        self.job = config["job"]
        self.annotate = annotate
        self.last: dict = {}

    def fit(self) -> dict:
        from repro.core import fit_components
        from repro.sparse import SparseCorpus
        from repro.sparse.engine import sparse_stats
        from repro.sparse.mesh_engine import mesh_sparse_stats

        cfg = self.cfg
        store = SparseCorpus.open(self.store_path)
        ingest: dict = {}
        pass_kw = dict(
            chunk_nnz=cfg.chunk_nnz, chunk_rows=cfg.chunk_rows,
            megabatch=cfg.megabatch_chunks, prefetch_depth=cfg.ingest_prefetch,
            impl=cfg.csr_impl, counters=ingest, io_retries=cfg.io_retries,
            io_backoff_s=cfg.io_backoff_s,
        )
        with harness.annotate(self.annotate, "bench.screen"):
            if cfg.mesh_devices > 1 and cfg.data_parallel:
                var, build = mesh_sparse_stats(
                    store, devices=cfg.mesh_devices,
                    min_devices=cfg.mesh_min_devices, **pass_kw)
            else:
                var, build = sparse_stats(store, **pass_kw)
        grams: list = []

        def gram_pass(support):
            with harness.annotate(self.annotate, "bench.gram"):
                G = build(support)
            grams.append((np.asarray(support), G))
            return G

        diag: dict = {}
        results = fit_components(
            None, self.job["components"], target_card=self.job["target_card"],
            cfg=cfg, stats=(np.asarray(var), gram_pass), diagnostics=diag)
        self.last = {"var": var, "grams": grams, "results": results,
                     "diag": diag, "ingest": ingest}
        return self.last


def _sum_k2(csr: genlib.CSR, support: np.ndarray) -> float:
    """Sum over documents of (entries on ``support``)^2."""
    on = np.zeros(int(max(csr.cols.max(initial=0), support.max(initial=0))) + 1,
                  bool)
    on[support] = True
    rows = np.repeat(np.arange(csr.n_rows), np.diff(csr.row_ptr))
    k = np.bincount(rows[on[csr.cols]], minlength=csr.n_rows).astype(np.float64)
    return float(np.sum(k * k))


def outputs(last: dict) -> dict:
    """What a fit produced, as plain host arrays: screen variances, each
    Gram pass's (support, Gram), and each component's support, loadings on
    it, the norm of its loadings off it, lambda and reported explained
    variance."""
    comps = []
    for r in last["results"]:
        x = np.asarray(r.x, np.float64)
        sup = np.asarray(r.support)
        off = np.delete(x, sup)
        comps.append({"support": sup, "x": x[sup],
                      "off": float(np.linalg.norm(off)), "lam": float(r.lam),
                      "variance": float(r.variance)})
    return {
        "var": np.asarray(last["var"], np.float64),
        "grams": [(s, np.asarray(G, np.float64)) for s, G in last["grams"]],
        "comps": comps,
    }


def search_log(last: dict) -> str:
    """Each component's cardinality, lambda, search evaluations, solver
    launches and sweeps: the path the lambda searches took."""
    per = last["diag"].get("components", [])
    parts = []
    for k, r in enumerate(last["results"]):
        d = per[k] if k < len(per) else {}
        parts.append(f"card {r.cardinality} lam {r.lam:.6g} evals "
                     f"{d.get('evals', '?')} solves "
                     f"{d.get('solve_launches', '?')} sweeps "
                     f"{d.get('total_sweeps', '?')}")
    return "; ".join(parts)


def control_outputs(csr: genlib.CSR, n_words: int, prog: dict) -> dict:
    """The control: the reference computed in bfloat16 put in the
    program's place, on the program's supports, lambdas and loadings."""
    var, mean = reference.screen(csr.values, csr.cols, csr.n_rows, n_words,
                                 precision="bf16")
    grams = [(s, reference.gram(csr.values, csr.cols, csr.row_ptr, s, mean,
                                precision="bf16")) for s, _ in prog["grams"]]
    comps = []
    for c in prog["comps"]:
        G = reference.gram(csr.values, csr.cols, csr.row_ptr, c["support"],
                           mean, precision="bf16")
        xb = reference.rounded(c["x"], "bf16")
        comps.append({**c, "x": xb,
                      "variance": float(reference.rounded(xb @ G @ xb, "bf16"))})
    return {"var": var, "grams": grams, "comps": comps}


def readings(csr: genlib.CSR, n_words: int, prog: dict) -> dict:
    """The numbers compared for ``correct``, for one fit's ``outputs``."""
    var_ref, mean_ref = reference.screen(csr.values, csr.cols, csr.n_rows,
                                         n_words)
    out = {"screen_rel_err": reference.screen_rel_err(prog["var"], var_ref)}
    gram_err, refs = 0.0, []
    for support, G in prog["grams"]:
        G_ref = reference.gram(csr.values, csr.cols, csr.row_ptr, support,
                               mean_ref)
        refs.append((support, G_ref))
        gram_err = max(gram_err, reference.gram_rel_err(G, G_ref))
    out["gram_rel_err"] = gram_err

    def sigma_of(sup):
        for base, G_ref in refs:
            pos = np.minimum(np.searchsorted(base, sup), base.size - 1)
            if np.array_equal(base[pos], sup):
                return G_ref[np.ix_(pos, pos)]
        return reference.gram(csr.values, csr.cols, csr.row_ptr, sup, mean_ref)

    comps = prog["comps"]
    out["pc_var_rel_err"] = reference.pc_var_rel_err(comps, sigma_of)
    out["pc_norm_err"] = reference.pc_norm_err(comps)
    out["pc_top_ratio"] = reference.pc_top_ratio(comps, var_ref, sigma_of)
    out["overlap"] = reference.overlap(comps)
    return out


def diagnostics(csr: genlib.CSR, n_words: int, prog: dict, *,
                margin: float) -> dict:
    """Read beside the numbers compared, not compared: ``eig_gap`` and
    ``below_lam`` of bench/lib/reference.py."""
    var_ref, mean_ref = reference.screen(csr.values, csr.cols, csr.n_rows,
                                         n_words)
    return {"eig_gap": reference.eig_gap(prog["comps"], lambda sup:
                                         reference.gram(csr.values, csr.cols,
                                                        csr.row_ptr, sup,
                                                        mean_ref)),
            "below_lam": reference.below_lam(prog["comps"], var_ref,
                                             margin=margin)}


def margin_of(cell) -> float:
    return float({**cell.config.get("checks", {}),
                  **cell.traffic.get("checks", {})}["screen_rel_err"])


def run(cell, *, seed: int, seconds: float, trace: bool, clog,
        t_start: float) -> dict:
    from repro.obs import metrics
    from repro.obs import trace as otrace

    config, traffic = cell.config, cell.traffic
    shape = genlib.Shape.from_config(config["corpus"])
    tmp = tempfile.mkdtemp(prefix="bench_fit_")
    try:
        t0 = time.perf_counter()
        csr = genlib.corpus(shape, seed)
        gen_s = time.perf_counter() - t0
        write_s = genlib.write_store(csr, f"{tmp}/store", shape.words)
        harness.log(f"setup: generated {csr.nnz} nnz over {csr.n_rows} docs "
                    f"({csr.nnz / csr.n_rows:.1f} per doc) in {gen_s:.2f}s, "
                    f"store written in {write_s:.2f}s")
        runner = FitRunner(f"{tmp}/store", config, traffic, annotate=trace)
        t0 = time.perf_counter()
        try:
            warm = runner.fit()
        except Exception as e:              # reported, not raised: see below
            harness.log(f"setup: warm-up fit failed: {e!r}")
            return {"attempted": 1, "failed": 1, "device":
                    harness.device_info(cell.chips),
                    "readings": {k: float("inf") for k in config["checks"]},
                    "e2e": {"setup_s": time.perf_counter() - t_start,
                            "fit_s": float("inf")}, "layer_ctx": None}
        n_hat = [int(s.size) for s, _ in warm["grams"]]
        ing = warm["ingest"]
        harness.log(f"setup: warm-up fit {time.perf_counter() - t0:.2f}s; "
                    f"n_hat {n_hat}; launches screen "
                    f"{ing.get('screen_launches', 0)} gram "
                    f"{ing.get('gram_launches', 0)}; "
                    f"solver fallbacks {warm['diag'].get('solver_fallbacks', 0)}")
        harness.log(f"setup: warm-up search: {search_log(warm)}")
        setup = clog.snapshot()

        metrics.reset()
        tracer = otrace.install(otrace.Tracer()) if trace else None
        trace_dir = None
        if trace:
            import jax

            trace_dir = tempfile.mkdtemp(prefix="bench_trace_", dir=tmp)
            jax.profiler.start_trace(trace_dir)
        fits, attempted, failed, grams_seen, searches = [], 0, 0, [], []
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        with harness.annotate(trace, "bench.window"):
            while True:
                attempted += 1
                f0 = time.perf_counter()
                try:
                    last = runner.fit()
                except Exception as e:          # a fit that fails is counted
                    failed += 1
                    harness.log(f"window: fit {attempted} failed: {e!r}")
                    break
                fits.append(time.perf_counter() - f0)
                searches.append(search_log(last))
                grams_seen.extend(s for s, _ in last["grams"])
                if time.perf_counter() - w0 >= seconds:
                    break
        w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            otrace.install(None)
        in_window = clog.snapshot()
        harness.log(f"window: {len(fits)} fit(s) in {w1 - w0:.2f}s "
                    f"(each {', '.join(f'{f:.3f}' for f in fits)}s); "
                    f"compiles inside the window "
                    f"{in_window['compiles'] - setup['compiles']}, cache "
                    f"loads {in_window['cache_hits'] - setup['cache_hits']}")
        for i, line in enumerate(searches):
            harness.log(f"window: fit {i + 1} search: {line}")
        harness.log(f"setup: {setup_s:.2f}s; compile {setup['compile_s']:.2f}s "
                    f"over {setup['compiles']} compile(s), persistent cache "
                    f"{setup['cache_hits']} hit(s) / {setup['cache_misses']} "
                    "miss(es)")
        device = harness.device_info(cell.chips)
        last = runner.last
        registry = metrics.get_registry()
        del runner
        gc.collect()

        t0 = time.perf_counter()
        if fits:
            read = readings(csr, shape.words, outputs(last))
        else:
            read = {k: float("inf") for k in config["checks"]}
        harness.log(f"reference: {time.perf_counter() - t0:.2f}s")

        out = {"attempted": attempted, "failed": failed, "readings": read,
               "device": device,
               "e2e": {"setup_s": setup_s,
                       "fit_s": sum(fits) / len(fits) if fits else float("inf")}}
        if trace:
            out.update(_layer(cell, csr, shape, setup, registry, tracer,
                              trace_dir, w0, w1, len(fits), grams_seen,
                              device["kind"]))
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _layer(cell, csr, shape, setup, registry, tracer, trace_dir, w0, w1,
           n_fits, grams_seen, device_kind) -> dict:
    path = xplane.find_xplane(trace_dir)
    dev = None
    if path:
        tr = xplane.load(path)
        win = xplane.annotations(tr["host"], "bench.window")
    spans = harness.span_table(tracer, w0, w1)
    if path and win:
        _, lo, hi = win[0]
        off = lo - int(w0 * 1e9)        # perf_counter -> trace clock
        extra = [("solve" if n != "solver.fallback" else "fallback",
                  int(s * 1e9) + off, int(e * 1e9) + off)
                 for n in SOLVER_SPANS for s, e in spans.get(n, [])]
        dev = xplane.reduce(tr, lo, hi, "bench.", extra_labels=extra)
    k2: dict = {}
    gram_work = [0.0, 0.0]
    for sup in grams_seen:
        key = tuple(sup.tolist())
        if key not in k2:
            k2[key] = _sum_k2(csr, sup)
        f, b = cell.work("csr_gram")(nnz=csr.nnz, n_hat=sup.size,
                                     sum_k2=k2[key])
        gram_work[0] += f
        gram_work[1] += b
    f, b = cell.work("csr_stats")(nnz=csr.nnz, n=shape.words)
    screens = len(spans.get("ingest.screen_pass", [])) or n_fits
    work = {"screen": (f * screens, b * screens), "gram": tuple(gram_work)}
    ctx = harness.layer_context(cell, spans=spans, registry=registry,
                                units=n_fits, setup=setup, device=dev,
                                work=work, device_kind=device_kind)
    return {"layer_ctx": ctx, "trace": dev}

