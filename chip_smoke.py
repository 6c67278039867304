#!/usr/bin/env python3
"""Smoke test of the system's main path on a TPU, in ONE process.

    python chip_smoke.py             # one chip: device, kernels, fit, serve
    python chip_smoke.py --chips 4   # the 4-device mesh fit vs 1-device fit

Phases (one chip):

  device   the first JAX device must be a TPU — there is no CPU fallback;
  kernels  every main-path Pallas kernel, compiled for the chip, at
           NYTimes widths, against a numpy f64 host reference;
  fit      ``spca_run --streaming --corpus nytimes --docs 300000`` at the
           full 102,660-word vocabulary, 5 components of cardinality 5,
           default chunk geometry, twice (cold, then warm), checked against
           a reference fit on the same store on the host CPU (host CSR
           reductions + the jnp solver);
  serve    ``serve_topics --words 102660``: fit, register, serve 4,000
           queries in batches of 64, drift check; one batch of projector
           scores against a numpy f64 projection.

``--chips 4`` runs only the mesh fit (``--devices 4 --batch-evals B``)
and the single-device fit it must equal (``--batch-evals 4B``).

Every check raises; the last line of stdout is one JSON object
``{"ok": true, "device": {...}}`` and is printed only when all phases pass.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
VOCAB = 102_660                     # NYTimes vocabulary (paper Table 1)
CHUNK_NNZ, CHUNK_ROWS, MEGABATCH = 16_384, 512, 8
COMPONENTS, TARGET_CARD = 5, 5      # paper Table 1
FIT_DOCS, MESH_DOCS = 300_000, 100_000
# kernels phase: (name, n_hat, chunks) Gram cases and (scheme, B, n_pad,
# supports) fused-solve cases
GRAM_CASES = (("csr_gram_batched", 384, MEGABATCH), ("csr_gram", 1536, 1))
BCD_CASES = (("resident", 4, 256, (256, 200, 97, 31)),
             ("tiled", 1, 640, (640,)))
VAR_RTOL = 1e-3                     # explained variance vs the reference fit


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- compile log


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.compile_s, self.compiles, self.cache_hits,
                self.cache_misses)

    def since(self, mark) -> str:
        s, n, h, m = mark
        return (f"compile {self.compile_s - s:.1f}s over "
                f"{self.compiles - n} compile(s), persistent cache "
                f"{self.cache_hits - h} hit(s) / {self.cache_misses - m} "
                "miss(es)")


# ---------------------------------------------------------------------- device


def device_phase(chips: int) -> dict:
    import importlib.metadata

    import jax

    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"first JAX device is {d0.platform!r}, not a TPU")
    check(len(devs) >= chips,
          f"{chips} chip(s) asked for, JAX sees {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    log(f"device: {d0.device_kind} x{len(devs)} (platform {d0.platform}); "
        f"jax {jax.__version__}, libtpu {libtpu}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------- kernels


def _run_compiled(fn, *args):
    """jit + compile for the default (TPU) device, check the program holds a
    Mosaic kernel — never interpret mode — and run it."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "compiled program holds no TPU kernel")
    return jax.block_until_ready(compiled(*args))


def _entries(rng, C, n_cols, n_rows, fill):
    """A (C, CHUNK_NNZ) megabatch in store layout: ``fill`` real entries per
    chunk (integer counts, sorted rows), zero padding after."""
    import numpy as np

    vals = np.zeros((C, CHUNK_NNZ), np.float32)
    cols = np.zeros((C, CHUNK_NNZ), np.int32)
    segs = np.zeros((C, CHUNK_NNZ), np.int32)
    vals[:, :fill] = rng.integers(1, 40, (C, fill))
    cols[:, :fill] = rng.integers(0, n_cols, (C, fill))
    segs[:, :fill] = np.sort(rng.integers(0, n_rows, (C, fill)), axis=1)
    return vals, cols, segs


def _gram_f64(vals, cols, segs, n_rows, n_hat):
    import numpy as np

    G = np.zeros((n_hat, n_hat))
    for c in range(vals.shape[0]):
        keep = cols[c] < n_hat
        B = np.zeros((n_rows, n_hat))
        np.add.at(B, (segs[c][keep], cols[c][keep]),
                  vals[c][keep].astype(np.float64))
        G += B.T @ B
    return G


def _bcd_host(Sigmas, lams, betas, n_valids, dtype, *, max_sweeps,
              qp_sweeps):
    """The masked BCD oracle in ``dtype`` on the host CPU."""
    import jax
    import numpy as np

    from repro.kernels import ref

    X0 = np.stack([np.diag((np.arange(Sigmas.shape[1]) < nv).astype(dtype))
                   for nv in n_valids])
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        out = jax.jit(
            ref.bcd_solve_batched_ref,
            static_argnames=("max_sweeps", "qp_sweeps", "tau_iters"),
        )(np.asarray(Sigmas, dtype), np.asarray(lams, dtype),
          np.asarray(betas, dtype), X0, dtype(-1.0),
          np.asarray(n_valids, np.int32),
          max_sweeps=max_sweeps, qp_sweeps=qp_sweeps)
        return [np.asarray(o, np.float64) for o in out]


def kernels_phase() -> None:
    import numpy as np

    from repro.kernels.bcd_fused import bcd_solve_batched_pallas
    from repro.kernels.csr_gram import csr_gram_batched_pallas, csr_gram_pallas
    from repro.kernels.csr_stats import csr_column_stats_pallas
    from repro.kernels.project import sparse_project_pallas

    rng = np.random.default_rng(0)

    # screen: per-column (sum, sumsq) of integer counts is exact in f32
    fill = CHUNK_NNZ - CHUNK_NNZ // 8
    vals, cols, _ = _entries(rng, MEGABATCH, VOCAB, CHUNK_ROWS, fill)
    s, ss = _run_compiled(lambda v, c: csr_column_stats_pallas(v, c, VOCAB),
                          vals, cols)
    v64 = vals.astype(np.float64).ravel()
    s_ref = np.bincount(cols.ravel(), weights=v64, minlength=VOCAB)
    ss_ref = np.bincount(cols.ravel(), weights=v64 * v64, minlength=VOCAB)
    check(np.array_equal(np.asarray(s, np.float64), s_ref)
          and np.array_equal(np.asarray(ss, np.float64), ss_ref),
          "csr_column_stats: count sums are not exact "
          f"(max err {np.max(np.abs(np.asarray(ss, np.float64) - ss_ref))})")
    log(f"kernel csr_column_stats (C={MEGABATCH}, E={CHUNK_NNZ}, "
        f"n={VOCAB}): exact vs f64 bincount")

    # gram: the megabatch kernel and the single-chunk large-support kernel
    for name, n_hat, C in GRAM_CASES:
        vals, cols, segs = _entries(rng, C, n_hat + 64, CHUNK_ROWS, fill)
        if C == 1:
            G = _run_compiled(
                lambda v, c, s: csr_gram_pallas(v, c, s, CHUNK_ROWS, n_hat),
                vals[0], cols[0], segs[0])
        else:
            G = _run_compiled(
                lambda v, c, s: csr_gram_batched_pallas(v, c, s, CHUNK_ROWS,
                                                        n_hat),
                vals, cols, segs)
        G_ref = _gram_f64(vals, cols, segs, CHUNK_ROWS, n_hat)
        err = np.max(np.abs(np.asarray(G, np.float64) - G_ref))
        check(err <= 1e-6 * np.max(np.abs(G_ref)),
              f"{name}: max err {err:.3g} vs f64 Gram")
        log(f"kernel {name} (C={C}, E={CHUNK_NNZ}, R={CHUNK_ROWS}, "
            f"n_hat={n_hat}): max err {err:.3g} of {np.max(G_ref):.3g}")

    # fused BCD: both schemes, a batch with mixed supports; f32 chip vs f64
    for scheme, B, n, sizes in BCD_CASES:
        Sig = np.zeros((B, n, n), np.float32)
        lams, betas = [], []
        for b, nv in enumerate(sizes):
            F = rng.normal(size=(4 * nv, nv))
            S = (F.T @ F) / (4 * nv)
            Sig[b, :nv, :nv] = S
            lams.append(0.3 * S.diagonal().max())
            betas.append(1e-4 * np.trace(S) / nv)
        X0 = np.stack([np.diag((np.arange(n) < nv).astype(np.float32))
                       for nv in sizes])
        kw = dict(max_sweeps=3, qp_sweeps=2)
        X, obj, sweeps, hist = _run_compiled(
            lambda S, L, Bt, X0, N: bcd_solve_batched_pallas(
                S, L, Bt, X0, -1.0, N, scheme=scheme, panel_rows=128, **kw),
            Sig, np.float32(lams), np.float32(betas), X0,
            np.int32(sizes))
        X_ref, _, _, h_ref = _bcd_host(Sig, lams, betas, sizes, np.float64,
                                       **kw)
        X_32, _, _, h_32 = _bcd_host(Sig, lams, betas, sizes, np.float32,
                                     **kw)

        def errs(X, h):
            return (np.max(np.abs(np.asarray(X, np.float64) - X_ref)),
                    np.max(np.abs(np.asarray(h, np.float64) - h_ref)
                           / np.abs(h_ref)))

        err, herr = errs(X, hist)
        err32, herr32 = errs(X_32, h_32)
        check(np.all(np.asarray(sweeps) == kw["max_sweeps"]),
              f"bcd {scheme}: sweeps {np.asarray(sweeps)}")
        # The f32 coordinate recursion drifts from the f64 trajectory by
        # order-of-summation noise; the kernel may drift no more than twice
        # as far as the same oracle run in f32 on the host.
        scale = np.max(np.abs(X_ref))
        check(err <= 2 * err32 + 1e-6 * scale
              and herr <= 2 * herr32 + 1e-6,
              f"bcd {scheme}: X max err {err:.3g} (host f32 {err32:.3g}) of "
              f"{scale:.3g}, objective rel err {herr:.3g} (host f32 "
              f"{herr32:.3g}) vs the f64 host oracle")
        log(f"kernel bcd_fused {scheme} (B={B}, n={n}): X max err "
            f"{err:.3g} of {scale:.3g} (host f32 oracle {err32:.3g}), "
            f"objective rel err {herr:.3g} (host f32 {herr32:.3g}) vs the "
            "f64 host oracle")

    # serving projector: a 64-doc batch at full vocabulary
    k, cap, B = 5, 8, 64
    X = np.zeros((B, VOCAB), np.float32)
    X[rng.integers(0, B, 20_000), rng.integers(0, VOCAB, 20_000)] = \
        rng.integers(1, 9, 20_000)
    idx = rng.integers(0, VOCAB, (k, cap)).astype(np.int32)
    loads = rng.normal(size=(k, cap)).astype(np.float32)
    loads[:, 6:] = 0.0                                  # padded slots
    out = _run_compiled(
        lambda X, i, c, v: sparse_project_pallas(X, i, c, v, k), X,
        idx.reshape(-1), np.repeat(np.arange(k, dtype=np.int32), cap),
        loads.reshape(-1))
    ref = X.astype(np.float64)[:, idx.reshape(-1)].reshape(B, k, cap)
    ref = np.einsum("bkc,kc->bk", ref, loads.astype(np.float64))
    err = np.max(np.abs(np.asarray(out, np.float64) - ref))
    check(err <= 1e-5 * max(1.0, np.max(np.abs(ref))),
          f"sparse_project: max err {err:.3g}")
    log(f"kernel sparse_project (B={B}, n={VOCAB}, k={k}): max err "
        f"{err:.3g}")


# ------------------------------------------------------------------------- fit


def _fit(argv: list[str], clog: CompileLog) -> dict:
    """One in-process ``spca_run.main`` with a fresh registry and tracer;
    returns its output plus the metrics and spans of that call only."""
    from repro.launch import spca_run
    from repro.obs import metrics, trace

    metrics.reset()
    tracer = trace.install(trace.Tracer())
    mark = clog.mark()
    t0 = time.perf_counter()
    try:
        out = spca_run.main(argv)
    finally:
        trace.install(None)
    out["wall_s"] = time.perf_counter() - t0
    out["compile"] = clog.since(mark)
    out["metrics"] = metrics.get_registry()
    out["spans"] = [sp for name in ("solver.solve", "solver.solve_many",
                                     "solver.device_grid")
                     for sp in tracer.find(name)]
    return out


def _check_fit(out: dict, *, devices: int = 1) -> None:
    reg = out["metrics"]
    ingest = out["ingest"]
    passes = ingest.get("screen_passes", 0) + ingest.get("gram_passes", 0)
    check(passes == 2, f"{passes} corpus passes, expected 2")
    impls = {sp.attrs.get("impl") for sp in out["spans"]}
    check(out["spans"] and impls == {"fused"},
          f"solves ran impl={sorted(map(str, impls))}, not fused only")
    if devices == 1:    # the mesh passes call the kernels inside shard_map
        for op in ("csr_column_stats", "csr_gram_batched"):
            check(reg.value(f"kernel.launches.{op}") > 0,
                  f"kernel.launches.{op} is 0")
    for c in ("solver.fallbacks", "solver.nonfinite", "mesh.degraded"):
        check(reg.value(c) == 0, f"{c} = {reg.value(c)}")
    check(out["diagnostics"].get("solver_fallbacks", 0) == 0,
          "the fit took solver fallbacks")


def _reference_fit(out: dict):
    """The same fit on the same store, on the host CPU: numpy/scipy CSR
    reductions and the jnp solver."""
    import jax

    from repro.core import fit_components
    from repro.sparse.engine import sparse_stats

    cfg = dataclasses.replace(out["cfg"], csr_impl="host", solver_impl="jnp",
                              mesh_devices=0, batch_evals=0)
    with jax.default_device(jax.devices("cpu")[0]):
        stats = sparse_stats(out["store"], impl="host", chunk_nnz=CHUNK_NNZ,
                             chunk_rows=CHUNK_ROWS, megabatch=MEGABATCH)
        return fit_components(None, COMPONENTS, target_card=TARGET_CARD,
                              cfg=cfg, stats=stats)


def _compare(results, ref, what: str) -> list[str]:
    check(len(results) == len(ref), f"{what}: component count differs")
    lines = []
    for c, (r, q) in enumerate(zip(results, ref)):
        check(set(r.support.tolist()) == set(q.support.tolist()),
              f"{what}: PC{c + 1} support {sorted(r.support.tolist())} != "
              f"{sorted(q.support.tolist())}")
        rel = abs(r.variance - q.variance) / abs(q.variance)
        check(rel <= VAR_RTOL,
              f"{what}: PC{c + 1} variance {r.variance:.6g} vs "
              f"{q.variance:.6g} (rel {rel:.2g} > {VAR_RTOL})")
        lines.append(f"PC{c + 1} card={r.cardinality} "
                     f"var={r.variance:.6g} rel_err={rel:.2g}")
    return lines


def fit_phase(clog: CompileLog, store_dir: str) -> None:
    import numpy as np

    # The 8-sweep budget stalls most solves (see CHANGES.md).  With the
    # fallback ladder on, every stall is re-solved on the jnp program and
    # the components would come from there, not from the kernel; off, every
    # component is the fused kernel's and stalls stay counted.
    argv = ["--streaming", "--corpus", "nytimes", "--docs", str(FIT_DOCS),
            "--words", str(VOCAB), "--components", str(COMPONENTS),
            "--target-card", str(TARGET_CARD),
            "--chunk-nnz", str(CHUNK_NNZ), "--chunk-rows", str(CHUNK_ROWS),
            "--megabatch", str(MEGABATCH), "--no-solver-fallback",
            "--store-dir", store_dir]
    cold = _fit(argv, clog)
    _check_fit(cold)
    _, var_exact = cold["corpus"].column_stats_exact()
    # exact f32 count sums folded in f64; the screen hands back f32
    var_err = np.abs(cold["variances"] - var_exact)
    check(np.all(var_err <= 2.0 ** -23 * np.abs(var_exact)),
          "screen variances differ from corpus.column_stats_exact() beyond "
          f"f32 rounding (max err {np.max(var_err):.3g})")
    stalled = cold["metrics"].value("solver.stalled")
    log(f"fit: nnz={cold['corpus'].nnz} cold wall {cold['wall_s']:.1f}s "
        f"(fit {cold['fit_s']:.1f}s; {cold['compile']}); "
        f"{len(cold['spans'])} fused solve(s), {int(stalled)} stalled at "
        f"the {cold['cfg'].max_sweeps}-sweep budget")
    warm = _fit(argv, clog)
    _check_fit(warm)
    log(f"fit: warm wall {warm['wall_s']:.1f}s (fit {warm['fit_s']:.1f}s; "
        f"{warm['compile']})")
    t0 = time.perf_counter()
    ref = _reference_fit(cold)
    for line in _compare(cold["results"], ref, "fit vs host reference"):
        log("  " + line)
    log(f"fit: supports identical to the host reference fit, variance "
        f"within {VAR_RTOL} ({time.perf_counter() - t0:.1f}s)")


# ----------------------------------------------------------------------- serve


def serve_phase(clog: CompileLog, registry_dir: str) -> None:
    import numpy as np

    from repro.launch import serve_topics
    from repro.obs import metrics

    metrics.reset()
    mark = clog.mark()
    t0 = time.perf_counter()
    out = serve_topics.main(["--words", str(VOCAB),
                             "--registry", registry_dir])
    check(metrics.get_registry().value("kernel.launches.sparse_project") > 0,
          "serving never traced the projector kernel")
    pack = out["model"].pack
    q = out["queries"]
    B = 64
    sel = q.doc_idx < B
    X = np.zeros((B, VOCAB), np.float32)
    np.add.at(X, (q.doc_idx[sel], q.word_idx[sel]), q.counts[sel])
    scores = np.asarray(out["model"].projector.project(X), np.float64)
    W = np.zeros((VOCAB, pack.k))
    for c in range(pack.k):
        np.add.at(W[:, c], pack.support_idx[c], pack.values[c])
    ref = X.astype(np.float64) @ W
    err = np.max(np.abs(scores - ref))
    check(err <= 1e-5 * max(1.0, np.max(np.abs(ref))),
          f"projector scores max err {err:.3g} vs numpy f64")
    log(f"serve: {out['served']} docs, p50={out['stats']['p50_ms']:.2f}ms "
        f"p99={out['stats']['p99_ms']:.2f}ms; projector batch max err "
        f"{err:.3g}; drift quiet in-distribution, flagged on shift "
        f"({time.perf_counter() - t0:.1f}s; {clog.since(mark)})")


# ------------------------------------------------------------------------ mesh


def mesh_phase(clog: CompileLog, store_dir: str, devices: int) -> None:
    B = 2
    base = ["--streaming", "--corpus", "nytimes", "--docs", str(MESH_DOCS),
            "--words", str(VOCAB), "--components", str(COMPONENTS),
            "--target-card", str(TARGET_CARD),
            "--no-solver-fallback", "--store-dir", store_dir]
    mesh = _fit(base + ["--devices", str(devices), "--batch-evals", str(B)],
                clog)
    _check_fit(mesh, devices=devices)
    reg = mesh["metrics"]
    check(reg.value("mesh.devices") == devices,
          f"mesh.devices = {reg.value('mesh.devices')}")
    lanes = [mesh["ingest"].get(f"shard_chunks.{d}", 0)
             for d in range(devices)]
    check(all(n > 0 for n in lanes), f"per-lane chunks {lanes}")
    log(f"mesh fit: {devices} devices, per-lane chunks {lanes}, wall "
        f"{mesh['wall_s']:.1f}s ({mesh['compile']})")
    one = _fit(base + ["--batch-evals", str(B * devices)], clog)
    _check_fit(one)
    for line in _compare(mesh["results"], one["results"],
                         "mesh vs single-device fit"):
        log("  " + line)
    log(f"mesh fit: supports identical to the 1-device --batch-evals "
        f"{B * devices} fit, variance within {VAR_RTOL}")


# ------------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh fit against the 1-device fit")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: src/repro not found next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # The reference fit runs on the host's CPU device beside the TPU.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    t0 = time.perf_counter()
    try:
        device = device_phase(args.chips)
        log(f"compile cache: {cache}")
        clog = CompileLog()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            if args.chips == 1:
                mark = clog.mark()
                kernels_phase()
                log(f"kernels: ok ({clog.since(mark)})")
                fit_phase(clog, os.path.join(tmp, "store"))
                serve_phase(clog, os.path.join(tmp, "registry"))
            else:
                mesh_phase(clog, os.path.join(tmp, "store"), args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
