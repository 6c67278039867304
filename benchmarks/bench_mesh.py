"""Device-mesh throughput: sharded streaming passes + device-grid solves.

The PR-9 claim in numbers: partitioning the megabatch stream across D
local devices (`sparse.mesh_engine`) turns ceil(B) per-pass dispatches
into ceil(B/D) — each sharded dispatch covers D megabatches — and
splitting a lambda-grid batch across D devices
(`ops.bcd_solve_batched(devices=D)`) turns ceil(E/B) solve launches into
ceil(E/(B*D)).  On a single-core CPU host the win is pure launch
amortization (device_put + dispatch + sync overhead per call), so the
bench geometry is deliberately dispatch-dominated: tiny chunks, megabatch
of one, many megabatches.  On a real mesh the same rows additionally show
the compute split.

On CPU the device count is locked at first jax init, so the parent
(already running under run.py's single-device jax) spawns ONE child
process with ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` that
prints ``ROW {json}`` lines; a child failure yields no rows rather than a
crash (run.py's --check tolerates missing ``mesh_*`` rows for exactly this
single-device-host case).  On an accelerator the parent already holds the
chips and a child could not open them, so the rows run in the parent over
the real local devices.

Reported rows (D=1 is the stock single-device engine path — the
apples-to-apples baseline a user actually gets without the knob):

  mesh_screen_pass_D{d}_* — one sharded screen pass; Mnnz/s, dispatch
                            count, speedup vs D=1
  mesh_gram_pass_D{d}_*   — same for the reduced-covariance pass
  mesh_solve_grid_D{d}_*  — an E-problem lambda grid at per-device batch
                            B; problems/s and launch count
  mesh_collectives_*      — the folded diag_collectives probe: per-device
                            collective bytes of the compiled finalize
                            psum (via `repro.launch.dryrun.collective_bytes`)

On the 1-core reference host the rows split cleanly by what dominates
them: the gram pass (heavy per-dispatch host work — support remapping,
three-array device_put) shows ~2x at D=4 from amortization alone; the
screen pass is scatter-compute-bound so its amortization shows in the
dispatch count (ceil(B/D)), not wall time; the solve grid is while-loop
compute-bound and stays flat while its launch count drops to
ceil(E/(B*D)).  Forced host devices serialize compute — none of these
rows can show a compute-split win until run on a real mesh.

``run_smoke`` is the --quick leg: tiny corpus, D in {1,2}, screen only.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH_DIR)


# --------------------------------------------------------------------------
# parent side: spawn the multi-device child, parse ROW lines
# --------------------------------------------------------------------------

def _child_rows(*, smoke: bool, devices: int, timeout_s: int) -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    env.get("PYTHONPATH", "")) if p
    )
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    if smoke:
        cmd.append("--smoke")
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout_s)
    except (subprocess.TimeoutExpired, OSError) as e:
        print(f"bench_mesh: child did not finish ({type(e).__name__}); "
              "no mesh rows this run", file=sys.stderr)
        return []
    if proc.returncode != 0:
        print(f"bench_mesh: child exited {proc.returncode}; "
              "no mesh rows this run\n" + proc.stderr[-2000:], file=sys.stderr)
        return []
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            rows.append(json.loads(line[4:]))
    return rows


def _rows(*, smoke: bool, devices: int, timeout_s: int) -> list[dict]:
    """Forced host devices in a child on CPU; the real local devices in
    this process on an accelerator (a child could not open the chips)."""
    import jax

    if jax.default_backend() == "cpu":
        return _child_rows(smoke=smoke, devices=devices, timeout_s=timeout_s)
    return _child(smoke)


def run():
    """Full leg: D in {1,2,4}, screen + gram + solve grid + collectives."""
    return _rows(smoke=False, devices=4, timeout_s=900)


def run_smoke():
    """--quick leg: D in {1,2}, screen passes only."""
    return _rows(smoke=True, devices=2, timeout_s=600)


# --------------------------------------------------------------------------
# child side: runs under the forced multi-device jax
# --------------------------------------------------------------------------

def _bench(fn, reps: int = 3) -> float:
    import time
    fn()   # warm-up: jit traces for the fixed (D, C, E) shapes
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _row(name: str, us: float, derived: str) -> dict:
    return {"name": name, "us_per_call": us, "derived": derived}


def _pass_rows(rows, store, Ds, tag, *, chunk_nnz, chunk_rows, megabatch,
               gram_support=None):
    import numpy as np

    from repro.sparse.mesh_engine import (
        mesh_feature_variances, mesh_reduced_covariance,
    )

    geometry = dict(chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
                    megabatch=megabatch)
    n_chunks = store.n_chunks(chunk_nnz=chunk_nnz, chunk_rows=chunk_rows)
    n_mega = -(-n_chunks // megabatch)

    t_screen: dict[int, float] = {}
    for D in Ds:
        t = _bench(lambda: mesh_feature_variances(store, devices=D,
                                                  **geometry))
        t_screen[D] = t
        dispatches = n_mega if D <= 1 else -(-n_mega // D)
        rows.append(_row(
            f"mesh_screen_pass_D{D}_{tag}", t * 1e6,
            f"{store.nnz / t / 1e6:.1f}Mnnz/s dispatches={dispatches} "
            f"megabatches={n_mega} nnz={store.nnz} "
            f"speedup={t_screen[Ds[0]] / t:.2f}x",
        ))

    if gram_support is None:
        return
    support = np.asarray(gram_support)
    t_gram: dict[int, float] = {}
    for D in Ds:
        t = _bench(lambda: mesh_reduced_covariance(store, support,
                                                   devices=D, **geometry))
        t_gram[D] = t
        dispatches = n_mega if D <= 1 else -(-n_mega // D)
        rows.append(_row(
            f"mesh_gram_pass_D{D}_{tag}", t * 1e6,
            f"n_hat={support.size} {store.nnz / t / 1e6:.1f}Mnnz/s "
            f"dispatches={dispatches} speedup={t_gram[Ds[0]] / t:.2f}x",
        ))


def _solve_rows(rows, Ds, tag, *, E=16, n=32, per_dev_batch=4):
    """An E-eval lambda grid at per-device batch B: ceil(E/(B*D)) launches.

    On a single-core host the solve is compute-bound (the while-loop
    sweeps serialize across forced devices), so the row's point is the
    launch count dropping as ceil(E/(B*D)) at flat wall time; on a real
    mesh the same rows show the compute split too."""
    import jax
    import numpy as np

    from repro.kernels import ops as kernel_ops
    from repro.obs import metrics

    rng = np.random.default_rng(0)
    A = rng.normal(size=(E, n, n))
    Sigmas = (A @ A.transpose(0, 2, 1) / n).astype(np.float64)
    lams = np.geomspace(0.05, 0.5, E)
    betas = np.full(E, 1e-3)
    X0 = np.broadcast_to(np.eye(n), (E, n, n)).copy()
    nv = np.full(E, n, np.int32)

    t_by_d: dict[int, float] = {}
    for D in Ds:
        round_B = per_dev_batch * D

        def grid():
            for lo in range(0, E, round_B):
                hi = min(lo + round_B, E)
                out = kernel_ops.bcd_solve_batched(
                    Sigmas[lo:hi], lams[lo:hi], betas[lo:hi], X0[lo:hi],
                    nv[lo:hi], max_sweeps=8, devices=D if D > 1 else 0)
                jax.block_until_ready(out[0])

        c0 = metrics.counter("kernel.launches.bcd_solve_batched").value
        t = _bench(grid)
        launches = (metrics.counter("kernel.launches.bcd_solve_batched").value
                    - c0) / 4  # warm-up + 3 reps
        t_by_d[D] = t
        rows.append(_row(
            f"mesh_solve_grid_D{D}_{tag}", t * 1e6,
            f"{E / t:.0f}problems/s E={E} n={n} B={per_dev_batch} "
            f"launches={launches:.0f} (ceil(E/(B*D))={-(-E // round_B)}) "
            f"speedup={t_by_d[Ds[0]] / t:.2f}x",
        ))


def _collectives_row(D: int, tag: str) -> dict:
    """The folded diag_collectives probe: compile the finalize-time pooled
    reduction and report its per-device collective bytes from post-SPMD
    HLO — the cross-device cost of the one host merge, as a number."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import psum_partials
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(D)
    n = 4096
    parts = (
        jax.device_put(np.zeros((D, n)), NamedSharding(mesh, P("data", None))),
        jax.device_put(np.zeros((D, n)), NamedSharding(mesh, P("data", None))),
    )
    fn = jax.jit(lambda t: psum_partials(t, mesh))
    txt = fn.lower(parts).compile().as_text()
    cb = collective_bytes(txt)
    return _row(
        f"mesh_collectives_{tag}", 0.0,
        f"devices={D} allreduce={cb['all-reduce'] / 1e3:.1f}kB "
        f"total={cb['total'] / 1e3:.1f}kB ops={cb['n_ops']} "
        f"payload=2x(1,{n})f64",
    )


def _child(smoke: bool) -> list[dict]:
    import jax

    jax.config.update("jax_enable_x64", True)

    import tempfile

    import numpy as np

    from repro.data import make_corpus
    from repro.sparse import write_corpus

    n_dev = jax.local_device_count()
    rows: list[dict] = []
    if smoke:
        Ds = [d for d in (1, 2) if d <= n_dev]
        corpus = make_corpus(300, 2_000, topics={"t": ["a", "b"]}, seed=0)
        with tempfile.TemporaryDirectory() as d:
            store = write_corpus(corpus, d, shard_nnz=1 << 17)
            _pass_rows(rows, store, Ds, "smoke", chunk_nnz=2_048,
                       chunk_rows=128, megabatch=1)
        return rows

    Ds = [d for d in (1, 2, 4) if d <= n_dev]
    n_docs, n_words = 1_200, 6_000
    tag = f"{n_docs}x{n_words}"
    corpus = make_corpus(n_docs, n_words,
                         topics={"t": ["a", "b", "c", "d"]}, seed=0)
    _, var = corpus.column_stats_exact()
    support = np.sort(np.argsort(var)[::-1][:128])
    with tempfile.TemporaryDirectory() as d:
        store = write_corpus(corpus, d, shard_nnz=1 << 19)
        _pass_rows(rows, store, Ds, tag, chunk_nnz=1_024, chunk_rows=128,
                   megabatch=1, gram_support=support)
    _solve_rows(rows, Ds, tag)
    rows.append(_collectives_row(Ds[-1], tag))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.child:
        for row in _child(args.smoke):
            print("ROW " + json.dumps(row), flush=True)
    else:
        for row in (run_smoke() if args.smoke else run()):
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
