"""Jit'd public wrappers for the Pallas kernels.

Each op auto-selects ``interpret=True`` off-TPU (this container is CPU-only;
interpret mode executes the kernel body in Python for correctness) and the
compiled path on TPU.  The ``impl`` argument forces a path for testing:
  'pallas'  — the kernel (interpret off-TPU)
  'ref'     — the pure-jnp oracle
  'host'    — (CSR ops only) numpy bincount / scipy spgemm on the host:
              XLA's CPU scatter lowers to a sequential loop ~100x slower
              than a fused bincount, so this is the off-TPU production
              backend for the ingest reductions
  'auto'    — kernel on TPU; off it the host path when the inputs are
              concrete host arrays (the streaming-ingest case), else the
              oracle (faster than interpret mode on CPU; all three are
              parity-tested against each other)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import functools

from dataclasses import dataclass

from repro.obs import metrics, trace

from . import ref
from .bcd_fused import bcd_solve_batched_pallas, bcd_solve_pallas
from .bcd_sweep import qp_sweep_pallas
from .csr_gram import csr_gram_megabatch_pallas, csr_gram_pallas
from .csr_stats import csr_column_stats_pallas
from .gram import gram_pallas
from .project import sparse_project_pallas
from .variance import column_stats_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Solver-fault seam (mirror of ``sparse.store.FILE_IO``): tests install a
# `repro.testing.faults.SolverFaultInjector` here to perturb solve results
# (non-finite objective, stalled sweep count) or raise dispatch errors at
# exact call occurrences, targeted by site name ("bcd_solve",
# "bcd_solve_batched", and the mesh pass sites "mesh.screen"/"mesh.gram").
# ``None`` (production) costs one attribute check per wrapper call.
SOLVER_FAULTS = None


def solver_fault_before(site: str) -> None:
    """Dispatch-error injection point — call sites that launch device work
    consult this first; an installed injector may raise here."""
    if SOLVER_FAULTS is not None:
        SOLVER_FAULTS.before(site)


def solver_fault_after(site: str, out, *, max_sweeps: int):
    """Result-perturbation injection point — wraps a solve's returned
    ``(X, obj, sweeps, history)`` tuple (single or batched)."""
    if SOLVER_FAULTS is not None:
        return SOLVER_FAULTS.after(site, out, max_sweeps=max_sweeps)
    return out


def _launch(op: str) -> None:
    """Per-op dispatch accounting at the wrapper boundary: bump the
    ``kernel.launches.<op>`` registry counter.  Counted here, not inside
    jit: the wrappers run eagerly per call, so counts are dispatches, not
    traces."""
    metrics.counter(f"kernel.launches.{op}").inc()


# VMEM budgets for the two fused-solve execution schemes, against the scoped
# VMEM limit the TPU compiler enforces per kernel (16 MiB by default on v5e).
#
# resident: Sigma + X in/out blocks plus loop temporaries (Y, the mask outer
# products) all live on-chip at once — ~4 n_pad^2 words, with headroom for
# the compiler's double-buffering.  Caps n_hat at 768 in f32.
_RESIDENT_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# tiled: only X is resident (n_pad^2); Sigma streams through two R x n_pad
# panel buffers, and the row-update/objective passes touch at most two more
# panel-sized temporaries plus a handful of n_pad vectors.  The kernel raises
# its scoped VMEM limit (`bcd_fused.TILED_VMEM_LIMIT_BYTES`, 32 MiB), so the
# budget sits above the 16 MiB default with headroom below the limit.
# Caps n_hat at 1664 in f32 (2048 falls back to the XLA program, which
# handles HBM spilling itself).
_TILED_VMEM_BUDGET_BYTES = 20 * 1024 * 1024
_PANEL_ROW_CHOICES = (512, 256, 128)    # 128-aligned Sigma panel heights


@dataclass(frozen=True)
class SolvePlan:
    """How one `pallas_call` executes a (batch of) whole solve(s)."""

    scheme: str         # 'resident' | 'tiled'
    n_pad: int          # 128-lane padded problem size
    panel_rows: int     # Sigma panel height (0 for resident)
    vmem_bytes: int     # accounted resident state under the scheme


def plan_fused_solve(n: int, itemsize: int = 4, batch: int = 1
                     ) -> SolvePlan | None:
    """Tile-budget computation for the fused solver at reduced size ``n``
    (post-elimination n_hat, pre-padding): pick the cheapest execution
    scheme whose accounted VMEM state fits, or ``None`` when no one-launch
    scheme does (the driver then falls back to the XLA program).

    With ``batch > 1`` the resident grid pipelines the next problem's
    blocks, so its accounting doubles the revolving buffers
    (conservatively); the tiled scheme's state does not revolve.
    """
    n_pad = max(128, ((n + 127) // 128) * 128)
    x_mult = 1 if batch == 1 else 2
    # resident blocks: Sigma in + X0 in + X out (each revolving under a
    # batch grid) plus one n_pad^2 of loop temporaries.
    resident = (3 * x_mult + 1) * n_pad * n_pad * itemsize
    if resident <= _RESIDENT_VMEM_BUDGET_BYTES:
        return SolvePlan("resident", n_pad, 0, resident)
    for R in _PANEL_ROW_CHOICES:
        if n_pad % R:
            continue
        # X is a single VMEM scratch whatever the batch (it DMAs in and out
        # itself) plus half again of loop temporaries; two Sigma panel
        # buffers.  Calibrated against the scoped VMEM the v5e compiler
        # reports for the kernel (n_pad 1536 / R 128: 14.1 MiB; 1664: 16.3).
        words = 3 * n_pad * n_pad // 2 + 2 * R * n_pad
        if words * itemsize <= _TILED_VMEM_BUDGET_BYTES:
            return SolvePlan("tiled", n_pad, R, words * itemsize)
    return None


def fused_solve_fits(n: int, itemsize: int = 4, batch: int = 1) -> bool:
    """Whether ANY one-launch scheme (resident or tiled) fits the VMEM
    budget at reduced size ``n`` — see `plan_fused_solve` for which."""
    return plan_fused_solve(n, itemsize, batch) is not None


_bcd_solve_ref_jit = jax.jit(
    ref.bcd_solve_ref, static_argnames=("max_sweeps", "qp_sweeps", "tau_iters")
)
_bcd_solve_masked_ref_jit = jax.jit(
    ref.bcd_solve_masked_ref,
    static_argnames=("max_sweeps", "qp_sweeps", "tau_iters"),
)
_bcd_solve_batched_ref_jit = jax.jit(
    ref.bcd_solve_batched_ref,
    static_argnames=("max_sweeps", "qp_sweeps", "tau_iters"),
)


def column_stats(A, *, impl: str = "auto", block_m: int = 256, block_n: int = 512):
    """(col_sum, col_sumsq) in f32 — feeds the Thm 2.1 variance screen."""
    _launch("column_stats")
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.column_stats_ref(A)
    return column_stats_pallas(
        A, block_m=block_m, block_n=block_n, interpret=not _on_tpu()
    )


def column_variances(A, *, impl: str = "auto"):
    """Convenience: (mean, var) from one streaming pass."""
    m = A.shape[0]
    s, ss = column_stats(A, impl=impl)
    mean = s / m
    var = jnp.maximum(ss / m - mean * mean, 0.0)
    return mean, var


def gram(A, *, impl: str = "auto", block_i: int = 128, block_j: int = 128,
         block_k: int = 512):
    """A^T A in f32 — the reduced covariance numerator."""
    _launch("gram")
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.gram_ref(A)
    return gram_pallas(
        A, block_i=block_i, block_j=block_j, block_k=block_k,
        interpret=not _on_tpu(),
    )


try:                                     # scipy ships with jax; the spgemm
    import scipy.sparse as _scipy_sparse  # fast path degrades gracefully
except ImportError:                      # pragma: no cover - image has scipy
    _scipy_sparse = None


def _host_path(impl: str, *arrays) -> bool:
    """Whether the host (numpy) backend serves this call: forced by
    ``impl='host'``, or picked by ``'auto'`` off-TPU when every input is a
    concrete host array (a tracer can't leave jit; a device array would
    pay a transfer)."""
    if impl == "host":
        return True
    return (
        impl == "auto" and not _on_tpu()
        and all(isinstance(a, np.ndarray) for a in arrays)
    )


def _csr_column_stats_host(values, col_ids, n: int):
    """Host backend of the CSR screen reduction: two fused f64 bincounts —
    O(nnz + n), no XLA scatter (which lowers to a ~100x slower sequential
    loop on CPU).  Columns >= n are dropped like the oracle's scatter."""
    v = np.asarray(values, np.float64).reshape(-1)
    c = np.asarray(col_ids, np.int64).reshape(-1)
    s = np.bincount(c, weights=v, minlength=n)[:n]
    ss = np.bincount(c, weights=v * v, minlength=n)[:n]
    return s.astype(np.float32), ss.astype(np.float32)


def _csr_gram_host(values, local_cols, seg_ids, n_rows: int, n_hat: int):
    """Host backend of the gather-Gram: only the on-support entries (a
    tiny fraction of the chunk after elimination) enter a sparse
    ``B^T B`` (scipy spgemm when available, bincount-densify + BLAS
    otherwise) — never an XLA scatter."""
    C = values.shape[0] if values.ndim == 2 else 1
    rows = (
        np.asarray(seg_ids, np.int64).reshape(C, -1)
        + n_rows * np.arange(C, dtype=np.int64)[:, None]
    ).reshape(-1)
    cols = np.asarray(local_cols, np.int64).reshape(-1)
    keep = cols < n_hat                      # off-support sentinel drop
    v = np.asarray(values, np.float64).reshape(-1)[keep]
    r = rows[keep]
    c = cols[keep]
    if _scipy_sparse is not None:
        B = _scipy_sparse.coo_matrix(
            (v, (r, c)), shape=(C * n_rows, n_hat)
        ).tocsr()
        return np.asarray((B.T @ B).toarray(), np.float32)
    Bd = np.bincount(
        r * n_hat + c, weights=v, minlength=C * n_rows * n_hat
    ).reshape(C * n_rows, n_hat).astype(np.float32)
    return Bd.T @ Bd


def _sync_host_inputs(*arrays, b=None):
    """Convert concrete host arrays bound for a jit path into device
    buffers, BLOCKING until the copies land.  Callers like the megabatch
    ring reuse their host buffers as soon as the wrapper returns; async
    dispatch makes no promise about when a raw numpy argument is read,
    and ``jnp.asarray`` may alias host memory on CPU — hence the
    explicit ``copy=True`` plus the block.  The copy is an
    ``ingest.h2d`` span carrying the megabatch index ``b``."""
    if not any(isinstance(a, np.ndarray) for a in arrays):
        return arrays
    with trace.span("ingest.h2d", b=b):
        out = tuple(jnp.array(a, copy=True) for a in arrays)
        jax.block_until_ready(out)
    return out


def _assert_csr_padding(values, nnz) -> None:
    """Enforce the store's chunk padding contract on concrete host arrays:
    slots at or past ``nnz`` must carry value 0 (their col/seg ids are then
    additively harmless for every CSR kernel).  ``nnz`` is a scalar for a
    single chunk or a (C,) vector for a megabatch; tracers (inside jit)
    and ``nnz=None`` skip the check."""
    if nnz is None or not isinstance(values, np.ndarray):
        return
    v = values if values.ndim == 2 else values[None, :]
    k = np.asarray(nnz, np.int64).reshape(-1, 1)
    lane = np.arange(v.shape[1], dtype=np.int64)[None, :]
    if np.any((lane >= k) & (v != 0)):
        raise ValueError(
            "CSR chunk padding contract violated: slots past nnz must "
            "carry value 0 (see sparse.store.CSRChunk)"
        )


@functools.partial(
    jax.jit, static_argnames=("n", "impl", "block_e")
)
def _csr_column_stats_jit(values, col_ids, *, n: int, impl: str,
                          block_e: int):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        if values.ndim == 2:
            return ref.csr_column_stats_batched_ref(values, col_ids, n)
        return ref.csr_column_stats_ref(values, col_ids, n)
    return csr_column_stats_pallas(
        values, col_ids, n, block_e=block_e, interpret=not _on_tpu()
    )


def csr_column_stats(values, col_ids, *, n: int, impl: str = "auto",
                     block_e: int = 4096, nnz=None, b=None):
    """(col_sum, col_sumsq) in f32 from CSR entries — the sparse leg of the
    Thm 2.1 screen.  ``values``/``col_ids`` are flat ``(E,)`` for one chunk
    or ``(C, E)`` for a megabatch of C chunks reduced in ONE dispatch (one
    `pallas_call` on TPU, one XLA scatter off it).  Chunks from the store
    have a fixed shape, so this traces once per (C, chunk_nnz, n) and
    never recompiles.  ``nnz`` (scalar or (C,)), when given with concrete
    host arrays, asserts the ``value 0`` padding contract.  ``b`` is the
    megabatch index the ``ingest.prep`` / ``ingest.h2d`` spans carry."""
    with trace.span("ingest.prep", b=b):
        _assert_csr_padding(values, nnz)
    _launch("csr_column_stats")
    if _host_path(impl, values, col_ids):
        return _csr_column_stats_host(values, col_ids, n)
    values, col_ids = _sync_host_inputs(values, col_ids, b=b)
    return _csr_column_stats_jit(values, col_ids, n=n, impl=impl,
                                 block_e=block_e)


# back-compat: tests introspect the jit cache through the public name
csr_column_stats._cache_size = _csr_column_stats_jit._cache_size


@functools.partial(
    jax.jit, static_argnames=("n_rows", "n_hat", "impl")
)
def _csr_gram_jit(values, local_cols, seg_ids, *, n_rows: int, n_hat: int,
                  impl: str):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.csr_gram_ref(values, local_cols, seg_ids, n_rows, n_hat)
    return csr_gram_pallas(
        values, local_cols, seg_ids, n_rows, n_hat, interpret=not _on_tpu()
    )


def csr_gram(values, local_cols, seg_ids, *, n_rows: int, n_hat: int,
             impl: str = "auto", nnz=None):
    """Chunk gather-Gram G = B^T B on the post-elimination support.

    ``local_cols`` are support positions with >= n_hat meaning "drop"
    (entry not on the support); ``seg_ids`` are chunk-local rows.  Fixed
    chunk shapes keep this a single trace per (chunk_nnz, n_hat)."""
    _assert_csr_padding(values, nnz)
    _launch("csr_gram")
    if _host_path(impl, values, local_cols, seg_ids):
        return _csr_gram_host(values, local_cols, seg_ids, n_rows, n_hat)
    values, local_cols, seg_ids = _sync_host_inputs(
        values, local_cols, seg_ids
    )
    return _csr_gram_jit(values, local_cols, seg_ids, n_rows=n_rows,
                         n_hat=n_hat, impl=impl)


@functools.partial(
    jax.jit, static_argnames=("n_rows", "n_hat", "impl")
)
def _csr_gram_batched_jit(values, local_cols, seg_ids, *, n_rows: int,
                          n_hat: int, impl: str):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.csr_gram_batched_ref(
            values, local_cols, seg_ids, n_rows, n_hat
        )
    return csr_gram_megabatch_pallas(
        values, local_cols, seg_ids, n_rows, n_hat, interpret=not _on_tpu(),
    )


def csr_gram_batched(values, local_cols, seg_ids, *, n_rows: int,
                     n_hat: int, impl: str = "auto", nnz=None, b=None):
    """Megabatch gather-Gram: C chunks' ``sum_c B_c^T B_c`` in ONE dispatch
    (grid=(C,) `pallas_call` with the Gram accumulator VMEM-resident across
    the batch on TPU, one stacked spgemm off it).  Inputs are (C, E);
    ``nnz`` (C,), when given with concrete host arrays, asserts the
    ``value 0`` padding contract; ``b`` is the megabatch index the
    ``ingest.prep`` / ``ingest.h2d`` spans carry."""
    with trace.span("ingest.prep", b=b):
        _assert_csr_padding(values, nnz)
    _launch("csr_gram_batched")
    if _host_path(impl, values, local_cols, seg_ids):
        return _csr_gram_host(values, local_cols, seg_ids, n_rows, n_hat)
    values, local_cols, seg_ids = _sync_host_inputs(
        values, local_cols, seg_ids, b=b
    )
    return _csr_gram_batched_jit(values, local_cols, seg_ids,
                                 n_rows=n_rows, n_hat=n_hat, impl=impl)


def _resolve_scheme(scheme: str, n: int, itemsize: int, batch: int):
    """Map scheme='auto' to a concrete (scheme, panel_rows) pair via the
    tile-budget plan; forced schemes get a default panel height."""
    if scheme == "auto":
        plan = plan_fused_solve(n, itemsize, batch)
        if plan is None:
            return None
        return plan.scheme, (plan.panel_rows or 128)
    return scheme, 128


def bcd_solve(Sigma, lam, beta, X0=None, *, max_sweeps: int = 20,
              qp_sweeps: int = 4, tol: float = 1e-7, tau_iters: int = 80,
              n_valid: int | None = None, impl: str = "auto",
              scheme: str = "auto", panel_rows: int = 0):
    """Whole-solve fused BCD (Algorithm 1) — ONE kernel launch per solve.

    ``impl='auto'`` selects a Pallas kernel on TPU when some one-launch
    scheme fits the VMEM budget (`plan_fused_solve`), else the jnp oracle.
    ``scheme`` picks the kernel ('auto' | 'resident' | 'tiled') and
    ``panel_rows`` (0 = auto) the tiled Sigma panel height.  ``n_valid``
    restricts the solve to the leading principal submatrix of a zero-padded
    problem (the bucketed-support contract).  Returns ``(X, obj, sweeps,
    history)``; ``obj``/``history`` are the barrier-free objective used for
    the in-kernel early exit (see `bcd_solve` module doc).
    """
    Sigma = jnp.asarray(Sigma)
    n = Sigma.shape[0]
    if X0 is None:
        X0 = jnp.eye(n, dtype=Sigma.dtype)
        if n_valid is not None and n_valid < n:
            X0 = X0 * (jnp.arange(n) < n_valid).astype(Sigma.dtype)
    lam = jnp.asarray(lam, Sigma.dtype)
    beta = jnp.asarray(beta, Sigma.dtype)
    tol = jnp.asarray(tol, Sigma.dtype)
    resolved = _resolve_scheme(scheme, n, Sigma.dtype.itemsize, 1)
    if impl == "pallas" and resolved is None:
        resolved = ("tiled", 128)       # forced: caller owns the VMEM risk
    # auto never hands f64 to the kernel: Mosaic cannot lower it
    use_pallas = (impl == "pallas" or (
        impl == "auto" and _on_tpu() and Sigma.dtype.itemsize <= 4
    )) and resolved is not None
    _launch("bcd_solve")
    solver_fault_before("bcd_solve")
    if not use_pallas:
        if n_valid is None:
            out = _bcd_solve_ref_jit(
                Sigma, lam, beta, X0, tol,
                max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
                tau_iters=tau_iters,
            )
        else:
            out = _bcd_solve_masked_ref_jit(
                Sigma, lam, beta, X0, tol, n_valid,
                max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
                tau_iters=tau_iters,
            )
    else:
        kscheme, kpanel = resolved
        out = bcd_solve_pallas(
            Sigma, lam, beta, X0, tol,
            max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
            tau_iters=tau_iters, n_valid=n_valid, scheme=kscheme,
            panel_rows=panel_rows or kpanel, interpret=not _on_tpu(),
        )
    return solver_fault_after("bcd_solve", out, max_sweeps=max_sweeps)


@functools.lru_cache(maxsize=None)
def _sharded_batched_solve(devices: int, use_pallas: bool, kscheme: str,
                           kpanel: int, max_sweeps: int, qp_sweeps: int,
                           tau_iters: int, panel_rows: int):
    """jit(shard_map) that splits a (B, n, n) problem batch across the
    1-D data mesh — each device runs its grid=(B/D,) one-launch solve on
    its slice.  Cached per (topology, kernel plan, sweep budget) so a
    bracket search traces once."""
    from repro.launch.mesh import make_data_mesh

    # The solve body is a while loop, which shard_map's replication checker
    # cannot analyse — each device's slice is independent, so the check is
    # vacuously satisfied and safely disabled.
    mesh = make_data_mesh(devices)
    from jax.sharding import PartitionSpec as P

    def device_solve(Sigmas, lams, betas, X0s, tol, n_valids):
        if use_pallas:
            return bcd_solve_batched_pallas(
                Sigmas, lams, betas, X0s, tol, n_valids,
                max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
                tau_iters=tau_iters, scheme=kscheme,
                panel_rows=panel_rows or kpanel, interpret=not _on_tpu(),
            )
        return ref.bcd_solve_batched_ref(
            Sigmas, lams, betas, X0s, tol, n_valids,
            max_sweeps=max_sweeps, qp_sweeps=qp_sweeps, tau_iters=tau_iters,
        )

    b = P("data")
    m = P("data", None, None)
    return jax.jit(jax.shard_map(
        device_solve, mesh=mesh,
        in_specs=(m, b, b, m, P(), b),
        out_specs=(m, b, b, P("data", None)),
        check_vma=False,
    ))


def bcd_solve_batched(Sigmas, lams, betas, X0s, n_valids, *,
                      max_sweeps: int = 20, qp_sweeps: int = 4,
                      tol: float = 1e-7, tau_iters: int = 80,
                      impl: str = "auto", scheme: str = "auto",
                      panel_rows: int = 0, devices: int = 0):
    """B independent whole solves in ONE launch (grid batch dimension).

    ``Sigmas``/``X0s`` are (B, n, n) zero-padded problems occupying their
    leading ``n_valids[b]`` coordinates.  On TPU this is a single
    `pallas_call` over grid=(B,); off-TPU it is the vmapped masked oracle —
    one XLA dispatch either way, which is the whole point: a lambda
    bracket/grid or a deflation round costs O(1) launches instead of O(B).
    Returns ``(X (B,n,n), obj (B,), sweeps (B,), history (B, max_sweeps))``.

    ``devices > 1`` additionally splits the batch across the first D local
    devices (1-D data mesh): each device runs its grid=(B/D,) solve on its
    slice, still ONE dispatch from the host, so a bracket round over E
    evals costs ceil(E/(B·D)) sequential launches.  B is padded up to a
    multiple of D by repeating problem 0 (results sliced back); the knob
    silently clamps to the batch size and the local device count.
    """
    Sigmas = jnp.asarray(Sigmas)
    B, n, _ = Sigmas.shape
    dtype = Sigmas.dtype
    lams = jnp.asarray(lams, dtype)
    betas = jnp.broadcast_to(jnp.asarray(betas, dtype), (B,))
    n_valids = jnp.asarray(n_valids, jnp.int32)
    X0s = jnp.asarray(X0s, dtype)
    tol = jnp.asarray(tol, dtype)
    resolved = _resolve_scheme(scheme, n, dtype.itemsize, B)
    if impl == "pallas" and resolved is None:
        resolved = ("tiled", 128)       # forced: caller owns the VMEM risk
    # auto never hands f64 to the kernel: Mosaic cannot lower it
    use_pallas = (impl == "pallas" or (
        impl == "auto" and _on_tpu() and dtype.itemsize <= 4
    )) and resolved is not None
    D = min(int(devices or 0), B, jax.local_device_count())
    if D > 1:
        kscheme, kpanel = resolved if use_pallas else ("", 0)
        metrics.gauge("mesh.devices").set(D)
        Bp = -(-B // D) * D
        if Bp != B:
            pad = Bp - B
            Sigmas = jnp.concatenate(
                [Sigmas, jnp.broadcast_to(Sigmas[:1], (pad, n, n))])
            lams = jnp.concatenate([lams, jnp.broadcast_to(lams[:1], (pad,))])
            betas = jnp.concatenate(
                [betas, jnp.broadcast_to(betas[:1], (pad,))])
            X0s = jnp.concatenate(
                [X0s, jnp.broadcast_to(X0s[:1], (pad, n, n))])
            n_valids = jnp.concatenate(
                [n_valids, jnp.broadcast_to(n_valids[:1], (pad,))])
        _launch("bcd_solve_batched")
        solver_fault_before("bcd_solve_batched")
        fn = _sharded_batched_solve(
            D, use_pallas, kscheme, kpanel,
            max_sweeps, qp_sweeps, tau_iters, panel_rows,
        )
        X, obj, sweeps, hist = fn(Sigmas, lams, betas, X0s, tol,
                                  n_valids)
        return solver_fault_after(
            "bcd_solve_batched", (X[:B], obj[:B], sweeps[:B], hist[:B]),
            max_sweeps=max_sweeps,
        )
    _launch("bcd_solve_batched")
    solver_fault_before("bcd_solve_batched")
    if not use_pallas:
        out = _bcd_solve_batched_ref_jit(
            Sigmas, lams, betas, X0s, tol, n_valids,
            max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
            tau_iters=tau_iters,
        )
    else:
        kscheme, kpanel = resolved
        out = bcd_solve_batched_pallas(
            Sigmas, lams, betas, X0s, tol, n_valids,
            max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
            tau_iters=tau_iters, scheme=kscheme,
            panel_rows=panel_rows or kpanel, interpret=not _on_tpu(),
        )
    return solver_fault_after("bcd_solve_batched", out,
                              max_sweeps=max_sweeps)


def qp_sweeps(Y, s, lam, u0, j, *, sweeps: int = 4, impl: str = "auto"):
    """Box-QP coordinate descent (11)+(13) — the BCD inner loop."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.qp_sweep_ref(Y, s, lam, u0, j, sweeps)
    return qp_sweep_pallas(Y, s, lam, u0, j, sweeps=sweeps, interpret=not _on_tpu())


def sparse_project(X, support_idx, values, *, impl: str = "auto",
                   block_b: int = 512):
    """(B, k) document->topic scores through the gather representation —
    the serving hot path (see ``repro.serve.projector``)."""
    _launch("sparse_project")
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.sparse_project_ref(X, support_idx, values)
    k, cap = support_idx.shape
    return sparse_project_pallas(
        X, support_idx.reshape(-1).astype(jnp.int32),
        jnp.repeat(jnp.arange(k, dtype=jnp.int32), cap),
        values.reshape(-1), k, block_b=block_b, interpret=not _on_tpu(),
    )
