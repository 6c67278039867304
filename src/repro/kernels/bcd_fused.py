r"""Pallas TPU kernels: fused whole-solve BCD (Algorithm 1), resident + tiled.

This is the end state of the per-row -> fused-sweep -> tiled/batched
migration (see "Solver kernel architecture" in ROADMAP.md).  The legacy path
(`core.bcd.row_update` + `kernels.bcd_sweep.qp_sweep_pallas`) launches one
`pallas_call` per row/column update; PR 2 fused the entire solve into ONE
launch with Sigma and X VMEM-resident, which capped the reduced size at
``4 n_pad^2`` words of VMEM (n_hat <= 768 in f32).  This module executes the
same Algorithm 1

  while |F(X_k) - F(X_{k-1})| > tol (1 + |F|) and k < max_sweeps:   # on-chip
      for j in 0..n_valid:                              # row/column updates
          Y   = X with row/col j masked to zero
          s   = Sigma[:, j] masked,  c = Sigma_jj - lam - Tr Y
          u   <- box-QP coordinate descent on (11) via closed form (13)
          tau <- branch-free bisection on the monotone derivative of (12)
          X   <- Y + (Yu/tau) e_j^T + e_j (Yu/tau)^T + (c + tau) e_j e_j^T

under two execution schemes selected by `ops.plan_fused_solve`:

* **resident** — Sigma and X both live in VMEM for the whole solve (the PR-2
  kernel).  Fastest when ``4 n_pad^2`` words fit the budget (n_hat <= 768).
* **tiled** — Sigma (and X0) stay in HBM; only X is VMEM-resident.  Sigma
  streams through VMEM in 128-aligned row-panels via double-buffered async
  copies that overlap the box-QP coordinate descent, so the one-launch solve
  works for n_hat in the thousands (~1664 in f32) instead of 768.  The row
  update exploits the symmetry BCD preserves (row j and column j are written
  identically), so Y-columns in the coordinate loop are *row* loads from the
  resident X — contiguous lanes, never a strided VMEM walk — and the write
  back touches exactly row j + column j instead of rebuilding the matrix.
  Per row update the kernel reads one Sigma row out of the current panel;
  panel p+1 is DMA'd while panel p's R row updates run, and the per-sweep
  objective is accumulated by one more panel pass at sweep end.

Both kernels carry a grid **batch dimension**: grid=(B,) runs B independent
(Sigma, lam, X0, n_valid) problems in ONE `pallas_call` — the lambda-grid
bracket of a search and the deflation round of a multi-component fit are
exactly such batches (supports nested / known up front), so the driver
collapses O(grid * K) launches per fit into O(1).

Padding: shapes are padded to 128 lanes; per-problem ``n_valid`` (< n_pad)
masks bucketed supports.  Padded rows/cols of Sigma/X0 must be zero; both
loops run only to n_valid, so padded coordinates never contribute to
w = Y u, the trace, or the objective.

The in-kernel early-exit criterion uses the barrier-free objective

    F(X) = Tr(Sigma X) - lam ||X||_1 - (Tr X)^2 / 2

(the beta*logdet barrier term would need an on-chip Cholesky; its
sweep-to-sweep variation is O(beta) ~ 1e-4 and is irrelevant for the
stopping test).  beta still enters the tau sub-problem exactly as in the
host solver, so the *iterates* match `core.bcd` bit-for-bit-modulo-padding;
only the stopping rule reads a different (equally monotone) functional.

The coordinate recursion is inherently sequential (each eta depends on the
w produced by the previous coordinate) so there is no intra-problem grid
parallelism — parallelism lives in the batch dimension.  Oracles:
`ref.bcd_solve_ref` (unpadded), `ref.bcd_solve_masked_ref` (padded +
n_valid, the semantics both kernels implement), `ref.bcd_solve_batched_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HIGHEST = jax.lax.Precision.HIGHEST
# Scoped VMEM the tiled scheme asks the compiler for.  The 16 MiB default
# stops the resident X at n_pad 1536 (v5e compiler: n_pad 1664 / R 128
# needs 16.3 MiB); the core has 128 MiB.  `ops.plan_fused_solve` budgets
# below this.
TILED_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _pad128(n: int) -> int:
    return max(128, ((n + 127) // 128) * 128)


def _pick(vec, lanes, i):
    """``vec[0, i]`` of a (1, n) row as a (1, 1) value.  A traced lane index
    has no vector lowering on TPU, so the pick is a one-hot masked lane
    reduction (exact: every other term is 0)."""
    return jnp.sum(jnp.where(lanes == i, vec, 0.0), axis=1, keepdims=True)


def _sum11(x):
    """Full sum of a 2-D value, kept as a (1, 1) vector."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _solve_tau(R2, c, beta, tau_iters):
    """min_{tau>0} R2/tau - beta*log(tau) + (c + tau)^2 / 2 — bisection on
    the strictly increasing derivative (branch-free, shared by both
    kernels; mirrors `core.bcd.solve_tau`).  ``R2``/``c`` are (1, 1)."""
    hi = jnp.maximum(1.0, -c) + jnp.sqrt(jnp.maximum(R2, 0.0)) + beta + 1.0
    lo = jnp.minimum(beta / (beta + jnp.maximum(-c, 0.0) + 1.0), hi) * 1e-12

    def bisect(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        g = mid + c - R2 / (mid * mid) - beta / mid
        lo = jnp.where(g < 0, mid, lo)
        hi = jnp.where(g < 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, tau_iters, bisect, (lo, hi))
    return 0.5 * (lo + hi)


def _solve(x_ref, hist_ref, meta_ref, *, n_pad, R, lam, beta, tol, n_valid,
           max_sweeps, qp_sweeps, tau_iters, sweep_rows, sigma_x):
    """Algorithm 1 on the VMEM-resident iterate ``x_ref[0]`` (n_pad, n_pad),
    shared by both schemes.  ``sweep_rows(row_update, dX)`` runs one sweep,
    calling ``row_update(j, sigma_row_j, dX)`` for j < n_valid in order;
    ``sigma_x()`` returns Tr(Sigma X) as (1, 1).  X-wide work walks
    R-row panels (static slices); every row vector is (1, n_pad) with the
    coordinate on the lane axis.

    ``dX`` carries diag(X) as a (1, n_pad) row: a row update changes only
    X_jj on the diagonal, so Tr X and Tr Y never re-read the matrix."""
    dtype = hist_ref.dtype
    n_panels = n_pad // R
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    lane128 = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)

    def panel(p):
        return x_ref[0, p * R:(p + 1) * R, :]

    def x_matvec(s):
        """s X as a (1, n_pad) row (= (X s)^T: X is symmetric)."""
        s8 = jnp.broadcast_to(s, (8, n_pad))
        acc = jnp.zeros((8, n_pad), jnp.float32 if dtype == jnp.float32
                        else dtype)
        for p in range(n_panels):
            acc = acc + jnp.dot(s8[:, p * R:(p + 1) * R], panel(p),
                                precision=_HIGHEST,
                                preferred_element_type=acc.dtype)
        return acc[0:1].astype(dtype)

    def write_row_and_col(j, newrow):
        """X[j, :] = X[:, j] = newrow.  The column lands through the
        128-lane tile holding j (an aligned dynamic lane window); the
        transposed values come from a (128, R) -> (R, 128) transpose."""
        jt = pl.multiple_of((j // 128) * 128, 128)
        for p in range(n_panels):
            colT = jnp.transpose(
                jnp.broadcast_to(newrow[:, p * R:(p + 1) * R], (128, R)))
            rows = slice(p * R, (p + 1) * R)
            tile = x_ref[0, rows, pl.ds(jt, 128)]
            x_ref[0, rows, pl.ds(jt, 128)] = jnp.where(
                lane128 == j - jt, colT, tile)
        x_ref[0, pl.ds(j, 1), :] = newrow

    def row_update(j, srow, dX):
        mfb = (lanes != j) & (lanes < n_valid)
        s = jnp.where(mfb, srow, 0.0)               # Sigma_j, masked
        t = _sum11(dX) - _pick(dX, lanes, j)        # Tr Y = Tr X - X_jj
        c = _pick(srow, lanes, j) - lam - t
        dY = jnp.where(mfb, dX, 0.0)                # diag(Y)
        pos = dY > 0
        div = jnp.where(pos, dY, 1.0)
        lo = s - lam
        hi = s + lam
        free = mfb                                  # j pinned, pad frozen

        def coord_step(i, carry):
            u, w = carry
            # BCD preserves symmetry, so Y's column i is X's ROW i masked:
            # a contiguous lane load.  eta is formed on every lane (the
            # closed form (13)) and lane i is kept.
            col = jnp.where(mfb, x_ref[0, pl.ds(i, 1), :], 0.0)
            g = w - dY * u                          # \hat y^T \hat u
            eta = jnp.where(pos, jnp.clip(-g / div, lo, hi),
                            jnp.where(g > 0, lo, hi))
            hit = (lanes == i) & free
            delta = jnp.sum(jnp.where(hit, eta - u, 0.0), axis=1,
                            keepdims=True)
            return jnp.where(hit, eta, u), w + col * delta

        def qp_sweep(_, carry):
            return jax.lax.fori_loop(0, n_valid, coord_step, carry)

        # w0 = Y s = mf o (X s): s is pre-masked, so column j and the
        # padding never contribute; masking the product removes row j.
        w0 = jnp.where(mfb, x_matvec(s), 0.0)
        u, w = jax.lax.fori_loop(0, qp_sweeps, qp_sweep, (s, w0))
        tau = _solve_tau(_sum11(u * w), c, beta, tau_iters)
        # X differs from Y + outer products ONLY in row j / column j.
        ej = lanes == j
        xjj = c + tau
        write_row_and_col(j, jnp.where(ej, xjj, w / tau))
        return jnp.where(ej, xjj, dX)

    def partial_obj(dX):
        tr = _sum11(dX)
        l1 = jnp.zeros((1, 1), dtype)
        for p in range(n_panels):
            l1 = l1 + _sum11(jnp.abs(panel(p)))
        return sigma_x() - lam * l1 - 0.5 * tr * tr

    sub = jax.lax.broadcasted_iota(jnp.int32, (R, n_pad), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, n_pad), 1)
    dX0 = jnp.zeros((1, n_pad), dtype)
    for p in range(n_panels):
        dX0 = dX0 + jnp.sum(jnp.where(sub + p * R == lane, panel(p), 0.0),
                            axis=0, keepdims=True)
    hlanes = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape[1:], 1)
    hist_ref[0] = jnp.full(hist_ref.shape[1:], jnp.nan, dtype)

    def cond(state):
        k, done, _, _ = state
        return (done == 0) & (k < max_sweeps)

    def body(state):
        k, _, dX, prev = state
        dX = sweep_rows(row_update, dX)
        obj = partial_obj(dX)
        hist_ref[0] = jnp.where(hlanes == k, obj, hist_ref[0])
        done = jnp.abs(obj - prev) <= tol * (1.0 + jnp.abs(obj))
        return k + 1, jnp.max(done.astype(jnp.int32)), dX, obj

    minus_inf = jnp.full((1, 1), -jnp.inf, dtype)
    k, _, _, obj = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(0), dX0, minus_inf))
    mlanes = jax.lax.broadcasted_iota(jnp.int32, meta_ref.shape[1:], 1)
    kf = jnp.full(meta_ref.shape[1:], k, jnp.int32).astype(dtype)
    meta_ref[0] = jnp.where(mlanes == 0, obj, jnp.where(mlanes == 1, kf, 0.0))


def _scalars(scal_ref, nv_ref):
    b = pl.program_id(0)
    return dict(lam=scal_ref[4 * b], beta=scal_ref[4 * b + 1],
                tol=scal_ref[4 * b + 2], n_valid=nv_ref[b])


# ---------------------------------------------------------------------------
# Resident scheme: Sigma and X VMEM-resident (n_hat <= 768 in f32).
# ---------------------------------------------------------------------------


def _bcd_resident_kernel(
    scal_ref, nv_ref, sig_ref, x0_ref, x_ref, hist_ref, meta_ref, *, n_pad,
    max_sweeps, qp_sweeps, tau_iters,
):
    sc = _scalars(scal_ref, nv_ref)
    x_ref[0] = x0_ref[0]

    def sweep_rows(row_update, dX):
        return jax.lax.fori_loop(
            0, sc["n_valid"],
            lambda j, dX: row_update(j, sig_ref[0, pl.ds(j, 1), :], dX), dX)

    def sigma_x():
        return _sum11(sig_ref[0] * x_ref[0])

    _solve(x_ref, hist_ref, meta_ref, n_pad=n_pad, R=n_pad,
           max_sweeps=max_sweeps, qp_sweeps=qp_sweeps, tau_iters=tau_iters,
           sweep_rows=sweep_rows, sigma_x=sigma_x, **sc)


# ---------------------------------------------------------------------------
# Tiled scheme: X VMEM-resident, Sigma streamed from HBM in row-panels.
# ---------------------------------------------------------------------------


def _bcd_tiled_kernel(
    scal_ref, nv_ref, sig_hbm, x0_hbm, x_hbm, hist_ref, meta_ref, x_ref, buf,
    sem, xsem, *, n_pad, panel_rows, max_sweeps, qp_sweeps, tau_iters,
):
    b = pl.program_id(0)
    R = panel_rows
    n_panels = n_pad // R
    sc = _scalars(scal_ref, nv_ref)

    # X0: HBM -> the resident VMEM scratch, one whole-matrix DMA (a single
    # buffer: an X output block would be double-buffered, 2 n_pad^2 words).
    cp = pltpu.make_async_copy(x0_hbm.at[b], x_ref.at[0], xsem)
    cp.start()
    cp.wait()

    def get_dma(slot, p):
        return pltpu.make_async_copy(
            sig_hbm.at[b, pl.ds(p * R, R), :], buf.at[slot], sem.at[slot]
        )

    def stream(panel_body, init):
        """One double-buffered pass of Sigma's row panels through VMEM."""
        get_dma(0, 0).start()

        def body(p, acc):
            @pl.when(p + 1 < n_panels)
            def _():
                get_dma((p + 1) % 2, p + 1).start()
            get_dma(p % 2, p).wait()
            return panel_body(p, acc)

        return jax.lax.fori_loop(0, n_panels, body, init)

    def sweep_rows(row_update, dX):
        def panel_rows_(p, dX):
            rows_here = jnp.clip(sc["n_valid"] - p * R, 0, R)
            return jax.lax.fori_loop(
                0, rows_here,
                lambda r, dX: row_update(
                    p * R + r, buf[p % 2, pl.ds(r, 1), :], dX), dX)
        return stream(panel_rows_, dX)

    def sigma_x():
        def acc(p, sx):
            rows = x_ref[0, pl.ds(pl.multiple_of(p * R, R), R), :]
            return sx + _sum11(buf[p % 2] * rows)
        return stream(acc, jnp.zeros((1, 1), x_ref.dtype))

    _solve(x_ref, hist_ref, meta_ref, n_pad=n_pad, R=R,
           max_sweeps=max_sweeps, qp_sweeps=qp_sweeps, tau_iters=tau_iters,
           sweep_rows=sweep_rows, sigma_x=sigma_x, **sc)
    cp = pltpu.make_async_copy(x_ref.at[0], x_hbm.at[b], xsem)
    cp.start()
    cp.wait()


# ---------------------------------------------------------------------------
# Launch wrappers.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_sweeps", "qp_sweeps", "tau_iters", "scheme", "panel_rows",
        "interpret",
    ),
)
def _launch(
    Sigma3, X03, scal, n_valids,
    *, max_sweeps, qp_sweeps, tau_iters, scheme, panel_rows, interpret,
):
    """One `pallas_call` over grid=(B,): B padded problems, either scheme.

    ``Sigma3``/``X03`` are (B, n_pad, n_pad) with zeroed padding; ``scal``
    is (B, 4) rows of [lam, beta, tol, 0] and ``n_valids`` (B,) int32 — both
    handed to the kernel in SMEM.  Returns ``(X, hist (B, hist_pad),
    meta (B, 2) = [obj, sweeps])``.
    """
    B, n_pad, _ = Sigma3.shape
    dtype = Sigma3.dtype
    hist_pad = max(128, ((max_sweeps + 127) // 128) * 128)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs = [
        pl.BlockSpec((1, n_pad, n_pad), lambda b: (b, 0, 0)),
        pl.BlockSpec((1, 1, hist_pad), lambda b: (b, 0, 0)),
        pl.BlockSpec((1, 1, 128), lambda b: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, n_pad, n_pad), dtype),
        jax.ShapeDtypeStruct((B, 1, hist_pad), dtype),
        jax.ShapeDtypeStruct((B, 1, 128), dtype),
    ]
    sweeps = dict(max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
                  tau_iters=tau_iters)
    if scheme == "tiled":
        if n_pad % panel_rows:
            raise ValueError(f"{panel_rows=} must divide {n_pad=}")
        X, hist, meta = pl.pallas_call(
            functools.partial(_bcd_tiled_kernel, n_pad=n_pad,
                              panel_rows=panel_rows, **sweeps),
            grid=(B,),
            in_specs=[
                smem, smem,
                pl.BlockSpec(memory_space=pl.ANY),      # Sigma stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),      # X0 stays in HBM
            ],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] + out_specs[1:],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((1, n_pad, n_pad), dtype),   # resident X
                pltpu.VMEM((2, panel_rows, n_pad), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=TILED_VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="bcd_fused_tiled",
        )(scal.reshape(-1), n_valids, Sigma3, X03)
    elif scheme == "resident":
        X, hist, meta = pl.pallas_call(
            functools.partial(_bcd_resident_kernel, n_pad=n_pad, **sweeps),
            grid=(B,),
            in_specs=[
                smem, smem,
                pl.BlockSpec((1, n_pad, n_pad), lambda b: (b, 0, 0)),
                pl.BlockSpec((1, n_pad, n_pad), lambda b: (b, 0, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="bcd_fused_resident",
        )(scal.reshape(-1), n_valids, Sigma3, X03)
    else:
        raise ValueError(f"unknown scheme {scheme!r}")
    return X, hist[:, 0], meta[:, 0, :2]


def _pad_stack(Sigma3, X03, n_pad):
    p = n_pad - Sigma3.shape[-1]
    if p:
        Sigma3 = jnp.pad(Sigma3, ((0, 0), (0, p), (0, p)))
        X03 = jnp.pad(X03, ((0, 0), (0, p), (0, p)))
    return Sigma3, X03


def bcd_solve_pallas(
    Sigma, lam, beta, X0, tol,
    *, max_sweeps: int = 20, qp_sweeps: int = 4, tau_iters: int = 80,
    n_valid: int | None = None, scheme: str = "resident",
    panel_rows: int = 128, interpret: bool = False,
):
    """Whole-solve fused BCD: ONE `pallas_call` for all sweeps of Algorithm 1.

    Returns ``(X, obj, sweeps, history)`` where ``obj`` is the barrier-free
    objective F(X) at exit, ``sweeps`` the number of sweeps executed, and
    ``history`` the (max_sweeps,) nan-padded per-sweep F(X) trace.

    ``scheme='resident'`` keeps Sigma+X in VMEM (n_hat <= 768 in f32);
    ``scheme='tiled'`` keeps only X resident and streams Sigma from HBM in
    ``panel_rows``-row panels (n_hat up to ~1664).  ``n_valid`` (default n)
    restricts the solve to the leading principal submatrix — the bucketed-
    support contract of `ops.bcd_solve`.
    """
    Sigma = jnp.asarray(Sigma)
    n = Sigma.shape[0]
    dtype = Sigma.dtype
    n_pad = _pad128(n)
    Sigma3, X03 = _pad_stack(
        Sigma[None].astype(dtype), jnp.asarray(X0, dtype)[None], n_pad
    )
    nv = n if n_valid is None else int(n_valid)
    scal = jnp.stack([
        jnp.asarray(lam, dtype), jnp.asarray(beta, dtype),
        jnp.asarray(tol, dtype), jnp.zeros((), dtype),
    ])[None, :]
    X, hist, meta = _launch(
        Sigma3, X03, scal, jnp.full((1,), nv, jnp.int32), max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
        tau_iters=tau_iters, scheme=scheme, panel_rows=panel_rows,
        interpret=interpret,
    )
    return (
        X[0, :n, :n],
        meta[0, 0],
        meta[0, 1].astype(jnp.int32),
        hist[0, :max_sweeps],
    )


def bcd_solve_batched_pallas(
    Sigmas, lams, betas, X0s, tol, n_valids,
    *, max_sweeps: int = 20, qp_sweeps: int = 4, tau_iters: int = 80,
    scheme: str = "resident", panel_rows: int = 128, interpret: bool = False,
):
    """B independent solves in ONE `pallas_call` (grid batch dimension).

    ``Sigmas``/``X0s`` are (B, n, n) with per-problem supports occupying the
    leading ``n_valids[b]`` coordinates and zeros beyond; ``lams``/``betas``/
    ``n_valids`` are (B,).  Returns ``(X (B,n,n), obj (B,), sweeps (B,),
    history (B, max_sweeps))``.
    """
    Sigmas = jnp.asarray(Sigmas)
    B, n, _ = Sigmas.shape
    dtype = Sigmas.dtype
    n_pad = _pad128(n)
    Sigma3, X03 = _pad_stack(Sigmas, jnp.asarray(X0s, dtype), n_pad)
    scal = jnp.stack([
        jnp.asarray(lams, dtype),
        jnp.broadcast_to(jnp.asarray(betas, dtype), (B,)),
        jnp.broadcast_to(jnp.asarray(tol, dtype), (B,)),
        jnp.zeros((B,), dtype),
    ], axis=1)
    X, hist, meta = _launch(
        Sigma3, X03, scal, jnp.asarray(n_valids, jnp.int32), max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
        tau_iters=tau_iters, scheme=scheme, panel_rows=panel_rows,
        interpret=interpret,
    )
    return (
        X[:, :n, :n],
        meta[:, 0],
        meta[:, 1].astype(jnp.int32),
        hist[:, :max_sweeps],
    )
