"""Block coordinate ascent for DSPCA (Algorithm 1 of Zhang & El Ghaoui, 2011).

Solves the augmented problem (6)

    max_X  Tr(Sigma X) - lam*||X||_1 - (Tr X)^2 / 2 + beta*logdet X,   X > 0

whose solution is an eps-suboptimal solution of the DSPCA SDP (1) when
``beta = eps/n``; the DSPCA variable is recovered as ``Z = X / Tr X``.

Each row/column update solves the box-constrained QP (11)

    R^2 = min_u u^T Y u   s.t.  ||u - s||_inf <= lam

by coordinate descent with the closed-form update (13), then a strictly
convex 1-D problem in tau (bisection on the monotone derivative), then writes

    y = Y u / tau,     x = sigma - lam - t + tau.

Complexity: O(qp_sweeps * n^2) per row, O(K n^3) overall — v.s. the
O(n^4 sqrt(log n)) first-order method (see `first_order.py`).

Implementation notes (JAX): rows are never physically deleted — ``Y`` is the
full matrix with row/column ``j`` masked to zero, and ``u`` is a full n-vector
with ``u_j`` pinned to 0, so every shape is static and the whole solver jits.
The coordinate loop carries ``w = Y @ u`` and refreshes it incrementally
(O(n) per coordinate).
"""
from __future__ import annotations

import functools
import itertools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics, trace


class SolverDivergenceError(RuntimeError):
    """A solve produced a non-finite objective on EVERY available path
    (fused kernel and the jnp oracle fallback) — the problem itself is
    numerically bad, not the backend.  Carries the repro coordinates and,
    when a debris dir was configured, the path of the dumped
    (Sigma_hat, lam, X0, n_valid) bundle."""

    def __init__(self, msg: str, *, lam: float | None = None,
                 n: int | None = None, debris_path: str | None = None):
        super().__init__(msg)
        self.lam = lam
        self.n = n
        self.debris_path = debris_path


def is_dispatch_error(e: BaseException) -> bool:
    """Whether ``e`` is a retriable device-dispatch failure.  XLA runtime
    errors (and the injected test double) subclass RuntimeError; data
    corruption (`sparse.store.ShardCorruptionError`) and
    `SolverDivergenceError` are permanent-and-loud and must propagate
    untouched, never be retried at fewer devices."""
    if not isinstance(e, RuntimeError) or isinstance(e, SolverDivergenceError):
        return False
    from repro.sparse.store import ShardCorruptionError

    return not isinstance(e, ShardCorruptionError)


_DEBRIS_SEQ = itertools.count()


def _dump_debris(debris_dir: str, *, Sigma, lam, X0, n_valid,
                 tag: str = "solve") -> str:
    """Dump a self-contained repro bundle for a diverged problem — the
    exact (Sigma_hat, lam, X0, n_valid) the failing solve saw, loadable
    with one ``np.load`` to replay it offline."""
    os.makedirs(debris_dir, exist_ok=True)
    Sigma = np.asarray(Sigma)
    n = Sigma.shape[0]
    while True:
        path = os.path.join(
            debris_dir, f"debris_{tag}_{next(_DEBRIS_SEQ):04d}.npz"
        )
        if not os.path.exists(path):
            break
    np.savez(
        path,
        Sigma_hat=Sigma,
        lam=np.asarray(float(lam), np.float64),
        X0=np.asarray(X0) if X0 is not None else np.eye(n, dtype=Sigma.dtype),
        n_valid=np.asarray(int(n_valid if n_valid is not None else n)),
    )
    return path


class BCDResult(NamedTuple):
    X: jax.Array          # solution of the augmented problem (6)
    Z: jax.Array          # X / Tr X — feasible for DSPCA (1)
    obj: jax.Array        # augmented objective value at X
    phi: jax.Array        # primal DSPCA value Tr(Sigma Z) - lam ||Z||_1
    # (max_sweeps,) per-sweep objective trace, nan-padded past the executed
    # sweeps.  The jnp path records the augmented objective (6); the fused
    # kernel impls record the barrier-free objective F(X) their on-chip
    # early exit tests (see kernels/bcd_fused.py — the two differ by the
    # O(beta) logdet term only).
    history: jax.Array
    sweeps: jax.Array     # number of sweeps actually executed
    beta: float = 0.0     # logdet barrier weight actually used (for kkt_gap)
    # Final barrier-free objective F(X) as computed ON-CHIP by the fused
    # kernel's early-exit test (kernels/bcd_fused.py) — None on the jnp
    # path, whose early exit uses the augmented objective (= ``obj``).
    # Surfaced so the driver can report solver convergence telemetry
    # without recomputing, and so kernel/oracle parity is checkable.
    kernel_obj: jax.Array | None = None


def augmented_objective(X, Sigma, lam, beta):
    """Objective of problem (6)."""
    sign, logdet = jnp.linalg.slogdet(X)
    logdet = jnp.where(sign > 0, logdet, -jnp.inf)
    return (
        jnp.sum(Sigma * X)
        - lam * jnp.sum(jnp.abs(X))
        - 0.5 * jnp.trace(X) ** 2
        + beta * logdet
    )


def primal_value(Z, Sigma, lam):
    """DSPCA primal objective phi(Z) = Tr(Sigma Z) - lam ||Z||_1."""
    return jnp.sum(Sigma * Z) - lam * jnp.sum(jnp.abs(Z))


def _coordinate_step(i, carry, Y, s, lam, j):
    """One coordinate update of the box QP — closed form (13)."""
    u, w = carry
    y1 = Y[i, i]
    ui = u[i]
    g = w[i] - y1 * ui            # \hat y^T \hat u : the off-diagonal inner product
    lo = s[i] - lam
    hi = s[i] + lam
    # y1 > 0: unconstrained minimiser -g/y1 clipped to the box.
    eta_pos = jnp.clip(-g / jnp.where(y1 > 0, y1, 1.0), lo, hi)
    # y1 == 0: objective is linear (2*g*eta): go to the box edge.
    eta_zero = jnp.where(g > 0, lo, hi)
    eta = jnp.where(y1 > 0, eta_pos, eta_zero)
    eta = jnp.where(i == j, ui, eta)      # coordinate j is not a variable
    w = w + Y[:, i] * (eta - ui)
    u = u.at[i].set(eta)
    return u, w


def qp_coordinate_descent(Y, s, lam, u0, j, sweeps: int):
    """Solve (11) ``min u^T Y u : ||u - s||_inf <= lam`` with ``u_j = 0``.

    ``Y`` must have row/column ``j`` zeroed.  Returns (u, w=Y@u, R2=u^T Y u).
    """
    n = Y.shape[0]
    w0 = Y @ u0

    def body(_, carry):
        return jax.lax.fori_loop(
            0, n, functools.partial(_coordinate_step, Y=Y, s=s, lam=lam, j=j), carry
        )

    u, w = jax.lax.fori_loop(0, sweeps, body, (u0, w0))
    return u, w, jnp.dot(u, w)


def solve_tau(R2, c, beta, iters: int = 80):
    """min_{tau>0} R2/tau - beta*log(tau) + (c + tau)^2 / 2.

    The derivative g(tau) = tau + c - R2/tau^2 - beta/tau is strictly
    increasing (g' = 1 + 2 R2/tau^3 + beta/tau^2 > 0), so bisection on the
    sign of g converges linearly and is branch-free for XLA.
    """
    hi = jnp.maximum(1.0, -c) + jnp.sqrt(jnp.maximum(R2, 0.0)) + beta + 1.0
    lo = jnp.minimum(beta / (beta + jnp.maximum(-c, 0.0) + 1.0), hi) * 1e-12

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        g = mid + c - R2 / (mid * mid) - beta / mid
        lo = jnp.where(g < 0, mid, lo)
        hi = jnp.where(g < 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


def row_update(
    X, Sigma, lam, beta, j, qp_sweeps: int, tau_iters: int = 80,
    qp_impl: str = "jnp",
):
    """Update row/column ``j`` of ``X`` (steps 4–6 of Algorithm 1)."""
    n = X.shape[0]
    ej = jax.nn.one_hot(j, n, dtype=X.dtype)
    mask = 1.0 - ej
    # Y = X_{\j\j} embedded in the full matrix (row/col j zeroed).
    Y = X * mask[:, None] * mask[None, :]
    s = Sigma[:, j] * mask                      # Sigma_j without the diagonal entry
    sigma = Sigma[j, j]
    t = jnp.trace(Y)
    c = sigma - lam - t

    u0 = s                                       # box centre — always feasible
    if qp_impl == "pallas":
        from repro.kernels.bcd_sweep import qp_sweep_pallas

        u, w, R2 = qp_sweep_pallas(
            Y, s, lam, u0, j, sweeps=qp_sweeps,
            interpret=jax.default_backend() != "tpu",
        )
    else:
        u, w, R2 = qp_coordinate_descent(Y, s, lam, u0, j, qp_sweeps)
    tau = solve_tau(R2, c, beta, tau_iters)

    y = w / tau                                  # y = Y u / tau  (zero at j)
    x = c + tau                                  # x = sigma - lam - t + tau
    # Write back: row/col j <- y, diagonal <- x.
    X = X * mask[:, None] * mask[None, :]
    X = X + y[:, None] * ej[None, :] + y[None, :] * ej[:, None]
    X = X + x * ej[:, None] * ej[None, :]
    return X


@functools.partial(
    jax.jit, static_argnames=("max_sweeps", "qp_sweeps", "tau_iters", "qp_impl")
)
def _solve_bcd_jit(
    Sigma, lam, beta, X0, max_sweeps, qp_sweeps, tol, tau_iters, qp_impl="jnp"
):
    n = Sigma.shape[0]

    def sweep(X):
        return jax.lax.fori_loop(
            0,
            n,
            lambda j, X: row_update(
                X, Sigma, lam, beta, j, qp_sweeps, tau_iters, qp_impl
            ),
            X,
        )

    def cond(state):
        _, _, prev, obj, k, done = state
        return (~done) & (k < max_sweeps)

    def body(state):
        X, hist, prev, _, k, _ = state
        X = sweep(X)
        obj = augmented_objective(X, Sigma, lam, beta)
        hist = jax.lax.dynamic_update_slice(hist, obj[None], (k,))
        done = jnp.abs(obj - prev) <= tol * (1.0 + jnp.abs(obj))
        return X, hist, obj, obj, k + 1, done

    minus_inf = jnp.array(-jnp.inf, Sigma.dtype)
    hist0 = jnp.full((max_sweeps,), jnp.nan, Sigma.dtype)
    X, hist, _, obj, k, _ = jax.lax.while_loop(
        cond, body,
        (X0, hist0, minus_inf, minus_inf, jnp.array(0), jnp.array(False)),
    )

    trX = jnp.trace(X)
    Z = X / trX
    return BCDResult(
        X=X,
        Z=Z,
        obj=obj,
        phi=primal_value(Z, Sigma, lam),
        history=hist,
        sweeps=k,
    )


def _resolve_solver_impl(solver_impl: str, n: int, itemsize: int,
                         batch: int = 1) -> str:
    """Map 'auto' to a concrete impl: a fused whole-solve kernel scheme on
    TPU when `plan_fused_solve` finds one that fits VMEM (resident Sigma+X
    for n_hat <= 768, tiled Sigma streaming up to ~1664), the jnp while/fori
    program elsewhere (interpret-mode Pallas on CPU measures the
    interpreter, not the kernel — see ROADMAP.md "Solver kernel
    architecture")."""
    if solver_impl != "auto":
        return solver_impl
    from repro.kernels import ops as kernel_ops

    # itemsize <= 4: Mosaic cannot lower f64 kernels, so x64 solves (the
    # benchmark/test default) stay on the jnp program even on TPU.
    if (
        jax.default_backend() == "tpu"
        and itemsize <= 4
        and kernel_ops.plan_fused_solve(n, itemsize, batch) is not None
    ):
        return "fused"
    return "jnp"


def solve_bcd(
    Sigma,
    lam: float,
    *,
    beta: float | None = None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tol: float = 1e-7,
    tau_iters: int = 80,
    X0=None,
    qp_impl: str = "jnp",
    solver_impl: str = "jnp",
    panel_rows: int = 0,
) -> BCDResult:
    """Solve DSPCA (1) by block coordinate ascent on the augmented problem (6).

    Args:
      Sigma: (n, n) PSD covariance (typically the *reduced* covariance after
        safe feature elimination — Thm 2.1 lets us assume lam < min_i Sigma_ii).
      lam: sparsity penalty, must satisfy lam >= 0.
      beta: logdet barrier weight; ``eps/n``-style default scaled to the data.
      max_sweeps: K in the paper (they report K~5 in practice).
      qp_sweeps: inner coordinate-descent sweeps for (11).
      X0: warm-start iterate (PD); defaults to the identity (cold start).
      qp_impl: inner-QP backend for the 'jnp' solver ('jnp' or the per-row
        'pallas' kernel — one launch per row update, the legacy path).
      solver_impl: 'jnp' (while/fori XLA program), 'fused' (ONE Pallas
        launch for the whole solve, kernels/bcd_fused.py — resident or
        tiled scheme chosen by `ops.plan_fused_solve`), 'fused_ref'
        (its jnp oracle), or 'auto' (fused on TPU when some one-launch
        scheme fits the VMEM budget, jnp otherwise).
      panel_rows: Sigma panel height for the tiled scheme (0 = auto).
    """
    Sigma = jnp.asarray(Sigma)
    n = Sigma.shape[0]
    if beta is None:
        beta = 1e-4 * float(jnp.trace(Sigma)) / n
    if X0 is None:
        X0 = jnp.eye(n, dtype=Sigma.dtype)
    else:
        X0 = jnp.asarray(X0, Sigma.dtype)
    lam = jnp.asarray(lam, Sigma.dtype)
    beta_ = jnp.asarray(beta, Sigma.dtype)
    impl = _resolve_solver_impl(solver_impl, n, Sigma.dtype.itemsize)
    if impl in ("fused", "fused_ref"):
        from repro.kernels import ops as kernel_ops

        with trace.span("solver.solve", n=n, impl=impl):
            X, kernel_obj, sweeps, hist = kernel_ops.bcd_solve(
                Sigma, lam, beta_, X0, max_sweeps=max_sweeps,
                qp_sweeps=qp_sweeps, tol=tol, tau_iters=tau_iters,
                panel_rows=panel_rows,
                impl="pallas" if impl == "fused" else "ref",
            )
            trace.device_sync(X)
        trX = jnp.trace(X)
        Z = X / trX
        return BCDResult(
            X=X,
            Z=Z,
            obj=augmented_objective(X, Sigma, lam, beta_),
            phi=primal_value(Z, Sigma, lam),
            history=hist,
            sweeps=sweeps,
            beta=float(beta),
            kernel_obj=kernel_obj,
        )
    with trace.span("solver.solve", n=n, impl=impl):
        res = _solve_bcd_jit(
            Sigma, lam, beta_, X0, max_sweeps, qp_sweeps,
            jnp.asarray(tol, Sigma.dtype), tau_iters, qp_impl,
        )
        trace.device_sync(res.X)
    return res._replace(beta=float(beta))


def solve_bcd_with_history(
    Sigma,
    lam: float,
    *,
    beta: float | None = None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tau_iters: int = 80,
) -> BCDResult:
    """Like ``solve_bcd`` but guaranteed to run all ``max_sweeps`` sweeps so
    ``history`` has no nan padding (Fig-1 convergence benchmark).  A negative
    tol can never satisfy ``|dobj| <= tol (1 + |obj|)``, disabling the early
    exit."""
    return solve_bcd(
        Sigma, lam, beta=beta, max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
        tau_iters=tau_iters, tol=-1.0,
    )


def solve_bcd_grid(
    Sigma,
    lams,
    *,
    beta: float | None = None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tol: float = 1e-7,
    tau_iters: int = 80,
    X0=None,
) -> BCDResult:
    """vmap the solver over a lambda grid — the outer-level parallelism the
    paper's laptop could not exploit (DESIGN.md §5): on a TPU pod each
    lambda's reduced problem runs on its own VMEM-resident solve.  Returns a
    batched BCDResult (leading axis = lambda).  The lambda-search bracketing
    probe (`spca.search_lambda` with ``lam_grid_probe``) routes its multi-
    lambda evaluations through here instead of solving one lambda at a time.

    Superseded for whole searches by `solve_bcd_many` /
    ``SPCAConfig.batch_evals``, which run mixed-size problems through the
    batched kernel launch (`ops.bcd_solve_batched`) instead of vmapping the
    XLA program over a shared Sigma; this stays as the lightweight probe
    primitive and a parity reference."""
    Sigma = jnp.asarray(Sigma)
    n = Sigma.shape[0]
    if beta is None:
        beta = 1e-4 * float(jnp.trace(Sigma)) / n
    lams = jnp.asarray(lams, Sigma.dtype)
    if X0 is None:
        X0 = jnp.eye(n, dtype=Sigma.dtype)

    def one(lam):
        return _solve_bcd_jit(
            Sigma, lam, jnp.asarray(beta, Sigma.dtype), X0, max_sweeps,
            qp_sweeps, jnp.asarray(tol, Sigma.dtype), tau_iters,
        )

    res = jax.vmap(one)(lams)
    return res._replace(beta=float(beta))


def _pad128(n: int) -> int:
    return max(128, ((n + 127) // 128) * 128)


def solve_bcd_many(
    Sigmas,
    lams,
    *,
    betas=None,
    X0s=None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tol: float = 1e-7,
    tau_iters: int = 80,
    panel_rows: int = 0,
    impl: str = "auto",
    devices: int = 0,
    min_devices: int = 1,
    counters: dict | None = None,
) -> list[BCDResult]:
    """Solve B independent problems of (possibly) different sizes in ONE
    batched launch (`ops.bcd_solve_batched`).

    ``Sigmas`` is a list of (n_b, n_b) reduced covariances, ``lams`` the
    per-problem penalties, ``X0s`` optional warm starts (None entries cold-
    start at the identity).  Problems are zero-padded to a common 128-lane
    size with per-problem ``n_valid`` masks — the kernels/oracle only touch
    the leading n_b coordinates, so each result equals its standalone
    solve.  This is the launch-economics primitive behind the batched
    lambda search and the batched deflation round: O(1) launches for a
    whole bracket/grid or component set instead of O(B).

    ``devices > 1`` fans the padded batch out across the local device mesh
    (`ops.bcd_solve_batched devices=`): each device solves its B/D slice,
    still one dispatch, traced as a ``solver.device_grid`` span.
    """
    B = len(Sigmas)
    if B == 0:
        return []
    Sigmas = [jnp.asarray(S) for S in Sigmas]
    dtype = Sigmas[0].dtype
    sizes = [int(S.shape[0]) for S in Sigmas]
    n_pad = _pad128(max(sizes))
    if betas is None:
        betas = [None] * B
    betas = [
        1e-4 * float(jnp.trace(S)) / n if b is None else float(b)
        for S, n, b in zip(Sigmas, sizes, betas)
    ]
    if X0s is None:
        X0s = [None] * B
    Sp = np.zeros((B, n_pad, n_pad), np.asarray(Sigmas[0]).dtype)
    Xp = np.zeros((B, n_pad, n_pad), Sp.dtype)
    for k, (S, n) in enumerate(zip(Sigmas, sizes)):
        Sp[k, :n, :n] = np.asarray(S)
        Xp[k, :n, :n] = np.eye(n) if X0s[k] is None else np.asarray(X0s[k])
    from repro.kernels import ops as kernel_ops

    def _dispatch(D: int):
        X, kernel_objs, sweeps, hist = kernel_ops.bcd_solve_batched(
            jnp.asarray(Sp, dtype), jnp.asarray(lams, dtype),
            jnp.asarray(betas, dtype), jnp.asarray(Xp, dtype),
            jnp.asarray(sizes, jnp.int32), max_sweeps=max_sweeps,
            qp_sweeps=qp_sweeps, tol=tol, tau_iters=tau_iters,
            panel_rows=panel_rows, impl=impl, devices=D,
        )
        trace.device_sync(X)
        return X, kernel_objs, sweeps, hist

    # Degraded-mode device grid: a failed sharded dispatch (an XLA/runtime
    # error — NOT corruption, which propagates untouched) retries the round
    # at D/2, halving down to ``min_devices``.  Each problem's result is a
    # pure function of its inputs, so a narrower grid changes launch
    # economics only, never the solves.
    D = min(max(int(devices or 0), 0), B)
    # the path ops.bcd_solve_batched takes, named like solve_bcd's spans
    ran = "fused" if impl == "pallas" or (
        impl == "auto" and _resolve_solver_impl(
            "auto", n_pad, np.dtype(Sp.dtype).itemsize, B) == "fused"
    ) else "fused_ref"
    while True:
        span_name = "solver.device_grid" if D > 1 else "solver.solve_many"
        kw = {"devices": D} if D > 1 else {}
        try:
            with trace.span(span_name, batch=B, n_pad=n_pad, impl=ran,
                            **kw):
                X, kernel_objs, sweeps, hist = _dispatch(D)
            break
        except RuntimeError as e:
            nD = max(int(min_devices), 1, D // 2)
            if D <= 1 or nD >= D or not is_dispatch_error(e):
                raise
            metrics.counter("mesh.degraded").inc()
            if counters is not None:
                counters["mesh_degraded"] = (
                    counters.get("mesh_degraded", 0) + 1
                )
            D = nD
    out: list[BCDResult] = []
    for k, n in enumerate(sizes):
        Xk = X[k, :n, :n]
        trX = jnp.trace(Xk)
        Zk = Xk / trX
        lam_k = jnp.asarray(lams[k], dtype)
        out.append(BCDResult(
            X=Xk,
            Z=Zk,
            obj=augmented_objective(Xk, Sigmas[k], lam_k, betas[k]),
            phi=primal_value(Zk, Sigmas[k], lam_k),
            history=hist[k],
            sweeps=sweeps[k],
            beta=betas[k],
            kernel_obj=kernel_objs[k],
        ))
    return out


def observe_result_health(res: BCDResult, *, max_sweeps: int) -> tuple[bool, bool]:
    """Numerical-health monitor over the solver telemetry a `BCDResult`
    already surfaces: a non-finite objective (the fused kernels' on-chip
    ``kernel_obj`` when present, else the augmented ``obj``) means the
    solve produced garbage; ``sweeps == max_sweeps`` means the
    objective-based early exit never fired (a stall — the result is the
    budget's best effort, not a converged optimum).

    Increments the ``solver.nonfinite`` / ``solver.stalled`` counters the
    default `obs.health.solver_rules` pack watches, so a NaN'd fit flips
    ``/healthz`` to 503 before its components can ship.  Returns
    ``(nonfinite, stalled)`` for callers that want to act directly.

    Call sites are the driver layers that already concretise the result
    (`core.spca` reads ``int(res.sweeps)`` and the KKT gap right after
    every solve), so the host transfer this check rides on has been paid.
    """
    obj = res.kernel_obj if res.kernel_obj is not None else res.obj
    nonfinite = not bool(np.isfinite(np.asarray(obj)))
    stalled = int(res.sweeps) >= int(max_sweeps)
    if nonfinite:
        metrics.counter("solver.nonfinite").inc()
    if stalled:
        metrics.counter("solver.stalled").inc()
    return nonfinite, stalled


def solve_bcd_supervised(
    Sigma,
    lam: float,
    *,
    beta: float | None = None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tol: float = 1e-7,
    tau_iters: int = 80,
    X0=None,
    qp_impl: str = "jnp",
    solver_impl: str = "jnp",
    panel_rows: int = 0,
    fallback: bool = True,
    debris_dir: str | None = None,
) -> tuple[BCDResult, int]:
    """`solve_bcd` under the fallback ladder: solve, observe health, and
    when the FUSED path reports a non-finite objective or a max-sweeps
    stall, transparently re-solve the same problem on the jnp oracle
    (counted as ``solver.fallbacks``, traced as a ``solver.fallback``
    span).  A problem that is non-finite on both paths raises
    `SolverDivergenceError` after dumping its repro bundle to
    ``debris_dir`` (``solver.divergence``).  Returns ``(result,
    fallbacks_taken)``; a stall on the oracle path is kept as the budget's
    best effort, exactly like the unsupervised driver."""
    res = solve_bcd(
        Sigma, lam, beta=beta, max_sweeps=max_sweeps, qp_sweeps=qp_sweeps,
        tol=tol, tau_iters=tau_iters, X0=X0, qp_impl=qp_impl,
        solver_impl=solver_impl, panel_rows=panel_rows,
    )
    nonfinite, stalled = observe_result_health(res, max_sweeps=max_sweeps)
    Sigma_j = jnp.asarray(Sigma)
    n = int(Sigma_j.shape[0])
    impl = _resolve_solver_impl(solver_impl, n, Sigma_j.dtype.itemsize)
    fallbacks = 0
    if (nonfinite or stalled) and fallback and impl in ("fused", "fused_ref"):
        fallbacks = 1
        metrics.counter("solver.fallbacks").inc()
        with trace.span("solver.fallback", n=n,
                        reason="nonfinite" if nonfinite else "stall"):
            res = solve_bcd(
                Sigma, lam, beta=beta, max_sweeps=max_sweeps,
                qp_sweeps=qp_sweeps, tol=tol, tau_iters=tau_iters, X0=X0,
                qp_impl=qp_impl, solver_impl="jnp",
            )
        nonfinite, _ = observe_result_health(res, max_sweeps=max_sweeps)
    if nonfinite:
        metrics.counter("solver.divergence").inc()
        path = None
        if debris_dir:
            path = _dump_debris(debris_dir, Sigma=Sigma, lam=lam, X0=X0,
                                n_valid=None)
        raise SolverDivergenceError(
            f"solve diverged on every path (n={n}, lam={float(lam):.6g}"
            + (f"; repro bundle at {path}" if path else ")"),
            lam=float(lam), n=n, debris_path=path,
        )
    return res, fallbacks


def supervise_many(
    results: list[BCDResult],
    Sigmas,
    lams,
    *,
    X0s=None,
    max_sweeps: int = 20,
    qp_sweeps: int = 4,
    tol: float = 1e-7,
    tau_iters: int = 80,
    fallback: bool = True,
    debris_dir: str | None = None,
) -> tuple[list[BCDResult], int]:
    """The fallback ladder over a batched round: observe every result's
    health and individually re-solve the unhealthy ones on the jnp oracle
    (the batched launch always runs a kernel-family backend, so the
    oracle re-solve is a genuinely independent path).  Returns the patched
    result list and the number of fallbacks taken; a problem that is
    non-finite on both paths raises `SolverDivergenceError`."""
    out = list(results)
    n_fallbacks = 0
    for k, res in enumerate(out):
        nonfinite, stalled = observe_result_health(res, max_sweeps=max_sweeps)
        if not (nonfinite or stalled):
            continue
        if not fallback:
            if nonfinite:
                metrics.counter("solver.divergence").inc()
                n_k = int(jnp.asarray(Sigmas[k]).shape[0])
                path = None
                if debris_dir:
                    path = _dump_debris(
                        debris_dir, Sigma=Sigmas[k], lam=lams[k],
                        X0=None if X0s is None else X0s[k], n_valid=None,
                        tag="batched",
                    )
                raise SolverDivergenceError(
                    f"batched solve {k} diverged (n={n_k}, "
                    f"lam={float(lams[k]):.6g})",
                    lam=float(lams[k]), n=n_k, debris_path=path,
                )
            continue
        n_fallbacks += 1
        metrics.counter("solver.fallbacks").inc()
        n_k = int(jnp.asarray(Sigmas[k]).shape[0])
        with trace.span("solver.fallback", n=n_k, batch_index=k,
                        reason="nonfinite" if nonfinite else "stall"):
            patched = solve_bcd(
                Sigmas[k], lams[k], beta=res.beta, max_sweeps=max_sweeps,
                qp_sweeps=qp_sweeps, tol=tol, tau_iters=tau_iters,
                X0=None if X0s is None else X0s[k], solver_impl="jnp",
            )
        still_bad, _ = observe_result_health(patched, max_sweeps=max_sweeps)
        if still_bad:
            metrics.counter("solver.divergence").inc()
            path = None
            if debris_dir:
                path = _dump_debris(
                    debris_dir, Sigma=Sigmas[k], lam=lams[k],
                    X0=None if X0s is None else X0s[k], n_valid=None,
                    tag="batched",
                )
            raise SolverDivergenceError(
                f"batched solve {k} diverged on every path (n={n_k}, "
                f"lam={float(lams[k]):.6g})"
                + (f"; repro bundle at {path}" if path else ""),
                lam=float(lams[k]), n=n_k, debris_path=path,
            )
        out[k] = patched
    return out, n_fallbacks


def leading_sparse_component(Z, *, rel_tol: float = 1e-2):
    """Extract the sparse PC from the DSPCA solution: the leading eigenvector
    of Z, with entries below ``rel_tol * max|x|`` zeroed (the SDP relaxation
    returns numerically-tiny off-support values, not exact zeros)."""
    w, V = jnp.linalg.eigh(Z)
    x = V[:, -1]
    thresh = rel_tol * jnp.max(jnp.abs(x))
    x = jnp.where(jnp.abs(x) > thresh, x, 0.0)
    norm = jnp.linalg.norm(x)
    x = x / jnp.where(norm > 0, norm, 1.0)
    # Deterministic sign: largest-|entry| positive.
    imax = jnp.argmax(jnp.abs(x))
    return x * jnp.sign(x[imax])
