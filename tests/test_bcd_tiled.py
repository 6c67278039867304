"""Tiled + batched fused BCD: VMEM-boundary plan selection, interpret-mode
parity of the tiled scheme against the oracle (including a size the
resident PR-2 kernel refuses), the masked-oracle contract, and
batched-vs-sequential parity."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bcd import _resolve_solver_impl
from repro.kernels import bcd_fused as bcd_fused_mod
from repro.kernels import ops, ref
from repro.kernels.bcd_fused import bcd_solve_batched_pallas, bcd_solve_pallas


def _gaussian_cov(n, m, seed=0):
    rng = np.random.default_rng(seed)
    F = rng.normal(size=(m, n))
    return jnp.asarray((F.T @ F) / m, jnp.float32)


def _problem(n, seed):
    Sigma = _gaussian_cov(n, n + 12, seed=seed)
    lam = 0.3 * float(jnp.max(jnp.diag(Sigma)))
    beta = 1e-4 * float(jnp.trace(Sigma)) / n
    return Sigma, lam, beta


# ---------------------------------------------------------------------------
# Tile-budget plan / auto-select behaviour at the VMEM boundary.
# ---------------------------------------------------------------------------


def test_plan_resident_up_to_768():
    for n in (128, 512, 768):
        plan = ops.plan_fused_solve(n)
        assert plan is not None and plan.scheme == "resident", (n, plan)


def test_plan_tiled_just_past_resident_cap():
    """n_hat = 769 is the first size the resident scheme refuses; the plan
    must hand it to the tiled scheme instead of giving up."""
    plan = ops.plan_fused_solve(769)
    assert plan is not None
    assert plan.scheme == "tiled"
    assert plan.panel_rows in (128, 256, 512)
    assert plan.n_pad == 896
    assert plan.vmem_bytes <= ops._TILED_VMEM_BUDGET_BYTES


def test_plan_none_at_2048():
    """2048 exceeds even the tiled budget (X alone would eat the core):
    no one-launch plan, the driver falls back to the XLA program."""
    assert ops.plan_fused_solve(2048) is None
    assert not ops.fused_solve_fits(2048)
    assert ops.fused_solve_fits(769)
    assert ops.fused_solve_fits(1664)


def test_plan_batched_is_more_conservative():
    """A batch grid pipelines the next problem's blocks, so the per-step
    budget shrinks: sizes near the single-problem ceiling must downgrade
    (resident->tiled) or drop out rather than silently oversubscribe."""
    single = ops.plan_fused_solve(768, batch=1)
    batched = ops.plan_fused_solve(768, batch=8)
    assert single.scheme == "resident"
    assert batched is None or batched.scheme == "tiled"
    # The tiled scheme's X is one VMEM scratch that DMAs itself in and out,
    # so nothing revolves under the batch grid: the v5e compiler reports the
    # same scoped VMEM at B=8 as at B=1 (n_pad 1664: 16.3 MiB).
    assert ops.plan_fused_solve(1664, batch=8) == ops.plan_fused_solve(1664)
    assert ops.plan_fused_solve(2048, batch=8) is None


def test_auto_resolves_to_jnp_off_tpu():
    # off-TPU 'auto' never picks the kernel, at any size
    for n in (100, 1000, 4000):
        assert _resolve_solver_impl("auto", n, 4) == "jnp"


# ---------------------------------------------------------------------------
# Tiled-kernel parity.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [3, 60, 130, 200])
def test_tiled_kernel_matches_ref_oracle(n):
    """Interpret-mode parity of the tiled scheme vs the jnp oracle.  The
    tiled kernel reorders f32 accumulations (panel matvec, incremental
    trace), so the tolerance is f32-roundoff-sized, not exactness-sized."""
    Sigma, lam, beta = _problem(n, seed=n)
    X0 = jnp.eye(n, dtype=Sigma.dtype)
    Xt, objt, st, ht = bcd_solve_pallas(
        Sigma, lam, beta, X0, -1.0, max_sweeps=3, qp_sweeps=2,
        scheme="tiled", interpret=True,
    )
    Xr, objr, sr, hr = ref.bcd_solve_ref(
        Sigma, jnp.float32(lam), jnp.float32(beta), X0, jnp.float32(-1.0),
        max_sweeps=3, qp_sweeps=2,
    )
    np.testing.assert_allclose(Xt, Xr, rtol=3e-4, atol=1e-5)
    np.testing.assert_allclose(ht, hr, rtol=1e-3)
    assert int(st) == int(sr) == 3


def test_tiled_parity_above_resident_cap():
    """Acceptance: the tiled scheme solves a size the PR-2 resident kernel
    refuses (4 * 896^2 * 4B > 12 MB) and matches the oracle.

    Runs in x64 so the parity bound is tight: at n=772 the f32 coordinate
    recursion accumulates ~1e-3 of benign order-of-summation noise, while
    in f64 kernel and oracle agree to ~1e-13 — i.e. the tiling is logically
    exact and only reorders floating-point accumulation."""
    import jax

    n = 772
    assert ops.plan_fused_solve(n).scheme == "tiled"
    with jax.enable_x64(True):
        rng = np.random.default_rng(99)
        F = rng.normal(size=(n + 12, n))
        Sigma = jnp.asarray((F.T @ F) / (n + 12), jnp.float64)
        lam = 0.3 * float(jnp.max(jnp.diag(Sigma)))
        beta = 1e-4 * float(jnp.trace(Sigma)) / n
        X0 = jnp.eye(n, dtype=Sigma.dtype)
        Xt, objt, st, ht = bcd_solve_pallas(
            Sigma, lam, beta, X0, -1.0, max_sweeps=2, qp_sweeps=1,
            tau_iters=40, scheme="tiled", interpret=True,
        )
        Xr, objr, sr, hr = ref.bcd_solve_ref(
            Sigma, jnp.float64(lam), jnp.float64(beta), X0,
            jnp.float64(-1.0), max_sweeps=2, qp_sweeps=1, tau_iters=40,
        )
        np.testing.assert_allclose(Xt, Xr, rtol=1e-10, atol=1e-12)
        np.testing.assert_allclose(ht, hr, rtol=1e-10)
        assert int(st) == int(sr) == 2


def test_tiled_multi_panel_uses_every_panel():
    """n just past one panel (129 -> n_pad 256, two 128-row panels): parity
    would fail if the second panel's rows never streamed in."""
    n = 129
    Sigma, lam, beta = _problem(n, seed=5)
    X0 = jnp.eye(n, dtype=Sigma.dtype)
    Xt, *_ = bcd_solve_pallas(
        Sigma, lam, beta, X0, -1.0, max_sweeps=2, qp_sweeps=2,
        scheme="tiled", panel_rows=128, interpret=True,
    )
    Xr, *_ = ref.bcd_solve_ref(
        Sigma, jnp.float32(lam), jnp.float32(beta), X0, jnp.float32(-1.0),
        max_sweeps=2, qp_sweeps=2,
    )
    np.testing.assert_allclose(Xt, Xr, rtol=3e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Masked oracle: the padded/n_valid contract both kernels implement.
# ---------------------------------------------------------------------------


def test_masked_ref_equals_plain_ref_on_embedded_problem():
    n, nv = 96, 60
    S = _gaussian_cov(nv, nv + 8, seed=7)
    Sp = jnp.zeros((n, n), jnp.float32).at[:nv, :nv].set(S)
    lam = 0.3 * float(jnp.max(jnp.diag(S)))
    beta = 1e-4 * float(jnp.trace(S)) / nv
    X0p = (jnp.eye(n) * (jnp.arange(n) < nv)).astype(jnp.float32)
    Xm, objm, sm, hm = ref.bcd_solve_masked_ref(
        Sp, jnp.float32(lam), jnp.float32(beta), X0p, jnp.float32(-1.0), nv,
        max_sweeps=3, qp_sweeps=2,
    )
    Xr, objr, sr, hr = ref.bcd_solve_ref(
        S, jnp.float32(lam), jnp.float32(beta), jnp.eye(nv, dtype=jnp.float32),
        jnp.float32(-1.0), max_sweeps=3, qp_sweeps=2,
    )
    np.testing.assert_allclose(Xm[:nv, :nv], Xr, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(hm, hr, rtol=1e-5)
    # frozen coordinates never move off zero
    assert float(jnp.max(jnp.abs(Xm[nv:, :]))) == 0.0
    assert float(jnp.max(jnp.abs(Xm[:, nv:]))) == 0.0


# ---------------------------------------------------------------------------
# Batched-vs-sequential parity (same supports and objectives to 1e-6).
# ---------------------------------------------------------------------------


def _mixed_batch(sizes, npad):
    Sl, X0l, lams, betas = [], [], [], []
    for k, nv in enumerate(sizes):
        S = _gaussian_cov(nv, nv + 5, seed=20 + k)
        Sl.append(jnp.zeros((npad, npad), jnp.float32).at[:nv, :nv].set(S))
        X0l.append((jnp.eye(npad) * (jnp.arange(npad) < nv))
                   .astype(jnp.float32))
        lams.append(0.3 * float(jnp.max(jnp.diag(S))))
        betas.append(1e-4 * float(jnp.trace(S)) / nv)
    return (jnp.stack(Sl), jnp.asarray(lams, jnp.float32),
            jnp.asarray(betas, jnp.float32), jnp.stack(X0l),
            jnp.asarray(sizes, jnp.int32))


def test_ops_batched_matches_sequential_solves():
    """The launch-economics contract: B problems in one batched call return
    the same supports and objectives (to 1e-6) as B standalone solves.

    Runs in x64: the comparison is then a pure semantics check (padding +
    masking must be invisible), free of f32 order-of-summation chaos —
    measured agreement is ~1e-12, far inside the 1e-6 contract.  In f32 an
    ill-conditioned problem can flip a box-QP clip boundary under 1e-7
    noise and legitimately walk to a different nearby iterate."""
    import jax

    with jax.enable_x64(True):
        sizes = [9, 33, 60, 41]
        npad = 64
        Sl, X0l, lams, betas = [], [], [], []
        for k, nv in enumerate(sizes):
            rng = np.random.default_rng(20 + k)
            F = rng.normal(size=(nv + 5, nv))
            S = jnp.asarray((F.T @ F) / (nv + 5), jnp.float64)
            Sl.append(jnp.zeros((npad, npad), jnp.float64)
                      .at[:nv, :nv].set(S))
            X0l.append((jnp.eye(npad) * (jnp.arange(npad) < nv))
                       .astype(jnp.float64))
            lams.append(0.3 * float(jnp.max(jnp.diag(S))))
            betas.append(1e-4 * float(jnp.trace(S)) / nv)
        Ss = jnp.stack(Sl)
        X0s = jnp.stack(X0l)
        lams = jnp.asarray(lams, jnp.float64)
        betas = jnp.asarray(betas, jnp.float64)
        nvs = jnp.asarray(sizes, jnp.int32)
        Xb, objb, sb, hb = ops.bcd_solve_batched(
            Ss, lams, betas, X0s, nvs, max_sweeps=6, qp_sweeps=2, tol=1e-9,
            impl="ref",
        )
        for k, nv in enumerate(sizes):
            Xs, objs, ss, hs = ops.bcd_solve(
                Ss[k, :nv, :nv], lams[k], betas[k], X0s[k, :nv, :nv],
                max_sweeps=6, qp_sweeps=2, tol=1e-9, impl="ref",
            )
            np.testing.assert_allclose(Xb[k, :nv, :nv], Xs,
                                       rtol=1e-8, atol=1e-10)
            assert float(objb[k]) == pytest.approx(float(objs), rel=1e-6)
            supp_b = np.flatnonzero(
                np.abs(np.diag(np.asarray(Xb[k]))) > 1e-8)
            supp_s = np.flatnonzero(np.abs(np.diag(np.asarray(Xs))) > 1e-8)
            assert set(supp_b.tolist()) == set(supp_s.tolist())


@pytest.mark.parametrize("scheme", ["resident", "tiled"])
def test_batched_kernel_matches_batched_oracle(scheme):
    sizes = [9, 33, 60]
    Ss, lams, betas, X0s, nvs = _mixed_batch(sizes, 64)
    Xk, objk, sk, hk = bcd_solve_batched_pallas(
        Ss, lams, betas, X0s, -1.0, nvs, max_sweeps=3, qp_sweeps=2,
        scheme=scheme, interpret=True,
    )
    Xm, objm, sm, hm = ref.bcd_solve_batched_ref(
        Ss, lams, betas, X0s, jnp.float32(-1.0), nvs,
        max_sweeps=3, qp_sweeps=2,
    )
    np.testing.assert_allclose(Xk, Xm, rtol=3e-4, atol=1e-5)
    np.testing.assert_allclose(hk, hm, rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sm))


def test_batched_is_one_pallas_call(monkeypatch):
    """B solves must issue exactly ONE pallas_call — that is the whole
    point of the batch grid dimension."""
    calls = {"n": 0}
    orig = bcd_fused_mod.pl.pallas_call

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(bcd_fused_mod.pl, "pallas_call", counting)
    sizes = [10, 20, 30]
    Ss, lams, betas, X0s, nvs = _mixed_batch(sizes, 32)
    # max_sweeps=5 + qp_sweeps=3 is a fresh static signature for this
    # session, so the jitted wrapper must trace (and count) the call.
    bcd_solve_batched_pallas(
        Ss, lams, betas, X0s, 1e-7, nvs, max_sweeps=5, qp_sweeps=3,
        interpret=True,
    )
    assert calls["n"] == 1
