"""End-to-end driver: lambda search, deflation, topic recovery."""
import os
from dataclasses import replace

import numpy as np
import pytest

from repro.core import SPCAConfig, fit_components, search_lambda, solve_at_lambda


def _planted(m=3000, n=400, seed=0, k=4, boost=6.0):
    rng = np.random.default_rng(seed)
    base = 0.5 / np.arange(1, n + 1) ** 1.1
    X = rng.poisson(base[None, :] * 8, size=(m, n)).astype(np.float64)
    topics = [list(range(i * k, (i + 1) * k)) for i in range(3)]
    seg = m // 3
    for t, words in enumerate(topics):
        X[t * seg : (t + 1) * seg, words] += rng.poisson(boost, size=(seg, k))
    return X, topics


def test_lambda_search_hits_cardinality():
    X, _ = _planted()
    cfg = SPCAConfig(max_sweeps=10, lam_search_evals=10)
    r = search_lambda(X, target_card=4, cfg=cfg)
    assert 4 <= r.cardinality <= 6
    assert r.reduced_n <= 100, "elimination failed to shrink the problem"


def test_topics_recovered_disjoint():
    X, topics = _planted()
    cfg = SPCAConfig(max_sweeps=10, lam_search_evals=8)
    pcs = fit_components(X, 3, target_card=4, cfg=cfg)
    supports = [set(pc.support.tolist()) for pc in pcs]
    # disjoint (word-removal deflation)
    for i in range(3):
        for j in range(i + 1, 3):
            assert not (supports[i] & supports[j])
    # each planted topic matched by some component
    for t in topics:
        assert any(s == set(t) for s in supports), (supports, topics)


def test_project_deflation_orthogonalish():
    X, _ = _planted(m=1500, n=200, seed=1)
    cfg = SPCAConfig(max_sweeps=8, lam_search_evals=6)
    pcs = fit_components(X, 2, target_card=4, cfg=cfg, deflation="project")
    x0, x1 = pcs[0].x, pcs[1].x
    c = abs(x0 @ x1) / (np.linalg.norm(x0) * np.linalg.norm(x1))
    assert c < 0.3


def test_lambda_search_cached_covariance_matches_rebuild():
    """Regression: the cached/sliced-covariance path must return the exact
    supports of the rebuild-per-eval path (a gram entry depends only on its
    own column pair, so slicing is bit-identical), while doing ONE build."""
    X, _ = _planted(m=1500, n=250, seed=2)
    cfg_cached = SPCAConfig(max_sweeps=12, lam_search_evals=8, warm_start=False)
    cfg_rebuild = replace(cfg_cached, reuse_covariance=False)
    d_cached, d_rebuild = {}, {}
    r_cached = search_lambda(X, 4, cfg=cfg_cached, diagnostics=d_cached)
    r_rebuild = search_lambda(X, 4, cfg=cfg_rebuild, diagnostics=d_rebuild)
    assert np.array_equal(r_cached.support, r_rebuild.support)
    assert r_cached.lam == r_rebuild.lam
    assert r_cached.variance == pytest.approx(r_rebuild.variance, rel=1e-12)
    # counting: one gather+matmul total (lazy seed at the first eval, every
    # later eval slices) vs one build per evaluation
    assert d_cached["cov_builds"] == 1
    assert d_cached["cov_slices"] == d_cached["evals"] - 1
    assert d_cached["cov_builds"] + d_cached["cov_slices"] == d_cached["evals"]
    assert d_rebuild["cov_builds"] == d_rebuild["evals"]


def test_lambda_search_warm_starts_every_subsequent_eval():
    """The search must not cold-start X after the first evaluation, and the
    warm-started search must land in the same acceptance window."""
    X, _ = _planted(m=1500, n=250, seed=3)
    cfg_warm = SPCAConfig(max_sweeps=12, lam_search_evals=8)
    cfg_cold = replace(cfg_warm, warm_start=False)
    d_warm, d_cold = {}, {}
    r_warm = search_lambda(X, 4, cfg=cfg_warm, diagnostics=d_warm)
    r_cold = search_lambda(X, 4, cfg=cfg_cold, diagnostics=d_cold)
    assert d_warm["warm_starts"] == d_warm["evals"] - 1
    assert d_cold["warm_starts"] == 0
    # warm starts can only reduce the sweeps needed across the search
    assert d_warm["total_sweeps"] <= d_cold["total_sweeps"]
    assert np.array_equal(r_warm.support, r_cold.support)
    # Both start points converge to the same unique optimum; at a finite
    # sweep budget they may sit on slightly different iterates, so compare
    # the explained variance with a relative tolerance.
    assert r_warm.variance == pytest.approx(r_cold.variance, rel=1e-2)
    # the returned result is stripped of the O(n_hat^2) iterate
    assert r_warm.X_reduced is None


def test_lambda_search_grid_probe_consistent():
    """The vmapped solve_bcd_grid bracketing probe must not change the
    answer, only (possibly) the number of bisection evaluations."""
    X, _ = _planted(m=1500, n=250, seed=4)
    cfg = SPCAConfig(max_sweeps=12, lam_search_evals=8)
    cfg_probe = replace(cfg, lam_grid_probe=5)
    d0, d1 = {}, {}
    r0 = search_lambda(X, 4, cfg=cfg, diagnostics=d0)
    r1 = search_lambda(X, 4, cfg=cfg_probe, diagnostics=d1)
    assert np.array_equal(r0.support, r1.support)
    assert d1["evals"] <= d0["evals"]


def test_solve_at_lambda_explained_variance_reasonable():
    X, topics = _planted()
    Xc = X - X.mean(0, keepdims=True)
    Sigma = (Xc.T @ Xc) / X.shape[0]
    r = search_lambda(X, target_card=4, cfg=SPCAConfig(max_sweeps=10))
    # the sparse PC should capture most of the variance of the best
    # same-cardinality planted topic direction
    best = 0.0
    for t in topics:
        v = np.zeros(X.shape[1]); v[t] = 1.0 / np.sqrt(len(t))
        best = max(best, v @ Sigma @ v)
    assert r.variance >= 0.8 * best


def test_launcher_refuses_more_devices_than_exist(monkeypatch):
    """`spca_run --devices D` on a host with fewer than D devices raises
    instead of running on the devices it finds."""
    from repro.launch import spca_run

    # a set variable makes the compile-cache helper leave jax's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    with pytest.raises(SystemExit, match="--devices 64 requested"):
        spca_run.main(["--docs", "100", "--words", "2500", "--devices", "64"])


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch):
    import jax

    from repro.launch import compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        d = compile_cache.enable_compile_cache()
        assert d == compile_cache.DEFAULT_DIR
        assert os.path.basename(d) == ".jax_cache"
        assert os.path.isdir(os.path.join(os.path.dirname(d), "src", "repro"))
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
