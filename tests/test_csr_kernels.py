"""CSR kernel correctness: interpret-mode Pallas vs jnp oracle vs numpy,
at the chunk edge cases the store produces (ragged nnz, empty rows,
all-zero columns, off-support sentinels)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.csr_gram import csr_gram_pallas
from repro.kernels.csr_stats import csr_column_stats_pallas


def _chunk(E, n, R, *, nnz, seed, all_zero_cols=(), empty_rows=()):
    """Synthetic padded chunk in store layout: ``nnz`` real entries, the
    rest zero-padding (value 0, col 0, seg 0)."""
    rng = np.random.default_rng(seed)
    cols_ok = np.setdiff1d(np.arange(n), np.asarray(all_zero_cols, int))
    rows_ok = np.setdiff1d(np.arange(R), np.asarray(empty_rows, int))
    vals = np.zeros(E, np.float32)
    cols = np.zeros(E, np.int32)
    segs = np.zeros(E, np.int32)
    vals[:nnz] = rng.normal(size=nnz)
    cols[:nnz] = rng.choice(cols_ok, size=nnz)
    segs[:nnz] = np.sort(rng.choice(rows_ok, size=nnz))
    return vals, cols, segs


# f32 unit roundoff: two f32 evaluations of the same sum in different
# orders each sit within (terms) * u * sum|terms| of the exact value.
_U32 = 2.0 ** -24


def _stats_rounding_bound(vals, cols, n):
    """Per-column bound on |s_a - s_b| for two f32 segmented sums (and on
    the sumsq pair, second element) taken in any order."""
    v = np.abs(vals.astype(np.float64))
    cnt = np.bincount(cols, minlength=n)[:n]
    s_abs = np.bincount(cols, weights=v, minlength=n)[:n]
    ss_abs = np.bincount(cols, weights=v * v, minlength=n)[:n]
    return 2 * cnt * _U32 * s_abs, 2 * cnt * _U32 * ss_abs


def _gram_rounding_bound(vals, cols, segs, R, n_hat):
    """Elementwise bound on |G_a - G_b| for two f32 evaluations of
    G = B^T B in any summation order: each is within (R + k) u |B|^T |B|
    of the exact Gram, k the most entries densified into one B cell."""
    keep = cols < n_hat
    B_abs = np.zeros((R, n_hat))
    np.add.at(B_abs, (segs[keep], cols[keep]),
              np.abs(vals[keep]).astype(np.float64))
    hits = np.zeros((R, n_hat))
    np.add.at(hits, (segs[keep], cols[keep]), 1)
    k = max(hits.max(initial=0.0), 1.0)
    return 2 * (R + k) * _U32 * (B_abs.T @ B_abs)


def _dense_stats(vals, cols, n):
    s = np.zeros(n)
    ss = np.zeros(n)
    np.add.at(s, cols, vals.astype(np.float64))
    np.add.at(ss, cols, vals.astype(np.float64) ** 2)
    return s, ss


# ---------------------------------------------------------------- csr_stats

@pytest.mark.parametrize("E,n,nnz,block_e", [
    (512, 300, 512, 128),    # full chunk
    (512, 300, 317, 128),    # ragged: nnz not a multiple of block_e
    (384, 129, 100, 256),    # E not a multiple of block_e either
    (256, 50, 0, 128),       # empty chunk
])
def test_csr_stats_parity(E, n, nnz, block_e):
    vals, cols, _ = _chunk(E, n, 8, nnz=nnz, seed=E + nnz)
    s_k, ss_k = csr_column_stats_pallas(
        jnp.asarray(vals), jnp.asarray(cols), n, block_e=block_e,
        interpret=True,
    )
    s_r, ss_r = ref.csr_column_stats_ref(jnp.asarray(vals), jnp.asarray(cols), n)
    # the vectorized kernel reduces 128 entries per MXU contraction, so
    # the summation order differs from the oracle's sequential scatter by
    # last-ulp f32 rounding — near-exact, not bit-exact
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ss_k), np.asarray(ss_r),
                               rtol=1e-6, atol=1e-6)
    s_d, ss_d = _dense_stats(vals, cols, n)
    np.testing.assert_allclose(np.asarray(s_k), s_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ss_k), ss_d, rtol=1e-5, atol=1e-5)


def test_csr_stats_all_zero_columns():
    """Columns with no entries must come out exactly zero (they are the
    ones Thm 2.1 eliminates first)."""
    dead = (0, 7, 41, 63)
    vals, cols, _ = _chunk(256, 64, 8, nnz=200, seed=9, all_zero_cols=dead)
    s, ss = csr_column_stats_pallas(
        jnp.asarray(vals), jnp.asarray(cols), 64, block_e=64, interpret=True
    )
    for c in dead:
        assert float(s[c]) == 0.0 and float(ss[c]) == 0.0
    assert float(jnp.sum(ss)) > 0


# ----------------------------------------------------------------- csr_gram

@pytest.mark.parametrize("E,R,n_hat,nnz", [
    (512, 32, 100, 512),     # full chunk, n_hat not a multiple of 128
    (512, 32, 100, 313),     # ragged tail
    (256, 16, 130, 200),     # n_hat straddles a 128 tile boundary
    (128, 8, 7, 0),          # empty chunk, tiny support
])
def test_csr_gram_parity(E, R, n_hat, nnz):
    vals, cols, segs = _chunk(E, n_hat + 40, R, nnz=nnz, seed=E + R,
                              empty_rows=(0, R - 1))
    # entries with col >= n_hat are off-support sentinels and must drop
    G_k = csr_gram_pallas(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat,
        interpret=True,
    )
    G_r = ref.csr_gram_ref(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat
    )
    # kernel and oracle sum in different orders: equal to f32 rounding
    bound = _gram_rounding_bound(vals, cols, segs, R, n_hat)
    assert np.all(np.abs(np.asarray(G_k, np.float64)
                         - np.asarray(G_r, np.float64)) <= bound)
    B = np.zeros((R, n_hat))
    keep = cols < n_hat
    np.add.at(B, (segs[keep], cols[keep]), vals[keep].astype(np.float64))
    np.testing.assert_allclose(np.asarray(G_k), B.T @ B, rtol=1e-4, atol=1e-4)
    # symmetry + PSD come free from G = B^T B; check symmetry exactly
    np.testing.assert_allclose(np.asarray(G_k), np.asarray(G_k).T,
                               rtol=0, atol=1e-5)


def test_csr_gram_empty_rows_are_harmless():
    """A chunk whose padded row slots are never touched must match the
    Gram of only its real rows."""
    E, R, n_hat = 128, 16, 40
    vals, cols, segs = _chunk(E, n_hat, R, nnz=90, seed=3)
    segs = np.minimum(segs, 4)   # squeeze all entries into rows 0..4
    G_full = csr_gram_pallas(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat,
        interpret=True,
    )
    G_tight = csr_gram_pallas(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), 5, n_hat,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(G_full), np.asarray(G_tight),
                               rtol=0, atol=1e-5)


def test_ops_wrappers_dispatch_and_cache():
    """ops.csr_* route to the oracle off-TPU and trace once per shape."""
    vals, cols, segs = _chunk(256, 80, 8, nnz=200, seed=11)
    s, ss = ops.csr_column_stats(jnp.asarray(vals), jnp.asarray(cols), n=80)
    s_r, ss_r = ref.csr_column_stats_ref(jnp.asarray(vals), jnp.asarray(cols), 80)
    # jit vs eager oracle may fuse the sums differently: f32-rounding bound
    s_bound, ss_bound = _stats_rounding_bound(vals, cols, 80)
    assert np.all(np.abs(np.asarray(s, np.float64)
                         - np.asarray(s_r, np.float64)) <= s_bound)
    assert np.all(np.abs(np.asarray(ss, np.float64)
                         - np.asarray(ss_r, np.float64)) <= ss_bound)
    G = ops.csr_gram(jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs),
                     n_rows=8, n_hat=80)
    G_r = ref.csr_gram_ref(jnp.asarray(vals), jnp.asarray(cols),
                           jnp.asarray(segs), 8, 80)
    bound = _gram_rounding_bound(vals, cols, segs, 8, 80)
    assert np.all(np.abs(np.asarray(G, np.float64)
                         - np.asarray(G_r, np.float64)) <= bound)
    # fixed chunk shapes: second call with new data must hit the jit cache
    n_traces = ops.csr_column_stats._cache_size()
    vals2 = np.roll(vals, 3)
    ops.csr_column_stats(jnp.asarray(vals2), jnp.asarray(cols), n=80)
    assert ops.csr_column_stats._cache_size() == n_traces


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 200), nnz=st.integers(0, 256), seed=st.integers(0, 999))
def test_property_csr_stats_match_dense_scatter(n, nnz, seed):
    E = 256
    vals, cols, _ = _chunk(E, n, 8, nnz=nnz, seed=seed)
    s, ss = csr_column_stats_pallas(
        jnp.asarray(vals), jnp.asarray(cols), n, block_e=64, interpret=True
    )
    s_d, ss_d = _dense_stats(vals, cols, n)
    np.testing.assert_allclose(np.asarray(s), s_d, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), ss_d, rtol=1e-4, atol=1e-4)
    assert (np.asarray(ss) >= 0).all()


@settings(max_examples=10, deadline=None)
@given(n_hat=st.integers(1, 150), R=st.integers(1, 24), seed=st.integers(0, 999))
def test_property_csr_gram_psd(n_hat, R, seed):
    vals, cols, segs = _chunk(128, n_hat + 10, R, nnz=100, seed=seed)
    G = np.asarray(csr_gram_pallas(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(segs), R, n_hat,
        interpret=True,
    ), np.float64)
    w = np.linalg.eigvalsh(G)
    assert w[0] > -1e-3 * max(1.0, w[-1])
