"""PR-9 device-mesh parallel fit (forced 4 fake CPU devices via
subprocess — the device count is locked at first jax init, so the
multi-device tests re-exec themselves like tests/test_distributed.py).

Covered: a D-device streaming pass reproduces the single-device engine's
screen/Gram numbers and a D-device fit reproduces the single-device
supports and explained variance; the pass/launch economics stay 1+1
corpus passes with ceil(B/D) ingest dispatches and ceil(E/(B*D)) solve
launches; the `ingest.shard_pass` / `solver.device_grid` spans and the
`mesh.devices` gauge + merged `ingest.shard.*` lane counters appear; a
mesh pass checkpoint resumes (and a device-topology change invalidates
the fingerprint into a clean pass)."""
import inspect
import os
import subprocess
import sys
import textwrap

from test_ingest_pipeline import _searchsorted_cols

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp, numpy as np
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_mesh_pass_and_fit_parity_with_economics():
    """The acceptance test in one child: sharded screen/Gram parity with
    the engine, 4-device fit == single-device fit, 1+1 passes, amortized
    dispatch counts, spans/metrics, and mesh-pass resume."""
    out = _run("""
    import tempfile
    from repro.core import SPCAConfig, fit_components
    from repro.obs import metrics, trace
    from repro.data import make_corpus
    from repro.sparse import write_corpus
    from repro.sparse.engine import (
        sparse_feature_variances, sparse_reduced_covariance,
    )
    from repro.sparse.mesh_engine import (
        mesh_feature_variances, mesh_reduced_covariance,
    )

    corpus = make_corpus(400, 1200, topics={"t": ["a", "b", "c"]}, seed=5)
    d = tempfile.mkdtemp()
    store = write_corpus(corpus, d, shard_nnz=16_000)
    geo = dict(chunk_nnz=1024, chunk_rows=64, megabatch=2)

    # --- screen parity + dispatch economics
    c_e, c_m = {}, {}
    s_e = sparse_feature_variances(store, counters=c_e, **geo)
    s_m = mesh_feature_variances(store, devices=4, counters=c_m, **geo)
    np.testing.assert_allclose(np.asarray(s_m.variances),
                               np.asarray(s_e.variances), atol=1e-9)
    np.testing.assert_allclose(np.asarray(s_m.means),
                               np.asarray(s_e.means), atol=1e-9)
    assert int(s_m.count) == int(s_e.count) == 400
    n_mega = -(-store.n_chunks(1024, 64) // 2)
    assert c_e["screen_launches"] == n_mega
    assert c_m["screen_launches"] == -(-n_mega // 4)   # amortized
    assert c_e["screen_passes"] == c_m["screen_passes"] == 1
    assert float(metrics.gauge("mesh.devices").value) == 4.0
    assert metrics.counter("ingest.shard.chunks").value > 0   # lane merge

    # --- gram parity on a real support
    support = np.sort(np.argsort(np.asarray(s_e.variances))[::-1][:64])
    means = np.asarray(s_e.means)
    g_e = sparse_reduced_covariance(store, support, means=means,
                                    counters=c_e, **geo)
    g_m = mesh_reduced_covariance(store, support, devices=4, means=means,
                                  counters=c_m, **geo)
    np.testing.assert_allclose(np.asarray(g_m), np.asarray(g_e), atol=1e-9)
    assert c_m["gram_launches"] == -(-n_mega // 4)

    # --- full fit parity + 1+1 passes + ceil(E/(B*D)) solve rounds.
    # A D-device search widens each round to B*D lambda evals, so the
    # math-identical single-device baseline is batch_evals = B*D with the
    # mesh off: same lambda grid, same solves, D only changes how the
    # round is dispatched.
    base = dict(max_sweeps=6, lam_search_evals=6,
                chunk_nnz=1024, chunk_rows=64, megabatch_chunks=2)
    d0, d4 = {}, {}
    r0 = fit_components(store, 2, target_card=4,
                        cfg=SPCAConfig(**base, batch_evals=12),
                        diagnostics=d0)
    tr = trace.install(trace.Tracer())
    r4 = fit_components(store, 2, target_card=4,
                        cfg=SPCAConfig(**base, batch_evals=3, mesh_devices=4),
                        diagnostics=d4)
    trace.install(None)
    for a, b in zip(r0, r4):
        assert a.support.tolist() == b.support.tolist()
        assert abs(a.variance - b.variance) <= 1e-6 * max(1.0, abs(a.variance))
    assert d4["corpus_passes"] == 2
    assert d4["ingest"]["screen_launches"] == -(-n_mega // 4)
    for comp in d4["components"]:
        assert comp["devices"] == 4
        assert comp["solve_launches"] == -(-6 // (3 * 4))   # ONE round
    txt = tr.tree_str()
    assert "ingest.shard_pass" in txt
    assert "solver.device_grid" in txt

    # --- resume: a complete checkpoint short-circuits the re-pass; a
    # different device topology invalidates the fingerprint (clean pass)
    rd = tempfile.mkdtemp()
    c1, c2, c3 = {}, {}, {}
    s1 = mesh_feature_variances(store, devices=4, counters=c1,
                                resume_dir=rd, checkpoint_every=2, **geo)
    s2 = mesh_feature_variances(store, devices=4, counters=c2,
                                resume_dir=rd, checkpoint_every=2, **geo)
    np.testing.assert_allclose(np.asarray(s2.variances),
                               np.asarray(s1.variances), atol=1e-12)
    assert c2.get("resumed_megabatches", 0) > 0
    assert c2.get("screen_launches", 0) == 0        # nothing re-streamed
    s3 = mesh_feature_variances(store, devices=2, counters=c3,
                                resume_dir=rd, checkpoint_every=2, **geo)
    assert c3.get("resumed_megabatches", 0) == 0    # topology changed
    assert c3["screen_launches"] == -(-n_mega // 2)
    np.testing.assert_allclose(np.asarray(s3.variances),
                               np.asarray(s1.variances), atol=1e-9)
    print("MESH-OK")
    """)
    assert "MESH-OK" in out


def test_mesh_gram_table_remap_matches_oracle_cols():
    """The sharded Gram pass hands its step the same local columns through
    the word->position table as through the searchsorted remap (G equal
    bit for bit, with and without word 0 on the support), and counts every
    stored entry once in ``ingest.gram_entries``."""
    out = _run(inspect.getsource(_searchsorted_cols) + textwrap.dedent("""
    import tempfile
    from repro.data import make_corpus
    from repro.data.bow import SupportRemap
    from repro.obs import metrics
    from repro.sparse import write_corpus
    from repro.sparse.mesh_engine import mesh_reduced_covariance

    corpus = make_corpus(400, 1200, topics={"t": ["a", "b", "c"]}, seed=5)
    d = tempfile.mkdtemp()
    store = write_corpus(corpus, d, shard_nnz=16_000)
    geo = dict(chunk_nnz=1024, chunk_rows=64, megabatch=2)
    table_cols = SupportRemap.cols

    def oracle_cols(self, col_ids):
        return _searchsorted_cols(np.flatnonzero(self.table < self.n_hat),
                                  col_ids)

    for support in (np.arange(0, 120, 2), np.arange(1, 120, 2)):
        SupportRemap.cols = table_cols
        with metrics.use_registry() as reg:
            g_t = np.asarray(mesh_reduced_covariance(store, support,
                                                     devices=4, **geo))
            assert reg.value("ingest.gram_entries") == store.nnz
            on = reg.value("ingest.gram_support_entries")
            assert 0 < on < store.nnz
        SupportRemap.cols = oracle_cols
        g_o = np.asarray(mesh_reduced_covariance(store, support, devices=4,
                                                 **geo))
        assert np.any(g_t != 0)
        np.testing.assert_array_equal(g_t, g_o)
    print("REMAP-OK")
    """))
    assert "REMAP-OK" in out


def test_device_grid_solve_parity_and_padding():
    """bcd_solve_batched(devices=D) matches the single-device batch to
    1e-9 — including a batch that does not divide D (pad + slice-back) —
    and still counts as ONE kernel launch."""
    out = _run("""
    from repro.kernels import ops
    from repro.obs import metrics

    rng = np.random.default_rng(0)
    B, n = 5, 32                        # 5 % 2 != 0: exercises padding
    A = rng.normal(size=(B, n, n))
    S = (A @ A.transpose(0, 2, 1) / n).astype(np.float64)
    lams = np.geomspace(0.05, 0.5, B)
    betas = np.full(B, 1e-3)
    X0 = np.broadcast_to(np.eye(n), (B, n, n)).copy()
    nv = np.full(B, n, np.int32)

    c0 = metrics.counter("kernel.launches.bcd_solve_batched").value
    ref = ops.bcd_solve_batched(S, lams, betas, X0, nv, max_sweeps=8)
    c1 = metrics.counter("kernel.launches.bcd_solve_batched").value
    got = ops.bcd_solve_batched(S, lams, betas, X0, nv, max_sweeps=8,
                                devices=2)
    c2 = metrics.counter("kernel.launches.bcd_solve_batched").value
    assert c1 - c0 == 1 and c2 - c1 == 1
    for a, b, name in zip(ref, got, ("X", "obj", "sweeps", "hist")):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=1e-9, err_msg=name)
    # over-asking clamps to the local device count and the batch
    got8 = ops.bcd_solve_batched(S, lams, betas, X0, nv, max_sweeps=8,
                                 devices=8)
    np.testing.assert_allclose(np.asarray(got8[0]), np.asarray(ref[0]),
                               atol=1e-9)
    print("GRID-OK")
    """)
    assert "GRID-OK" in out


def test_pass_fingerprint_includes_device_topology():
    """No subprocess needed: the resume fingerprint must key on the device
    count, so a cursor written at one D never restores at another."""
    import numpy as np

    from repro.data import make_corpus
    from repro.sparse import write_corpus
    from repro.sparse.resume import pass_fingerprint

    corpus = make_corpus(60, 200, topics={"t": ["a"]}, seed=0)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        store = write_corpus(corpus, d, shard_nnz=4096)
        sig = {"acc": "mesh_stats", "n": 200, "devices": 4, "dtype": "float64"}
        kw = dict(chunk_nnz=512, chunk_rows=64, megabatch=2, host_id=0,
                  num_hosts=1, signature=sig)
        fp1 = pass_fingerprint("screen", store, n_devices=1, **kw)
        fp4 = pass_fingerprint("screen", store, n_devices=4, **kw)
        assert fp1 != fp4
        assert fp4 == pass_fingerprint("screen", store, n_devices=4, **kw)


def test_degraded_mode_mesh_halves_devices_and_keeps_parity():
    """Degraded-mode execution: an injected dispatch error on the sharded
    screen/Gram retries the whole pass at D/2 with `mesh.degraded`
    recorded and bit-parity with the engine; corruption never degrades;
    an exhausted ladder (min_devices == D) re-raises."""
    out = _run("""
    import tempfile
    from repro.data import make_corpus
    from repro.obs import metrics
    from repro.sparse import write_corpus
    from repro.sparse.engine import sparse_feature_variances
    from repro.sparse.mesh_engine import (
        mesh_feature_variances, mesh_reduced_covariance,
    )
    from repro.testing import (
        SolverFaultInjector, dispatch_error, install_solver,
    )

    corpus = make_corpus(300, 400, topics={"t": ["a", "b"]}, seed=0)
    d = tempfile.mkdtemp()
    store = write_corpus(corpus, d, shard_nnz=2500)
    geo = dict(chunk_nnz=512, chunk_rows=64, megabatch=2)
    ref = sparse_feature_variances(store, **geo)

    # screen: fail the first sharded dispatch -> whole pass redone at D=2
    ctr = {}
    inj = SolverFaultInjector(dispatch_error(n=0, match="mesh.screen"))
    with install_solver(inj):
        scr = mesh_feature_variances(store, devices=4, counters=ctr, **geo)
    assert ctr["mesh_degraded"] == 1
    assert metrics.counter("mesh.degraded").value == 1.0
    np.testing.assert_allclose(np.asarray(scr.variances),
                               np.asarray(ref.variances), atol=1e-9)

    # gram: two failures ladder 4 -> 2 -> 1 (the engine path)
    sup = np.sort(np.argsort(np.asarray(ref.variances))[::-1][:48])
    means = np.asarray(ref.means)
    from repro.sparse.engine import sparse_reduced_covariance
    G_ref = np.asarray(sparse_reduced_covariance(store, sup, means=means,
                                                 **geo))
    ctr2 = {}
    inj2 = SolverFaultInjector(dispatch_error(n=0, match="mesh.gram",
                                              times=2))
    with install_solver(inj2):
        G = np.asarray(mesh_reduced_covariance(store, sup, devices=4,
                                               means=means, counters=ctr2,
                                               **geo))
    assert ctr2["mesh_degraded"] == 2
    np.testing.assert_allclose(G, G_ref, atol=1e-9)

    # min_devices stops the ladder: the dispatch error propagates
    inj3 = SolverFaultInjector(dispatch_error(n=0, match="mesh.screen"))
    try:
        with install_solver(inj3):
            mesh_feature_variances(store, devices=4, min_devices=4, **geo)
        raise AssertionError("ladder should have been exhausted")
    except RuntimeError as e:
        assert type(e).__name__ == "InjectedDispatchError"

    # corruption propagates untouched (never retried at lower D)
    from repro.sparse import ShardCorruptionError, SparseCorpus
    from repro.testing import corrupt_file
    import os
    name = store.manifest["shards"][0]["files"]["values"]
    corrupt_file(os.path.join(store.path, name), n_flips=3, seed=7)
    bad = SparseCorpus.open(store.path)
    before = metrics.counter("mesh.degraded").value
    try:
        mesh_feature_variances(bad, devices=4, **geo)
        raise AssertionError("corruption should raise")
    except ShardCorruptionError:
        pass
    assert metrics.counter("mesh.degraded").value == before
    print("DEGRADE-OK")
    """)
    assert "DEGRADE-OK" in out
