"""Multi-device behaviour (8 fake CPU devices via subprocess — the device
count is locked at first jax init, so these tests re-exec themselves)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_distributed_variance_and_gram_match_local():
    out = _run("""
    from repro.launch.mesh import make_dev_mesh
    from repro.core.distributed import distributed_variances, distributed_gram
    mesh = make_dev_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(64, 40)))
    with mesh:
        sc = distributed_variances(A, mesh)
        np.testing.assert_allclose(np.asarray(sc.variances),
                                   np.asarray(A).var(0), rtol=1e-5, atol=1e-6)
        g = distributed_gram(A, mesh, means=sc.means)
        Ac = np.asarray(A) - np.asarray(A).mean(0)
        np.testing.assert_allclose(np.asarray(g), Ac.T @ Ac / 64, rtol=1e-5,
                                   atol=1e-6)
    print("DIST-OK")
    """)
    assert "DIST-OK" in out


def test_distributed_screen_and_gram_pipeline():
    out = _run("""
    from repro.launch.mesh import make_dev_mesh
    from repro.core.distributed import distributed_screen_and_gram
    from repro.core import solve_bcd
    from repro.core.bcd import leading_sparse_component
    mesh = make_dev_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    n = 60
    u = np.zeros(n); u[[3, 7, 11]] = 1/np.sqrt(3)
    X = rng.normal(size=(400, n)) + 4.0 * rng.normal(size=(400, 1)) * u[None, :]
    with mesh:
        Sig, sup, screen = distributed_screen_and_gram(jnp.asarray(X), mesh, lam=2.0)
    res = solve_bcd(jnp.asarray(Sig), 2.0, max_sweeps=20)
    x = np.asarray(leading_sparse_component(res.Z))
    rec = set(np.asarray(sup)[np.flatnonzero(x)].tolist())
    assert rec == {3, 7, 11}, rec
    print("PIPE-OK")
    """)
    assert "PIPE-OK" in out


def test_psum_partials_matches_host_pooling():
    """The ONE partial-pooling implementation (core.distributed.psum_partials,
    shared by the dense passes and sparse/mesh_engine): a device-side psum
    over stacked per-device moments must equal combine_screens' host-side
    merge of the same shards."""
    out = _run("""
    jax.config.update("jax_enable_x64", True)   # f64 partials end-to-end
    from repro.core.distributed import psum_partials
    from repro.core.elimination import combine_screens
    from repro.data.bow import StreamingStats
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(8)
    rng = np.random.default_rng(7)
    D, rows, n = 8, 16, 40
    A = rng.normal(size=(D, rows, n))

    # host-side truth: per-shard StreamingStats merged via combine_screens
    parts = []
    for d in range(D):
        acc = StreamingStats(n)
        acc.update(A[d])
        parts.append(acc.finalize())
    truth = combine_screens(parts)

    # device-side: stacked partial moments pooled in ONE psum
    s = jnp.asarray(A.sum(axis=1))                 # (D, n) per-device sums
    ss = jnp.asarray((A * A).sum(axis=1))
    cnt = jnp.full((D, 1), float(rows))
    sharding = NamedSharding(mesh, P("data", None))
    s, ss, cnt = (jax.device_put(x, sharding) for x in (s, ss, cnt))
    ps, pss, pcnt = psum_partials((s, ss, cnt), mesh, axes=("data",))
    m = float(pcnt[0])
    assert m == D * rows
    # host truth folds through the column-stats kernel (f32-level), so the
    # agreement bar matches the dense distributed tests above
    mean = np.asarray(ps) / m
    var = np.asarray(pss) / m - mean * mean
    np.testing.assert_allclose(mean, np.asarray(truth.means),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.maximum(var, 0.0),
                               np.asarray(truth.variances),
                               rtol=1e-5, atol=1e-6)
    # second call with the same shapes reuses the cached compiled pool
    ps2, _, _ = psum_partials((s, ss, cnt), mesh, axes=("data",))
    np.testing.assert_array_equal(np.asarray(ps2), np.asarray(ps))
    print("PSUM-OK")
    """)
    assert "PSUM-OK" in out


def test_compressed_pmean_error_feedback():
    out = _run("""
    from repro.launch.mesh import make_dev_mesh
    from repro.optim.compression import compressed_pmean
    mesh = make_dev_mesh((8,), ("data",))
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)  # per-shard grads

    def f(gs, res):
        return compressed_pmean(gs, res, "data")

    sm = jax.shard_map(f, mesh=mesh,
                       in_specs=(P("data", None), P("data", None)),
                       out_specs=(P(None), P("data", None)), check_vma=False)
    res = jnp.zeros((8, 1024), jnp.float32)
    exact = np.asarray(g).mean(0)
    # single step: quantisation error bounded
    mean1, res1 = sm(g, res)
    err1 = np.abs(np.asarray(mean1)[0] - exact).max()
    assert err1 < 0.05, err1
    # error feedback: repeated reduction of the SAME gradient converges
    total = np.zeros_like(exact)
    res_i = jnp.zeros_like(res)
    for i in range(20):
        m_i, res_i = sm(g, res_i)
        total += np.asarray(m_i)[0]
    # average of accumulated means -> exact (residual is re-injected)
    np.testing.assert_allclose(total / 20, exact, atol=5e-3)
    print("EF-OK", err1)
    """)
    assert "EF-OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    out = _run("""
    import tempfile
    from repro.launch.mesh import make_dev_mesh
    from repro.checkpoint import checkpoint as ck
    rng = np.random.default_rng(3)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    mesh1 = make_dev_mesh((4, 2), ("data", "model"))
    xs = jax.device_put(x, NamedSharding(mesh1, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 1, {"w": xs})
        mesh2 = make_dev_mesh((2, 4), ("data", "model"))
        sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
        r = ck.restore(d, 1, {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}, sh2)
        np.testing.assert_array_equal(np.asarray(r["w"]), x)
        assert r["w"].sharding.spec == P("model", "data")
    print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run("""
    from repro.launch.mesh import make_dev_mesh
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.train import init_state, make_train_step
    from repro.launch.inputs import param_tree_shardings
    from repro.distributed.sharding import use_mesh
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      dtypes=("float32", "float32"))
    m = build_model(cfg)
    state = init_state(m, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128)
    batch = {"tokens": toks}
    step = jax.jit(make_train_step(m))
    s1, m1 = step(state, batch)

    mesh = make_dev_mesh((4, 2), ("data", "model"))
    with use_mesh(mesh):
        step_sh = jax.jit(make_train_step(m))
        s2, m2 = step_sh(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)))
    assert d < 1e-4, d
    print("SHARD-OK", d)
    """)
    assert "SHARD-OK" in out
