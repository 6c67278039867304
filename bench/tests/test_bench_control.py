"""The comparison that decides ``correct`` fails its control and every
fault a cell can have, at a size a test run holds.

* control: the reference computed in bfloat16 (one step below the
  configurations' float32) put in the program's place must fail the
  cell's limits, while the program's own outputs pass them;
* faults: a whole run (bench/run.py, with the look for a chip skipped)
  over a timed path broken underneath must print ``correct: false``.

The program runs on the CPU here, with x64 on as in the repository's own
tests.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench.lib import gen, harness  # noqa: E402

TINY = {"config": {"corpus": {"docs": 1500, "words": 12000}}}
SEED = 2**31 + 4242


@pytest.fixture(autouse=True)
def _restore_jax_cache_config(monkeypatch):
    """bench/run.py points JAX's persistent cache at the checkout; put this
    worker's settings back for the test files that run after these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    for var in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
        if var in os.environ:
            monkeypatch.setenv(var, os.environ[var])
        else:
            monkeypatch.delenv(var, raising=False)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


def run_cell(capsys, cell, seconds=0.3, overrides=TINY):
    import run

    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", "0"], require_tpu=False,
                  overrides=overrides)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def tiny_store(tmp_path, name="pubmed"):
    cfg = harness.load_json(ROOT, "bench", "configs", name + ".json")
    sh = gen.Shape.from_config(cfg["corpus"], docs=1500, words=12000)
    csr = gen.corpus(sh, SEED)
    gen.write_store(csr, str(tmp_path / "s"), sh.words)
    return cfg, sh, csr


def test_fit_control_fails_and_program_passes(tmp_path, x64):
    from bench.drivers import fit_loop

    cfg, sh, csr = tiny_store(tmp_path)
    runner = fit_loop.FitRunner(str(tmp_path / "s"), cfg, {})
    prog = fit_loop.outputs(runner.fit())
    cell = harness.Cell("pubmed.fit_fused")
    ok = harness.check_table(cell, fit_loop.readings(csr, sh.words, prog))
    assert all(c["ok"] for c in ok.values()), ok
    ctrl = fit_loop.control_outputs(csr, sh.words, prog)
    bad = harness.check_table(cell, fit_loop.readings(csr, sh.words, ctrl))
    assert not all(c["ok"] for c in bad.values()), bad


def test_serve_control_fails():
    from bench.drivers import serve_open
    from repro.serve import pack_components

    cfg = harness.load_json(ROOT, "bench", "configs", "nytimes.json")
    cell = harness.Cell("nytimes.serve")
    g = gen.Generator(gen.Shape.from_config(cfg["corpus"]), SEED)
    pack = pack_components(serve_open.make_model(
        g, cell.traffic, np.random.default_rng(1)), n_features=g.shape.words)
    docs = g.block(1, 2000)
    ok = list(range(docs.n_rows))
    ctrl = serve_open.control_scores(docs, pack, g.shape.words)
    bad = harness.check_table(
        cell, serve_open.readings(docs, pack, g.shape.words, ok, ctrl, 0))
    assert not bad["score_rel_err"]["ok"], bad


# ------------------------------------------------------------------ faults


def _state_unchanged(monkeypatch):
    from repro.data import bow

    monkeypatch.setattr(bow.StreamingStats, "update_csr_batch",
                        lambda self, mb: self)


def _half_batch(monkeypatch):
    from repro.data import bow

    orig = bow.StreamingStats.update_csr_batch

    def half(self, mb):
        keep = mb.values.shape[0] // 2
        vals = mb.values.copy()
        vals[keep:] = 0.0
        self.count += int(np.sum(mb.n_rows[keep:]))   # mean over the rest
        return orig(self, mb._replace(values=vals,
                                      n_rows=np.where(np.arange(len(mb.n_rows)) < keep,
                                                      mb.n_rows, 0)))

    monkeypatch.setattr(bow.StreamingStats, "update_csr_batch", half)


def _answer_altered(monkeypatch):
    from repro.core import spca

    orig = spca.fit_components

    def altered(*a, **kw):
        res = orig(*a, **kw)
        res[-1].variance *= 1.01
        return res

    import repro.core
    monkeypatch.setattr(repro.core, "fit_components", altered)


def _solve_returns_start(monkeypatch):
    from bench.lib import faults

    faults.solve_returns_start(monkeypatch.setattr)


def _component_emptied(monkeypatch):
    """The last component comes back empty: no words, zero loadings."""
    from repro.core import spca

    orig = spca.fit_components

    def emptied(*a, **kw):
        res = orig(*a, **kw)
        r = res[-1]
        r.x, r.support = np.zeros_like(r.x), r.support[:0]
        r.variance, r.cardinality = 0.0, 0
        return res

    import repro.core
    monkeypatch.setattr(repro.core, "fit_components", emptied)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _solve_returns_start,
                                   _component_emptied])
def test_fit_fault_reads_incorrect(fault, monkeypatch, capsys, x64):
    fault(monkeypatch)
    line = run_cell(capsys, "pubmed.fit_fused")
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_serve_fault_reads_incorrect(fault, monkeypatch, capsys):
    from repro.serve import projector

    orig = projector.TopicProjector.project

    def broken(self, X):
        out = np.array(orig(self, X))
        if fault == "half_batch":        # every other row, the first included
            out[0::2] = 0.0
        else:
            out[0, 0] += 1.0
        return out

    monkeypatch.setattr(projector.TopicProjector, "project", broken)
    line = run_cell(capsys, "nytimes.serve", seconds=0.5,
                    overrides={"traffic": {"rate_per_s": 400, "warmup_s": 0.2}})
    assert line["correct"] is False, line["checks"]


def test_sound_runs_read_correct(capsys, x64):
    assert run_cell(capsys, "pubmed.fit_fused")["correct"] is True
    assert run_cell(capsys, "nytimes.serve", seconds=0.5, overrides={
        "traffic": {"rate_per_s": 400, "warmup_s": 0.2}})["correct"] is True


_MESH = r"""
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench"]
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import run
from bench.lib import faults
from repro.sparse import mesh_engine
if {fault!r}:
    faults.FAULTS[{fault!r}]()
if {broken!r}:
    # the exchange between chips left out: each pooled moment is device 0's
    orig = mesh_engine.psum_partials
    mesh_engine.psum_partials = lambda parts, mesh, axes=None: jax.tree_util.tree_map(
        lambda x: x[0], parts)
run.main(["--workload", "pubmed.fit_fused.d4", "--seed", "{seed}", "--seconds", "0.3",
          "--trace", "0"], require_tpu=False,
         overrides={{"config": {{"corpus": {{"docs": 3000, "words": 12000}}}}}})
"""


def _mesh_line(broken=False, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _MESH.format(root=ROOT, broken=broken,
                                            fault=fault, seed=SEED)],
        env=env, capture_output=True, text=True, timeout=600)
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("broken", [False, True])
def test_mesh_exchange_left_out_reads_incorrect(broken):
    line, err = _mesh_line(broken=broken)
    assert line["correct"] is (not broken), (line["checks"], err[-3000:])


def test_mesh_solve_returns_start_reads_incorrect():
    """The device-grid solves of the four-device cell doing no work."""
    line, err = _mesh_line(fault="solve_returns_start")
    assert line["correct"] is False, (line["checks"], err[-3000:])
