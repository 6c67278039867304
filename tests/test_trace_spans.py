"""Program spans on the device trace's clock, and the spans of the ingest
feed and of the serving queue.

Covered: a span opened under a tracer lands in a CPU `jax.profiler` trace
(read with ``bench/lib/xplane.load``), nested and in order; with no
tracer no annotation is made and ``span()`` is the shared no-op;
`profile.trace_device` installs a tracer when none is; a streaming pass
records the feed's ``ingest.read`` / ``feed_wait`` / ``prep`` / ``h2d`` /
``readback`` spans joined by the megabatch index ``b`` across threads, on
one device and on four forced host devices; a batcher run records one
``serve.queue_wait_s`` observation per request and ``serve.build`` /
``serve.batch`` pairs with matching ``seq``."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from repro.obs import metrics, profile, trace
from repro.serve import BatcherConfig, MicroBatcher, TopicProjector
from repro.serve.projector import ProjectorPack

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench.lib import xplane  # noqa: E402


def _profiled(body):
    """Run ``body()`` inside a CPU `jax.profiler` trace; the host events
    whose names are program spans, as xplane.load reads them."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        host = xplane.load(xplane.find_xplane(d))["host"]
    return [h for h in host if h[0].startswith(("ingest.", "serve.", "t."))]


# ------------------------------------------------------------------ clock

def test_spans_reach_the_device_trace_nested_and_in_order():
    def body():
        with trace.enable():
            with trace.span("t.outer", b=3):
                with trace.span("t.first"):
                    pass
                with trace.span("t.second", kind="x"):
                    pass

    evs = {name: (t0, t1) for name, t0, t1 in _profiled(body)}
    assert set(evs) == {"t.outer", "t.first", "t.second"}
    o, a, b = evs["t.outer"], evs["t.first"], evs["t.second"]
    assert o[0] <= a[0] <= a[1] <= b[0] <= b[1] <= o[1]


@pytest.mark.parametrize("installed", [False, True])
def test_annotation_made_only_under_a_tracer(monkeypatch, installed):
    made = []

    class Fake:
        def __init__(self, name, **stats):
            made.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_TraceAnnotation", Fake)
    if installed:
        with trace.enable() as t:
            with trace.span("t.one", b=np.int64(2), note="a,b", obj=[1]):
                pass
        assert made == [("t.one", {"b": 2})]     # scalar, parse-safe stats
        assert t.find("t.one")[0].attrs["note"] == "a,b"
    else:
        assert trace.active() is None
        sp = trace.span("t.one", b=2)
        with sp:
            pass
        assert sp is trace.span("t.two") and made == []
        assert _profiled(lambda: trace.span("t.three").__enter__()) == []


def test_span_knows_its_parent_on_its_thread():
    with trace.enable() as t:
        with trace.span("t.outer"):
            with trace.span("t.inner"):
                pass
    outer, = t.find("t.outer")
    inner, = t.find("t.inner")
    assert inner.parent is outer and outer.parent is None


def test_trace_device_installs_a_tracer_only_when_none_is(tmp_path):
    with profile.trace_device(str(tmp_path / "a")):
        assert trace.active() is not None
    assert trace.active() is None
    with trace.enable() as t:
        with profile.trace_device(str(tmp_path / "b")):
            assert trace.active() is t
        assert trace.active() is t
    assert xplane.find_xplane(str(tmp_path / "a"))


# ------------------------------------------------------------ ingest feed

_FEED = """
import json, os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.data import make_corpus
from repro.obs import trace
from repro.sparse import write_corpus
from repro.sparse.mesh_engine import (
    mesh_feature_variances, mesh_reduced_covariance)

corpus = make_corpus(400, 1200, topics={"t": ["a", "b", "c"]}, seed=5)
store = write_corpus(corpus, tempfile.mkdtemp(), shard_nnz=16_000)
geo = dict(chunk_nnz=1024, chunk_rows=64, megabatch=2, prefetch_depth=2,
           impl="ref", devices=DEVICES)
out = {}
for kind in ("screen", "gram"):
    with trace.enable() as t:
        if kind == "screen":
            screen = mesh_feature_variances(store, **geo)
        else:
            top = np.argsort(-np.asarray(screen.variances))[:40]
            mesh_reduced_covariance(store, np.sort(top), **geo)
    spans = []
    def rec(sp, owner):
        if sp.name == "ingest.megabatch":
            owner = sp.attrs["b"]
        spans.append({"name": sp.name, "tid": sp.tid,
                      "b": sp.attrs.get("b"), "megabatch": owner})
        for c in sp.children:
            rec(c, owner)
    for r in t.roots():
        rec(r, None)
    out[kind] = spans
print(json.dumps(out))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_streaming_pass_records_feed_spans_joined_by_b(devices):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_FEED).replace(
            "DEVICES", str(devices))],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    step = devices                       # a superbatch holds D megabatches
    for kind, spans in out.items():
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        want = {"ingest.read", "ingest.feed_wait", "ingest.h2d"}
        if kind == "gram":
            want.add("ingest.prep")      # the support remap (and padding)
        if kind == "screen" and devices == 1:
            want |= {"ingest.prep", "ingest.readback"}
        assert want <= set(by), (kind, sorted(by))
        if devices > 1:
            assert "ingest.readback" not in by
        mbs = sorted(s["b"] for s in by["ingest.megabatch"])
        assert mbs == list(range(0, step * len(mbs), step)), mbs
        pass_tid = by["ingest.megabatch"][0]["tid"]
        # the reader thread packs every megabatch the pass thread folds
        reads = by["ingest.read"]
        assert all(s["tid"] != pass_tid for s in reads)
        assert set(mbs) <= {s["b"] for s in reads}
        assert set(mbs) <= {s["b"] for s in by["ingest.feed_wait"]}
        for s in by["ingest.feed_wait"]:
            assert s["tid"] == pass_tid and s["megabatch"] is None
        # prep, copy and readback of megabatch b sit inside its span
        for name in want - {"ingest.read", "ingest.feed_wait"}:
            got = by[name]
            for s in got:
                assert s["tid"] == pass_tid and s["megabatch"] == s["b"]
            assert {s["b"] for s in got} == set(mbs), (kind, name)


# ------------------------------------------------------------ serve queue

def test_batcher_records_queue_wait_and_build_batch_pairs():
    n = 300
    pack = ProjectorPack(
        support_idx=np.array([[1, 5, 9, 0], [2, 7, 0, 0]], np.int32),
        values=np.array([[0.5, 0.5, 0.7, 0.0], [0.6, 0.8, 0.0, 0.0]],
                        np.float32),
        n_features=n)
    rng = np.random.default_rng(0)
    with metrics.use_registry() as reg:
        b = MicroBatcher(TopicProjector(pack), n,
                         BatcherConfig(max_batch=8, max_wait_ms=1.0)).start()
        with trace.enable() as t:
            futs = [b.submit(rng.choice(n, 6, replace=False),
                             np.ones(6, np.float32)) for _ in range(40)]
            for f in futs:
                f.result(timeout=60)
            b.stop()
    h = reg.get("serve.queue_wait_s")
    assert h is not None and h.count == 40 and h.total >= 0.0
    builds = {s.attrs["seq"]: s for s in t.find("serve.build")}
    batches = t.find("serve.batch")
    assert batches and len(builds) == len(batches)
    for s in batches:
        mate = builds[s.attrs["seq"]]
        assert mate.attrs["batch"] == s.attrs["batch"]
        assert mate.tid != s.tid            # collector vs server thread
        assert mate.t1 <= s.t0              # built before it is served
    assert sum(s.attrs["batch"] for s in batches) == 40
    h2d = t.find("serve.h2d")
    assert len(h2d) == len(batches)
    assert all(s.parent is not None and s.parent.name == "serve.batch"
               for s in h2d)
