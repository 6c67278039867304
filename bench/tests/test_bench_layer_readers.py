"""The readers of the ingest-feed and serving-queue metrics, on synthetic
layer contexts: the expected value from their spans or histogram, and
None where the run recorded none (as on a program without those spans)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench.lib import harness  # noqa: E402
from repro.obs.metrics import Registry  # noqa: E402

SPANS = {
    "ingest.feed_wait": [(0.0, 0.5), (1.0, 1.25)],
    "ingest.h2d": [(2.0, 2.1), (3.0, 3.3)],
    "ingest.prep": [(4.0, 4.2), (4.1, 4.4)],        # overlap counts once
    "ingest.readback": [(5.0, 5.6)],
    "serve.build": [(0.0, 0.002), (1.0, 1.004)],
    "serve.h2d": [(0.0, 0.001), (1.0, 1.003)],
}


def _ctx(cell, spans, registry, units):
    return harness.layer_context(cell, spans=spans, registry=registry,
                                 units=units, setup={"compile_s": 0.0},
                                 device=None, work={}, device_kind="x")


@pytest.mark.parametrize("metric,cell,expected", [
    ("ingest.feed_wait_s", "pubmed.fit_fused", 0.75 / 2),
    ("ingest.h2d_s", "pubmed.fit_fused", 0.4 / 2),
    ("ingest.host_s", "pubmed.fit_fused", (0.4 + 0.6) / 2),
    ("serve.queue_ms", "nytimes.serve", 1e3 * 0.09 / 3),
    ("serve.build_ms", "nytimes.serve", 3.0),
    ("serve.h2d_ms", "nytimes.serve", 2.0),
])
def test_reader_value_and_silence(metric, cell, expected):
    c = harness.Cell(cell)
    assert metric in [m["name"] for m in c.per_layer]
    reg = Registry()
    reg.histogram("serve.queue_wait_s").observe_many([0.01, 0.03, 0.05])
    read = c.reader(metric).read
    assert read(_ctx(c, SPANS, reg, 2)) == pytest.approx(expected)
    assert read(_ctx(c, {}, Registry(), 2)) is None
    if metric.startswith("ingest."):
        assert read(_ctx(c, SPANS, reg, 0)) is None
