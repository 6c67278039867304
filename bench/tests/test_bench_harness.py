"""Cells, configurations, traffic mixes and metrics are found by name from
files of their own: a new cell needs new files and entries, no edit."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark with one cell, configuration, traffic mix and
    metric added as new files."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny", "source": "x",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "x"})
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "serve.new_thing", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "serving batcher",
                              "moves": "serve_p95_ms",
                              "workloads": ["tiny.burst"]})
    spec["end_to_end"][2]["workloads"].append("tiny.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/nytimes.json")))
    cfg["corpus"]["docs"] = 10
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/burst.json").write_text(
        json.dumps({"kind": "serve_open", "rate_per_s": 5}))
    (tmp_path / "bench/metrics/serve.new_thing.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    return tmp_path


def test_new_cell_is_found_by_name(tree):
    cell = harness.Cell("tiny.burst", bench_dir=str(tree / "bench"))
    assert cell.config["corpus"]["docs"] == 10
    assert cell.traffic["rate_per_s"] == 5
    assert cell.driver().run.__module__ == "bench_driver_serve_open"
    names = [m["name"] for m in cell.per_layer]
    assert "serve.new_thing" in names and "setup.compile_s" in names
    assert "ingest.screen_s" not in names          # listed for fit cells only
    assert cell.reader("serve.new_thing").read({}) == 42.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_p95_ms"]


def test_unknown_cell_is_an_error(tree):
    with pytest.raises(KeyError):
        harness.Cell("nope", bench_dir=str(tree / "bench"))


def test_every_listed_file_exists_and_every_metric_has_a_reader():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.driver().run
        assert cell.end_to_end[0]["name"] == "setup_s"
        assert cell.per_layer, w["name"]
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}
    for m in spec["per_layer"]:
        assert callable(harness.Cell(spec["workloads"][0]["name"])
                        .reader(m["name"]).read)
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) <= set(cfg["corpus"])


def test_check_table_holds_each_number_to_its_limit():
    cell = harness.Cell("nytimes.serve")
    t = harness.check_table(cell, {"score_rel_err": 1e-9, "unanswered": 1})
    assert t["score_rel_err"]["ok"] and not t["unanswered"]["ok"]
