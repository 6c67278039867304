"""The trace reduction (bench/lib/xplane.py) on small synthetic traces."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import xplane  # noqa: E402

K = "custom-call hlo_op=custom-call.3"     # what a Pallas kernel event reads


def ev(name, start, dur, text=None):
    return (name, start, dur, text if text is not None else name)


def test_union_counts_overlap_once_and_clips():
    ivs = [(0, 10), (5, 20), (30, 40), (35, 38), (90, 200)]
    assert xplane.union_ns(ivs, 0, 100) == 20 + 10 + 10


@pytest.mark.parametrize("events,busy", [
    ([], 0),
    ([ev("a", 10, 10), ev("b", 15, 10)], 15),
    ([ev("a", -5, 10), ev("b", 95, 10)], 10),
])
def test_busy_and_idle_share(events, busy):
    assert xplane.busy_ns(events, 0, 100) == busy
    out = xplane.reduce({"devices": {"/device:TPU:0": events}, "host": []},
                        0, 100, "bench.")
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["window_s"] == pytest.approx(100 / 1e9)


def test_busy_is_averaged_over_devices():
    tr = {"devices": {"/device:TPU:0": [ev("a", 0, 60)],
                      "/device:TPU:1": [ev("a", 0, 20)]}, "host": []}
    assert xplane.reduce(tr, 0, 100, "bench.")["busy_s"] == pytest.approx(40e-9)


def test_kernel_time_attributed_by_span_window():
    events = [ev("fusion.1", 0, 5), ev("k", 10, 7, K), ev("k", 50, 3, K),
              ev("k", 95, 20, K), ev("copy", 12, 4)]
    host = [("bench.screen", 0, 40), ("bench.gram", 45, 90),
            ("other", 90, 120)]
    out = xplane.reduce({"devices": {"/device:TPU:0": events}, "host": host},
                        0, 200, "bench.")
    assert out["kernel_s"]["bench.screen"] == pytest.approx(7e-9)
    assert out["kernel_s"]["bench.gram"] == pytest.approx(3e-9)
    assert "other" not in out["kernel_s"]


def test_kernels_are_summed_over_devices():
    tr = {"devices": {"/device:TPU:0": [ev("k", 1, 5, K)],
                      "/device:TPU:1": [ev("k", 2, 4, K)]},
          "host": [("bench.gram", 0, 10)]}
    assert xplane.reduce(tr, 0, 10, "bench.")["kernel_s"]["bench.gram"] == \
        pytest.approx(9e-9)


def test_idle_gaps_named_by_innermost_open_span():
    events = [ev("a", 0, 10), ev("b", 40, 10), ev("c", 55, 5)]
    labels = [("window", 0, 100), ("solve", 15, 35)]
    gaps = xplane.idle_gaps(events, 0, 100, labels)
    assert gaps[0] == ("window", 40e-9)           # 60..100
    assert gaps[1] == ("solve", 30e-9)            # 10..40, midpoint in solve
    assert gaps[2] == ("window", 5e-9)            # 50..55
    assert xplane.idle_gaps([], 0, 10, [])[0] == ("host", 10e-9)


def test_top_ops_ranks_by_total_time():
    devs = {"/device:TPU:0": [ev("x", 0, 5), ev("y", 10, 3), ev("x", 20, 5)],
            "/device:TPU:1": [ev("y", 0, 9), ev("z", 500, 99)]}
    assert xplane.top_ops(devs, 0, 100) == [("y", 12e-9), ("x", 10e-9)]


def test_nested_events_are_dropped_and_op_names_shortened():
    evs = [ev("%while.1 = (f32[2]{0}, s32[]) while((f32[2]{0}) %t), body=%b", 0, 100),
           ev("%dynamic_slice.3 = f32[1]{0} dynamic-slice(f32[96]{0} %x)", 10, 5),
           ev("%k.1 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target=x", 150, 5)]
    top = xplane.top_level(evs)
    assert [e[1] for e in top] == [0, 150]
    assert xplane.op_name(evs[0][0]) == "%while.1 while"
    assert xplane.op_name(evs[2][0]) == "%k.1 custom-call"
    assert xplane.op_name("plain") == "plain"
