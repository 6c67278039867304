"""The open-loop client (bench/lib/openloop.py)."""
import math
import os
import sys
import time
from concurrent.futures import Future

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import openloop  # noqa: E402


def test_percentile_counts_failures_as_missing():
    lat = [0.001 * i for i in range(1, 96)] + [None] * 5
    assert openloop.percentile(lat, 95) == pytest.approx(0.095)
    assert openloop.percentile(lat, 96) == math.inf
    assert openloop.percentile([None, None], 50) == math.inf
    assert openloop.percentile([], 50) == math.inf


def test_percentile_nearest_rank():
    vals = list(range(1, 21))
    assert openloop.percentile(vals, 95) == 19
    assert openloop.percentile(vals, 50) == 10
    assert openloop.percentile(vals, 100) == 20


def test_poisson_schedule_rate_and_seed():
    a = openloop.poisson_schedule(np.random.default_rng(7), 1000.0, 20.0)
    b = openloop.poisson_schedule(np.random.default_rng(7), 1000.0, 20.0)
    assert np.array_equal(a, b)
    c = openloop.poisson_schedule(np.random.default_rng(8), 1000.0, 20.0)
    assert a.size == c.size == 20000 and not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and a[-1] < 20.0
    gaps = np.diff(a)                  # exponential gaps: cv about 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)


def test_drive_times_from_due_and_records_lateness():
    done = []

    def submit(req):
        f = Future()
        if req == "fail":
            f.set_exception(RuntimeError("x"))
        elif req == "raise":
            raise RuntimeError("refused")
        else:
            done.append(f)
            f.set_result(req)
        return f

    due = np.array([0.0, 0.01, 0.02, 0.03])
    t0 = time.perf_counter() + 0.05
    res = openloop.drive(submit, ["a", "fail", "raise", "b"], due, t0)
    openloop.wait(res, time.perf_counter() + 1)
    lat = res.latencies()
    assert lat[1] is None and lat[2] is None
    assert 0 <= lat[0] < 0.05 and 0 <= lat[3] < 0.05
    assert np.all(res.lateness() >= 0)


def test_a_stall_charges_the_requests_behind_it():
    """Requests due while the client is held up are timed from their due
    time, not from when they were finally sent."""
    def submit(req):
        if req == 0:
            time.sleep(0.1)
        f = Future()
        f.set_result(req)
        return f

    due = np.array([0.0, 0.01, 0.02])
    res = openloop.drive(submit, [0, 1, 2], due, time.perf_counter())
    lat = res.latencies()
    assert lat[1] >= 0.08 and lat[2] >= 0.07
    assert res.lateness()[1] >= 0.08
