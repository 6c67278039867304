"""Open-loop client: requests sent on a fixed schedule, whatever the server
does, each timed from when it was DUE (so a stall also charges the
requests queued behind it).

* ``poisson_schedule`` — due times of a Poisson stream at a fixed rate,
  with the same count for every seed;
* ``drive`` — one thread sends each request at its due time and records
  how late it ran; completions are timed by a callback on the future;
* ``percentile`` — nearest-rank percentile over ALL requests, a request
  that failed or never completed counting as missing (+inf).
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np


def poisson_schedule(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Due times (s, from 0) of a Poisson stream of ``rate`` per second over
    [0, seconds), conditioned on its expected count: round(rate x seconds)
    arrivals placed uniformly, sorted.  Every seed then offers the same
    number of requests, in a different order."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def percentile(latencies, q: float) -> float:
    """Nearest-rank ``q``-th percentile; ``None``/nan entries (failed or
    missing requests) rank above every answer."""
    vals = sorted(math.inf if (v is None or v != v) else float(v)
                  for v in latencies)
    if not vals:
        return math.inf
    k = max(0, min(len(vals) - 1, math.ceil(q / 100.0 * len(vals)) - 1))
    return vals[k]


class Result:
    """Per-request record of one open-loop run (times in perf_counter s)."""

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.sent = np.zeros(n)
        self.done = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.futures: list = [None] * n

    def latencies(self) -> list:
        return [None if (f or d != d) else d - u
                for u, d, f in zip(self.due, self.done, self.failed)]

    def lateness(self) -> np.ndarray:
        return self.sent - self.due


def drive(submit, requests, due_s: np.ndarray, t0: float) -> Result:
    """Send ``requests[i]`` through ``submit(req) -> Future`` at
    ``t0 + due_s[i]``.  Returns once every request has been SENT; the
    caller waits for completions (``wait``)."""
    res = Result(len(due_s))
    res.due[:] = t0 + due_s
    lock = threading.Lock()

    def on_done(i):
        def cb(fut):
            t = time.perf_counter()
            with lock:
                res.done[i] = t
                res.failed[i] = fut.exception() is not None
        return cb

    for i, (req, due) in enumerate(zip(requests, res.due)):
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        res.sent[i] = time.perf_counter()
        try:
            fut = submit(req)
        except Exception:           # a refused submit is a failed request
            res.failed[i] = True
            res.done[i] = res.sent[i]
            continue
        res.futures[i] = fut
        fut.add_done_callback(on_done(i))
    return res


def wait(res: Result, deadline: float) -> None:
    """Block until every sent request completed or ``deadline`` passed."""
    for f in res.futures:
        if f is None:
            continue
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        try:
            f.result(timeout=left)
        except Exception:
            pass
