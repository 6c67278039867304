"""What every cell shares: finding its files by name, the compile log, the
device, the per-layer context the metric readers take, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its files are
found by name and nothing here names a cell, a configuration or a metric:

  bench/configs/<config>.json    corpus shape, job and solver settings
  bench/traffic/<traffic>.json   the mix; ``kind`` names bench/drivers/<kind>.py
  bench/metrics/<metric>.py      ``read(ctx)`` -> number or None
  bench/work/<kernel>.py         ``work(**shape)`` -> (operations, bytes)
  bench/peaks.json               peak FLOP/s and bytes/s by device kind
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with its configuration, traffic and metric lists."""

    def __init__(self, name: str, *, bench_dir: str = BENCH):
        self.bench_dir = bench_dir
        spec = load_json(os.path.dirname(bench_dir), "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = spec
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        self.config = load_json(bench_dir, "configs",
                                self.workload["config"] + ".json")
        self.traffic = load_json(bench_dir, "traffic",
                                 self.workload["traffic"] + ".json")

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self._applies(m)]

    @property
    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end}
        return [m for m in self.spec["per_layer"]
                if self._applies(m) and m["moves"] in e2e]

    def driver(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(self.bench_dir, "drivers", kind + ".py"),
                           f"bench_driver_{kind}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics", metric + ".py"),
                           f"bench_metric_{metric.replace('.', '_')}")

    def work(self, kernel: str):
        return load_module(os.path.join(self.bench_dir, "work", kernel + ".py"),
                           f"bench_work_{kernel}").work

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.bench_dir, "peaks.json")
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "bench/peaks.json")
        return table[device_kind]


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (as ``chip_smoke.CompileLog``)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(n: int) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest of the ``n`` devices used."""
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:n]:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def annotate(on: bool, name: str):
    """A profiler annotation while a device trace runs, else nothing."""
    import contextlib

    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def span_table(tracer, lo: float, hi: float) -> dict:
    """obs.trace spans closed inside [lo, hi] (perf_counter seconds), by
    name, as (start, end) pairs."""
    out: dict[str, list] = {}
    if tracer is None:
        return out

    def rec(sp):
        if sp.t1 is not None:
            s, e = sp.t0 / 1e9, sp.t1 / 1e9
            if s >= lo and e <= hi:
                out.setdefault(sp.name, []).append((s, e))
        for c in sp.children:
            rec(c)

    for r in tracer.roots():
        rec(r)
    return out


def union_s(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def layer_context(cell: Cell, *, spans: dict, registry, units: int,
                  setup: dict, device: dict | None, work: dict,
                  device_kind: str, extra: dict | None = None) -> dict:
    """The dict every per-layer reader takes (see bench/metrics/)."""

    def span_s(*names):
        return union_s([iv for n in names for iv in spans.get(n, [])])

    def counter(name):
        return float(registry.value(name, 0) or 0)

    def histogram(name):
        h = registry.get(name)
        if h is None:
            return None
        snap = h.snapshot()
        return {"count": snap.get("count", 0), "total": snap.get("sum", 0.0)}

    def roofline(label, kernel):
        if device is None or kernel not in work:
            return None
        k_s = device["kernel_s"].get(label, 0.0)
        if k_s <= 0:
            return None
        peaks = cell.peaks(device_kind)
        flops, nbytes = work[kernel]
        least = max(flops / peaks["flops_per_s"],
                    nbytes / peaks["bytes_per_s"])
        return 100.0 * least / k_s

    def idle_share():
        if device is None or not device["devices"] or device["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - device["busy_s"] / device["window_s"])

    ctx = {"spans": spans, "units": units, "setup": setup,
           "span_s": span_s, "counter": counter, "histogram": histogram,
           "roofline": roofline, "idle_share": idle_share}
    ctx.update(extra or {})
    return ctx


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: dict, device: dict, checks: dict,
                 breakdown: dict | None = None) -> dict:
    """Checks on the last lines of stderr, then the result line (checks
    last) on stdout."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['ok'] else '  FAILED'}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                      for n, c in checks.items()}
    print(json.dumps(line), flush=True)
    return line


def check_table(cell: Cell, readings: dict) -> dict:
    """Each reading beside its limit from the configuration's ``checks``
    (or the traffic's, which wins): ``ok`` when value <= limit."""
    limits = dict(cell.config.get("checks", {}))
    limits.update(cell.traffic.get("checks", {}))
    out = {}
    for name, value in readings.items():
        limit = limits[name]
        v = float(value)
        out[name] = {"value": v, "limit": limit, "ok": v <= limit}
    return out
