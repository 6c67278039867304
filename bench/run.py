#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix and per-layer metrics are files under bench/
found by name (bench/lib/harness.py).  The run makes its corpus or
requests from ``--seed``, warms up every shape it will use (set-up), drives
the program for ``--seconds`` (the window), then checks what the window
produced against a plain float64 reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces the
window (host spans and a device trace) and prints its per-layer metrics.
The last line of stdout is one JSON object; the numbers compared for
``correct`` come last in it, and on the last lines of stderr.  With no TPU,
or fewer chips than the cell asks for, the run exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _prepare_env() -> None:
    """Source path and the persistent compile cache, fixed inside the
    checkout, before JAX is imported."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def init_jax():
    """JAX with the persistent compile cache in the checkout, every
    program cached however short its compile.  Shared by the chip-only
    tools beside this file (knee.py, readings.py)."""
    _prepare_env()
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None, *, require_tpu: bool = True, overrides=None) -> int:
    """Run one cell; returns the exit code.  ``require_tpu`` and
    ``overrides`` (a dict merged into the cell's configuration and traffic,
    e.g. a smaller corpus) exist for the CPU tests of the harness only."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _prepare_env()
    from bench.lib import harness

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        harness.log("bench: the program (src/repro) is not in this checkout")
        return 2
    jax = init_jax()

    cell = harness.Cell(args.workload)
    if overrides:
        for part in ("config", "traffic"):
            for key, val in overrides.get(part, {}).items():
                target = getattr(cell, part)
                if isinstance(val, dict):
                    target[key] = {**target.get(key, {}), **val}
                else:
                    target[key] = val
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        harness.log(f"bench: {cell.name} needs {cell.chips} TPU chip(s); "
                    f"JAX sees {len(devs)} {devs[0].platform} device(s)")
        return 3
    if len(devs) < cell.chips:
        harness.log(f"bench: {cell.name} needs {cell.chips} device(s)")
        return 3

    clog = harness.CompileLog()
    out = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), clog=clog,
                            t_start=T_START)
    checks = harness.check_table(cell, out["readings"])
    correct = (out["failed"] == 0 and out["attempted"] > 0
               and all(c["ok"] for c in checks.values()))
    device = out["device"]
    breakdown = None
    if args.trace:
        metrics = {}
        for m in cell.per_layer if out.get("layer_ctx") else ():
            v = cell.reader(m["name"]).read(out["layer_ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev = out.get("trace") or {}
        device = {**device, "busy_s": dev.get("busy_s", 0.0),
                  "window_s": dev.get("window_s", 0.0)}
        breakdown = {"device_ops": [list(x) for x in dev.get("device_ops", [])],
                     "idle_gaps": [list(x) for x in dev.get("idle_gaps", [])]}
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    harness.print_result(correct=correct, attempted=out["attempted"],
                         failed=out["failed"], metrics=metrics,
                         device=device, checks=checks, breakdown=breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
