#!/usr/bin/env python3
"""Rate sweep of a serve cell, to find the knee once (chip only).

    python bench/knee.py --workload nytimes.serve --rates 1000,2000,4000 \
        --seconds 10 --seeds 1,2,3

Runs the cell's driver at each offered rate and seed in one process, the
rates in the given order for the first seed, reversed for the second, and
so on, and prints one line per point: the offered and served docs/s and
p95 latency (the driver's log adds p50, p99 and how late the generator
ran).  The cell's traffic file keeps the rate chosen from it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    jax = run.init_jax()
    from bench.lib import harness

    cell = harness.Cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        harness.log("knee: needs a TPU")
        return 3
    clog = harness.CompileLog()
    driver = cell.driver()
    rates = [float(r) for r in args.rates.split(",")]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        for rate in rates if i % 2 == 0 else rates[::-1]:
            cell.traffic["rate_per_s"] = rate
            out = driver.run(cell, seed=seed, seconds=args.seconds,
                             trace=False, clog=clog,
                             t_start=time.perf_counter())
            print(json.dumps({"rate_per_s": rate, "seed": seed, **out["e2e"],
                              "failed": out["failed"],
                              "readings": out["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
