#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (chip only).

    python bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds S] [--spca key=json,...] \
        [--fault name]

For each seed, in ONE process: the program's numbers compared (what
bench/run.py prints under ``checks``), and for the control seeds the same
numbers for the control, the reference computed in bfloat16 put in the
program's place.  Fit cells run one whole fit per seed through the
window's own ``FitRunner`` and add each component's search path and the
diagnostics read beside the numbers compared.  ``--spca`` overrides
solver settings, for a witness on another path of the program;
``--fault`` plants one of bench/lib/faults.py.  Serve cells run the
cell's driver with a short window at the cell's rate.  One JSON line per
reading goes to stdout and to ``bench/out/readings_<cell>*.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import run

BENCH = run.BENCH


def fit_readings(cell, seeds, control):
    import shutil

    from bench.drivers import fit_loop
    from bench.lib import gen

    shape = gen.Shape.from_config(cell.config["corpus"])
    margin = fit_loop.margin_of(cell)
    for seed in seeds:
        tmp = tempfile.mkdtemp(prefix="bench_readings_")
        try:
            csr = gen.corpus(shape, seed)
            gen.write_store(csr, f"{tmp}/store", shape.words)
            runner = fit_loop.FitRunner(f"{tmp}/store", cell.config,
                                        cell.traffic)
            t0 = time.perf_counter()
            try:
                    prog = fit_loop.outputs(runner.fit())
            except Exception as e:
                yield {"seed": seed, "of": "program", "error": repr(e)}
                continue
            fit_s = time.perf_counter() - t0
            diag = runner.last["diag"]
            yield {"seed": seed, "of": "program", "fit_s": fit_s,
                   "n_hat": [int(s.size) for s, _ in prog["grams"]],
                   "fallbacks": diag.get("solver_fallbacks", 0),
                   "cards": [int(len(c["support"])) for c in prog["comps"]],
                   "search": fit_loop.search_log(runner.last),
                   **fit_loop.diagnostics(csr, shape.words, prog,
                                          margin=margin),
                   "readings": fit_loop.readings(csr, shape.words, prog)}
            if seed in control:
                ctrl = fit_loop.control_outputs(csr, shape.words, prog)
                yield {"seed": seed, "of": "control",
                       "readings": fit_loop.readings(csr, shape.words, ctrl)}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def serve_readings(cell, seeds, control, seconds, clog):
    import numpy as np

    from bench.drivers import serve_open
    from bench.lib import gen
    from repro.serve import pack_components

    for seed in seeds:
        out = serve_open.run(cell, seed=seed, seconds=seconds, trace=False,
                             clog=clog, t_start=time.perf_counter())
        yield {"seed": seed, "of": "program", "e2e": out["e2e"],
               "readings": out["readings"]}
        if seed in control:
            shape = gen.Shape.from_config(cell.config["corpus"])
            g = gen.Generator(shape, seed)
            rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5E7E]))
            pack = pack_components(serve_open.make_model(g, cell.traffic, rng),
                                   n_features=shape.words)
            docs = g.block(2, int(cell.traffic["rate_per_s"] * seconds))
            ok = list(range(docs.n_rows))
            yield {"seed": seed, "of": "control", "readings":
                   serve_open.readings(docs, pack, shape.words, ok,
                                       serve_open.control_scores(docs, pack,
                                                                 shape.words),
                                       0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--spca", default="",
                    help="solver settings over the cell's, key=json,...")
    ap.add_argument("--fault", default="",
                    help="a fault of bench/lib/faults.py to plant")
    args = ap.parse_args(argv)
    jax = run.init_jax()
    from bench.lib import faults, harness

    if args.fault:
        faults.FAULTS[args.fault]()

    cell = harness.Cell(args.workload)
    for item in filter(None, args.spca.split(",")):
        key, val = item.split("=", 1)
        cell.traffic.setdefault("spca", {})[key] = json.loads(val)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        harness.log("readings: needs the cell's TPU chips")
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.join(BENCH, "out"), exist_ok=True)
    tag = "".join(f"_{t}" for t in (args.spca.replace("=", "-")
                                     .replace(",", "_"), args.fault) if t)
    path = os.path.join(BENCH, "out", f"readings_{cell.name}{tag}.jsonl")
    if cell.traffic["kind"] == "fit_loop":
        rows = fit_readings(cell, seeds, control)
    else:
        rows = serve_readings(cell, seeds, control, args.seconds,
                              harness.CompileLog())
    with open(path, "a") as f:
        for row in rows:
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
