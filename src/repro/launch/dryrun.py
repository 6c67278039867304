import os
if __name__ == "__main__":
    # 512 forced host devices for the production meshes; set before jax
    # starts, and only when run as a program (importers keep theirs).
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  1. **Compile proof** — jit(train_step | prefill | serve_step) with the
     production in/out shardings, `.lower().compile()` on the single-pod
     (16,16) mesh AND the 2-pod (2,16,16) mesh.  Failures (sharding
     mismatch, OOM at compile, unsupported collective) are bugs.
  2. **memory_analysis()** — per-device bytes; proves the cell fits HBM.
  3. **Cost probes** — XLA's cost_analysis counts `while` (scan) bodies
     exactly once (measured), so scanned-depth costs are extracted by
     lowering python-unrolled probe variants at n_periods=2 and 4 and
     extrapolating F(n) = A + n*B.  Collective bytes are parsed from the
     probes' post-SPMD HLO the same way.  Probes run on the single-pod
     mesh (the roofline table is single-pod); multi-pod compile is the
     coherence proof for the 'pod' axis.

Results append to a JSON file consumed by benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--jobs N] [--out benchmarks/dryrun.json]
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells, get_config
from repro.distributed.sharding import use_mesh
from repro.launch.inputs import cell_specs
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.train.train_step import make_prefill_step, make_serve_step, make_train_step

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b"
)
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([\d,]*)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
         "pred": 1, "f64": 8, "s64": 8, "c64": 8}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of collective ops in post-SPMD HLO (per-device)."""
    out = {k: 0.0 for k in
           ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")}
    count = 0
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "-start" in line and "-done" not in line and False:
            continue
        # Only count op definitions (lines with '= <type> <opcode>(').
        if f" {m.group(1)}(" not in line and f" {m.group(1)}-start(" not in line:
            continue
        lhs = line.split("=")[1] if "=" in line else line
        type_str = lhs.split(m.group(1))[0]
        b = 0.0
        for dt, dims in SHAPE_RE.findall(type_str):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b += n * BYTES[dt]
        out[m.group(1)] += b
        count += 1
    out["n_ops"] = count
    out["total"] = sum(v for k, v in out.items() if k != "n_ops")
    return out


def _probe_cfg(cfg, n: int):
    """Same arch, n periods per stack, python-unrolled (cost probe)."""
    over = dict(unroll_stacks=True, remainder=(), n_periods=n,
                n_layers=len(cfg.period) * n)
    if cfg.is_encoder_decoder:
        over["n_encoder_layers"] = len(cfg.encoder_period) * n
    return cfg.scaled(**over)


def _lower_cell(cfg, shape, mesh, *, donate=True, microbatches=1):
    model, kind, structs, shardings = cell_specs(cfg, shape, mesh)
    if kind == "train":
        fn = make_train_step(model, microbatches=microbatches)
        donate_argnums = (0,) if donate else ()
    elif kind == "prefill":
        fn = make_prefill_step(model)
        donate_argnums = ()
    else:
        fn = make_serve_step(model)
        donate_argnums = (1,) if donate else ()
    with use_mesh(mesh):
        jf = jax.jit(fn, in_shardings=shardings, donate_argnums=donate_argnums)
        lowered = jf.lower(*structs)
    return lowered


def run_cell(arch: str, shape_name: str, *, probes: bool = True,
             overrides: dict | None = None) -> dict:
    overrides = dict(overrides or {})
    microbatches = overrides.pop("microbatches", 1)
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "kind": shape.kind, "ok": False}
    if overrides or microbatches > 1:
        rec["overrides"] = {**overrides, "microbatches": microbatches}
    if microbatches > 1:
        # gradient-accumulation memory probe: cost extrapolation is invalid
        # under the microbatch scan (nested while), so probes are skipped.
        probes = False
    try:
        # --- multi-pod compile proof (512 chips) ---
        mesh_mp = make_production_mesh(multi_pod=True)
        t0 = time.time()
        comp_mp = _lower_cell(cfg, shape, mesh_mp,
                              microbatches=microbatches).compile()
        rec["multi_pod"] = {
            "compile_s": round(time.time() - t0, 1),
            "memory": _mem_dict(comp_mp.memory_analysis()),
        }
        del comp_mp

        # --- single-pod compile + memory (256 chips) ---
        mesh_sp = make_production_mesh(multi_pod=False)
        t0 = time.time()
        comp_sp = _lower_cell(cfg, shape, mesh_sp,
                              microbatches=microbatches).compile()
        ca = comp_sp.cost_analysis()
        rec["single_pod"] = {
            "compile_s": round(time.time() - t0, 1),
            "memory": _mem_dict(comp_sp.memory_analysis()),
            "cost_once": {"flops": ca.get("flops", 0.0),
                          "bytes": ca.get("bytes accessed", 0.0)},
        }
        del comp_sp

        # --- cost probes (unrolled n=2 and n=4, single-pod) ---
        if probes:
            probe = {}
            for n in (2, 4):
                pc = _probe_cfg(cfg, n)
                comp = _lower_cell(pc, shape, mesh_sp, donate=False).compile()
                ca = comp.cost_analysis()
                txt = comp.as_text()
                probe[str(n)] = {
                    "flops": ca.get("flops", 0.0),
                    "bytes": ca.get("bytes accessed", 0.0),
                    "collectives": collective_bytes(txt),
                }
                del comp, txt
            rec["probes"] = probe
            rec["n_periods"] = cfg.periods
            rec["n_remainder"] = len(cfg.remainder)
            rec["period_len"] = len(cfg.period)
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def _mem_dict(m) -> dict:
    return {
        "argument_gb": m.argument_size_in_bytes / 2**30,
        "output_gb": m.output_size_in_bytes / 2**30,
        "temp_gb": m.temp_size_in_bytes / 2**30,
        "alias_gb": m.alias_size_in_bytes / 2**30,
        "code_mb": m.generated_code_size_in_bytes / 2**20,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimb variants), "
                         "e.g. --set seq_parallel=true --set attn_kv_block=512")
    ap.add_argument("--out", default="benchmarks/dryrun.json")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = float(v)

    if args.all and args.jobs > 1:
        # Fan out cells across subprocesses (each needs its own 512-device
        # runtime); merge results into --out.
        todo = cells()
        procs = []
        for i, (arch, shape) in enumerate(todo):
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out]
            if args.no_probes:
                cmd.append("--no-probes")
            procs.append((arch, shape, subprocess.Popen(cmd)))
            while len([p for *_ , p in procs if p.poll() is None]) >= args.jobs:
                time.sleep(2)
        for arch, shape, p in procs:
            p.wait()
            print(f"[{arch} x {shape}] rc={p.returncode}")
        return

    todo = cells() if args.all else [(args.arch, args.shape)]
    for arch, shape in todo:
        t0 = time.time()
        rec = run_cell(arch, shape, probes=not args.no_probes,
                       overrides=overrides or None)
        rec["wall_s"] = round(time.time() - t0, 1)
        _append(args.out, rec)
        status = "OK" if rec["ok"] else f"FAIL: {rec.get('error')}"
        print(f"[{arch} x {shape}] {status} ({rec['wall_s']}s)", flush=True)
        if rec["ok"]:
            sp = rec["single_pod"]["memory"]
            print(f"    mem/dev: args {sp['argument_gb']:.2f} GB, "
                  f"temp {sp['temp_gb']:.2f} GB", flush=True)


def _append(path: str, rec: dict):
    import fcntl

    with open(path, "a+") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        f.seek(0)
        try:
            data = json.load(f)
        except (json.JSONDecodeError, ValueError):
            data = []
        data = [r for r in data
                if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"])]
        data.append(rec)
        f.seek(0)
        f.truncate()
        json.dump(data, f, indent=1)


if __name__ == "__main__":
    main()
