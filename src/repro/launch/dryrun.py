import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any other import (jax locks the
# device count on first init) — per the multi-pod dry-run contract.

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract memory / cost / collective stats.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

Each run writes results/dryrun/<arch>__<shape>__<mesh>.json with
bytes-per-device, HLO FLOPs/bytes, and per-collective byte counts.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro import configs as config_lib
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.roofline import analysis as roofline
from repro.roofline import hlo_analysis


def run_one(arch: str, shape: str, multi_pod: bool,
            out_dir: str = "results/dryrun", verbose: bool = True,
            overrides=None, tag: str = "") -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if tag:
        mesh_name = f"{mesh_name}+{tag}"
    t0 = time.time()
    with mesh:
        if arch in ("flux1-dev", "dit-small"):
            # the paper's own denoiser: shape selects full vs cached step
            spec = steps_lib.build_dit(arch, mesh,
                                       cached_step=(shape == "cached_step"))
        else:
            spec = steps_lib.build(arch, shape, mesh, overrides=overrides)
        jitted = jax.jit(spec.fn,
                         in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate_argnums)
        lowered = jitted.lower(*spec.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = hlo_analysis.analyze(compiled.as_text())
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": roofline.memory_dict(mem),
        # trip-count-aware per-device costs (XLA's cost_analysis counts
        # while bodies once; ours multiplies by known_trip_count)
        "flops": hlo["flops"],
        "bytes_accessed": hlo["bytes_accessed"],
        "collectives": hlo["collectives"],
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape} on {mesh_name}: "
              f"compile={t_compile:.1f}s "
              f"argbytes/dev={record['memory'].get('argument_size_bytes', 0)/1e9:.2f}GB "
              f"temp/dev={record['memory'].get('temp_size_bytes', 0)/1e9:.2f}GB "
              f"flops={record['flops']:.3e}")
        print("  memory_analysis:", record["memory"])
        print("  cost_analysis: flops=%.4e bytes=%.4e"
              % (record["flops"], record["bytes_accessed"]))
        print("  collectives:", json.dumps(record["collectives"]))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch}__{shape}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(config_lib.INPUT_SHAPES)
                    + ["denoise_step", "cached_step", None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    # §Perf iteration knobs
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--moe-impl", default=None,
                    choices=["einsum", "gather", None])
    ap.add_argument("--serve-tp-gb", type=float, default=4.0)
    ap.add_argument("--moe-pad", type=int, default=0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    overrides = {"microbatch": args.microbatch, "moe_impl": args.moe_impl,
                 "serve_tp_gb": args.serve_tp_gb, "moe_pad": args.moe_pad}

    combos = []
    if args.all:
        for arch in config_lib.ASSIGNED:
            for shape in config_lib.INPUT_SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[dryrun] skip existing {arch} x {shape} ({mesh_name})")
            continue
        try:
            run_one(arch, shape, args.multi_pod, args.out,
                    overrides=overrides, tag=args.tag)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print(f"dry-run OK: {len(combos)} combo(s)")


if __name__ == "__main__":
    main()
