"""The control of the correctness check: the plain reference put in the
program's place, computed one precision below the configuration's
(float8_e4m3 operands for a bfloat16 model), and judged by the
harness's own check.  It has to come out as not correct.

  python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it makes the arrival plan as a run of the cell does and
stands the float8 reference in for the engine: every request due in the
window counts as served, one image at a time, and the float8 reference
makes the latents of those that ``check.sample`` draws from the seed.
``check.run_check`` then compares them with the float32 reference, as
after a run, and one JSON line per seed gives ``correct`` and each
compared number beside its limit.  It runs on whatever device JAX gives
it; the readings that set a limit come from the chip.
"""
import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench import cell as cell_lib  # noqa: E402
from bench import check, loadgen, serve  # noqa: E402

# what check.sample and check.compare read of a served request
Served = collections.namedtuple("Served", "bucket n_full_steps")


def control_run(cell, seed: int, seconds: float) -> serve.Run:
    """A ``serve.Run`` whose answers the float8 reference made."""
    ref_mod = cell_lib.reference(cell.family)
    lat = loadgen.latent_shape(cell.traffic, cell.model["in_channels"])
    ctl = ref_mod.Reference(cell.model, cell.policy, cell.engine["n_steps"],
                            lat, quant="fp8")
    run = serve.Run(cell=cell, seed=seed, seconds=seconds, peak={},
                    chips=cell.chips)
    run.plan = loadgen.make_plan(cell.traffic, seed, seconds)
    for a in run.plan:
        if a.due_s <= seconds:
            a.result = Served(bucket=1, n_full_steps=None)
    weights = ref_mod.make_weights(cell.model, loadgen.fold(seed, "weights"))
    for a in check.sample(run, cell.limits["check_requests"]):
        x, n_full = ctl.sample(weights, **ref_mod.inputs(ctl, cell, a))
        run.latents[a.index] = np.asarray(x)
        a.result = Served(bucket=1, n_full_steps=n_full)
    del weights
    return run


def readings(cell, seed: int, seconds: float) -> dict:
    correct, checks, _ = check.run_check(control_run(cell, seed, seconds))
    return {"seed": seed, "correct": correct, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    cell = cell_lib.load(args.workload, False)
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = readings(cell, seed, seconds)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
