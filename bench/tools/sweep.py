"""Sweep of offered rates for one cell, to find the highest rate the
system sustains (the knee a cell's traffic rate is set from).

  python3 bench/tools/sweep.py --workload <cell> \\
      --fractions 0.7,0.8,0.9,1,1.1 --seconds 30 --seed 7

A first window offers far more than the chip serves, so that every cut
is a full bucket; the full-bucket throughput it measures (``max_batch``
over the mean wall time of a full batch) is the estimate ``rates`` are
then given as fractions of.  Each rate is one open-loop window of the
cell's traffic at that rate with no backlog, so every bucket is warmed,
in one process on one chip.  Printed per rate: the offered and
completed images per second, the latency quantiles from due time of the
requests completed (those still queued at the close are cancelled), and
the mean latency of the last third of them over the first third (a
ratio that grows with the rate means the queue grows through the
window: the rate is above the knee).
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cell_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fractions", required=True,
                    help="rates as fractions of the full-bucket throughput")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import numpy as np
    run_mod = cell_lib.load_module(ROOT / "bench" / "run.py")
    from bench import serve
    cell = cell_lib.load(args.workload, False)
    devs, peak = run_mod.devices(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    cell.traffic = dict(cell.traffic, backlog=0)
    mb = cell.engine["max_batch"]

    def window(rate):
        cell.traffic.update(rate_per_s=rate)
        return serve.serve(cell, args.seed, args.seconds, False, devs[0],
                           peak, time.perf_counter(), run_mod.TRACE_DIR)

    first = window(4.0 * mb)
    walls = [a.result.wall_time_s for a in first.completed()
             if a.result.bucket == mb]
    knee = mb / float(np.mean(walls))
    print(json.dumps({"full_bucket_wall_s": float(np.mean(walls)),
                      "full_bucket_images_per_s": knee}), flush=True)
    for rate in (knee * float(f) for f in args.fractions.split(",")):
        run = window(rate)
        sub = [a for a in run.plan if a.submit_s is not None]
        done = sorted(run.completed(), key=lambda a: a.due_s)
        lat = np.array([a.done_s - a.due_s for a in done])
        third = max(len(lat) // 3, 1)
        buckets = [a.result.bucket for a in done]
        print(json.dumps({
            "rate": rate, "offered": len(sub) / run.close_s,
            "cancelled": len(sub) - len(done),
            "completed_per_s": len(run.in_window()) / run.close_s,
            "p50_s": float(np.percentile(lat, 50)),
            "p90_s": float(np.percentile(lat, 90)),
            "trend": float(lat[-third:].mean() / lat[:third].mean()),
            "mean_bucket": float(np.mean(buckets)),
            "lateness_max_ms": 1e3 * max(a.submit_s - a.due_s for a in sub),
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
