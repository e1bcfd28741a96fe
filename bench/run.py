"""Benchmark of the FreqCa serving stack on a TPU: one cell, one run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its
configuration, traffic mix, limits and metric readers are files found
by name (``bench/cell.py``).  The run makes the weights and the
requests from ``--seed``, warms up the buckets its traffic cuts, serves
the open-loop traffic for ``--seconds`` through
``AsyncDiffusionEngine.submit``, then checks a sample of the outputs
against the plain reference (``bench/check.py``).  With ``--trace 1``
the window is traced and the per-layer metrics are reported in place of
the end-to-end ones.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit, which are also the last lines of standard error.  Without a TPU,
with fewer chips than the cell asks for, or on a device kind that
``bench/peaks.json`` lacks, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs to a fixed directory under /tmp unless told not to;
# a run writes only inside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import cell as cell_lib  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def devices(chips: int):
    """The chips JAX reports and their peak entry, or SystemExit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform "
                         f"{devs[0].platform!r}; this benchmark measures "
                         "only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX reports "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    peaks = cell_lib.peaks()
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} has no entry in "
                         "bench/peaks.json")
    return devs, peaks[kind]


def read_metrics(run, cell) -> dict:
    """Each metric's reader; one that finds nothing to read is left out."""
    out = {}
    for m in cell.metrics:
        got = cell_lib.reader(m["name"]).read(run)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        out[m["name"]] = {"value": float(entry.pop("value")),
                          "unit": m["unit"], **entry}
    return out


def result(run, cell, devs, correct, checks, check_s) -> dict:
    submitted = [a for a in run.plan if a.submit_s is not None]
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": correct, "attempted": len(submitted),
           "failed": sum(a.error is not None for a in submitted),
           "metrics": read_metrics(run, cell), "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown
    out["compiles_in_window"] = run.compiles_in_window
    out["check_s"] = check_s
    out["checks"] = checks
    return out


def measure(cell, seed: int, seconds: float, traced: bool, devs, peak,
            t_start: float, keep_trace: bool = False) -> dict:
    """One run of ``cell`` on ``devs``: the result line (a dict); the
    compared numbers are printed on standard error."""
    from bench import check, serve
    serve.clear(TRACE_DIR)
    try:
        run = serve.serve(cell, seed, seconds, traced, devs[0], peak,
                          t_start, TRACE_DIR)
    finally:
        if not keep_trace:
            serve.clear(TRACE_DIR)
    correct, checks, check_s = check.run_check(run)
    line = result(run, cell, devs, correct, checks, check_s)
    marks = ", ".join(f"{k} {v:.2f}" for k, v in run.setup_marks.items())
    print(f"run {cell.name} seed {seed}: set-up {run.setup_s:.2f} s "
          f"(ended at s: {marks}), "
          f"close {run.close_s:.2f} s, {len(run.in_window())} images in "
          f"the window, {len(run.completed())} completed, "
          f"{run.compiles_in_window} compiles in the window, check "
          f"{check_s:.1f} s", file=sys.stderr)
    check.print_checks(checks, correct)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the raw trace in .bench_trace/")
    args = ap.parse_args(argv)
    cell = cell_lib.load(args.workload, bool(args.trace))
    devs, peak = devices(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    line = measure(cell, args.seed, args.seconds, bool(args.trace), devs,
                   peak, T_START, args.keep_trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
