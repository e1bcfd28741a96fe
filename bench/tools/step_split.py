"""Split a traced run's device time by sampler step and its host time by
the engine's spans.

  python3 bench/tools/step_split.py --workload <cell> --seed <n> \\
      [--seconds 24]

Makes one traced run of the cell as ``bench/run.py --trace 1`` does
(without the correctness check) and prints one JSON line with:

* ``metrics``: the cell's per-layer metrics as the benchmark reads them,
  and ``images_per_s`` of this traced run;
* ``step_split``: what the program's own scopes and spans give, which
  the benchmark's trace reduction does not keep yet:
  - ``full_step_ms``: device self time of the ops under the
    ``sampler.full_step`` scope over the batch full steps (forwards),
    with ``unscoped_ms_per_step``, the ops under neither scope over all
    steps;
  - ``cached_step_ms``: the same under ``sampler.cached_step`` over the
    cached steps;
  - ``engine_host_ms``: per batch, the summed length of the engine
    spans in ``HOST_SPANS``, with each span's share;
* ``stalls``: the ten longest stretches inside a program step
  (``serving.*`` but ``wait`` and ``sync``, where the worker sleeps by
  design) in which its thread ran no traced call: a long one is the
  process frozen or pure Python at work;
* ``breakdown``: the benchmark's own breakdown of the same trace, whose
  ``idle_gaps`` name each gap by the span the host was in.

Batches and forwards are the growth of the engine's ``n_batches`` and
``forwards`` counters over the served span, from the async engine's
start to its shutdown, where ``bench/serve.py`` reads its lane counters.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

FULL, CACHED, UNSCOPED = "sampler.full_step", "sampler.cached_step", ""
# the op metadata stat that holds an XLA op's name-scope path on a TPU
SCOPE_STAT = "tf_op"
SPAN_PREFIX = "serving."
HOST_SPANS = ("serving.form_batch", "serving.build_x_init",
              "serving.dispatch", "serving.results", "serving.resolve")
# spans in which the worker sleeps by design: on the queue, on the device
ASLEEP = ("serving.wait", "serving.sync")
Span = Tuple[str, float, float, dict]     # name, start ns, end ns, args


def _window(profile, window: str) -> Tuple[float, float]:
    spans = [(e.start_ns, e.end_ns) for p in profile.planes
             if p.name.startswith("/host") for ln in p.lines
             for e in ln.events if e.name == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        out |= (byte & 0x7F) << shift
        i += 1
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """(field number, value) of each field of the protobuf message in
    ``buf[i:end]``; a length-delimited value is its (start, end)."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """The sampler scope of each device op, by the op's event name (its
    HLO text): ``FULL`` or ``CACHED`` where the op's ``SCOPE_STAT`` names
    one, else ``UNSCOPED``.  A TPU keeps that stat on the event's
    metadata, which ``ProfileData`` does not show, so the serialized
    XSpace is read here: planes (XSpace field 1) with their name (2),
    event metadata (4: name 2, stats 5) and stat metadata (5: id 1,
    name 2), both maps of entries (key 1, value 2); a stat (metadata id
    1) holds a string (5) or a stat metadata id whose name is the
    string (7)."""
    out: Dict[str, str] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(xspace, *plane):
            if f == 2:
                name = _text(xspace, v)
            elif f in (4, 5):
                entry = dict(_fields(xspace, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(xspace, *entry[2]))
                    stat_names[meta.get(1, 0)] = _text(xspace,
                                                       meta.get(2, (0, 0)))
        if not trace.DEVICE_PLANE.match(name):
            continue
        for span in events:
            meta = list(_fields(xspace, *span))
            op = next((_text(xspace, v) for f, v in meta if f == 2), "")
            scope = UNSCOPED
            for f, v in meta:
                stat = dict(_fields(xspace, *v)) if f == 5 else {}
                if stat_names.get(stat.get(1)) != SCOPE_STAT:
                    continue
                text = (_text(xspace, stat[5]) if 5 in stat
                        else stat_names.get(stat.get(7), ""))
                scope = next((s for s in (FULL, CACHED) if s in text),
                             UNSCOPED)
            # the same HLO text under two scopes (two programs) counts
            # as neither
            out[op] = scope if out.get(op, scope) == scope else UNSCOPED
    return out


def scoped(profile, window: str, scopes: Dict[str, str]
           ) -> Dict[str, Tuple[float, int]]:
    """Device self seconds and op count inside the window by sampler
    scope (``scopes``: ``op_scopes`` of the same trace), averaged over
    the device planes."""
    w0, w1 = _window(profile, window)
    out: Dict[str, List[float]] = {}
    planes = 0
    for plane in profile.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for ln in plane.lines:
            if ln.name != trace.OPS_LINE:
                continue
            planes += 1
            clipped = [(e.name, max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in ln.events if e.end_ns > w0 and e.start_ns < w1]
            for (name, _, _), own in zip(clipped, trace.self_times(clipped),
                                         strict=True):
                acc = out.setdefault(scopes.get(name, UNSCOPED), [0.0, 0])
                acc[0] += own * 1e-9
                acc[1] += 1
    if not planes:
        raise ValueError(f"no device plane with an {trace.OPS_LINE!r} line")
    return {k: (s / planes, n // planes) for k, (s, n) in out.items()}


def spans(profile, window: str) -> List[Span]:
    """The program's host spans (``serving.*``) that overlap the window,
    clipped to it."""
    w0, w1 = _window(profile, window)
    return [(e.name, max(e.start_ns, w0), min(e.end_ns, w1), dict(e.stats))
            for p in profile.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events
            if e.name.startswith(SPAN_PREFIX) and e.end_ns > w0
            and e.start_ns < w1]


def full_step_ms(by_scope, forwards: int, steps: int) -> Optional[dict]:
    if FULL not in by_scope or not forwards:
        return None
    unscoped = by_scope.get(UNSCOPED, (0.0, 0))[0]
    return {"value": 1e3 * by_scope[FULL][0] / forwards,
            "unscoped_ms_per_step": 1e3 * unscoped / max(steps, 1)}


def cached_step_ms(by_scope, cached_steps: int) -> Optional[dict]:
    if CACHED not in by_scope or not cached_steps:
        return None
    return {"value": 1e3 * by_scope[CACHED][0] / cached_steps}


def engine_host_ms(program_spans: List[Span], batches: int) -> Optional[dict]:
    secs = {n: 0.0 for n in HOST_SPANS}
    for name, a, b, _ in program_spans:
        if name in secs:
            secs[name] += (b - a) * 1e-9
    total = sum(secs.values())
    if not total or not batches:
        return None
    return {"value": 1e3 * total / batches,
            "share": {n: s / total for n, s in secs.items()}}


def stalls(profile, window: str) -> List[list]:
    """The ten longest stretches inside a program step in which its
    thread ran no traced call: [seconds, span, its ``batch``].  Steps are
    the ``serving.*`` spans but ``ASLEEP``; the calls are the other
    events on the span's thread (JAX's own, nested in the span)."""
    w0, w1 = _window(profile, window)
    out = []
    for p in profile.planes:
        if not p.name.startswith("/host"):
            continue
        for ln in p.lines:
            calls = sorted((e.start_ns, e.end_ns) for e in ln.events
                           if not e.name.startswith(SPAN_PREFIX))
            for e in ln.events:
                if not e.name.startswith(SPAN_PREFIX) or e.name in ASLEEP:
                    continue
                lo, hi = max(e.start_ns, w0), min(e.end_ns, w1)
                longest, at = 0.0, lo
                for a, b in calls:
                    if a >= hi:
                        break
                    if a < e.start_ns or b > e.end_ns or b <= at:
                        continue        # not nested in it, or seen
                    longest, at = max(longest, a - at), b
                longest = max(longest, hi - at)
                if longest > 0:
                    out.append([longest * 1e-9, e.name,
                                dict(e.stats).get("batch")])
    return sorted(out, key=lambda x: -x[0])[:trace.TOP]


@contextlib.contextmanager
def served_counters():
    """Yields a dict that, after the served span, holds ``batches`` and
    ``forwards``: the growth of the engine's counters from the async
    engine's start to its shutdown.  ``bench/serve.py`` starts the async
    engine by this module's name after the warm-up and reads its own lane
    counters after the shutdown; ``Run`` does not carry these."""
    from repro.serving import async_engine
    base = async_engine.AsyncDiffusionEngine
    out: Dict[str, int] = {}
    at_start: Dict[int, Tuple[int, int]] = {}

    def counts(m) -> Tuple[int, int]:
        return m.n_batches, m.forwards

    class Counted(base):
        def start(self):
            at_start.setdefault(id(self), counts(self.metrics))
            return super().start()

        def shutdown(self, *a, **k):
            super().shutdown(*a, **k)
            (b0, f0), (b1, f1) = at_start[id(self)], counts(self.metrics)
            out.update(batches=b1 - b0, forwards=f1 - f0)

    async_engine.AsyncDiffusionEngine = Counted
    try:
        yield out
    finally:
        async_engine.AsyncDiffusionEngine = base


def split(by_scope, program_spans: List[Span], batches: int,
          forwards: int, n_steps: int) -> dict:
    steps = batches * n_steps
    return {"full_step_ms": full_step_ms(by_scope, forwards, steps),
            "cached_step_ms": cached_step_ms(by_scope, steps - forwards),
            "engine_host_ms": engine_host_ms(program_spans, batches),
            "forwards": forwards, "batches": batches,
            "dispatch_spans": sum(s[0] == "serving.dispatch"
                                  for s in program_spans),
            "by_scope": by_scope}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=24.0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    from bench import cell as cell_lib
    from bench import run as run_lib
    from bench import serve
    cell = cell_lib.load(args.workload, True)
    devs, peak = run_lib.devices(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    serve.clear(run_lib.TRACE_DIR)
    try:
        with served_counters() as counted:
            run = serve.serve(cell, args.seed, args.seconds, True, devs[0],
                              peak, T_START, run_lib.TRACE_DIR)
        path = glob.glob(str(run_lib.TRACE_DIR / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        profile = ProfileData.from_file(path)
        by_scope = scoped(profile, "bench.window",
                          op_scopes(Path(path).read_bytes()))
        program_spans = spans(profile, "bench.window")
        stall_list = stalls(profile, "bench.window")
    finally:
        serve.clear(run_lib.TRACE_DIR)
    metrics = run_lib.read_metrics(run, cell)
    metrics["images_per_s"] = cell_lib.reader("images_per_s").read(run)
    out = split(by_scope, program_spans, counted["batches"],
                counted["forwards"], cell.engine["n_steps"])
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "metrics": metrics, "step_split": out,
                      "stalls": stall_list,
                      "breakdown": run.trace.breakdown}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
