"""One run of a cell through the served path: set-up, the measured
window, and what the metrics and the check read afterwards.

The window's entry is ``AsyncDiffusionEngine.submit``, one future per
request, fed by the open-loop clients of ``loadgen``.  Behind it run the
scheduler's cuts, ``DiffusionEngine.execute_plan``, the jitted sampler
with the traffic's cache policy, the denoiser and the Pallas kernels.

The window opens when the clients start (the backlog is due then) and
closes at the first batch completion at or after ``seconds``.  Then the
clients stop, what is still queued is cancelled and the running batch
ends.  A traced run traces from the open to the moment the engine stops,
so the trace holds whole batches only.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import shutil
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen


class CompileTally:
    """Backend compiles in this process, from JAX's monitoring events
    (a persistent-cache read counts as one)."""

    def __init__(self):
        from jax import monitoring
        self._lock = threading.Lock()
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1

    def mark(self) -> int:
        with self._lock:
            return self.count


@dataclasses.dataclass
class Run:
    """What one run leaves for the metric readers and the check."""
    cell: object
    seed: int
    seconds: float
    peak: dict
    chips: int
    setup_s: float = 0.0
    # seconds after the process start at which each part of set-up ended
    setup_marks: Dict[str, float] = dataclasses.field(default_factory=dict)
    plan: List[loadgen.Arrival] = dataclasses.field(default_factory=list)
    close_s: float = 0.0
    compiles_in_window: int = 0
    # engine counters over the served span (lanes padded included)
    full_lane_steps: int = 0
    total_lane_steps: int = 0
    memory_peak_bytes: Optional[int] = None
    trace: object = None                  # trace.Reduced, --trace 1 only
    latents: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def tokens(self) -> int:
        """Image tokens of one request: the length of its cached feature."""
        from bench import work
        return work.image_tokens(self.cell.model, self.cell.traffic["image_px"])

    @functools.cached_property
    def program(self):
        """The family's program file, ``bench/programs/<family>.py``."""
        from bench import cell as cell_lib
        return cell_lib.program(self.cell.family)

    def completed(self) -> List[loadgen.Arrival]:
        return [a for a in self.plan if a.result is not None]

    def batches(self) -> Dict[tuple, float]:
        """First completion time of each batch (lanes of one batch share
        their bucket and measured wall time)."""
        done: Dict[tuple, float] = {}
        for a in self.completed():
            key = (a.result.bucket, a.result.wall_time_s)
            done[key] = min(done.get(key, a.done_s), a.done_s)
        return done

    def in_window(self) -> List[loadgen.Arrival]:
        """Requests whose batch completed by the close."""
        done = self.batches()
        return [a for a in self.completed()
                if done[(a.result.bucket, a.result.wall_time_s)]
                <= self.close_s]


def _warm_plan(prog, cell, bucket: int, lat: tuple):
    """A real batch of ``bucket`` requests, one an edit where the traffic
    has edits, for the warm-up."""
    from repro.serving.scheduler import BatchPlan
    reqs = []
    for i in range(bucket):
        a = loadgen.Arrival(index=-1 - i, due_s=0.0,
                            seed=loadgen.fold(i, "warmup"),
                            edit=bool(cell.traffic.get("edit_every"))
                            and i == bucket - 1)
        reqs.append(prog.request(cell, a, lat))
    return BatchPlan(requests=reqs, bucket=bucket, formed_at=time.monotonic())


def warm_buckets(cell, buckets: List[int]) -> List[int]:
    """The buckets this traffic cuts: the largest alone where a backlog
    is due at the open (a mix offered above capacity, so every cut is
    full), else the whole ladder."""
    return buckets[-1:] if cell.traffic["backlog"] > 0 else buckets


def serve(cell, seed: int, seconds: float, traced: bool, device,
          peak: dict, t_start: float, trace_dir: Path) -> Run:
    import jax
    import jax.numpy as jnp
    from repro.serving.async_engine import AsyncDiffusionEngine
    run = Run(cell=cell, seed=seed, seconds=seconds, peak=peak,
              chips=cell.chips)
    prog = run.program

    def mark(part: str) -> None:
        run.setup_marks[part] = time.perf_counter() - t_start

    mark("devices")
    tally = CompileTally()
    model = cell.model
    lat = loadgen.latent_shape(cell.traffic, model["in_channels"])
    crf = (run.tokens, model["d_model"])
    full_fn, from_crf_fn = prog.denoiser(model, cell.config["name"])
    params = prog.weights(model, cell.config["name"],
                          loadgen.fold(seed, "weights"), device)
    jax.block_until_ready(params)
    mark("weights")
    engine = prog.engine(cell, full_fn, from_crf_fn, params, lat, crf,
                         prog.policy(cell.policy))
    # warm-up: one real batch per bucket the traffic cuts, through the
    # same execute_plan the window drives; then the per-lane reads of a
    # batch's counts, whose shapes follow the number of real lanes
    buckets = warm_buckets(cell, engine.buckets)
    for b in buckets:
        engine.execute_plan(_warm_plan(prog, cell, b, lat))
        mark(f"warm{b}")
        lanes = jax.device_put(jnp.zeros((b,), jnp.int32), device)
        for n in range(1, b + 1):
            [int(v) for v in lanes[:n]]
    if len(buckets) > 1:
        jnp.zeros(lat).block_until_ready()    # a padded lane
    steps0 = (engine.metrics.full_steps, engine.metrics.total_steps)
    run.plan = loadgen.make_plan(cell.traffic, seed, seconds)
    aeng = AsyncDiffusionEngine(engine).start()

    def submit(a: loadgen.Arrival):
        with jax.profiler.TraceAnnotation("bench.submit"):
            return aeng.submit(prog.request(cell, a, lat))

    loop = loadgen.OpenLoop(run.plan, submit)
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
    compiles0 = tally.mark()
    run.setup_s = time.perf_counter() - t_start
    loop.start()
    try:
        run.close_s = loop.wait_close(seconds, timeout_s=seconds + 600)
    finally:
        loop.stop()
        aeng.shutdown(drain=False)
    run.compiles_in_window = tally.mark() - compiles0
    if traced:
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    run.full_lane_steps = engine.metrics.full_steps - steps0[0]
    run.total_lane_steps = engine.metrics.total_steps - steps0[1]
    stats = device.memory_stats() or {}
    run.memory_peak_bytes = stats.get("peak_bytes_in_use")
    # the program's outputs to the host, then its state off the device
    for a in run.completed():
        run.latents[a.index] = np.asarray(a.result.latents)
        a.result = a.result._replace(latents=None)
    del params, engine, aeng, full_fn, from_crf_fn
    gc.collect()
    if traced:
        from bench import trace as trace_lib
        path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                         recursive=True)
        run.trace = trace_lib.reduce(path[0], window="bench.window")
    return run


def clear(trace_dir: Path) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)
