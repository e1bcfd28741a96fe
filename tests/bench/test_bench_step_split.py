"""The step split (``bench/tools/step_split.py``) on small hand-made
traces: device time by sampler scope, host time by engine span, the
stretches of a program step with no JAX call, the engine's counters over
a served span; and the benchmark's own reduction reading the same
with the program's scopes and spans in the trace as without them."""
import re

import pytest
from jax.profiler import ProfileData

import bench_tiny  # noqa: F401  (puts the checkout on the path)
from bench import trace
from bench.tools import step_split as ss

SCOPE_STAT = ss.SCOPE_STAT
FULL = "jit(_jit_run)/while/body/cond/branch_1_fun/sampler.full_step"
CACHED = "jit(_jit_run)/while/body/cond/branch_0_fun/sampler.cached_step"

# device, in ms from 1 ms: a while loop 0-9 holding a full step (cond 0-5:
# a fusion 0-1 and a flash call 1-4 under the full scope) and a cached
# step (cond 5-7: a fusion 5-6 under the cached scope); an unscoped op
# 10-11 after a 1-ms gap.  As on a TPU, the scope path is a stat of the
# op's metadata, held as a string or as a reference to a stat name.  Host: the window 0-12 and the worker's spans
# of batch 0, one JAX call nested in `serving.results`.
HAND = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 9000000000 }}
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 5000000000 }}
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 1000000000 }}
    events {{ metadata_id: 4 offset_ps: 1000000000 duration_ps: 3000000000 }}
    events {{ metadata_id: 2 offset_ps: 5000000000 duration_ps: 2000000000 }}
    events {{ metadata_id: 5 offset_ps: 5000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 6 offset_ps: 10000000000 duration_ps: 1000000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.1 = f32[4] while(f32[4] %a)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%cond.2 = f32[4] conditional(pred[] %p)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.3 = f32[4] fusion(f32[4] %a)"
    stats {{ metadata_id: 9 str_value: "{FULL}/dot_general" }} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%_flash.4 = f32[4] custom-call(f32[4] %fusion.3), custom_call_target=\\"tpu_custom_call\\""
    stats {{ metadata_id: 9 ref_value: 10 }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%fusion.5 = f32[4] fusion(f32[4] %a)"
    stats {{ metadata_id: 8 str_value: "{CACHED}/not/the/scope/stat" }}
    stats {{ metadata_id: 9 str_value: "{CACHED}/add" }} }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%copy.6 = f32[4] copy(f32[4] %a)"
    stats {{ metadata_id: 9 str_value: "jit(_place)/copy" }} }} }}
  stat_metadata {{ key: 8 value {{ id: 8 name: "source" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "{SCOPE_STAT}" }} }}
  stat_metadata {{ key: 10 value {{ id: 10 name: "{FULL}/jit(_flash)/pallas_call" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 12000000000 }} }}
  lines {{ id: 2 name: "diffusion-engine-worker" timestamp_ns: 1000000
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 100000000
      stats {{ metadata_id: 9 int64_value: 0 }} }}
    events {{ metadata_id: 3 offset_ps: 100000000 duration_ps: 200000000
      stats {{ metadata_id: 9 int64_value: 0 }} }}
    events {{ metadata_id: 4 offset_ps: 300000000 duration_ps: 100000000
      stats {{ metadata_id: 9 int64_value: 0 }} }}
    events {{ metadata_id: 5 offset_ps: 400000000 duration_ps: 8600000000
      stats {{ metadata_id: 9 int64_value: 0 }} }}
    events {{ metadata_id: 6 offset_ps: 9000000000 duration_ps: 1500000000
      stats {{ metadata_id: 9 int64_value: 0 }} }}
    events {{ metadata_id: 7 offset_ps: 9100000000 duration_ps: 200000000 }}
    events {{ metadata_id: 8 offset_ps: 10500000000 duration_ps: 100000000
      stats {{ metadata_id: 9 int64_value: 0 }} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "serving.form_batch" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "serving.build_x_init" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "serving.dispatch" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "serving.sync" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "serving.results" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "PjitFunction(_unstack)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "serving.resolve" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "batch" }} }}
}}
'''


def _bare(text: str) -> str:
    """The same trace without op scopes, its spans renamed to events
    that are not the program's."""
    text = re.sub(r"\n\s*stats \{ metadata_id: [89] [^}]*\}", "", text)
    return text.replace('"serving.', '"client.')


def _scoped(text: str):
    xspace = ProfileData.text_proto_to_serialized_xspace(text)
    return ss.scoped(ProfileData.from_serialized_xspace(xspace),
                     "bench.window", ss.op_scopes(xspace))


@pytest.fixture(scope="module")
def hand():
    return ProfileData.from_text_proto(HAND)


def test_op_scopes_from_the_op_metadata():
    scopes = ss.op_scopes(ProfileData.text_proto_to_serialized_xspace(HAND))
    by_op = {op.split(" ")[0]: s for op, s in scopes.items()}
    assert by_op == {"%while.1": ss.UNSCOPED, "%cond.2": ss.UNSCOPED,
                     "%fusion.3": ss.FULL, "%_flash.4": ss.FULL,
                     "%fusion.5": ss.CACHED, "%copy.6": ss.UNSCOPED}


def test_device_time_by_scope():
    by_scope = _scoped(HAND)
    assert by_scope[ss.FULL] == (pytest.approx(0.004), 2)
    assert by_scope[ss.CACHED] == (pytest.approx(0.001), 1)
    # the while loop's and the conditionals' own time, and the copy
    assert by_scope[ss.UNSCOPED] == (pytest.approx(0.005), 4)


def test_step_times_from_scopes():
    by_scope = _scoped(HAND)
    full = ss.full_step_ms(by_scope, forwards=1, steps=2)
    assert full["value"] == pytest.approx(4.0)
    assert full["unscoped_ms_per_step"] == pytest.approx(2.5)
    assert ss.cached_step_ms(by_scope, 1)["value"] == pytest.approx(1.0)


def test_engine_host_time_from_spans(hand):
    spans = ss.spans(hand, "bench.window")
    assert {s[0] for s in spans} == set(ss.HOST_SPANS) | {"serving.sync"}
    assert all(s[3] == {"batch": 0} for s in spans)
    got = ss.engine_host_ms(spans, batches=1)
    # form_batch 0.1 + build_x_init 0.2 + dispatch 0.1 + results 1.5 +
    # resolve 0.1; the sync (waiting on the device) is left out
    assert got["value"] == pytest.approx(2.0)
    assert got["share"]["serving.results"] == pytest.approx(0.75)
    assert sum(got["share"].values()) == pytest.approx(1.0)


def test_stalls_inside_program_steps(hand):
    got = ss.stalls(hand, "bench.window")
    # `serving.results` (9-10.5 ms) holds a JAX call at 9.1-9.3 ms, so
    # its longest stretch with none is 9.3-10.5; the sync (0.4-9 ms), in
    # which the worker waits on the device, is no program step
    assert got[0] == [pytest.approx(0.0012), "serving.results", 0]
    assert got[1] == [pytest.approx(0.0002), "serving.build_x_init", 0]
    assert {g[1] for g in got} == set(ss.HOST_SPANS)


class _Engine:
    """What the async worker needs of an engine: every batch runs three
    forwards of ten steps."""

    def __init__(self):
        from repro.serving.metrics import ServeMetrics
        from repro.serving.scheduler import Scheduler
        self.scheduler = Scheduler(max_batch=2, max_wait_s=0.0)
        self.metrics = ServeMetrics()
        self.next_batch = 0

    def execute_plan(self, plan):
        from repro.serving.engine import DiffusionResult
        batch, self.next_batch = self.next_batch, self.next_batch + 1
        self.metrics.observe_batch(plan.bucket, plan.n_real, 0.01, 3, 10)
        return [DiffusionResult(r.request_id, None, 3, 0.01, batch=batch)
                for r in plan.requests]


def test_served_counters_count_from_start_to_shutdown():
    from repro.serving import async_engine
    from repro.serving.scheduler import DiffusionRequest
    base = async_engine.AsyncDiffusionEngine
    eng = _Engine()
    eng.metrics.observe_batch(2, 2, 0.01, 3, 10)      # a warm-up batch
    with ss.served_counters() as counted:
        aeng = async_engine.AsyncDiffusionEngine(eng).start()
        futs = [aeng.submit(DiffusionRequest(request_id=i, seed=i))
                for i in range(5)]
        batches = {f.result(timeout=30).batch for f in futs}
        aeng.shutdown()
    assert async_engine.AsyncDiffusionEngine is base
    assert counted == {"batches": len(batches),
                       "forwards": 3 * len(batches)}
    assert eng.metrics.n_batches == len(batches) + 1


def test_split_of_a_run():
    by_scope = _scoped(HAND)
    got = ss.split(by_scope, [], batches=1, forwards=1, n_steps=2)
    assert got["full_step_ms"]["value"] == pytest.approx(4.0)
    assert got["cached_step_ms"]["value"] == pytest.approx(1.0)
    assert (got["engine_host_ms"], got["dispatch_spans"]) == (None, 0)


def test_a_trace_without_scopes_or_spans_reads_nothing():
    by_scope = _scoped(_bare(HAND))
    assert set(by_scope) == {ss.UNSCOPED}
    assert ss.full_step_ms(by_scope, forwards=1, steps=2) is None
    assert ss.cached_step_ms(by_scope, 1) is None
    profile = ProfileData.from_text_proto(_bare(HAND))
    assert ss.spans(profile, "bench.window") == []
    assert ss.engine_host_ms([], batches=1) is None


def test_benchmark_reduction_reads_the_same_with_scopes_and_spans(hand):
    """The program's scopes change no number the accepted metrics read,
    and its spans take the idle gaps' labels from the JAX calls nested
    in them."""
    bare = _bare(HAND)
    with_marks = trace.reduce_profile(ProfileData.from_text_proto(HAND),
                                      "bench.window")
    without = trace.reduce_profile(ProfileData.from_text_proto(bare),
                                   "bench.window")
    assert (with_marks.window_s, with_marks.busy_s, with_marks.ops) \
        == (without.window_s, without.busy_s, without.ops)
    assert with_marks.breakdown["device_ops"] \
        == without.breakdown["device_ops"]
    assert with_marks.kernel_time(("_flash",)) == (pytest.approx(0.003), 1)
    assert [g[0] for g in with_marks.breakdown["idle_gaps"]] \
        == ["serving.results", "no host span"]


def test_served_counters_over_a_served_run(tmp_path):
    """Around the harness's own served span (a tiny cell on the CPU, every
    bucket cut), the counters give one batch per batch id the results
    carry and agree with the harness's lane counters."""
    import time

    import jax

    import bench_tiny
    from bench import cell as cell_lib
    from bench import serve
    cell = bench_tiny.cell(rate_per_s=40.0)
    with ss.served_counters() as counted:
        run = serve.serve(cell, 2 ** 40 + 5, 1.0, False, jax.devices()[0],
                          cell_lib.peaks()["TPU v5 lite"],
                          time.perf_counter(), tmp_path)
    buckets = {a.result.batch: a.result.bucket for a in run.completed()}
    n_steps = cell.engine["n_steps"]
    assert counted["batches"] == len(buckets) > 0
    assert run.total_lane_steps == n_steps * sum(buckets.values())
    assert counted["forwards"] * min(buckets.values()) \
        <= run.full_lane_steps \
        <= counted["forwards"] * max(buckets.values())
    assert 0 < counted["forwards"] < counted["batches"] * n_steps
