"""Fleet serving tests: ServeMetrics wire format (to_dict/from_dict
roundtrip, associative merge), end-to-end two-replica serving through
``FleetRouter`` (every future resolves, results bitwise-equal to the
in-process engine, zero steady-state recompiles per replica, routing
counters account for every placement), and the failure path (SIGKILL a
replica mid-stream: the router marks it unhealthy, requeues its
in-flight work onto the survivor, and every submitted future still
resolves exactly once).

``tiny_engine`` must stay module-level: the spawn start method pickles
the factory by reference and re-imports this module in the child.
"""
import time

import numpy as np
import pytest

from repro.serving.engine import DiffusionRequest
from repro.serving.fleet import FleetMetrics, FleetRouter
from repro.serving.metrics import ServeMetrics

SIZE = 8
N_STEPS = 6
MAX_BATCH = 4


def tiny_engine():
    """Zero-arg picklable factory: reduced DiT engine, built fresh in
    whichever process calls it (each fleet worker initialises its own
    params — deterministic from key(0), so replicas are identical)."""
    import jax

    import repro.configs as config_lib
    from repro.core.policies import FreqCaPolicy
    from repro.models import common, dit
    from repro.serving.engine import DiffusionEngine

    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)
    return DiffusionEngine(full_fn, from_crf_fn, params,
                           (SIZE, SIZE, cfg.in_channels),
                           (16, cfg.d_model),
                           FreqCaPolicy(interval=3),
                           n_steps=N_STEPS, max_batch=MAX_BATCH,
                           max_wait_s=0.05)


# ---------------------------------------------------------------------------
# ServeMetrics wire format (satellite: to_dict / from_dict / merge)
# ---------------------------------------------------------------------------

def _sample_metrics(n_batches=3, seed=0):
    m = ServeMetrics()
    for i in range(n_batches):
        m.observe_compile(hit=i > 0)
        m.observe_batch(4, 3, 0.1 * (i + 1 + seed), 2, N_STEPS,
                        lane_full=[2, 3, 2], group_key=f"g{seed}",
                        lane_errors=[0.01 * (i + 1)], lane_events=[1])
        m.observe_request(0.01 * i, 0.2 + 0.1 * i, n_full=2,
                          realized_error=0.02, budget_events=1)
        m.observe_queue_depth(i)
    m.observe_first_result(0.5 + seed)
    m.observe_state_bytes(1024)
    m.observe_compiled_signatures(3)
    m.observe_shed_events(seed)
    return m


def test_metrics_dict_roundtrip():
    m = _sample_metrics()
    d = m.to_dict()
    # plain python values only (pickles across a process boundary)
    assert all(isinstance(v, (int, float, list, dict, type(None)))
               for v in d.values()), d
    m2 = ServeMetrics.from_dict(d)
    assert m2.to_dict() == d
    assert m2.summary() == m.summary()


def test_metrics_merge_is_lossless_and_associative():
    parts = [_sample_metrics(seed=s) for s in range(3)]
    merged = ServeMetrics.merge(parts)
    # counters sum, observations concatenate (exact fleet percentiles)
    assert merged.n_requests == sum(p.n_requests for p in parts)
    assert merged.compile_misses == sum(p.compile_misses for p in parts)
    assert sorted(merged.request_latencies) == sorted(
        x for p in parts for x in p.request_latencies)
    # ttfr is the fleet minimum; signatures the fleet total
    assert merged.time_to_first_result_s == min(
        p.time_to_first_result_s for p in parts)
    assert merged.compiled_signatures == 9
    # associativity: pairwise folds == one flat fold (dicts and
    # instances are interchangeable parts)
    left = ServeMetrics.merge(
        [ServeMetrics.merge(parts[:2]).to_dict(), parts[2]])
    assert left.summary() == merged.summary()
    right = ServeMetrics.merge(
        [parts[0], ServeMetrics.merge([p.to_dict() for p in parts[1:]])])
    assert right.summary() == merged.summary()


def test_metrics_forwards_and_running_maxima_roundtrip_and_merge():
    parts = [_sample_metrics(seed=s) for s in range(3)]
    parts[1].observe_queue_depth(9)
    parts[2].observe_batch(4, 2, 0.1, 5, N_STEPS, lane_full=[5, 1])
    m = parts[0]
    assert (m.forwards, m.max_queue_depth, m.max_lane_full_spread) \
        == (6, 2, 1)
    back = ServeMetrics.from_dict(m.to_dict())
    assert (back.forwards, back.max_queue_depth,
            back.max_lane_full_spread) == (6, 2, 1)
    merged = ServeMetrics.merge([p.to_dict() for p in parts])
    # forwards sum; the maxima take the largest of any replica
    assert merged.forwards == merged.summary()["forwards"] == 6 + 6 + 11
    assert merged.summary()["max_queue_depth"] == 9
    assert merged.summary()["max_lane_full_spread"] == 4


def test_metrics_keep_no_list_per_worker_turn():
    m = ServeMetrics()
    before = m.to_dict()
    for depth in range(1000):
        m.observe_queue_depth(depth % 7)
    after = m.to_dict()
    assert after["max_queue_depth"] == 6
    assert {k: v for k, v in after.items() if isinstance(v, list)} \
        == {k: v for k, v in before.items() if isinstance(v, list)}


def test_metrics_snapshot_with_observation_lists_still_loads():
    """A replica one release behind ships the raw lists in place of the
    running maxima: they load as their maxima and merge with new ones."""
    old = _sample_metrics().to_dict()
    for f in ("max_queue_depth", "max_lane_full_spread", "forwards"):
        del old[f]
    old.update(queue_depths=[1, 7, 2], batch_lane_spread=[0, 3, 1])
    m = ServeMetrics.from_dict(old)
    assert (m.max_queue_depth, m.max_lane_full_spread, m.forwards) \
        == (7, 3, 0)
    merged = ServeMetrics.merge([old, _sample_metrics(seed=1)])
    assert merged.summary()["max_queue_depth"] == 7
    assert merged.summary()["max_lane_full_spread"] == 3
    assert merged.forwards == 6


def test_fleet_metrics_summary_sections():
    snaps = {i: _sample_metrics(seed=i).to_dict() for i in range(2)}
    fm = FleetMetrics(snaps, routing={"affinity_hits": 5, "spills": 1},
                      meta={0: {"warmup_compiles": 1},
                            1: {"warmup_compiles": 0}})
    s = fm.summary()
    assert s["fleet"]["replicas"] == 2
    assert s["fleet"]["requests"] == 6
    # steady recompiles = misses beyond each replica's boot warmup
    assert s["per_replica"][0]["steady_recompiles"] == 0
    assert s["per_replica"][1]["steady_recompiles"] == 1
    assert s["routing"]["spills"] == 1
    assert fm.steady_recompiles(7) is None   # unknown replica


# ---------------------------------------------------------------------------
# launcher flag (satellite: --replicas 1 stays on the in-process path)
# ---------------------------------------------------------------------------

def test_replicas_flag_defaults_to_inprocess():
    from repro.launch.serve import build_parser
    args = build_parser().parse_args([])
    assert args.replicas == 1          # default: in-process engine path
    args = build_parser().parse_args(["--replicas", "2"])
    assert args.replicas == 2


# ---------------------------------------------------------------------------
# end-to-end fleet serving (slow: boots worker processes)
# ---------------------------------------------------------------------------

def _requests(n):
    return [DiffusionRequest(request_id=i, seed=i) for i in range(n)]


def test_fleet_two_replicas_end_to_end():
    n = 10
    router = FleetRouter(tiny_engine, n_replicas=2)
    try:
        router.start()
        assert all(r.healthy for r in router.replicas)
        assert router.spill_slack == MAX_BATCH   # from ready metadata
        futs = [router.submit(r) for r in _requests(n)]
        assert router.drain(timeout=300.0)
        outs = [f.result(timeout=10.0) for f in futs]
        fm = router.fleet_metrics()
    finally:
        router.shutdown(drain=False)

    assert sorted(o.request_id for o in outs) == list(range(n))
    # bitwise-identical to the in-process engine on the same stream:
    # per-request sampling is deterministic in the seed, independent of
    # which replica / batch composition served it
    eng = tiny_engine()
    eng.warmup()
    for r in _requests(n):
        eng.submit(r)
    ref = {o.request_id: np.asarray(o.latents)
           for o in eng.serve_until_drained()}
    for o in outs:
        assert np.array_equal(np.asarray(o.latents), ref[o.request_id]), \
            f"request {o.request_id} diverged from in-process engine"

    s = fm.summary()
    assert s["fleet"]["requests"] == n
    assert s["fleet"]["replicas"] == 2
    # the fleet invariant: once warm, no replica ever compiles again
    for idx, pr in s["per_replica"].items():
        assert pr["steady_recompiles"] == 0, (idx, pr)
    rt = s["routing"]
    assert rt["submitted"] == rt["resolved"] == n
    assert rt["failed"] == 0 and rt["duplicate_results"] == 0
    assert rt["requeued"] == 0 and rt["replicas_lost"] == 0
    # every placement is accounted for: one new group for the default
    # policy, the rest affinity follows or load spills
    assert rt["new_groups"] >= 1
    assert rt["new_groups"] + rt["affinity_hits"] + rt["spills"] == n


def test_replica_crash_requeues_onto_survivor():
    n = 8
    router = FleetRouter(tiny_engine, n_replicas=2,
                         health_interval_s=0.1)
    try:
        router.start()
        futs = [router.submit(r) for r in _requests(n)]
        # SIGKILL the replica holding the most in-flight work while the
        # stream is mid-flight — the crash case (SIGTERM would drain)
        with router._lock:
            victim = max(router.replicas, key=lambda r: len(r.inflight))
            assert victim.inflight, "victim had no in-flight work"
        victim.proc.kill()
        outs = [f.result(timeout=300.0) for f in futs]  # exactly once
        # death observed and accounted
        deadline = time.monotonic() + 10.0
        while victim.healthy and time.monotonic() < deadline:
            time.sleep(0.05)
        st = router.status()
    finally:
        router.shutdown(drain=False)

    assert sorted(o.request_id for o in outs) == list(range(n))
    assert not victim.healthy
    assert st["healthy_replicas"] == 1
    rt = st["counters"]
    assert rt["replicas_lost"] == 1
    assert rt["requeued"] >= 1, rt          # orphans moved to the survivor
    assert rt["resolved"] == n and rt["failed"] == 0
    assert rt["duplicate_results"] == 0
    survivor = next(r for r in router.replicas if r is not victim)
    assert not survivor.inflight


def test_router_rejects_bad_config():
    with pytest.raises(ValueError):
        FleetRouter(tiny_engine, n_replicas=0)
    router = FleetRouter(tiny_engine, n_replicas=1)
    with pytest.raises(RuntimeError):       # not started yet
        router.submit(DiffusionRequest(request_id=0, seed=0))


# ---------------------------------------------------------------------------
# exactly-once futures: a seeded double-resolution is absorbed, counted
# ---------------------------------------------------------------------------

class _FakeReplica:
    """Just enough of ``Replica`` for the router's result path: an
    inflight table.  No process is spawned."""

    def __init__(self):
        self.inflight = {}
        self.healthy = True
        self.stopped = False


def test_double_set_result_absorbed_by_duplicate_counter():
    """The requeue race, replayed deterministically: a replica dies
    after shipping a result, its in-flight request is requeued onto a
    survivor under a NEW token with the SAME future, then both results
    arrive.  The second resolution must bump ``duplicate_results`` —
    never raise ``InvalidStateError`` into the receiver thread."""
    from concurrent.futures import Future

    router = FleetRouter(tiny_engine, n_replicas=2)   # never started
    dead, survivor = _FakeReplica(), _FakeReplica()
    req = DiffusionRequest(request_id=7, seed=0)
    fut = Future()
    dead.inflight[0] = (req, fut)       # original placement
    survivor.inflight[1] = (req, fut)   # requeued under a new token

    router._finish(dead, 0, value="res-a")      # first result wins
    router._finish(survivor, 1, value="res-b")  # late duplicate

    assert fut.result(timeout=1) == "res-a"
    assert router.counters["duplicate_results"] == 1
    assert router.counters["resolved"] == 2     # both tokens retired
    assert not dead.inflight and not survivor.inflight


def test_finish_is_idempotent_per_token():
    """A token already popped (requeued/cancelled meanwhile) is a
    no-op: no counter bump, no resolution attempt."""
    from concurrent.futures import Future

    router = FleetRouter(tiny_engine, n_replicas=1)
    r = _FakeReplica()
    fut = Future()
    r.inflight[5] = (DiffusionRequest(request_id=1, seed=0), fut)
    router._finish(r, 5, value="first")
    router._finish(r, 5, value="again")         # token already gone
    assert fut.result(timeout=1) == "first"
    assert router.counters["duplicate_results"] == 0
    assert router.counters["resolved"] == 1


def test_async_engine_absorbs_duplicate_resolution():
    """The async worker's ``_serve`` uses the same exactly-once guard:
    a future that somehow resolved early must degrade to the
    ``duplicate_results`` metric, not kill the worker thread."""
    from concurrent.futures import Future

    from repro.serving.async_engine import AsyncDiffusionEngine
    from repro.serving.engine import DiffusionResult
    from repro.serving.metrics import ServeMetrics

    class _Eng:
        def __init__(self):
            self.metrics = ServeMetrics()

        def execute_plan(self, plan):
            return [DiffusionResult(0, None, 0, 0.0)]

    aeng = AsyncDiffusionEngine.__new__(AsyncDiffusionEngine)
    aeng.engine = _Eng()
    aeng.metrics = aeng.engine.metrics
    aeng._t0 = None

    fut = Future()
    # repro: allow[future-guard]: seeding the double resolution this test exists to exercise
    fut.set_result("early")
    aeng._serve(plan=None, futs=[fut])  # must not raise
    assert fut.result() == "early"
    assert aeng.metrics.duplicate_results == 1
    assert aeng.metrics.to_dict()["duplicate_results"] == 1
