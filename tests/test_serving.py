"""Continuous-batching serving tests: bucket selection, age/deadline
batch formation (incl. the deadline-starvation promotion fix),
padded-lane isolation, the editing noising path, the
zero-steady-state-recompile guarantee (via the jit cache probe), the
threaded async submit path (futures resolve exactly once, ids
conserved, lapsed deadlines served first), and policy-homogeneous
batch formation (compatibility grouping: pure cuts, one warmed ladder
per group, bitwise-golden equivalence against the ungrouped mixed-lane
path — sync and through the async engine under concurrent
submitters)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.core import policies
from repro.data import synthetic
from repro.diffusion import sampler, schedule
from repro.serving import metrics as metrics_lib
from repro.serving.async_engine import AsyncDiffusionEngine
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.serving.scheduler import Scheduler, bucket_for, bucket_sizes

SIZE = 8
N_STEPS = 6


@pytest.fixture(scope="module")
def dit_fns():
    from repro.models import common, dit
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    return cfg, full_fn, from_crf_fn, params


def make_engine(dit_fns, max_batch=4, n_steps=N_STEPS, **kw):
    cfg, full_fn, from_crf_fn, params = dit_fns
    return DiffusionEngine(full_fn, from_crf_fn, params, (SIZE, SIZE,
                                                  cfg.in_channels),
                           (16, cfg.d_model),
                           policies.FreqCaPolicy(interval=3),
                           n_steps=n_steps, max_batch=max_batch, **kw)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_bucket_sizes_and_selection():
    assert bucket_sizes(8) == [1, 2, 4, 8]
    assert bucket_sizes(6) == [1, 2, 4, 6]   # non-pow2 max still included
    assert bucket_sizes(1) == [1]
    assert bucket_for(1, 8) == 1
    assert bucket_for(3, 8) == 4
    assert bucket_for(5, 8) == 8
    assert bucket_for(5, 6) == 6
    with pytest.raises(ValueError):
        bucket_for(9, 8)
    with pytest.raises(ValueError):
        bucket_for(0, 8)


def test_scheduler_age_based_formation():
    sched = Scheduler(max_batch=4, max_wait_s=10.0, clock=lambda: 0.0)
    sched.submit(DiffusionRequest(request_id=0, seed=0), now=0.0)
    assert not sched.ready(now=1.0)          # young + underfull: hold
    assert sched.form_batch(now=1.0) is None
    assert sched.ready(now=10.0)             # age threshold reached
    plan = sched.form_batch(now=10.0)
    assert plan.n_real == 1 and plan.bucket == 1

    for i in range(4):                        # full largest bucket: cut now
        sched.submit(DiffusionRequest(request_id=i, seed=i), now=11.0)
    assert sched.ready(now=11.0)
    plan = sched.form_batch(now=11.0)
    assert plan.n_real == 4 and plan.bucket == 4 and plan.occupancy == 1.0


def test_scheduler_deadline_and_flush():
    sched = Scheduler(max_batch=8, max_wait_s=100.0, clock=lambda: 0.0)
    sched.submit(DiffusionRequest(request_id=0, seed=0, deadline_s=2.0),
                 now=0.0)
    assert not sched.ready(now=1.0)
    assert sched.ready(now=2.5)               # deadline pressure wins
    # flush drains regardless of age
    sched2 = Scheduler(max_batch=8, max_wait_s=100.0, clock=lambda: 0.0)
    for i in range(3):
        sched2.submit(DiffusionRequest(request_id=i, seed=i), now=0.0)
    plan = sched2.form_batch(now=0.0, flush=True)
    assert plan.n_real == 3 and plan.bucket == 4
    assert len(sched2) == 0


def test_scheduler_deadline_starvation_promotion():
    """Regression: a deadline-lapsed request beyond position max_batch
    used to trigger the cut yet be excluded from it (queue[:take]) —
    under sustained load it could lapse indefinitely.  It must be
    promoted into the cut batch, stable FIFO order otherwise."""
    sched = Scheduler(max_batch=2, max_wait_s=100.0, clock=lambda: 0.0)
    for i in range(2):
        sched.submit(DiffusionRequest(request_id=i, seed=i), now=0.0)
    # lapsed request sits at position 2, beyond max_batch=2
    sched.submit(DiffusionRequest(request_id=2, seed=2, deadline_s=1.0),
                 now=0.0)
    assert sched.ready(now=5.0)
    plan = sched.form_batch(now=5.0)
    ids = [r.request_id for r in plan.requests]
    assert 2 in ids, "lapsed request must be promoted into the cut"
    assert ids == [0, 2]          # stable FIFO order among the picked
    assert [r.request_id for r in sched.queue] == [1]

    # sustained load: fresh undeadlined arrivals keep the queue full —
    # the lapsed request still gets out in the very next cut
    sched2 = Scheduler(max_batch=2, max_wait_s=0.0, clock=lambda: 0.0)
    for i in range(4):
        sched2.submit(DiffusionRequest(request_id=i, seed=i), now=0.0)
    sched2.submit(DiffusionRequest(request_id=9, seed=9, deadline_s=0.5),
                  now=0.0)
    plan = sched2.form_batch(now=2.0)
    assert 9 in [r.request_id for r in plan.requests]


def test_scheduler_seconds_until_ready():
    sched = Scheduler(max_batch=4, max_wait_s=10.0, clock=lambda: 0.0)
    assert sched.seconds_until_ready(now=0.0) is None        # empty queue
    sched.submit(DiffusionRequest(request_id=0, seed=0), now=0.0)
    assert sched.seconds_until_ready(now=2.0) == pytest.approx(8.0)
    sched.submit(DiffusionRequest(request_id=1, seed=1, deadline_s=3.0),
                 now=2.0)
    # deadline (at t=5) beats the age threshold (at t=10)
    assert sched.seconds_until_ready(now=2.0) == pytest.approx(3.0)
    assert sched.seconds_until_ready(now=6.0) == 0.0          # lapsed
    assert sched.ready(now=6.0)


def test_scheduler_thread_safe_submit():
    sched = Scheduler(max_batch=8, max_wait_s=0.0)
    n_threads, per_thread = 8, 50

    def client(k):
        for i in range(per_thread):
            sched.submit(DiffusionRequest(request_id=k * per_thread + i,
                                          seed=0))

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sched.submitted == n_threads * per_thread
    served = []
    while sched.depth:
        served.extend(sched.form_batch(flush=True).requests)
    assert sorted(r.request_id for r in served) == \
        list(range(n_threads * per_thread))


def test_scheduler_pad_to_max_signature():
    sched = Scheduler(max_batch=8, pad_to_max=True)
    sched.submit(DiffusionRequest(request_id=0, seed=0))
    plan = sched.form_batch(flush=True)
    assert plan.bucket == 8 and plan.n_real == 1


def test_scheduler_policy_grouping_and_families():
    """Grouped formation cuts policy-pure batches; compatible static
    families share one group (taylorseer(5) with the freqca(5) default,
    fora(interval=1) with none)."""
    fre = policies.FreqCaPolicy(interval=5)
    sched = Scheduler(max_batch=4, max_wait_s=0.0, clock=lambda: 0.0,
                      group_policies=True, default_policy=fre)
    pols = [None, policies.TaylorSeerPolicy(interval=5),
            policies.ForaPolicy(interval=1),
            policies.NoCachePolicy(interval=1)]
    for i, p in enumerate(pols):
        sched.submit(DiffusionRequest(request_id=i, seed=i, policy=p),
                     now=0.0)
    assert len(sched.groups()) == 2
    p1 = sched.form_batch(now=1.0)
    p2 = sched.form_batch(now=1.0)
    assert [r.request_id for r in p1.requests] == [0, 1]
    assert [r.request_id for r in p2.requests] == [2, 3]
    assert p1.group_key != p2.group_key
    assert len(sched) == 0
    # full-group trigger is per group: 3 groups of 2 fill no bucket of 4
    sched2 = Scheduler(max_batch=4, max_wait_s=100.0, clock=lambda: 0.0,
                       group_policies=True, default_policy=fre)
    mixed = [fre, policies.ForaPolicy(interval=2),
             policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25)]
    for i in range(6):
        sched2.submit(DiffusionRequest(request_id=i, seed=i,
                                       policy=mixed[i % 3]), now=0.0)
    assert not sched2.ready(now=0.0)
    sched2.submit(DiffusionRequest(request_id=6, seed=6, policy=mixed[0]),
                  now=0.0)
    sched2.submit(DiffusionRequest(request_id=7, seed=7, policy=mixed[0]),
                  now=0.0)
    assert sched2.ready(now=0.0)          # the freqca group is full now
    plan = sched2.form_batch(now=0.0)
    assert [r.request_id for r in plan.requests] == [0, 3, 6, 7]


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_padded_lanes_never_leak(dit_fns):
    """A request's output is identical whether it runs alone (bucket 1)
    or padded inside a larger bucket — and pad lanes are never returned."""
    eng = make_engine(dit_fns, max_batch=4)
    for i in range(3):
        eng.submit(DiffusionRequest(request_id=i, seed=i))
    batched = eng.run_batch()                 # 3 real lanes in bucket 4
    assert [o.request_id for o in batched] == [0, 1, 2]
    assert batched[0].bucket == 4
    solo = []
    for i in range(3):
        eng.submit(DiffusionRequest(request_id=i, seed=i))
        solo.extend(eng.run_batch())          # bucket 1, same seeds
    assert solo[0].bucket == 1
    for b, s in zip(batched, solo, strict=True):
        np.testing.assert_allclose(np.asarray(b.latents),
                                   np.asarray(s.latents), atol=1e-5)


def test_editing_request_noising_path(dit_fns):
    cfg = dit_fns[0]
    eng = make_engine(dit_fns, max_batch=4)
    ref = synthetic.shapes_batch(jax.random.key(5), 1, size=SIZE,
                                 channels=cfg.in_channels)[0]
    strength = 0.4
    eng.submit(DiffusionRequest(request_id=0, seed=7, init_latents=ref,
                                edit_strength=strength))
    plan = eng.scheduler.form_batch(flush=True)
    x_init = eng.build_x_init(plan)
    assert x_init.shape[0] == 1               # bucket 1 for a lone request
    noise = jax.random.normal(jax.random.key(7), eng.latent_shape)
    want = schedule.add_noise(ref.astype(noise.dtype), noise, strength)
    np.testing.assert_allclose(np.asarray(x_init[0]), np.asarray(want),
                               atol=1e-6)
    out = eng._execute(plan)
    assert jnp.isfinite(out[0].latents).all()


def test_padding_lanes_are_zero_noise(dit_fns):
    eng = make_engine(dit_fns, max_batch=4)
    for i in range(3):
        eng.submit(DiffusionRequest(request_id=i, seed=i))
    plan = eng.scheduler.form_batch(flush=True)
    x_init = eng.build_x_init(plan)
    assert x_init.shape[0] == 4 and plan.n_real == 3
    np.testing.assert_array_equal(np.asarray(x_init[3]), 0.0)


def test_no_recompile_across_mixed_sizes(dit_fns):
    """Warmup compiles one executable per bucket; serving any mix of
    batch sizes afterwards never grows the jit cache."""
    eng = make_engine(dit_fns, max_batch=4)
    eng.warmup()
    assert eng.compiled_buckets() == len(eng.buckets) == 3
    warm_misses = eng.metrics.compile_misses
    rid = 0
    for _ in range(2):                        # two rounds of mixed sizes
        for burst in (1, 3, 4, 2):
            for _ in range(burst):
                eng.submit(DiffusionRequest(request_id=rid, seed=rid))
                rid += 1
            out = eng.run_batch()
            assert len(out) == burst
    # jit cache probe: still exactly one executable per bucket
    assert eng.compiled_buckets() == len(eng.buckets)
    assert eng.metrics.compile_misses == warm_misses
    assert eng.metrics.compile_hits >= 8
    assert eng.metrics.summary()["mean_occupancy"] <= 1.0


def test_open_loop_poisson_serving(dit_fns):
    """Open-loop client: timestamped Poisson arrivals, batches cut by
    the scheduler's own age pressure (flush=False), everything served."""
    from repro.launch.serve import poisson_stream, serve_open_loop
    eng = make_engine(dit_fns, max_batch=4, max_wait_s=0.01)
    eng.warmup()
    warm_misses = eng.metrics.compile_misses
    plan = poisson_stream(8, rate=200.0, size=SIZE,
                          channels=dit_fns[0].in_channels, edit_every=0)
    outs, wall = serve_open_loop(eng, plan)
    assert sorted(o.request_id for o in outs) == list(range(8))
    assert all(jnp.isfinite(o.latents).all() for o in outs)
    assert eng.metrics.compile_misses == warm_misses   # still zero steady
    assert eng.scheduler.depth == 0


def test_deferred_formation_through_engine(dit_fns):
    eng = make_engine(dit_fns, max_batch=4, max_wait_s=30.0)
    eng.scheduler.clock = lambda: 0.0
    eng.submit(DiffusionRequest(request_id=0, seed=0), now=0.0)
    assert eng.run_batch(flush=False, now=5.0) == []    # held back
    out = eng.run_batch(flush=False, now=31.0)          # age triggers
    assert len(out) == 1 and out[0].queue_wait_s == pytest.approx(31.0)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_percentiles_and_summary():
    m = metrics_lib.ServeMetrics()
    for w in [0.1, 0.2, 0.3, 0.4, 1.0]:
        m.observe_batch(bucket=4, n_real=2, wall_s=w, n_forwards=2,
                        n_steps=10, lane_full=[2, 1])
    m.observe_request(0.0, 0.5, n_full=2)
    m.observe_compile(hit=False)
    m.observe_compile(hit=True)
    m.observe_queue_depth(3)
    s = m.summary()
    assert s["batch_wall_p50_s"] == 0.3
    assert s["batch_wall_p95_s"] == 1.0
    assert s["mean_occupancy"] == 0.5
    assert s["full_step_fraction"] == 0.2
    assert s["request_full_p50"] == 2
    assert s["max_lane_full_spread"] == 1
    assert s["compile_hits"] == 1 and s["compile_misses"] == 1
    assert s["max_queue_depth"] == 3
    assert metrics_lib.throughput(m, 2.0) == 0.5


# ---------------------------------------------------------------------------
# per-lane policies
# ---------------------------------------------------------------------------

def test_mixed_policy_batch_per_lane_accounting(dit_fns):
    """The ISSUE-2 acceptance path (ungrouped mixed-lane former): one
    lane freqca_a, one lane fora in the same batch -> per-request
    n_full_steps differ, each lane's latents match its solo-batch run,
    and the mixed signature serves with zero steady-state recompiles
    once warm."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=12,
                      group_policies=False)
    pol_a = policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25)
    pol_b = policies.ForaPolicy(interval=2)
    lanes = (pol_a, pol_b)
    warm_s = eng.warmup(buckets=[1], lane_policy_sets=[lanes])
    assert warm_s > 0 and eng.metrics.compile_misses >= 2

    def submit_pair():
        eng.submit(DiffusionRequest(request_id=0, seed=0, policy=pol_a))
        eng.submit(DiffusionRequest(request_id=1, seed=1, policy=pol_b))
        return eng.run_batch()

    out = submit_pair()
    assert [o.request_id for o in out] == [0, 1]
    # per-request activated-step counts decouple across lanes
    assert out[0].n_full_steps != out[1].n_full_steps
    assert eng.metrics.summary()["max_lane_full_spread"] > 0

    # each lane matches its solo (bucket-1, uniform-policy) run
    for o, pol in zip(out, lanes, strict=True):
        eng.submit(DiffusionRequest(request_id=o.request_id,
                                    seed=o.request_id, policy=pol))
        solo = eng.run_batch()[0]
        assert solo.n_full_steps == o.n_full_steps
        np.testing.assert_allclose(np.asarray(o.latents),
                                   np.asarray(solo.latents), atol=1e-5)

    # steady state: every signature seen so far is warm — repeated
    # mixed-policy batches never recompile
    warm_misses = eng.metrics.compile_misses
    for _ in range(2):
        submit_pair()
    assert eng.metrics.compile_misses == warm_misses


def test_uniform_nondefault_policy_collapses_signature(dit_fns):
    """All lanes on the same non-default policy -> single-policy jit
    signature (one compile), not a per-lane tuple per bucket."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=6)
    eng.warmup()
    misses = eng.metrics.compile_misses
    pol = policies.ForaPolicy(interval=3)
    for rep in range(2):
        for i in range(2):
            eng.submit(DiffusionRequest(request_id=i, seed=i, policy=pol))
        out = eng.run_batch()
        assert len(out) == 2
    # one new executable for the fora signature, reused on the repeat
    assert eng.metrics.compile_misses == misses + 1


# ---------------------------------------------------------------------------
# policy-homogeneous grouping (golden equivalence vs the ungrouped path)
# ---------------------------------------------------------------------------

MIXED_POLS = (None,                                  # engine default
              policies.ForaPolicy(interval=2),
              policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25))


def _mixed_requests(n=6):
    return [DiffusionRequest(request_id=i, seed=i,
                             policy=MIXED_POLS[i % len(MIXED_POLS)])
            for i in range(n)]


@pytest.fixture(scope="module")
def ungrouped_baseline(dit_fns):
    """The PR-2 mixed-lane path: per-request results of the reference
    stream served without grouping (mixed batches, per-lane masks)."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=8,
                      group_policies=False)
    for r in _mixed_requests():
        eng.submit(r, now=0.0)
    return {o.request_id: o for o in eng.serve_until_drained()}


def test_grouped_golden_equivalence(dit_fns, ungrouped_baseline):
    """Grouped serving of the same mixed-policy stream: policy-pure
    cuts, compile-free after one warmed ladder per group, signatures
    within the groups x buckets budget — and bitwise-identical
    per-request outputs to the ungrouped path."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=8)
    assert eng.group_policies and eng.scheduler.group_policies
    eng.warmup(policies=[p for p in MIXED_POLS if p is not None])
    warm_misses = eng.metrics.compile_misses
    for r in _mixed_requests():
        eng.submit(r, now=0.0)
    outs = eng.serve_until_drained()
    s = eng.metrics.summary()
    # three policy-pure cuts of two lanes each
    assert s["policy_groups"] == 3
    assert all(g["batches"] == 1 and g["requests"] == 2
               for g in s["per_group"].values())
    # compile-free serving; the probe stays within the grouped budget
    assert eng.metrics.compile_misses == warm_misses
    assert s["compiled_signatures"] <= 3 * len(eng.buckets)
    # bitwise golden vs the ungrouped mixed-lane path
    assert sorted(o.request_id for o in outs) == \
        sorted(ungrouped_baseline)
    for o in outs:
        base = ungrouped_baseline[o.request_id]
        assert o.n_full_steps == base.n_full_steps
        np.testing.assert_array_equal(np.asarray(o.latents),
                                      np.asarray(base.latents))


def test_grouped_async_concurrent_submitters_golden(dit_fns,
                                                    ungrouped_baseline):
    """The same stream through ``AsyncDiffusionEngine`` over a grouped
    engine, submitted from concurrent client threads: every future
    resolves to the bitwise result of the ungrouped sync path, with
    zero steady-state recompiles."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=8, max_wait_s=0.005)
    eng.warmup(policies=[p for p in MIXED_POLS if p is not None])
    warm_misses = eng.metrics.compile_misses
    reqs = _mixed_requests()
    futures, lock = {}, threading.Lock()
    with AsyncDiffusionEngine(eng) as aeng:
        def client(k):
            for i in range(k, len(reqs), 3):
                fut = aeng.submit(reqs[i])
                with lock:
                    futures[i] = fut

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert aeng.drain(timeout=120)
    assert eng.metrics.compile_misses == warm_misses
    assert sorted(futures) == sorted(ungrouped_baseline)
    for i, fut in futures.items():
        res = fut.result(timeout=0)
        base = ungrouped_baseline[i]
        assert res.request_id == i
        assert res.n_full_steps == base.n_full_steps
        np.testing.assert_array_equal(np.asarray(res.latents),
                                      np.asarray(base.latents))


def test_family_batch_composition_signature(dit_fns):
    """A static-family cut mixing distinct member policies (fora(1) +
    none: identical activation masks) executes correctly and keys the
    jit cache by CANONICAL composition — re-serving the same
    composition under a different arrival interleaving adds zero
    compiles, and each lane bitwise-matches its solo run."""
    eng = make_engine(dit_fns, max_batch=2, n_steps=6)
    fora1 = policies.ForaPolicy(interval=1)
    none = policies.NoCachePolicy(interval=1)
    assert eng.scheduler.group_key(
        DiffusionRequest(request_id=0, seed=0, policy=fora1)) == \
        eng.scheduler.group_key(
            DiffusionRequest(request_id=0, seed=0, policy=none))

    def serve_pair(pol0, pol1):
        eng.submit(DiffusionRequest(request_id=0, seed=0, policy=pol0))
        eng.submit(DiffusionRequest(request_id=1, seed=1, policy=pol1))
        out = eng.run_batch()      # one family batch: the group is full
        assert len(out) == 2
        return {o.request_id: o for o in out}

    out1 = serve_pair(fora1, none)
    misses = eng.metrics.compile_misses
    serve_pair(none, fora1)        # reversed interleaving, same mix
    assert eng.metrics.compile_misses == misses
    # family lanes bitwise-match their solo (bucket-1, uniform) runs
    for rid, pol in [(0, fora1), (1, none)]:
        eng.submit(DiffusionRequest(request_id=rid, seed=rid, policy=pol))
        solo = eng.run_batch()[0]
        assert solo.n_full_steps == out1[rid].n_full_steps
        np.testing.assert_array_equal(np.asarray(out1[rid].latents),
                                      np.asarray(solo.latents))


# ---------------------------------------------------------------------------
# async engine
# ---------------------------------------------------------------------------

def test_async_submit_returns_future_immediately(dit_fns):
    eng = make_engine(dit_fns, max_batch=2, max_wait_s=0.0)
    eng.warmup()
    with AsyncDiffusionEngine(eng) as aeng:
        fut = aeng.submit(DiffusionRequest(request_id=7, seed=7))
        res = fut.result(timeout=60)
        assert res.request_id == 7
        assert jnp.isfinite(res.latents).all()
        assert fut.done()
    # post-shutdown submits are refused, worker is stopped
    with pytest.raises(RuntimeError):
        aeng.submit(DiffusionRequest(request_id=8, seed=8))
    s = eng.metrics.summary()
    assert s["time_to_first_result_s"] is not None


def test_async_stress_many_client_threads(dit_fns):
    """N client threads submitting concurrently against a small ladder:
    every future resolves exactly once, request ids are conserved, zero
    steady-state recompiles, nothing lost or double-served."""
    eng = make_engine(dit_fns, max_batch=4, max_wait_s=0.005)
    eng.warmup()
    warm_misses = eng.metrics.compile_misses
    n_threads, per_thread = 4, 6
    results, results_lock = [], threading.Lock()
    futures = []

    def on_done(f):
        with results_lock:
            results.append(f.result(timeout=0))

    with AsyncDiffusionEngine(eng) as aeng:
        def client(k):
            futs = []
            for i in range(per_thread):
                rid = k * per_thread + i
                fut = aeng.submit(DiffusionRequest(request_id=rid, seed=rid))
                fut.add_done_callback(on_done)
                futs.append(fut)
            with results_lock:
                futures.extend(futs)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert aeng.drain(timeout=120)

    total = n_threads * per_thread
    assert len(futures) == total
    # exactly-once: every future done, each id appears exactly once
    assert all(f.done() for f in futures)
    got = sorted(f.result(timeout=0).request_id for f in futures)
    assert got == list(range(total))
    # done-callbacks fired exactly once per future too
    assert sorted(r.request_id for r in results) == list(range(total))
    # ladder was warm: serving added zero steady-state recompiles
    assert eng.metrics.compile_misses == warm_misses
    assert eng.scheduler.depth == 0
    assert eng.metrics.summary()["requests"] == total


def test_async_deadline_lapsed_served_first(dit_fns):
    """While the worker is busy, the queue overflows max_batch; when the
    next batch is cut, the deadline-lapsed request is promoted into it
    ahead of an earlier undeadlined one — which keeps waiting under the
    long age threshold until drain."""
    eng = make_engine(dit_fns, max_batch=2, max_wait_s=30.0)
    eng.warmup()
    aeng = AsyncDiffusionEngine(eng).start()
    try:
        # fills the largest bucket -> cut at once, worker goes busy
        fa = aeng.submit(DiffusionRequest(request_id=10, seed=10))
        fb = aeng.submit(DiffusionRequest(request_id=11, seed=11))
        # these three land while the worker executes: queue > max_batch
        f2 = aeng.submit(DiffusionRequest(request_id=2, seed=2))
        f3 = aeng.submit(DiffusionRequest(request_id=3, seed=3))
        f4 = aeng.submit(DiffusionRequest(request_id=4, seed=4,
                                          deadline_s=0.0))   # lapses now
        # next cut is [2, 4]: the lapsed request jumps FIFO position 3
        assert f4.result(timeout=60).request_id == 4
        assert f2.result(timeout=60).request_id == 2
        assert fa.result(timeout=60).request_id == 10
        assert fb.result(timeout=60).request_id == 11
        assert not f3.done()       # still held back by the age threshold
    finally:
        aeng.shutdown(drain=True, timeout=120)
    assert f3.result(timeout=0).request_id == 3   # drained on shutdown


def test_async_client_cancel_does_not_kill_worker(dit_fns):
    """A client cancelling a still-queued future must not crash the
    worker when its batch is cut (the lane still runs; the cancelled
    future just never gets a result) — later requests keep serving."""
    eng = make_engine(dit_fns, max_batch=2, max_wait_s=0.0)
    eng.warmup()
    with AsyncDiffusionEngine(eng) as aeng:
        # keep the worker busy so the next submits stay queued
        f0 = aeng.submit(DiffusionRequest(request_id=0, seed=0))
        f1 = aeng.submit(DiffusionRequest(request_id=1, seed=1))
        f2 = aeng.submit(DiffusionRequest(request_id=2, seed=2))
        cancelled = f2.cancel()    # races the cut: either way is legal
        f3 = aeng.submit(DiffusionRequest(request_id=3, seed=3))
        assert f3.result(timeout=60).request_id == 3   # worker alive
        assert f0.result(timeout=60).request_id == 0
        assert f1.result(timeout=60).request_id == 1
        if cancelled:
            assert f2.cancelled()
        else:
            assert f2.result(timeout=60).request_id == 2
    # duplicate submission of the same pending object is refused
    eng2 = make_engine(dit_fns, max_batch=2, max_wait_s=30.0)
    eng2.warmup(buckets=[1])
    aeng2 = AsyncDiffusionEngine(eng2).start()
    try:
        req = DiffusionRequest(request_id=0, seed=0)
        aeng2.submit(req)
        with pytest.raises(ValueError):
            aeng2.submit(req)
    finally:
        aeng2.shutdown(drain=True, timeout=120)


def test_async_shutdown_without_drain_cancels_queued(dit_fns):
    eng = make_engine(dit_fns, max_batch=2, max_wait_s=30.0)
    eng.warmup()
    aeng = AsyncDiffusionEngine(eng).start()
    fut = aeng.submit(DiffusionRequest(request_id=0, seed=0))
    aeng.shutdown(drain=False, timeout=120)
    # either served before the stop landed, or cancelled — never lost
    assert fut.done()
    if not fut.cancelled():
        assert fut.result(timeout=0).request_id == 0
    assert eng.scheduler.depth == 0


def test_sampler_executables_take_weights_as_inputs():
    """The weights are arguments of the jitted sampler, never constants
    baked into it: the lowered program does not grow with the model,
    so a FLUX-width model lowers at all and every signature shares one
    copy of its weights."""
    import dataclasses

    from repro.models import common, dit
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    sizes = {}
    for n_layers in (1, 4):
        c = dataclasses.replace(cfg, n_layers=n_layers)
        params = common.init_params(dit.dit_specs(c), jax.random.key(0))
        full_fn, from_crf_fn = dit.denoiser(c)
        eng = DiffusionEngine(full_fn, from_crf_fn, params,
                              (SIZE, SIZE, c.in_channels), (16, c.d_model),
                              policies.FreqCaPolicy(interval=3),
                              n_steps=N_STEPS, max_batch=1)
        x = jnp.zeros((1, SIZE, SIZE, c.in_channels))
        text = eng._jit_run.lower(eng.params, x, eng.policy,
                                  eng.crf_shape).as_text()
        nbytes = sum(p.nbytes for p in jax.tree.leaves(params))
        sizes[n_layers] = (len(text), nbytes)
    (text1, bytes1), (text4, bytes4) = sizes[1], sizes[4]
    assert bytes4 - bytes1 > 500_000          # the model grew ...
    assert abs(text4 - text1) < 1_000         # ... its program did not


# ---------------------------------------------------------------------------
# profiler scopes and spans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,scopes", [
    ("freqca", {sampler.FULL_STEP, sampler.CACHED_STEP}),
    ("none", {sampler.FULL_STEP})])
def test_sampler_step_scopes_in_op_metadata(dit_fns, policy, scopes):
    """Every op of a full step and of a cached step carries its step's
    name scope; the uncached policy calls the full step alone."""
    cfg, full_fn, from_crf_fn, params = dit_fns
    pol = (policies.FreqCaPolicy(interval=3) if policy == "freqca"
           else policies.NoCachePolicy())
    eng = DiffusionEngine(full_fn, from_crf_fn, params,
                          (SIZE, SIZE, cfg.in_channels), (16, cfg.d_model),
                          pol, n_steps=N_STEPS, max_batch=1)
    x = jnp.zeros((1, SIZE, SIZE, cfg.in_channels))
    text = eng._jit_run.lower(eng.params, x, eng.policy,
                              eng.crf_shape).as_text(debug_info=True)
    assert {s for s in (sampler.FULL_STEP, sampler.CACHED_STEP)
            if s in text} == scopes


WORKER_SPANS = ("serving.form_batch", "serving.build_x_init",
                "serving.dispatch", "serving.sync", "serving.results",
                "serving.resolve")


@pytest.mark.parametrize("max_wait_s", [0.0, 30.0])
def test_worker_spans_are_flat_and_share_batch_ids(dit_fns, tmp_path,
                                                   max_wait_s):
    """A profiled async run holds one of each worker span per batch, all
    carrying the batch id its results report, one submit span per
    request, and no program span inside another on any thread.  With a
    wait before an underfull cut, the tries that cut nothing add
    ``serving.form_batch`` spans with the next batch's id."""
    from jax.profiler import ProfileData
    eng = make_engine(dit_fns, max_wait_s=max_wait_s)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with AsyncDiffusionEngine(eng) as aeng:
            futs = [aeng.submit(DiffusionRequest(request_id=i, seed=i))
                    for i in range(6)]
            futs[0].result(timeout=120)
            # time for a try at the rest, which a wait leaves uncut
            # until the exit's drain cuts it
            time.sleep(0.2)
        results = [f.result(timeout=120) for f in futs]
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    threads = [sorted((e.start_ns, e.end_ns, e.name, dict(e.stats))
                      for e in ln.events if e.name.startswith("serving."))
               for p in profile.planes for ln in p.lines]
    spans = [s for t in threads for s in t]
    batches = sorted({r.batch for r in results})
    assert len(batches) >= 2
    for name in WORKER_SPANS[1:]:
        assert sorted(s[3]["batch"] for s in spans if s[2] == name) \
            == batches, name
    tries = sorted(s[3]["batch"] for s in spans
                   if s[2] == "serving.form_batch")
    assert sorted(set(tries)) == batches
    # 6 requests in batches of at most 4: the last batch is underfull,
    # and with a wait it is tried before the drain cuts it
    assert (len(tries) > len(batches)) == (max_wait_s > 0)
    assert sorted(s[3]["request"] for s in spans
                  if s[2] == "serving.submit") == list(range(6))
    for t in threads:
        assert all(a[1] <= b[0] for a, b in zip(t, t[1:])), t
