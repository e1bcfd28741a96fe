"""Quality-SLO tests: error-budgeted activation (freqca_eb), budget
tiers, the per-request ``max_error`` path through scheduler + engine,
load shedding (relax, never drop), and the golden guarantee that
requests without a budget are bitwise-identical to the pre-SLO serving
path (feedback stays a None pytree, so non-SLO jit signatures are
unchanged programs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.core import policies
from repro.core.policies import base as policy_base
from repro.core.policies.freqca_eb import (ERROR_TIERS, FreqCaEbState,
                                           FreqCaErrorBudgetPolicy,
                                           budget_tier)
from repro.diffusion import sampler, schedule
from repro.serving.async_engine import AsyncDiffusionEngine
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.serving.scheduler import Scheduler

SIZE = 8
N_STEPS = 6


@pytest.fixture(scope="module")
def dit_fns():
    from repro.models import common, dit
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    return cfg, full_fn, from_crf_fn, params


def make_engine(dit_fns, policy, max_batch=4, **kw):
    cfg, full_fn, from_crf_fn, params = dit_fns
    return DiffusionEngine(full_fn, from_crf_fn, params,
                           (SIZE, SIZE, cfg.in_channels),
                           (16, cfg.d_model), policy,
                           n_steps=N_STEPS, max_batch=max_batch, **kw)


# ---------------------------------------------------------------------------
# budget tiers / with_budget / compatibility keys
# ---------------------------------------------------------------------------

def test_budget_tier_snaps_down_never_up():
    assert budget_tier(0.015) == 0.01     # snap DOWN (more quality)
    assert budget_tier(0.1) == 0.1        # exact tier is itself
    assert budget_tier(0.35) == 0.2
    assert budget_tier(7.0) == 1.0        # above the ladder: loosest tier
    assert budget_tier(0.001) == 0.01     # below the ladder: strictest
    assert all(budget_tier(t) == t for t in ERROR_TIERS)


def test_with_budget_replaces_and_folds_into_key():
    pol = FreqCaErrorBudgetPolicy(method="dct", rho=0.25)
    assert pol.with_budget(None) is pol
    tight = pol.with_budget(0.011)
    assert tight.budget == 0.01
    assert tight is not pol
    key = policies.compatibility_key
    # distinct tiers are distinct groups/signatures; same tier collapses
    assert key(tight) != key(pol.with_budget(0.2))
    assert key(pol.with_budget(0.013)) == key(tight)
    # non-feedback policies ignore the budget (base default)
    fre = policies.FreqCaPolicy(interval=5)
    assert fre.with_budget(0.05) is fre


# ---------------------------------------------------------------------------
# deterministic budget accumulation (decide() is pure bookkeeping)
# ---------------------------------------------------------------------------

EB = FreqCaErrorBudgetPolicy(method="dct", rho=0.25, budget=0.1)


def _hot_state(batch=1, rate_low=0.03, rate_high=0.01):
    """Post-warm-up state with known band rates."""
    st = EB.init(batch, (4, 8))
    return st._replace(
        n_valid=jnp.full((batch,), EB.needed_history + 1, jnp.int32),
        rate_low=jnp.full((batch,), rate_low, jnp.float32),
        rate_high=jnp.full((batch,), rate_high, jnp.float32))


def test_budget_spend_and_carry_over():
    st = _hot_state()                      # rate = 0.04 / cached step
    st, act = EB.decide(st, None)
    assert not bool(act[0])
    assert st.acc[0] == pytest.approx(0.04)
    st, act = EB.decide(st, None)          # carry-over accumulates
    assert not bool(act[0])
    assert st.acc[0] == pytest.approx(0.08)
    assert st.peak[0] == pytest.approx(0.08)
    assert int(st.events[0]) == 0


def test_budget_event_triggers_and_resets():
    st = _hot_state()
    for _ in range(2):
        st, act = EB.decide(st, None)
    # third cached step would spend 0.12 > 0.1: full forward fires
    st, act = EB.decide(st, None)
    assert bool(act[0])
    assert st.acc[0] == pytest.approx(0.0)         # reset on full step
    assert int(st.events[0]) == 1
    # peak is the realized SLO: never exceeds the budget by construction
    assert st.peak[0] == pytest.approx(0.08)
    assert float(st.peak[0]) <= EB.budget


def test_rate_above_budget_means_every_step_full():
    st = _hot_state(rate_low=0.2, rate_high=0.05)
    for i in range(3):
        st, act = EB.decide(st, None)
        assert bool(act[0])
        assert int(st.events[0]) == i + 1
    assert st.peak[0] == pytest.approx(0.0)


def test_warmup_fulls_are_not_budget_events():
    st = EB.init(1, (4, 8))                # n_valid = 0: warm
    st = st._replace(rate_low=jnp.full((1,), 9.9, jnp.float32))
    st, act = EB.decide(st, None)
    assert bool(act[0])
    assert int(st.events[0]) == 0          # warm full, not an event
    # one calibration full beyond the predictor's warm-up
    st = st._replace(n_valid=jnp.full((1,), EB.needed_history, jnp.int32))
    _, act = EB.decide(st, None)
    assert bool(act[0])


def test_lanes_spend_independently():
    st = _hot_state(batch=2)
    st = st._replace(rate_low=jnp.array([0.03, 0.2], jnp.float32))
    st, act = EB.decide(st, None)
    assert not bool(act[0]) and bool(act[1])
    assert st.acc[0] == pytest.approx(0.04)
    assert int(st.events[0]) == 0 and int(st.events[1]) == 1


def test_observe_updates_band_rates():
    st = EB.init(2, (4, 8))
    err = jnp.array([[0.01, 0.02], [0.3, 0.4]], jnp.float32)
    st = EB.observe(st, err, None)
    np.testing.assert_allclose(np.asarray(st.rate_low), [0.01, 0.3])
    np.testing.assert_allclose(np.asarray(st.rate_high), [0.02, 0.4])
    fb = EB.error_feedback(st)
    assert isinstance(fb, policy_base.ErrorFeedback)
    assert fb.realized.shape == (2,) and fb.events.shape == (2,)


def test_state_bytes_count_feedback_scalars():
    batch = 4
    fre = policies.FreqCaPolicy(method="dct", rho=0.25, high_order=2)
    eb = FreqCaErrorBudgetPolicy(method="dct", rho=0.25, high_order=2)
    d = (eb.state_bytes(eb.init(batch, (16, 32)))
         - fre.state_bytes(fre.init(batch, (16, 32))))
    # two band rates + accumulator + peak (f32) + event count (i32)
    assert d == batch * 5 * 4


# ---------------------------------------------------------------------------
# end-to-end on synthetic rough dynamics (deterministic, no model)
# ---------------------------------------------------------------------------

def _rough_fns(s=4, d=8, size=4, ch=2, amp=0.3, freq=8.0):
    """CRF oscillates fast in t, so Hermite forecasts err at a rate the
    budget can meter.  s*d must equal size*size*ch."""
    def full_fn(params, x, t, cond=()):
        crf = jnp.tanh(x.reshape(x.shape[0], s, d))
        crf = crf + amp * jnp.sin(freq * t)
        return crf.reshape(x.shape) * 0.1, crf

    def from_crf_fn(params, crf, t, cond=()):
        return crf.reshape(crf.shape[0], size, size, ch) * 0.1

    return full_fn, from_crf_fn


def _run_eb(budget, n_steps=40):
    full_fn, from_crf_fn = _rough_fns()
    x0 = jax.random.normal(jax.random.key(3), (2, 4, 4, 2))
    pol = FreqCaErrorBudgetPolicy(method="dct", rho=0.25).with_budget(budget)
    return sampler.sample(full_fn, from_crf_fn, None, x0,
                          schedule.timesteps(n_steps), pol,
                          crf_shape=(2, 4, 8))


def test_eb_realized_error_respects_budget():
    for budget in (0.02, 0.1, 0.5):
        res = _run_eb(budget)
        assert res.feedback is not None
        assert float(jnp.max(res.feedback.realized)) <= budget + 1e-6


def test_eb_tighter_budget_means_more_fulls():
    fulls = [int(_run_eb(b).n_full) for b in (0.02, 0.1, 0.5)]
    assert fulls == sorted(fulls, reverse=True), fulls
    assert fulls[0] > fulls[-1], fulls     # budgets actually differentiate
    res = _run_eb(0.02)
    assert int(jnp.sum(res.feedback.events)) > 0


def test_non_feedback_policies_report_no_feedback():
    full_fn, from_crf_fn = _rough_fns()
    x0 = jax.random.normal(jax.random.key(3), (2, 4, 4, 2))
    for pol in (policies.NoCachePolicy(),
                policies.FreqCaPolicy(interval=3, method="dct", rho=0.25),
                policies.ForaPolicy(interval=2),
                policies.FreqCaAdaptivePolicy(method="dct", rho=0.25,
                                              tea_threshold=0.3)):
        res = sampler.sample(full_fn, from_crf_fn, None, x0,
                             schedule.timesteps(12), pol,
                             crf_shape=(2, 4, 8))
        assert res.feedback is None, pol


# ---------------------------------------------------------------------------
# load shedding: relax budgets under queue pressure, never drop
# ---------------------------------------------------------------------------

def test_shed_relaxes_effective_budget_never_drops():
    eb = FreqCaErrorBudgetPolicy(method="dct", rho=0.25)
    sched = Scheduler(max_batch=4, default_policy=eb, shed_depth=2,
                      shed_factor=4.0, group_policies=True,
                      clock=lambda: 0.0)
    reqs = [DiffusionRequest(request_id=i, seed=i, max_error=0.05)
            for i in range(4)]
    for r in reqs:
        sched.submit(r, now=0.0)
    # below shed depth: budget honored; at/over: relaxed, not dropped
    assert reqs[0].effective_max_error == 0.05
    assert reqs[1].effective_max_error == 0.05
    assert reqs[2].effective_max_error == pytest.approx(0.2)
    assert reqs[3].effective_max_error == pytest.approx(0.2)
    assert sched.shed_events == 2
    tiers = {sched.effective_policy(r).budget for r in reqs}
    assert tiers == {budget_tier(0.05), budget_tier(0.2)}
    served = []
    while len(sched):
        plan = sched.form_batch(now=0.0, flush=True)
        served += [r.request_id for r in plan.requests]
        # every cut is budget-tier pure (tier folds into the group key)
        assert len({sched.effective_policy(r).budget
                    for r in plan.requests}) == 1
    assert sorted(served) == [0, 1, 2, 3]  # relaxed, NEVER dropped


def test_no_shed_below_depth_and_no_budget_requests_untouched():
    eb = FreqCaErrorBudgetPolicy(method="dct", rho=0.25)
    sched = Scheduler(max_batch=8, default_policy=eb, shed_depth=100,
                      shed_factor=4.0, clock=lambda: 0.0)
    a = DiffusionRequest(request_id=0, seed=0, max_error=0.05)
    b = DiffusionRequest(request_id=1, seed=1)          # no SLO
    sched.submit(a, now=0.0)
    sched.submit(b, now=0.0)
    assert a.effective_max_error == 0.05
    assert b.effective_max_error is None
    assert sched.shed_events == 0
    assert sched.effective_policy(b) == eb              # default untouched


# ---------------------------------------------------------------------------
# engine: SLO report + golden no-budget path
# ---------------------------------------------------------------------------

def test_engine_reports_realized_error_and_metrics(dit_fns):
    eb = FreqCaErrorBudgetPolicy(method="dct", rho=0.25)
    eng = make_engine(dit_fns, eb)
    reqs = [DiffusionRequest(request_id=i, seed=i, max_error=0.1)
            for i in range(3)]
    outs = eng.run_batch(reqs=reqs, now=0.0)
    assert len(outs) == 3
    for o in outs:
        assert o.realized_error is not None
        assert o.realized_error <= budget_tier(0.1) + 1e-6
        assert isinstance(o.budget_events, int)
    s = eng.metrics.summary()
    assert s["realized_error_p95"] is not None
    assert s["realized_error_p95"] <= budget_tier(0.1) + 1e-6
    assert s["budget_events"] == sum(o.budget_events for o in outs)
    assert s["shed_events"] == 0
    (group,) = s["per_group"].values()
    assert "budget_events" in group and "realized_error_p95" in group
    snap = eng.metrics.snapshot().summary()   # snapshot carries SLO state
    assert snap["realized_error_p95"] == s["realized_error_p95"]


def test_run_batch_reqs_equals_submit_then_run(dit_fns):
    eb = FreqCaErrorBudgetPolicy(method="dct", rho=0.25)
    reqs = lambda: [DiffusionRequest(request_id=i, seed=i, max_error=0.05)
                    for i in range(2)]
    eng_a = make_engine(dit_fns, eb)
    out_a = eng_a.run_batch(reqs=reqs(), now=0.0)
    eng_b = make_engine(dit_fns, eb)
    for r in reqs():
        eng_b.submit(r, now=0.0)
    out_b = eng_b.run_batch(now=0.0)
    for a, b in zip(out_a, out_b, strict=True):
        np.testing.assert_array_equal(np.asarray(a.latents),
                                      np.asarray(b.latents))
        assert a.realized_error == b.realized_error


def test_no_budget_requests_are_bitwise_pre_slo(dit_fns):
    """max_error=None must leave the serving path untouched: same
    results with or without the shedding config, across grouped /
    ungrouped / async submission, and no SLO fields reported."""
    fre = policies.FreqCaPolicy(interval=3)

    def reqs():
        return [DiffusionRequest(request_id=i, seed=i, max_error=None)
                for i in range(4)]

    golden = make_engine(dit_fns, fre).run_batch(reqs=reqs(), now=0.0)
    assert all(o.realized_error is None and o.budget_events is None
               for o in golden)
    variants = [
        make_engine(dit_fns, fre, shed_depth=1, shed_factor=8.0),
        make_engine(dit_fns, fre, group_policies=False),
    ]
    for eng in variants:
        outs = eng.run_batch(reqs=reqs(), now=0.0)
        for g, o in zip(golden, outs, strict=True):
            np.testing.assert_array_equal(np.asarray(g.latents),
                                          np.asarray(o.latents))
            assert o.realized_error is None
    # async submit path: same request type, same bitwise results
    aeng_inner = make_engine(dit_fns, fre)
    with AsyncDiffusionEngine(aeng_inner) as aeng:
        futs = [aeng.submit(r) for r in reqs()]
        outs = {f.result().request_id: f.result() for f in futs}
    for g in golden:
        np.testing.assert_array_equal(
            np.asarray(g.latents), np.asarray(outs[g.request_id].latents))
    s = aeng_inner.metrics.summary()
    assert s["realized_error_p95"] is None and s["budget_events"] == 0
