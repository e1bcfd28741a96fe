"""Quality-SLO serving benchmark: error-budgeted activation vs the
scheduled interval, and load shedding under overload.  Emits
``results/bench/BENCH_serve_quality.json`` (asserted in CI).

Both parts run the trained bench DiT through a *stiff-dynamics*
wrapper: the DiT's time input is frozen (its own step-to-step CRF
drift at smoke step counts would swamp any budget tier) and the CRF is
modulated by a controlled oscillation whose amplitude decays along the
trajectory — ~0.5 rad of phase per sampler step at any ``n_steps``, so
the cache's per-step error rate is in the same meterable range at
smoke and full scale, and is *time-varying*, which is the regime
feedback-driven activation exists for.  The velocity is re-derived
from the modulated CRF, so cached steps approximate exactly the
trajectory full steps produce.

* **Pareto** — ``freqca_eb`` at each budget tier vs scheduled
  ``freqca`` at each interval.  Scheduled freqca is run through an
  instrumented variant (schedule-driven activation + the eb error
  meter) so both report the same *realized* cache error: the peak
  error accumulated between consecutive full forwards — the quantity
  ``max_error`` bounds.  Guarded: some eb point must skip MORE than a
  scheduled point at equal-or-lower realized error, and every eb
  point's realized error must respect its budget.  (Final-output
  ``rel_err`` vs the uncached baseline is recorded for context.)
* **Shed** — the same overload burst served twice through the engine:
  with shedding off, every request keeps its tight budget; with
  shedding on, requests submitted while the queue is >= ``shed_depth``
  deep have their budget relaxed by ``shed_factor`` (snapped to a
  looser tier) — quality is shed, requests never are.  Guarded:
  >= 1.1x req/s, zero drops, p95 realized error within the shed tier,
  zero steady-state recompiles (both tier ladders warmed).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmarks import common as B
from repro.core.policies import (FreqCaErrorBudgetPolicy, FreqCaPolicy,
                                 NoCachePolicy)
from repro.diffusion import sampler, schedule
from repro.serving import metrics as metrics_lib
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.models import dit

BUDGETS = (0.05, 0.2, 0.5)
INTERVALS = (2, 3, 5)
AMP = 0.8


@dataclasses.dataclass(frozen=True)
class _SchedMeasured(FreqCaErrorBudgetPolicy):
    """Measurement instrument: interval-scheduled activation with the
    eb error meter still attached, so scheduled freqca reports the
    same realized-cache-error metric as the budgeted policy."""
    name = "freqca_sched_measured"

    def decide(self, state, ctx):
        warm = state.n_valid < self.needed_history + 1
        act = warm | ((ctx.step_idx % self.interval) == 0)
        rate = state.rate_low + state.rate_high
        acc = jnp.where(act, 0.0, state.acc + rate)
        return state._replace(acc=acc,
                              peak=jnp.maximum(state.peak, acc)), act


def _stiff_fns(cfg, n_steps):
    full_fn, from_crf_fn = dit.denoiser(cfg)
    freq = 0.5 * n_steps          # ~0.5 rad per step at any n_steps

    def stiff_full(params, x, t, cond=()):
        _, crf = full_fn(params, x, jnp.full((), 0.5), cond)
        # amplitude decays with t^2: early trajectory stiff, tail calm
        crf = crf * (1.0 + AMP * t * t * jnp.sin(freq * t))
        return from_crf_fn(params, crf, t, cond), crf

    return stiff_full, from_crf_fn


def _pareto_rows(cfg, full_fn, from_crf_fn, params, n_steps):
    n_tok = (B.IMG_SIZE // cfg.patch_size) ** 2
    x0 = jax.random.normal(jax.random.key(0),
                           (B.BATCH, B.IMG_SIZE, B.IMG_SIZE,
                            cfg.in_channels))
    ts = schedule.timesteps(n_steps)
    crf_shape = (B.BATCH, n_tok, cfg.d_model)

    def run_pol(pol):
        fn = jax.jit(lambda p, x: sampler.sample(
            full_fn, from_crf_fn, p, x, ts, pol, crf_shape=crf_shape))
        res = fn(params, x0)
        res.x.block_until_ready()
        return res

    def row(method, res):
        fulls = [int(v) for v in res.n_full_lanes]
        mean_full = sum(fulls) / len(fulls)
        return {
            "section": "pareto", "method": method,
            "n_full": round(mean_full, 2),
            "skips": round(n_steps - mean_full, 2),
            "realized": round(float(jnp.max(res.feedback.realized)), 4),
            "budget_events": int(jnp.sum(res.feedback.events)),
            "rel_err": round(float(
                jnp.linalg.norm(res.x - ref.x)
                / jnp.linalg.norm(ref.x)), 5),
        }

    ref = run_pol(NoCachePolicy())
    rows = []
    for interval in INTERVALS:
        res = run_pol(_SchedMeasured(interval=interval, method="dct",
                                     rho=0.25))
        rows.append(row(f"freqca(N={interval})", res))
    for budget in BUDGETS:
        pol = FreqCaErrorBudgetPolicy(method="dct",
                                      rho=0.25).with_budget(budget)
        res = run_pol(pol)
        r = row(f"freqca_eb(b={pol.budget})", res)
        # the budget is an SLO: realized cache error never exceeds it
        assert r["realized"] <= pol.budget + 1e-6, r
        rows.append(r)
    # the Pareto claim: feedback-placed fulls buy more skips per unit
    # of realized cache error than any fixed cadence
    sched = [r for r in rows if not r["method"].startswith("freqca_eb")]
    ebs = [r for r in rows if r["method"].startswith("freqca_eb")]
    wins = [(e["method"], s["method"]) for e in ebs for s in sched
            if e["realized"] <= s["realized"] + 1e-6
            and e["skips"] > s["skips"]]
    assert wins, rows
    for r in rows:
        r["pareto_wins"] = len(wins) if r is rows[-1] else None
    return rows, wins


def _shed_rows(cfg, full_fn, from_crf_fn, params, n_steps, n_requests,
               max_batch, tight, shed_factor, shed_depth):
    n_tok = (B.IMG_SIZE // cfg.patch_size) ** 2
    tight_pol = FreqCaErrorBudgetPolicy(
        method="dct", rho=0.25).with_budget(tight)
    shed_pol = tight_pol.with_budget(tight * shed_factor)
    assert shed_pol.budget > tight_pol.budget
    rows = []
    for name, depth in [("no_shed", None), ("shed", shed_depth)]:
        eng = DiffusionEngine(
            full_fn, from_crf_fn, params,
            (B.IMG_SIZE, B.IMG_SIZE, cfg.in_channels),
            (n_tok, cfg.d_model), tight_pol, n_steps=n_steps,
            max_batch=max_batch, shed_depth=depth,
            shed_factor=shed_factor)
        # both tier ladders warmed: overload serving stays compile-free
        eng.warmup(policies=[shed_pol] if depth is not None else ())
        warm_misses = eng.metrics.compile_misses
        for i in range(n_requests):
            eng.submit(DiffusionRequest(request_id=i, seed=i,
                                        max_error=tight))
        t0 = time.perf_counter()
        outs = eng.serve_until_drained()
        wall = time.perf_counter() - t0
        s = eng.metrics.summary()
        rows.append({
            "section": "shed", "engine": name,
            "submitted": n_requests, "served": len(outs),
            "dropped": n_requests - len(outs),
            "shed_events": s["shed_events"],
            "wall_s": round(wall, 3),
            "req_per_s": round(
                metrics_lib.throughput(eng.metrics, wall), 3),
            "full_step_fraction": s["full_step_fraction"],
            "realized_error_p95": s["realized_error_p95"],
            "budget_events": s["budget_events"],
            "tight_tier": tight_pol.budget,
            "shed_tier": shed_pol.budget,
            "steady_recompiles": s["compile_misses"] - warm_misses,
        })
    base, shed = rows
    shed["rps_vs_no_shed"] = round(
        shed["req_per_s"] / max(base["req_per_s"], 1e-9), 3)
    # shedding relaxes budgets, never drops: every request served, the
    # loosened tier still honored, and >= 1.1x the no-shed throughput
    for r in rows:
        assert r["dropped"] == 0, r
        assert r["steady_recompiles"] == 0, r
    assert base["shed_events"] == 0 and shed["shed_events"] > 0, rows
    assert shed["realized_error_p95"] <= shed_pol.budget + 1e-6, shed
    assert base["realized_error_p95"] <= tight_pol.budget + 1e-6, base
    assert shed["full_step_fraction"] < base["full_step_fraction"], rows
    assert shed["rps_vs_no_shed"] >= 1.1, rows
    return rows


def run(out: str = "results/bench/BENCH_serve_quality.json",
        n_steps: int = 0, n_requests: int = 16, max_batch: int = 4,
        tight: float = 0.05, shed_factor: float = 20.0,
        shed_depth: int = 4,
        title: str = "Quality SLO — error budgets, shedding"):
    n_steps = n_steps or max(B.N_STEPS, 32)
    cfg, params = B.get_model()
    full_fn, from_crf_fn = _stiff_fns(cfg, n_steps)
    pareto, wins = _pareto_rows(cfg, full_fn, from_crf_fn, params, n_steps)
    shed_rows = _shed_rows(cfg, full_fn, from_crf_fn, params, n_steps,
                           n_requests, max_batch, tight, shed_factor,
                           shed_depth)
    B.print_table(title + " (Pareto)", pareto)
    B.print_table(title + " (shedding)", shed_rows)
    rows = pareto + shed_rows
    shed = rows[-1]
    print(f"eb pareto wins vs schedule: {wins}; shedding: "
          f"{shed['rps_vs_no_shed']}x req/s at p95 error "
          f"{shed['realized_error_p95']} <= tier {shed['shed_tier']}, "
          f"0 drops")
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
