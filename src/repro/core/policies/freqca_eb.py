"""FreqCa-EB (beyond paper): error-budgeted, feedback-driven activation.

FreqCa's spectral split makes per-band prediction error cheap to
measure: on every full step the low ring already holds the coefficients
the lane would have served, so scoring them against the fresh
``_split`` output costs one subtraction in the spectral basis — the
low band is never synthesized back to the spatial domain.  Following
SpectralCache's error-bounded activation (arXiv 2603.05315) and
error-feedback event-driven caching (arXiv 2604.22901), the measured
per-band error rate is carried forward as policy state and *spent*
against a budget:

* each cached step spends ``rate = rate_low + rate_high`` from the
  accumulator (``acc``) — the projected error the lane commits by
  serving the prediction;
* a full forward fires as an **event** exactly when the next cached
  step would overspend (``acc + rate > budget``), resetting ``acc``;
* the full step re-measures both band rates (``observe``), closing the
  feedback loop.

The budget is a per-request quality SLO: ``with_budget(max_error)``
snaps the request's ``max_error`` down to a tier from ``ERROR_TIERS``
so jit signatures and scheduler compatibility groups stay bounded —
the tier is a dataclass field, so it folds into ``compatibility_key``
(adaptive policies key on their full value) automatically.

By construction the accumulated error between two consecutive full
forwards never exceeds the budget, and the peak accumulator value is
reported per lane through ``error_feedback`` as the realized SLO.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core.policies import base
from repro.core.policies.freqca import FreqCaPolicy

# Budget quantization ladder: requested max_error snaps DOWN to the
# nearest tier (never promising less quality than asked), so at most
# len(ERROR_TIERS) compiled signatures / compatibility groups exist.
ERROR_TIERS: Tuple[float, ...] = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)


def budget_tier(max_error: float) -> float:
    """Largest tier <= max_error (strictest tier when below them all)."""
    eligible = [t for t in ERROR_TIERS if t <= max_error + 1e-12]
    return eligible[-1] if eligible else ERROR_TIERS[0]


class FreqCaEbState(NamedTuple):
    low: base.Ring                 # [B, K_low,  *feat|m] SPECTRAL low band
    high: base.Ring                # [B, K_high, *feat] spatial high band
    n_valid: jnp.ndarray           # [B] int32 — activated steps per lane
    rate_low: jnp.ndarray          # [B] f32 — low-band error rate
    rate_high: jnp.ndarray         # [B] f32 — high-band error rate
    acc: jnp.ndarray               # [B] f32 — error spent since last full
    peak: jnp.ndarray              # [B] f32 — max inter-full spend (SLO)
    events: jnp.ndarray            # [B] int32 — budget-triggered fulls


@dataclasses.dataclass(frozen=True)
class FreqCaErrorBudgetPolicy(FreqCaPolicy):
    name = "freqca_eb"
    per_lane = True
    uses_error_feedback = True

    budget: float = 0.1            # max error accumulated between fulls

    def with_budget(self, max_error: Optional[float]) -> "FreqCaPolicy":
        if max_error is None:
            return self
        return dataclasses.replace(self, budget=budget_tier(max_error))

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        zf = jnp.zeros((batch,), jnp.float32)
        return FreqCaEbState(
            low=base.ring_init(batch, self.k_low,
                               self.low_feat_shape(feat_shape), crf_dtype),
            high=base.ring_init(batch, self.k_high, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32),
            rate_low=zf, rate_high=zf, acc=zf, peak=zf,
            events=jnp.zeros((batch,), jnp.int32))

    def decide(self, state, ctx):
        # +1: one calibration full past the predictor's warm-up, so the
        # first adaptive skip is backed by a trusted measurement (the
        # rings only hold needed_history entries at the last warm-up
        # full, making that step's measurement meaningless)
        warm = state.n_valid < self.needed_history + 1
        rate = state.rate_low + state.rate_high
        spend = state.acc + rate
        act = warm | (spend > self.budget)
        # the sampler commits to this mask, so the budget bookkeeping
        # lands here: a cached lane spends (carry-over), an activated
        # lane resets its accumulator (reset on full step)
        acc = jnp.where(act, 0.0, spend)
        return state._replace(
            acc=acc,
            peak=jnp.maximum(state.peak, acc),
            events=state.events + (act & ~warm).astype(jnp.int32)), act

    def measure_error(self, state, crf, ctx):
        """Per-band prediction error vs the fresh CRF -> [B, 2] f32.

        Both bands are scored where they live: the low ring entry
        directly against the fresh spectral coefficients (the basis is
        orthonormal, so spectral L2 == spatial L2 — no synthesis), the
        high Hermite forecast against the fresh spatial high band.
        Each band is normalized by the *whole*-feature norm so the two
        rates add up to a bound on the full relative error.
        """
        low_spec, high = self._split(crf)
        low_pred = self._low_coeffs(state, ctx)
        high_pred = (base.ring_last(state.high) if self.high_order == 0
                     else base.ring_predict(state.high, ctx.t_now,
                                            self.high_order))

        def _sq(x):
            x = x.astype(jnp.float32)
            return jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))

        den = jnp.sqrt(jnp.maximum(_sq(low_spec) + _sq(high), 1e-12))
        e_low = jnp.sqrt(_sq(low_pred - low_spec)) / den
        e_high = jnp.sqrt(_sq(high_pred - high)) / den
        # warm lanes predict from underfilled rings — not a measurement
        valid = (state.n_valid >= self.needed_history).astype(jnp.float32)
        return jnp.stack([e_low * valid, e_high * valid], axis=-1)

    def observe(self, state, realized_error, ctx):
        return state._replace(rate_low=realized_error[:, 0],
                              rate_high=realized_error[:, 1])

    def error_feedback(self, state):
        return base.ErrorFeedback(realized=state.peak, events=state.events)
