"""FreqCa-A (beyond paper): FreqCa predictor + self-calibrated adaptive
schedule, per lane.

At every activated step the cache already contains what FreqCa *would
have predicted* for that step, so its relative error against the fresh
CRF is free to measure.  A lane then skips while the projected error of
the next cached step — ``(steps_since_full + 1) · err_last`` — stays
under ``tea_threshold``.  The skip counter and last-error scalar are
policy state (per lane), and the warm-up length is derived from the
predictor's ``needed_history`` instead of a hard-coded constant, so
non-default ``high_order`` never samples from an underfilled ring.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.policies import base
from repro.core.policies.freqca import FreqCaPolicy


class FreqCaAState(NamedTuple):
    low: base.Ring                 # [B, K_low,  *feat|m] SPECTRAL low band
    high: base.Ring                # [B, K_high, *feat]
    n_valid: jnp.ndarray           # [B] int32
    since: jnp.ndarray             # [B] int32 — steps since last full
    err_last: jnp.ndarray          # [B] f32 — last measured pred error


@dataclasses.dataclass(frozen=True)
class FreqCaAdaptivePolicy(FreqCaPolicy):
    name = "freqca_a"
    per_lane = True

    tea_threshold: float = 0.15

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        return FreqCaAState(
            low=base.ring_init(batch, self.k_low,
                               self.low_feat_shape(feat_shape), crf_dtype),
            high=base.ring_init(batch, self.k_high, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32),
            since=jnp.zeros((batch,), jnp.int32),
            err_last=jnp.zeros((batch,), jnp.float32))

    def decide(self, state, ctx):
        warm = state.n_valid < self.needed_history
        projected = (state.since.astype(jnp.float32) + 1.0) * state.err_last
        act = warm | (projected > self.tea_threshold)
        # the sampler commits to this mask, so the skip counter resets
        # here; update() below only runs on the activated lanes
        return state._replace(
            since=jnp.where(act, 0, state.since + 1)), act

    def update(self, state, crf, ctx):
        # score the prediction FreqCa would have made for THIS step
        # against the fresh CRF (self-calibration, free at full steps)
        err = base.lane_rel_norm(self.predict(state, ctx), crf)
        low_spec, high = self._split(crf)
        return state._replace(
            low=base.ring_push(state.low, low_spec, ctx.t_now),
            high=base.ring_push(state.high, high, ctx.t_now),
            n_valid=state.n_valid + 1,
            err_last=err)
