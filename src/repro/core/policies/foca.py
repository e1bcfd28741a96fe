"""FoCa-style forecast-then-calibrate policy (cf. arXiv 2508.16211).

A member of the policy family that needed no change to the sampler.
Forecast = TaylorSeer's Hermite extrapolation of the whole CRF;
calibrate = at every activated step the stale forecast for that step
is scored against the fresh CRF and a per-lane scalar gain
``γ = ⟨forecast, crf⟩ / ||forecast||²`` (clipped to
``[1/calib_clip, calib_clip]``) is refit, then applied to subsequent
cached-step forecasts.  A drifting forecast is pulled back toward the
observed trajectory instead of being replayed verbatim.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.policies import base


class FoCaState(NamedTuple):
    hist: base.Ring                # [B, K, *feat]
    n_valid: jnp.ndarray           # [B] int32
    gain: jnp.ndarray              # [B] f32 calibration gain


@dataclasses.dataclass(frozen=True)
class FoCaPolicy(base.Policy):
    name = "foca"

    high_order: int = 2
    calib_clip: float = 2.0        # gain clipped to [1/clip, clip]

    @property
    def k_high(self) -> int:
        return self.high_order + 1

    @property
    def needed_history(self) -> int:
        return self.k_high

    @property
    def cache_units(self) -> int:
        return self.k_high

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        return FoCaState(
            hist=base.ring_init(batch, self.k_high, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32),
            gain=jnp.ones((batch,), jnp.float32))

    def update(self, state, crf, ctx):
        pred = base.ring_predict(state.hist, ctx.t_now, self.high_order)
        axes = tuple(range(1, crf.ndim))
        p = pred.astype(jnp.float32)
        c = crf.astype(jnp.float32)
        g = (jnp.sum(p * c, axis=axes)
             / (jnp.sum(p * p, axis=axes) + 1e-6))
        g = jnp.clip(g, 1.0 / self.calib_clip, self.calib_clip)
        # only calibrate once the ring is full — earlier forecasts are fit
        # on zero-padded history and would poison the gain
        gain = jnp.where(state.n_valid >= self.needed_history, g, 1.0)
        return FoCaState(
            hist=base.ring_push(state.hist, crf, ctx.t_now),
            n_valid=state.n_valid + 1,
            gain=gain)

    def predict(self, state, ctx):
        pred = base.ring_predict(state.hist, ctx.t_now, self.high_order)
        g = state.gain.reshape(state.gain.shape + (1,) * (pred.ndim - 1))
        return (g * pred.astype(jnp.float32)).astype(pred.dtype)
