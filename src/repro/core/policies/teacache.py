"""TeaCache-style adaptive reuse, now with per-lane activation.

Each lane accumulates the relative change of *its own* model input
``x_t`` between steps and triggers a full forward when the accumulator
crosses ``tea_threshold`` (the interval schedule is ignored);
prediction = reuse, like FORA.  The accumulator and previous-input
carries — sampler-resident state before the policy-object redesign —
live in the policy state, and every lane resets independently, so mixed
workloads sharing a batch no longer couple their activation decisions.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.policies import base


class TeaCacheState(NamedTuple):
    hist: base.Ring                # [B, 1, *feat] last full CRF
    n_valid: jnp.ndarray           # [B] int32
    acc: jnp.ndarray               # [B] f32 accumulated relative change
    prev_x: jnp.ndarray            # [B, *latent] previous model input


@dataclasses.dataclass(frozen=True)
class TeaCachePolicy(base.Policy):
    name = "teacache"
    per_lane = True

    tea_threshold: float = 0.15

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, latent_shape: Tuple[int, ...] = (),
             latent_dtype=jnp.float32):
        return TeaCacheState(
            hist=base.ring_init(batch, 1, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32),
            acc=jnp.zeros((batch,), jnp.float32),
            prev_x=jnp.zeros((batch,) + tuple(latent_shape), latent_dtype))

    def decide(self, state, ctx):
        rel = base.lane_mean_abs(ctx.x - state.prev_x) / jnp.maximum(
            base.lane_mean_abs(state.prev_x), 1e-6)
        acc = state.acc + rel
        act = ((state.n_valid < 1) | (acc > self.tea_threshold)
               | (ctx.step_idx == 0))
        return state._replace(
            acc=jnp.where(act, 0.0, acc),
            prev_x=ctx.x.astype(state.prev_x.dtype)), act

    def update(self, state, crf, ctx):
        return state._replace(
            hist=base.ring_push(state.hist, crf, ctx.t_now),
            n_valid=state.n_valid + 1)

    def predict(self, state, ctx):
        return base.ring_last(state.hist)
