"""FORA baseline: whole-feature reuse (order-0 cache).

The paper's main reuse baseline — cached steps replay the CRF of the
most recent activated step unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp

from repro.core.policies import base
from repro.core.policies.taylorseer import ForecastState


@dataclasses.dataclass(frozen=True)
class ForaPolicy(base.Policy):
    name = "fora"

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        return ForecastState(
            hist=base.ring_init(batch, 1, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32))

    def update(self, state, crf, ctx):
        return ForecastState(
            hist=base.ring_push(state.hist, crf, ctx.t_now),
            n_valid=state.n_valid + 1)

    def predict(self, state, ctx):
        return base.ring_last(state.hist)
