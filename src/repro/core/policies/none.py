"""No caching: every lane activates every step (ground truth / baseline
latency).  The prediction path is never *used*, but it is still traced
inside mixed batches, so ``predict`` returns well-formed zeros.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.policies import base


class NoCacheState(NamedTuple):
    n_valid: jnp.ndarray           # [B] int32


@dataclasses.dataclass(frozen=True)
class NoCachePolicy(base.Policy):
    name = "none"

    @property
    def cache_units(self) -> int:
        return 0

    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        return NoCacheState(n_valid=jnp.zeros((batch,), jnp.int32))

    def decide(self, state, ctx):
        return state, jnp.ones((ctx.batch,), bool)

    def update(self, state, crf, ctx):
        return NoCacheState(n_valid=state.n_valid + 1)

    def predict(self, state, ctx):
        return jnp.zeros((ctx.batch,) + tuple(ctx.feat_shape),
                         ctx.crf_dtype)
