"""Reference feature-cache state machines: the tests' oracle for the
policy objects in ``repro.core.policies``, not a user API.

The served path drives policy objects (per-lane activation masks,
policy-owned adaptive state).  The functions below are the plain
spatial-cache reference the objects are pinned against: golden sampler
equivalence, the layer-wise vs CRF ablation and cache-byte accounting.
Each takes a policy object, branches on its class-level ``name`` and
reads only the dataclass fields that kind has (``interval``,
``method``, ``rho``, ``low_order``, ``high_order``, ``token_axis``,
``tea_threshold``); it calls none of the object's methods or
properties, so it stays independent of the code under test.

Kinds (``policy.name``):
  freqca      — paper: low band reused (order ``low_order``, default 0),
                high band Hermite-predicted (order ``high_order``, default
                2), bands split by ``method`` (fft | dct) at fraction
                ``rho``.  Cache = (low_order+1) + (high_order+1) feature
                tensors — O(1) in depth (CRF caching).
  taylorseer  — whole-feature polynomial forecast of order ``high_order``
                (no decomposition) == the paper's main forecast baseline.
  fora        — whole-feature reuse (order 0) == the paper's main reuse
                baseline.
  teacache    — reuse like FORA; the activation rule (accumulated
                relative change of x_t against ``tea_threshold``) lives
                in the golden sampler of the tests.
  foca        — forecast-then-calibrate: here the uncalibrated
                taylorseer forecast (the object adds a per-lane gain).
  freqca_a    — adaptive FreqCa: bands and predictors identical to
                freqca; its error-budget rule lives in the golden
                sampler of the tests.
  none        — never cache (ground truth / baseline latency).

``should_activate`` implements the paper's schedule: a full forward every
``interval`` steps, plus a warm-up of full steps until the history is
populated.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import frequency, hermite
from repro.core.policies.base import Policy

_REUSE = ("fora", "teacache")
_FORECAST = ("taylorseer", "foca")
_FREQCA = ("freqca", "freqca_a")


def _k_low(policy: Policy) -> int:
    return policy.low_order + 1


def _k_high(policy: Policy) -> int:
    return policy.high_order + 1


class CacheState(NamedTuple):
    low_hist: jnp.ndarray     # [K_low,  *feat] spatial-domain low band
    high_hist: jnp.ndarray    # [K_high, *feat] spatial-domain high band
    ts_low: jnp.ndarray       # [K_low]
    ts_high: jnp.ndarray      # [K_high]
    n_valid: jnp.ndarray      # [] int32 — activated steps seen so far


def init_state(policy: Policy, feat_shape: Tuple[int, ...],
               dtype=jnp.float32) -> CacheState:
    if policy.name in _FREQCA:
        kl, kh = _k_low(policy), _k_high(policy)
    elif policy.name in _FORECAST:
        kl, kh = 1, _k_high(policy)   # unused low slot kept tiny-but-static
    else:                             # reuse kinds and none
        kl, kh = 1, 1
    return CacheState(
        low_hist=jnp.zeros((kl,) + tuple(feat_shape), dtype),
        high_hist=jnp.zeros((kh,) + tuple(feat_shape), dtype),
        ts_low=jnp.full((kl,), -1.0, jnp.float32),
        ts_high=jnp.full((kh,), -1.0, jnp.float32),
        n_valid=jnp.zeros((), jnp.int32),
    )


def _needed_history(policy: Policy) -> int:
    if policy.name in _FORECAST:
        return _k_high(policy)
    if policy.name in _FREQCA:
        return max(_k_low(policy), _k_high(policy))
    return 1


def should_activate(policy: Policy, state: CacheState,
                    step_idx: jnp.ndarray) -> jnp.ndarray:
    if policy.name == "none":
        return jnp.asarray(True)
    scheduled = (step_idx % policy.interval) == 0
    warmup = state.n_valid < _needed_history(policy)
    return scheduled | warmup


def _push(hist, ts, value, t):
    hist = jnp.roll(hist, -1, axis=0).at[-1].set(value.astype(hist.dtype))
    ts = jnp.roll(ts, -1).at[-1].set(jnp.asarray(t, jnp.float32))
    return hist, ts


def update(policy: Policy, state: CacheState, z: jnp.ndarray,
           t) -> CacheState:
    """Push the freshly computed CRF ``z`` (activated step at time t)."""
    if policy.name == "none":
        return state
    if policy.name in _REUSE + _FORECAST:
        low, high = jnp.zeros_like(z), z
    else:  # freqca / freqca_a
        bands = frequency.decompose(z, policy.rho, policy.method,
                                    axis=policy.token_axis)
        low, high = bands.low, bands.high
    low_hist, ts_low = _push(state.low_hist, state.ts_low, low, t)
    high_hist, ts_high = _push(state.high_hist, state.ts_high, high, t)
    return CacheState(low_hist=low_hist, high_hist=high_hist,
                      ts_low=ts_low, ts_high=ts_high,
                      n_valid=state.n_valid + 1)


def predict(policy: Policy, state: CacheState, t) -> jnp.ndarray:
    """Reconstruct ẑ_t from the cache (cached step at time t)."""
    if policy.name in _REUSE:
        return state.high_hist[-1]
    if policy.name in _FORECAST:
        # no per-lane gain state: foca is the uncalibrated forecast here
        return hermite.predict(state.ts_high, state.high_hist, t,
                               policy.high_order)
    assert policy.name in _FREQCA, policy.name
    if policy.low_order == 0:
        low = state.low_hist[-1]
    else:
        low = hermite.predict(state.ts_low, state.low_hist, t,
                              policy.low_order)
    if policy.high_order == 0:
        high = state.high_hist[-1]
    else:
        high = hermite.predict(state.ts_high, state.high_hist, t,
                               policy.high_order)
    return low + high


def cache_bytes(state: CacheState, policy: Policy = None) -> int:
    """Bytes the policy actually caches.

    ``init_state`` keeps a tiny-but-static dummy ``low_hist`` slot for
    the kinds that never decompose (``update`` pushes zeros into it), so
    a plain pytree sum over-reports those policies.  Pass ``policy`` to
    exclude the dummy slots (the paper's Table-5 memory accounting);
    without it the raw pytree size is returned (allocation footprint).
    """
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    if policy is None:
        return total
    if policy.name == "none":
        return 0
    if policy.name in _REUSE + _FORECAST:
        return total - (state.low_hist.size * state.low_hist.dtype.itemsize
                        + state.ts_low.size * state.ts_low.dtype.itemsize)
    return total


# ---------------------------------------------------------------------------
# layer-wise variant (paper Fig. 4 / Table 5 ablation)
# ---------------------------------------------------------------------------

class LayerwiseState(NamedTuple):
    """Caches every layer's residual delta — the O(L) baseline."""
    hist: jnp.ndarray        # [K, L, *feat]
    ts: jnp.ndarray          # [K]
    n_valid: jnp.ndarray


def layerwise_init(policy: Policy, n_layers: int,
                   feat_shape: Tuple[int, ...], dtype=jnp.float32):
    k = _k_high(policy)
    return LayerwiseState(
        hist=jnp.zeros((k, n_layers) + tuple(feat_shape), dtype),
        ts=jnp.full((k,), -1.0, jnp.float32),
        n_valid=jnp.zeros((), jnp.int32),
    )


def layerwise_update(policy: Policy, state: LayerwiseState,
                     residuals: jnp.ndarray, t) -> LayerwiseState:
    hist, ts = _push(state.hist, state.ts, residuals, t)
    return LayerwiseState(hist=hist, ts=ts, n_valid=state.n_valid + 1)


def layerwise_predict(policy: Policy, state: LayerwiseState, t,
                      h0: jnp.ndarray) -> jnp.ndarray:
    """Predict each layer residual, reconstruct CRF = h0 + sum_l F̂^l."""
    res = hermite.predict(state.ts, state.hist, t, policy.high_order)
    return h0 + jnp.sum(res, axis=0)
