"""FreqCa (the paper's policy): frequency-split CRF caching with a
**spectral** low-band ring.

The cached Cumulative Residual Feature is decomposed into a low band —
held as ``m = spectral_kept_bins(S, rho, method)`` frequency-domain
coefficient rows, ~``rho`` of the spatial footprint (SpectralCache,
arXiv 2603.05315) — and a spatial high band forecast with an
order-``high_order`` Hermite fit over the ``k_high`` most recent
activated steps (paper §3.2, eq. 1).

Both halves of the cache datapath go through the kernel dispatch layer
(``repro.kernels.ops``): ``update`` is one fused analysis pass emitting
``(low_spec, high)`` without ever materialising the spatial low band,
and ``predict`` fuses the ``[S, m]`` synthesis matmul with the K-entry
Hermite FMA (folded per-lane weights) — on the Pallas backend the
cached step is a single pass over HBM.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.core import frequency
from repro.core.policies import base
from repro.kernels import ops


class FreqCaState(NamedTuple):
    low: base.Ring                 # [B, K_low,  *feat|m] SPECTRAL low band
    high: base.Ring                # [B, K_high, *feat] spatial high band
    n_valid: jnp.ndarray           # [B] int32 — activated steps per lane


@dataclasses.dataclass(frozen=True)
class FreqCaPolicy(base.Policy):
    name = "freqca"

    method: str = "dct"            # fft | dct | none
    rho: float = 0.0625            # low-frequency fraction of the spectrum
    low_order: int = 0             # 0 = direct reuse (paper default)
    high_order: int = 2            # Hermite order for the high band
    token_axis: int = 1            # token axis of the per-lane [B, S, D] CRF

    @property
    def k_low(self) -> int:
        return self.low_order + 1

    @property
    def k_high(self) -> int:
        return self.high_order + 1

    @property
    def needed_history(self) -> int:
        return max(self.k_low, self.k_high)

    @property
    def cache_units(self) -> int:
        """Paper §4.4.1 feature-tensor accounting (the spectral low ring
        actually occupies ~``rho`` of its unit — see ``state_bytes``)."""
        return self.k_low + self.k_high

    # --- spectral layout --------------------------------------------------
    def spectral_bins(self, s: int) -> int:
        return frequency.spectral_kept_bins(s, self.rho, self.method)

    def low_feat_shape(self, feat_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        """Per-lane low-ring shape: the token axis shrinks S -> m."""
        ax = self.token_axis - 1
        s = feat_shape[ax]
        return feat_shape[:ax] + (self.spectral_bins(s),) + feat_shape[
            ax + 1:]

    def _fusable(self, feat_shape: Tuple[int, ...]) -> bool:
        # the fused kernels take the [B, S, D] token-major layout
        return len(feat_shape) == 2 and self.token_axis == 1

    def _split(self, crf: jnp.ndarray):
        """CRF -> (low_spec, high) through the dispatch layer."""
        if self._fusable(crf.shape[1:]):
            return ops.band_split_spectral(crf, self.rho, self.method)
        x = jnp.moveaxis(crf, self.token_axis, -2).astype(jnp.float32)
        basis = frequency.low_band_basis(x.shape[-2], self.rho, self.method)
        low_spec = jnp.einsum("ms,...sd->...md", basis, x)
        high = x - jnp.einsum("ms,...md->...sd", basis, low_spec)
        return (jnp.moveaxis(low_spec, -2, self.token_axis).astype(crf.dtype),
                jnp.moveaxis(high, -2, self.token_axis).astype(crf.dtype))

    def _synthesize(self, low_spec: jnp.ndarray, s: int) -> jnp.ndarray:
        """Spectral low ring entry -> spatial low band (Bᵀ·coeffs)."""
        basis = frequency.low_band_basis(s, self.rho, self.method)
        x = jnp.moveaxis(low_spec, self.token_axis, -2).astype(jnp.float32)
        low = jnp.einsum("ms,...md->...sd", basis, x)
        return jnp.moveaxis(low, -2, self.token_axis).astype(low_spec.dtype)

    # --- protocol ---------------------------------------------------------
    def init(self, batch: int, feat_shape: Tuple[int, ...],
             crf_dtype=jnp.float32, **_):
        return FreqCaState(
            low=base.ring_init(batch, self.k_low,
                               self.low_feat_shape(feat_shape), crf_dtype),
            high=base.ring_init(batch, self.k_high, feat_shape, crf_dtype),
            n_valid=jnp.zeros((batch,), jnp.int32))

    def update(self, state, crf, ctx):
        low_spec, high = self._split(crf)
        return state._replace(
            low=base.ring_push(state.low, low_spec, ctx.t_now),
            high=base.ring_push(state.high, high, ctx.t_now),
            n_valid=state.n_valid + 1)

    def _low_coeffs(self, state, ctx):
        return (base.ring_last(state.low) if self.low_order == 0 else
                base.ring_predict(state.low, ctx.t_now, self.low_order))

    def predict(self, state, ctx):
        s = ctx.feat_shape[self.token_axis - 1]
        low_spec = self._low_coeffs(state, ctx)
        if (ops.use_pallas() and self.high_order > 0
                and self._fusable(ctx.feat_shape)):
            # one fused pass: synthesis matmul + K-entry Hermite FMA,
            # consuming the high ring in slot order (the K folded
            # weights are permuted instead of the K feature tensors)
            synth = frequency.low_band_basis(s, self.rho, self.method).T
            w = base.ring_slot_weights(state.high, ctx.t_now,
                                       self.high_order)
            return ops.freqca_predict_spectral(low_spec, synth,
                                               state.high.vals, w)
        low = self._synthesize(low_spec, s)
        high = (base.ring_last(state.high) if self.high_order == 0 else
                base.ring_predict(state.high, ctx.t_now, self.high_order))
        return low + high
