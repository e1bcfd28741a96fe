"""Fused FreqCa cached-step kernel.

The cached step is pure memory traffic: read the low band + K high-band
history tensors, combine with K scalar Hermite weights, write ẑ.  A
naive implementation is K+1 separate elementwise kernels (2(K+1) HBM
passes); this kernel does it in ONE pass over [token x d_model] tiles —
4 reads + 1 write for the paper's K=3, putting the cached step at the
memory-roofline minimum (DESIGN.md §3).

The Hermite evaluation weights are computed host-side (they depend only
on the K cached timestamps and the query time — a (m+1)-vector) and
passed as a tiny operand broadcast to every tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hermite


def _fused_kernel(w_ref, low_ref, hist_ref, o_ref):
    """low [bs, bd]; hist [K, bs, bd]; w [K]; o = low + sum_k w_k hist_k."""
    acc = low_ref[...].astype(jnp.float32)
    k = hist_ref.shape[0]
    for i in range(k):                      # K is tiny & static: unrolled FMA
        acc += w_ref[i] * hist_ref[i].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def hermite_eval_weights(ts: jnp.ndarray, t_query, order: int) -> jnp.ndarray:
    """Weights w st. prediction = sum_k w_k · hist_k (least-squares fold).

    Alias of :func:`repro.core.hermite.eval_weights` — the shared
    normal-equation setup lives there so the folded kernel path and the
    explicit fit can never drift apart.
    """
    return hermite.eval_weights(ts, t_query, order)


def freqca_predict_fused(low: jnp.ndarray, high_hist: jnp.ndarray,
                         ts: jnp.ndarray, t_query, order: int,
                         block_s: int = 256, block_d: int = 256,
                         interpret: bool = True) -> jnp.ndarray:
    """ẑ = low + Hermite(high_hist)(t_query), one fused pass.

    low: [B, S, D]; high_hist: [K, B, S, D]; ts: [K].
    """
    w = hermite_eval_weights(ts, t_query, order)
    kh, b, s, d = high_hist.shape
    bs = min(block_s, s)
    bd = min(block_d, d)
    assert s % bs == 0 and d % bd == 0, (s, d, bs, bd)
    grid = (s // bs, d // bd)

    def run_one(low2, hist2):  # [S, D], [K, S, D]
        return pl.pallas_call(
            _fused_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((kh,), lambda i, j: (0,)),
                pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
                pl.BlockSpec((kh, bs, bd), lambda i, j: (0, i, j)),
            ],
            out_specs=pl.BlockSpec((bs, bd), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((s, d), low2.dtype),
            interpret=interpret,
        )(w, low2, hist2)

    return jax.vmap(run_one, in_axes=(0, 1))(low, high_hist)


# ---------------------------------------------------------------------------
# spectral cached step: synthesis matmul fused with the Hermite FMA
# ---------------------------------------------------------------------------

def _fused_spectral_kernel(w_ref, synth_ref, low_ref, hist_ref, o_ref):
    """w [B·K] (SMEM); synth [bs, m]; low [m, bd]; hist [K, bs, bd].

    ẑ tile = synth·low + Σ_k w[b, k]·hist_k — the low band is
    synthesised from its m spectral rows on the MXU inside the same pass
    that FMAs the K high-band history tiles, so the cached step reads
    only K·S·D + m·D + S·m floats from HBM and writes S·D once.  The
    lane's K weights are scalars read from SMEM (scalar prefetch): a
    ``(K,)`` VMEM block per lane is not a tile Mosaic accepts."""
    k = hist_ref.shape[0]
    lane = pl.program_id(0)
    acc = jnp.dot(synth_ref[...].astype(jnp.float32),
                  low_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    for i in range(k):                      # K is tiny & static: unrolled FMA
        acc += w_ref[lane * k + i] * hist_ref[i].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def freqca_predict_fused_spectral(low_spec: jnp.ndarray, synth: jnp.ndarray,
                                  high_hist: jnp.ndarray, w: jnp.ndarray,
                                  block_s: int = 256, block_d: int = 256,
                                  interpret: bool = True) -> jnp.ndarray:
    """ẑ = synthᵀ-reconstructed low band + per-lane Hermite(high), fused.

    low_spec: [B, m, D] spectral low-band coefficients (already combined
    across the low ring — order 0 is just the freshest entry);
    synth: [S, m] synthesis basis (``frequency.low_band_basis(S).T``);
    high_hist: [B, K, S, D]; w: [B, K] per-lane folded Hermite weights
    (lanes activate at different times, so each carries its own fold).
    The grid runs over (lane, S tiles, D tiles).
    """
    b, kh, s, d = high_hist.shape
    bs = min(block_s, s)
    bd = min(block_d, d)
    assert s % bs == 0 and d % bd == 0, (s, d, bs, bd)
    m = synth.shape[1]
    squeezed = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // bs, d // bd),
        in_specs=[
            pl.BlockSpec((bs, m), lambda n, i, j, w_ref: (i, 0)),
            pl.BlockSpec((squeezed, m, bd), lambda n, i, j, w_ref: (n, 0, j)),
            pl.BlockSpec((squeezed, kh, bs, bd),
                         lambda n, i, j, w_ref: (n, 0, i, j)),
        ],
        out_specs=pl.BlockSpec((squeezed, bs, bd),
                               lambda n, i, j, w_ref: (n, i, j)),
    )
    return pl.pallas_call(
        _fused_spectral_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, d), high_hist.dtype),
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(-1), synth, low_spec, high_hist)
