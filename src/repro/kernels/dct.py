"""Tiled token-axis basis matmul — DCT-II / IDCT / fused band-split on MXU.

GPU FreqCa calls cuFFT; TPUs have no FFT unit but a DCT-II along the
token axis is ``Y = C @ X`` with a fixed S x S basis — a dense matmul
that maps straight onto the 128x128 MXU (DESIGN.md §3).  Because
FreqCa's low-pass path is ``low = C^T · diag(mask) · C · x``, the whole
band-split collapses into ONE basis matmul with the precomputed
projection matrix ``L = C^T diag(m) C`` — ``band_split_basis`` below.

Kernel: classic 3-loop tiled matmul, K innermost in the grid with
accumulation in the output tile (revisited across the K grid dim), all
tiles MXU-aligned (multiples of 128 for real shapes; smaller shapes run
single-tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from repro.core import frequency


def _matmul_kernel(basis_ref, x_ref, o_ref):
    """Grid (i over S-tiles, j over D-tiles, k over K-tiles); K innermost."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        basis_ref[...], x_ref[...],
        preferred_element_type=o_ref.dtype)


def token_basis_matmul(basis: jnp.ndarray, x: jnp.ndarray,
                       block_s: int = 128, block_d: int = 128,
                       block_k: int = 128, interpret: bool = True):
    """y[..., s, d] = sum_k basis[s, k] * x[..., k, d].

    basis: [S, S]; x: [B, S, D].  Tiles are VMEM-resident:
    (block_s x block_k) basis + (block_k x block_d) x + accumulator.
    """
    b, s, d = x.shape
    bs = min(block_s, s)
    bd = min(block_d, d)
    bk = min(block_k, s)
    assert s % bs == 0 and d % bd == 0 and s % bk == 0, (s, d, bs, bd, bk)
    grid = (s // bs, d // bd, s // bk)

    def run_one(x2):  # [S, D]
        return pl.pallas_call(
            _matmul_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bs, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bd), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bs, bd), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
            interpret=interpret,
        )(basis.astype(jnp.float32), x2.astype(jnp.float32))

    y = jax.vmap(run_one)(x)
    return y.astype(x.dtype)


@functools.lru_cache(maxsize=16)
def _band_split_basis_np(s: int, rho: float, method: str):
    """Low-pass projection L = C^T diag(mask) C (idempotent, symmetric).

    The kept bins come from ``frequency.low_pass_mask_np`` — the single
    source of the band-width rounding rule."""
    if method == "dct":
        c = frequency._dct_basis_np(s)
        mask = frequency.low_pass_mask_np(s, rho, "dct")
        return (c.T * mask.astype(np.float64)) @ c
    # fft: real low-pass projection is circulant; build from the mask
    mask = frequency.low_pass_mask_np(s, rho, "fft")
    f = np.fft.fft(np.eye(s), axis=0)
    finv = np.fft.ifft(np.diag(mask.astype(np.float64)) @ f, axis=0)
    return np.real(finv)


def band_split_basis(s: int, rho: float, method: str = "dct",
                     dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(_band_split_basis_np(s, rho, method), dtype)


def band_split_dispatch_ok(s: int, d: int, block: int = 128) -> bool:
    """Shapes ``token_basis_matmul``'s default tiling accepts — keep in
    sync with its ``block_*=128`` defaults (it asserts divisibility at
    trace time, so dispatch layers must pre-check here)."""
    return s % min(block, s) == 0 and d % min(block, d) == 0


def spectral_dispatch_ok(s: int, d: int, block: int = 256) -> bool:
    """Shapes the spectral kernels' default tiling accepts
    (``band_split_spectral`` and
    ``freqca_fused.freqca_predict_fused_spectral`` block_s/block_d are
    all 256)."""
    return d % min(block, d) == 0 and s % min(block, s) == 0


def band_split(x: jnp.ndarray, rho: float, method: str = "dct",
               interpret: bool = True):
    """FreqCa band split as a single tiled matmul: returns (low, high)."""
    s = x.shape[-2]
    basis = band_split_basis(s, rho, method)
    low = token_basis_matmul(basis, x, interpret=interpret)
    return low, x - low


# ---------------------------------------------------------------------------
# spectral band split: (low coefficients, spatial high), token axis tiled
# ---------------------------------------------------------------------------

def _spectral_analysis_kernel(basis_ref, x_ref, low_ref, acc_ref):
    """basis [m, bs]; x [bs, bd] -> low [m, bd] = Σ over S tiles of B·x.

    The S tiles are the innermost grid axis: the f32 accumulator stays
    in VMEM scratch and the output tile is written on the last one."""
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(basis_ref[...].astype(jnp.float32),
                            x_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(si == pl.num_programs(2) - 1)
    def _store():
        low_ref[...] = acc_ref[...]


def _spectral_residual_kernel(synth_ref, low_ref, x_ref, high_ref):
    """synth [bs, m]; low [m, bd]; x [bs, bd] -> high = x − Bᵀ·low."""
    recon = jnp.dot(synth_ref[...].astype(jnp.float32), low_ref[...],
                    preferred_element_type=jnp.float32)
    high_ref[...] = (x_ref[...].astype(jnp.float32)
                     - recon).astype(high_ref.dtype)


def band_split_spectral(x: jnp.ndarray, rho: float, method: str = "dct",
                        block_s: int = 256, block_d: int = 256,
                        interpret: bool = True):
    """Fused spectral band split: ``(low_spec [B, m, D], high [B, S, D])``.

    ``m = frequency.spectral_kept_bins(S, rho, method)`` — the low band
    lives in the frequency domain at a ``rho`` fraction of the spatial
    footprint (the SpectralCache representation).  Two passes, both
    tiled over the token axis so VMEM holds ``block_s`` tokens at a
    time whatever S is (a whole-token-axis tile ran out of v5e VMEM at
    S=4096, D=3072): the analysis pass accumulates ``B·x`` over S tiles
    into an f32 ``[m, block_d]`` tile, and the residual pass reads x
    again and writes ``high = x − Bᵀ·low`` from that f32 low band, so
    ``Bᵀ·low_spec + high == x`` to float round-off.
    """
    b, s, d = x.shape
    basis = frequency.low_band_basis(s, rho, method)
    synth = basis.T
    m = basis.shape[0]
    bs = min(block_s, s)
    bd = min(block_d, d)
    assert s % bs == 0 and d % bd == 0, (s, d, bs, bd)
    squeezed = pl.Squeezed()
    low = pl.pallas_call(
        _spectral_analysis_kernel,
        grid=(b, d // bd, s // bs),
        in_specs=[
            pl.BlockSpec((m, bs), lambda n, j, i: (0, i)),
            pl.BlockSpec((squeezed, bs, bd), lambda n, j, i: (n, i, j)),
        ],
        out_specs=pl.BlockSpec((squeezed, m, bd), lambda n, j, i: (n, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bd), jnp.float32)],
        interpret=interpret,
    )(basis, x)
    high = pl.pallas_call(
        _spectral_residual_kernel,
        grid=(b, s // bs, d // bd),
        in_specs=[
            pl.BlockSpec((bs, m), lambda n, i, j: (i, 0)),
            pl.BlockSpec((squeezed, m, bd), lambda n, i, j: (n, 0, j)),
            pl.BlockSpec((squeezed, bs, bd), lambda n, i, j: (n, i, j)),
        ],
        out_specs=pl.BlockSpec((squeezed, bs, bd), lambda n, i, j: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        interpret=interpret,
    )(synth, low, x)
    return low.astype(x.dtype), high
