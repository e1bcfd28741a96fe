"""Backend-dispatch layer for the Pallas kernel suite.

``REPRO_KERNELS=pallas|xla`` selects the implementation behind every op
here; unset, it defaults to ``pallas`` on TPU and ``xla`` elsewhere
(interpret-mode Pallas is correct but slow on CPU, so off-TPU the
pure-jnp paths win).  Consumers — ``core.frequency.decompose``,
``core.policies.base.ring_predict``, ``core.policies.freqca``,
``models.dit._joint_attention``, ``models.dit._joint_qkv`` — route
their hot paths through this module so the cached step, the band split,
the q/k norm and RoPE, and joint attention run the fused kernels on TPU
without forking any call sites.

Both the backend and interpret mode are read **lazily at call time**
(``backend()`` / ``interpret()``), never frozen at import, so a test
can flip ``REPRO_KERNELS`` between calls without reimporting; the
jitted implementations carry them as static arguments, which keys the
jit cache correctly across flips.  (Dispatch is resolved at trace time:
executables already compiled — e.g. a warmed serving engine — keep the
backend they were traced with.)
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core import hermite
from repro.kernels import dct as dct_kernel
from repro.kernels import freqca_fused as fused_kernel
from repro.kernels import qk_norm_rope as qk_kernel
from repro.kernels import ref
from repro.kernels import ssd_scan as ssd_kernel


# ---------------------------------------------------------------------------
# backend selection (lazy — never frozen at import time)
# ---------------------------------------------------------------------------

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def backend() -> str:
    """'pallas' | 'xla' — from ``REPRO_KERNELS``, else by jax backend.

    On a TPU the kernels are the served path: ``REPRO_KERNELS=xla``
    there is refused rather than silently taking them out."""
    env = os.environ.get("REPRO_KERNELS", "").strip().lower()
    if env == "xla" and _on_tpu():
        raise ValueError("REPRO_KERNELS=xla would take the Pallas kernels "
                         "out of the served path on a TPU; unset it")
    if env in ("pallas", "xla"):
        return env
    if env:
        raise ValueError(
            f"REPRO_KERNELS must be 'pallas' or 'xla', got {env!r}")
    return "pallas" if _on_tpu() else "xla"


def use_pallas() -> bool:
    return backend() == "pallas"


def interpret() -> bool:
    """Pallas interpret mode: forced via ``REPRO_KERNELS_INTERPRET``,
    else on everywhere except a real TPU backend, where it is never on
    (forcing it there is refused)."""
    env = os.environ.get("REPRO_KERNELS_INTERPRET", "").strip().lower()
    if env in ("1", "true"):
        if _on_tpu():
            raise ValueError("REPRO_KERNELS_INTERPRET=1 on a TPU would "
                             "interpret the kernels; unset it")
        return True
    if env in ("0", "false"):
        return False
    if env:
        raise ValueError("REPRO_KERNELS_INTERPRET must be 0/false or "
                         f"1/true, got {env!r}")
    return not _on_tpu()


def __getattr__(name: str):
    # back-compat: ops.INTERPRET used to be a module constant frozen at
    # import; keep the attribute but compute it lazily
    if name == "INTERPRET":
        return interpret()
    raise AttributeError(name)


# ---------------------------------------------------------------------------
# kernel wrappers (jitted, backend/interpret as static args)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_s", "block_d", "block_k",
                                             "interpret_"))
def _dct_tokens(x, block_s, block_d, block_k, interpret_):
    basis = dct_kernel.frequency.dct_basis(x.shape[-2])
    return dct_kernel.token_basis_matmul(basis, x, block_s, block_d, block_k,
                                         interpret=interpret_)


def dct_tokens(x: jnp.ndarray, block_s: int = 128, block_d: int = 128,
               block_k: int = 128) -> jnp.ndarray:
    """Orthonormal DCT-II along the token axis of [B, S, D]."""
    return _dct_tokens(x, block_s, block_d, block_k, interpret())


@functools.partial(jax.jit, static_argnames=("rho", "method", "interpret_"))
def _band_split(x, rho, method, interpret_):
    return dct_kernel.band_split(x, rho, method, interpret=interpret_)


def band_split(x: jnp.ndarray, rho: float = 0.0625, method: str = "dct"):
    """FreqCa band split (low, high) as one fused projection matmul."""
    return _band_split(x, rho, method, interpret())


# non-divisible shapes fall back to the jnp path; the predicate lives
# next to the kernels' block defaults (kernels/dct.py)
_spectral_shapes_ok = dct_kernel.spectral_dispatch_ok


@functools.partial(jax.jit, static_argnames=("rho", "method", "interpret_"))
def _band_split_spectral_pallas(x, rho, method, interpret_):
    return dct_kernel.band_split_spectral(x, rho, method,
                                          interpret=interpret_)


@functools.partial(jax.jit, static_argnames=("rho", "method"))
def _band_split_spectral_xla(x, rho, method):
    return ref.band_split_spectral_ref(x, rho, method)


def band_split_spectral(x: jnp.ndarray, rho: float = 0.0625,
                        method: str = "dct"):
    """Spectral band split: ``(low_spec [B, m, D], high [B, S, D])``.

    The cache-facing op: the low band never materialises spatially —
    ``m = spectral_kept_bins(S, rho, method)`` coefficient rows are the
    stored representation (~``rho`` of the spatial footprint).
    """
    _, s, d = x.shape
    if use_pallas() and _spectral_shapes_ok(s, d):
        return _band_split_spectral_pallas(x, rho, method, interpret())
    return _band_split_spectral_xla(x, rho, method)


@functools.partial(jax.jit, static_argnames=("order", "interpret_"))
def _freqca_predict(low, high_hist, ts, t_query, order, interpret_):
    return fused_kernel.freqca_predict_fused(low, high_hist, ts, t_query,
                                             order, interpret=interpret_)


def freqca_predict(low: jnp.ndarray, high_hist: jnp.ndarray,
                   ts: jnp.ndarray, t_query, order: int = 2) -> jnp.ndarray:
    """Fused cached-step reconstruction: ẑ = low + Hermite(high)(t)."""
    return _freqca_predict(low, high_hist, ts, t_query, order, interpret())


@functools.partial(jax.jit, static_argnames=("interpret_",))
def _freqca_predict_spectral_pallas(low_spec, synth, high_hist, w,
                                    interpret_):
    return fused_kernel.freqca_predict_fused_spectral(
        low_spec, synth, high_hist, w, interpret=interpret_)


@jax.jit
def _freqca_predict_spectral_xla(low_spec, synth, high_hist, w):
    return ref.freqca_predict_spectral_ref(low_spec, synth, high_hist, w)


def freqca_predict_spectral(low_spec: jnp.ndarray, synth: jnp.ndarray,
                            high_hist: jnp.ndarray,
                            w: jnp.ndarray) -> jnp.ndarray:
    """Fused spectral cached step: synth·low_spec + Σ_k w[:, k]·high_k.

    low_spec [B, m, D]; synth [S, m]; high_hist [B, K, S, D];
    w [B, K] per-lane folded Hermite weights (``hermite_weights``).
    """
    _, _, s, d = high_hist.shape
    if use_pallas() and _spectral_shapes_ok(s, d):
        return _freqca_predict_spectral_pallas(low_spec, synth, high_hist,
                                               w, interpret())
    return _freqca_predict_spectral_xla(low_spec, synth, high_hist, w)


@functools.partial(jax.jit, static_argnames=("order",))
def hermite_weights(ts: jnp.ndarray, t_query, order: int) -> jnp.ndarray:
    """Per-lane folded Hermite evaluation weights: [B, K] from ts [B, K].

    The host-side half of the fused cached step — the normal-equation
    solve collapses to K scalars per lane (``hermite.eval_weights``),
    so prediction is one FMA pass regardless of backend.
    """
    return jax.vmap(lambda t: hermite.eval_weights(t, t_query, order))(ts)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret_"))
def _ssd(x, dt, A, B, C, chunk, interpret_):
    return ssd_kernel.ssd_chunk_scan(x, dt, A, B, C, chunk,
                                     interpret=interpret_)


def ssd(x, dt, A, B, C, chunk: int = 256):
    """Mamba2 SSD chunk scan."""
    return _ssd(x, dt, A, B, C, chunk, interpret())


@functools.partial(jax.jit,
                   static_argnames=("q_per_kv", "causal", "window",
                                    "q_block", "kv_block", "interpret_"))
def _flash(q, k, v, q_per_kv, causal, window, q_block, kv_block,
           interpret_):
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, q_per_kv, causal=causal,
                              window=window, q_block=q_block,
                              kv_block=kv_block, interpret=interpret_)


def flash(q, k, v, q_per_kv: int, causal: bool = True, window: int = 0,
          q_block: int | None = None, kv_block: int | None = None):
    """Flash attention (GQA) kernel; blocks of None follow from the
    shape (``flash_attention.tiles``)."""
    return _flash(q, k, v, q_per_kv, causal, window, q_block, kv_block,
                  interpret())


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret_"))
def _qk_norm_rope(xs, scales, cos, sin, n_heads, interpret_):
    out = qk_kernel.norm_rope(xs, scales, cos, sin, n_heads,
                              interpret=interpret_)
    return out.transpose(0, 2, 1, 3)


def qk_norm_rope(xs, scales, rope, n_heads: int):
    """q or k [B, S, H, hd] of projections ``xs`` [B, S_i, H·hd] joined
    along the tokens: each stream LayerNormed per head by its own
    ``scales[i]``, then rotated by ``rope``'s (cos, sin) of the joined
    sequence — one kernel pass per stream, written in the layout flash's
    token-major blocks read (the oracle: ``models.dit.apply_rope`` of
    ``common.layernorm``, which the XLA path runs)."""
    return _qk_norm_rope(tuple(xs), tuple(scales), *rope, n_heads,
                         interpret())
