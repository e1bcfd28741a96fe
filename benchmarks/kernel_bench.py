"""Kernel-path microbenchmarks -> ``results/bench/BENCH_kernels.json``.

Three rows, each pairing a measured wall time with a bytes-moved model
(the roofline-side story).  The kernels take their interpret flag from
``repro.kernels.ops``: compiled on a TPU, interpreted elsewhere, where
the *bytes* columns are the load-bearing numbers and the kernel wall
times are correctness-priced, not speed-priced (each row records
``interpret``):

* ``cached_step`` — spatial low ring vs the spectral low ring at the
  paper's rho: state bytes, bytes the cached step must move, and the
  measured jnp cached-step wall for both layouts.  The CI guard asserts
  ``spectral_low_bytes <= rho * spatial_low_bytes + eps``.
* ``band_split`` — pure-jnp ``frequency.decompose`` (transform
  round-trip) vs the fused spectral Pallas kernel (one pass emitting
  ``(low_spec, high)``).
* ``attention`` — full-logits ``_sdpa`` vs the flash kernel at a shape
  above the DiT's ``_FLASH_MIN_SEQ`` routing threshold.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common as B
from repro.core import frequency
from repro.core.policies import base as policy_base
from repro.core.policies.freqca import FreqCaPolicy
from repro.kernels import dct as dct_kernel
from repro.kernels import ops
from repro.models import attention as attn_lib

def _wall(fn, *args, reps: int = 3) -> float:
    out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps


def _ring_bytes(ring: policy_base.Ring) -> int:
    return sum(x.size * x.dtype.itemsize for x in ring)


def cached_step_row(batch: int, s: int, d: int, rho: float) -> dict:
    """Spatial-vs-spectral cached step: state footprint + wall time."""
    pol = FreqCaPolicy(interval=5, method="dct", rho=rho)
    state = pol.init(batch, (s, d))
    ctx = policy_base.StepContext(
        step_idx=jnp.asarray(0), t_now=jnp.asarray(0.5),
        x=jnp.zeros((batch, 1)), batch=batch, feat_shape=(s, d))
    crf = jax.random.normal(jax.random.key(0), (batch, s, d))

    # a spatial twin of the same cache: low band stored at [B, K, S, D]
    spatial_low = policy_base.ring_init(batch, pol.k_low, (s, d))

    @jax.jit
    def spectral_step(st):
        st = pol.update(st, crf, ctx)
        return st, pol.predict(st, ctx)

    @jax.jit
    def spatial_step(low_ring, high_ring):
        bands = frequency.decompose(crf, rho, "dct")
        low_ring = policy_base.ring_push(low_ring, bands.low, ctx.t_now)
        high_ring = policy_base.ring_push(high_ring, bands.high, ctx.t_now)
        pred = (policy_base.ring_last(low_ring)
                + policy_base.ring_predict(high_ring, ctx.t_now,
                                           pol.high_order))
        return low_ring, high_ring, pred

    m = pol.spectral_bins(s)
    itemsize = 4
    spatial_low_bytes = batch * pol.k_low * s * d * itemsize
    spectral_low_bytes = _ring_bytes(state.low)
    high_bytes = batch * pol.k_high * s * d * itemsize
    return {
        "name": "cached_step",
        "batch": batch, "tokens": s, "d_model": d, "rho": rho,
        "kept_bins": m,
        "spatial_low_bytes": spatial_low_bytes,
        "spectral_low_bytes": spectral_low_bytes,
        "low_ring_compression": round(
            spatial_low_bytes / max(spectral_low_bytes, 1), 2),
        # cached-step HBM traffic model: read low ring + high ring,
        # write ẑ once
        "step_bytes_spatial": (spatial_low_bytes + high_bytes
                               + batch * s * d * itemsize),
        "step_bytes_spectral": (spectral_low_bytes + high_bytes
                                + batch * s * d * itemsize),
        "wall_spatial_ms": round(
            1e3 * _wall(spatial_step, spatial_low, state.high), 3),
        "wall_spectral_ms": round(1e3 * _wall(spectral_step, state), 3),
    }


def band_split_row(batch: int, s: int, d: int, rho: float) -> dict:
    """jnp transform round-trip vs fused spectral kernel."""
    x = jax.random.normal(jax.random.key(1), (batch, s, d))
    itemsize = 4
    m = frequency.spectral_kept_bins(s, rho, "dct")

    jnp_split = jax.jit(lambda z: frequency.decompose(z, rho, "dct"))
    kern_split = jax.jit(lambda z: dct_kernel.band_split_spectral(
        z, rho, "dct", interpret=ops.interpret()))
    return {
        "name": "band_split",
        "batch": batch, "tokens": s, "d_model": d, "rho": rho,
        # jnp path: read x, write low + high (both spatial);
        # fused kernel: read x once, write low_spec + high
        "bytes_jnp": 3 * batch * s * d * itemsize,
        "bytes_kernel": (2 * batch * s * d + batch * m * d) * itemsize,
        "wall_jnp_ms": round(1e3 * _wall(jnp_split, x), 3),
        "wall_kernel_ms": round(1e3 * _wall(kern_split, x), 3),
        "interpret": ops.interpret(),
    }


def attention_row(batch: int, s: int, heads: int, hd: int) -> dict:
    """Full-logits sdpa vs flash kernel, non-causal."""
    q = jax.random.normal(jax.random.key(2), (batch, s, heads, hd))
    k = jax.random.normal(jax.random.key(3), (batch, s, heads, hd))
    v = jax.random.normal(jax.random.key(4), (batch, s, heads, hd))
    mask = jnp.ones((1, s, s), bool)
    itemsize = 4
    sdpa = jax.jit(lambda a, b, c: attn_lib._sdpa(a, b, c, mask, 1))
    flash = jax.jit(_flash_call)
    return {
        "name": "attention",
        "batch": batch, "tokens": s, "heads": heads, "head_dim": hd,
        # sdpa materialises the [B, H, S, S] logits+probs at fusion
        # boundaries; flash keeps them in VMEM
        "bytes_sdpa": (3 * batch * s * heads * hd
                       + 2 * batch * heads * s * s
                       + batch * s * heads * hd) * itemsize,
        "bytes_flash": 4 * batch * s * heads * hd * itemsize,
        "wall_sdpa_ms": round(1e3 * _wall(sdpa, q, k, v), 3),
        "wall_flash_ms": round(1e3 * _wall(flash, q, k, v), 3),
        "interpret": ops.interpret(),
    }


def _flash_call(q, k, v):
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, 1, causal=False, q_block=128,
                              kv_block=128, interpret=ops.interpret())


def run(out: str = "results/bench/BENCH_kernels.json"):
    # call-time read: run.py --smoke sets BENCH_REDUCED after import
    if B.reduced():
        batch, s, d = 1, 256, 128
        attn_s, heads, hd = 256, 2, 32
    else:
        batch, s, d = 2, 1024, 512
        attn_s, heads, hd = 1024, 4, 64
    rho = 0.0625
    rows = [
        cached_step_row(batch, s, d, rho),
        band_split_row(batch, s, d, rho),
        attention_row(batch, attn_s, heads, hd),
    ]
    for row in rows:  # heterogeneous schemas: one table per row
        B.print_table(f"Kernel paths — {row['name']}", [row])
    step = rows[0]
    # the tentpole claim: the low ring shrank to ~rho of its spatial
    # footprint (one extra bin can survive rounding; eps covers the
    # [B, K] ts + head bookkeeping)
    eps = 1024 + step["spatial_low_bytes"] / s  # one spectral row
    assert (step["spectral_low_bytes"]
            <= rho * step["spatial_low_bytes"] + eps), step
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
