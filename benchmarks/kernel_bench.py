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
* ``attention`` — the non-causal flash kernel in bf16 at the two
  served shapes (FLUX cut: B=4, S=4096, 24 x 128; DiT-XL/2: B=8,
  S=1024, 16 x 72) with its default tiles: wall time beside the share of
  ``bench/work.flash``'s roofline (on a chip in ``bench/peaks.json``;
  None elsewhere).

``python -m benchmarks.kernel_bench --sweep 512x512,1024x512,...`` times
the served shapes at each listed (q_block, kv_block) instead, on the
chip, into ``results/bench/BENCH_flash_sweep.json``.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

from benchmarks import common as B
from repro.core import frequency
from repro.core.policies import base as policy_base
from repro.core.policies.freqca import FreqCaPolicy
from repro.kernels import dct as dct_kernel
from repro.kernels import ops

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


# the flash kernel's two served shapes: (cell's config, B, S, heads, hd)
SERVED_ATTENTION = (("flux1-dev-cut", 4, 4096, 24, 128),
                    ("dit-xl2-512", 8, 1024, 16, 72))


def attention_row(batch: int, s: int, heads: int, hd: int,
                  dtype=jnp.bfloat16, blocks: tuple | None = None) -> dict:
    """The non-causal flash kernel at one shape, at the (q, kv) blocks
    given (None: ``flash_attention.tiles``): host wall time of a call,
    the work ``bench/work.flash`` counts, and on a chip listed in
    ``bench/peaks.json`` the share of that work's roofline."""
    from bench import cell, work
    from repro.kernels import flash_attention as fa
    q, k, v = (jax.random.normal(kk, (batch, s, heads, hd)).astype(dtype)
               for kk in jax.random.split(jax.random.key(2), 3))
    qb, kb = blocks or fa.tiles(s, s, hd, dtype)
    flash = jax.jit(functools.partial(
        fa.flash_attention, q_per_kv=1, causal=False, q_block=qb,
        kv_block=kb, interpret=ops.interpret()))
    wall = _wall(flash, q, k, v, reps=5)
    need = work.flash(batch, s, heads, hd, jnp.dtype(dtype).name)
    share = bound = None
    peak = cell.peaks().get(jax.devices()[0].device_kind)
    if not ops.interpret() and peak is not None:
        least, bound = work.roofline_s(need, peak)
        share = round(100.0 * least / wall, 3)
    return {
        "name": "attention",
        "batch": batch, "tokens": s, "heads": heads, "head_dim": hd,
        "dtype": jnp.dtype(dtype).name, "q_block": qb, "kv_block": kb,
        "grid_steps": batch * heads * (s // qb) * (s // kb),
        "flops": need.flops, "bytes": need.bytes,
        "wall_flash_ms": round(1e3 * wall, 3),
        # host clock around the call: an upper bound on device time
        "flash_roofline_pct": share, "bound": bound,
        "interpret": ops.interpret(),
    }


def sweep(blocks, out: str = "results/bench/BENCH_flash_sweep.json"):
    """Each served attention shape at ``tiles``' blocks and at every
    (q_block, kv_block) of ``blocks`` that divides its length: the
    sweep the caps ``Q_CAP``/``TILE_CAP`` are chosen from."""
    from repro.kernels import flash_attention as fa
    rows = []
    for config, batch, s, heads, hd in SERVED_ATTENTION:
        seen = set()
        for qb, kb in [fa.tiles(s, s, hd, jnp.bfloat16), *blocks]:
            qb, kb = min(qb, s), min(kb, s)
            if s % qb or s % kb or (qb, kb) in seen:
                continue
            seen.add((qb, kb))
            row = attention_row(batch, s, heads, hd, jnp.bfloat16, (qb, kb))
            rows.append({"config": config, **row})
            print(json.dumps(rows[-1]), flush=True)
    B.save_rows(out, rows)
    return rows


def run(out: str = "results/bench/BENCH_kernels.json"):
    # call-time read: run.py --smoke sets BENCH_REDUCED after import
    if B.reduced():
        batch, s, d = 1, 256, 128
        attention = [("tiny", 1, 256, 2, 32)]
    else:
        batch, s, d = 2, 1024, 512
        attention = SERVED_ATTENTION
    rho = 0.0625
    rows = [
        cached_step_row(batch, s, d, rho),
        band_split_row(batch, s, d, rho),
        *(attention_row(b, n, h, hd) for _, b, n, h, hd in attention),
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sweep", metavar="QxK,...",
                    help="time the served attention shapes at these "
                         "blocks instead, e.g. 512x512,1024x512")
    args = ap.parse_args(argv)
    if args.sweep is None:
        run()
        return
    sweep([tuple(int(n) for n in b.split("x"))
           for b in args.sweep.split(",")])


if __name__ == "__main__":
    main()
