"""Paper Table 1 (FLUX.1-dev grid) at CPU scale.

DCT decomposition (the paper's FLUX setting).  Compares FreqCa against
FORA (reuse), TaylorSeer (forecast) and plain step reduction at matched
intervals; ImageReward/CLIP are replaced by PSNR/SSIM/relative error vs
the 50-step uncached model (the paper's own perceptual columns are this
comparison).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common as B
from repro.core.cache import CachePolicy
from repro.diffusion import sampler, schedule
from repro.models import dit


def run(method: str = "dct", title: str = "Table 1 — FLUX.1-dev-like (DCT)",
        out: str = "results/bench/table1.json"):
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    x0 = jax.random.normal(jax.random.key(42),
                           (B.BATCH, B.IMG_SIZE, B.IMG_SIZE,
                            cfg.in_channels))

    base = B.run_policy(cfg, full_fn, from_crf_fn, params,
                        CachePolicy(kind="none"), x0)
    rows = [B.quality_row(f"{B.N_STEPS} steps (baseline)", base, base["x"],
                          base["wall_s"], base["flops"])]

    # step-reduction baselines (fewer solver steps, no caching)
    for frac, nm in [(0.5, "50% steps"), (0.2, "20% steps")]:
        n = max(int(B.N_STEPS * frac), 2)
        red = B.run_policy(cfg, full_fn, from_crf_fn, params,
                           CachePolicy(kind="none"), x0, n_steps=n)
        rows.append(B.quality_row(nm, red, base["x"], base["wall_s"],
                                  base["flops"]))

    for interval in (3, 5, 7, 10):
        for kind in ("fora", "taylorseer", "foca", "freqca"):
            pol = CachePolicy(kind=kind, interval=interval, method=method,
                              rho=0.0625, high_order=2)
            res = B.run_policy(cfg, full_fn, from_crf_fn, params, pol, x0)
            rows.append(B.quality_row(f"{kind}(N={interval})", res,
                                      base["x"], base["wall_s"],
                                      base["flops"]))

    # TeaCache-style adaptive-threshold reuse baseline (paper Table 1)
    for thresh in (0.1, 0.25, 0.5):
        pol = CachePolicy(kind="teacache", tea_threshold=thresh)
        res = B.run_policy(cfg, full_fn, from_crf_fn, params, pol, x0)
        rows.append(B.quality_row(f"teacache(l={thresh})", res,
                                  base["x"], base["wall_s"],
                                  base["flops"]))

    # beyond-paper: FreqCa-A — FreqCa predictor + self-calibrated adaptive
    # schedule (error budget from the free activated-step prediction error)
    for tol in (0.2, 0.4, 0.8):
        pol = CachePolicy(kind="freqca_a", tea_threshold=tol,
                          method=method, rho=0.25, high_order=2)
        res = B.run_policy(cfg, full_fn, from_crf_fn, params, pol, x0)
        rows.append(B.quality_row(f"freqca_a(tol={tol})", res,
                                  base["x"], base["wall_s"],
                                  base["flops"]))

    B.print_table(title, rows)
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
