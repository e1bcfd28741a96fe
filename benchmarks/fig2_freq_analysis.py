"""Paper Figure 2: frequency-band dynamics of diffusion features.

(a)-(b) temporal cosine similarity of low/high bands across step
intervals; (c)-(d) trajectory continuity proxy: the relative magnitude
of the second temporal difference (low = smooth/continuous).  The paper's
claims to validate:
  * low band:  HIGH similarity, POOR continuity (jumps),
  * high band: LOWER similarity, GOOD continuity (predictable).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as B
from repro.core import frequency
from repro.diffusion import sampler, schedule
from repro.models import dit


def band_series(crfs: jnp.ndarray, rho: float, method: str):
    lows, highs = [], []
    for i in range(crfs.shape[0]):
        b = frequency.decompose(crfs[i], rho, method)
        lows.append(b.low)
        highs.append(b.high)
    return jnp.stack(lows), jnp.stack(highs)


def similarity_at_intervals(series: jnp.ndarray, intervals):
    out = {}
    t = series.shape[0]
    for k in intervals:
        sims = [float(frequency.cosine_similarity(series[i], series[i + k]))
                for i in range(0, t - k, max(1, (t - k) // 8))]
        out[k] = float(np.mean(sims))
    return out


def continuity(series: jnp.ndarray) -> float:
    """||second difference|| / ||first difference|| — lower = smoother
    (more continuous, easier to extrapolate)."""
    d1 = series[1:] - series[:-1]
    d2 = series[2:] - 2 * series[1:-1] + series[:-2]
    n1 = float(jnp.linalg.norm(d1.astype(jnp.float32)))
    n2 = float(jnp.linalg.norm(d2.astype(jnp.float32)))
    return n2 / max(n1, 1e-9)


def run(out: str = "results/bench/fig2.json"):
    cfg, params = B.get_model()
    full_fn, _ = dit.denoiser(cfg)
    x0 = jax.random.normal(jax.random.key(3),
                           (2, B.IMG_SIZE, B.IMG_SIZE, cfg.in_channels))
    ts = schedule.timesteps(B.N_STEPS)
    _, _, crfs = sampler.reference_features(full_fn, params, x0, ts)

    rows = []
    for method in ("dct", "fft"):
        for rho in (0.0625, 0.25):
            low, high = band_series(crfs, rho, method)
            intervals = [1, 2, 4, 8]
            sim_low = similarity_at_intervals(low, intervals)
            sim_high = similarity_at_intervals(high, intervals)
            c_low, c_high = continuity(low), continuity(high)
            for k in intervals:
                rows.append({"method": method, "rho": rho, "interval": k,
                             "cos_sim_low": round(sim_low[k], 4),
                             "cos_sim_high": round(sim_high[k], 4)})
            rows.append({"method": method, "rho": rho,
                         "interval": "2nd-diff ratio",
                         "cos_sim_low": round(c_low, 4),
                         "cos_sim_high": round(c_high, 4)})
            # paper-consistent claims that hold robustly at bench scale:
            # (i) the low band stays highly similar at EVERY interval
            #     (paper: "> 0.90 at most timesteps");
            assert min(sim_low.values()) > 0.9, (method, rho, sim_low)
            # (ii) high-band similarity decays FASTER with interval;
            decay_low = sim_low[1] - sim_low[8]
            decay_high = sim_high[1] - sim_high[8]
            assert decay_high > decay_low, (method, rho, sim_low, sim_high)
            # (iii) the high band is smoother along the trajectory
            #     (better extrapolable — lower 2nd/1st difference ratio).
            assert c_high < c_low, (method, rho, c_low, c_high)
    B.print_table("Fig 2 — band similarity & continuity "
                  "(low: similar but jumpy; high: continuous)", rows)
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
