"""Paper Figure 4: prediction MSE — layer-wise caching vs CRF caching.

Runs the reference (uncached) trajectory, and at every predictable step
forecasts the model output feature two ways from the same K=3 history:
(a) layer-wise: predict each block's residual delta, sum them;
(b) CRF: predict the single cumulative residual feature directly.
Reports per-step MSE stats; the paper finds CRF within ~4% of layer-wise
while using ~1% of the memory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as B
from repro.core import cache as cache_lib
from repro.core.cache import CachePolicy
from repro.diffusion import schedule
from repro.models import common as mcommon
from repro.models import dit


def forward_with_residuals(params, latents, t, cfg):
    """Unrolled dit forward returning (crf, per-layer residual deltas)."""
    b, h, w, c = latents.shape
    dtype = jnp.dtype(cfg.dtype)
    x = dit.patchify(latents.astype(dtype), cfg.patch_size)
    x = mcommon.dense(params["patch_proj"], x)
    x = x + dit._pos_embedding(x.shape[1], cfg.d_model).astype(dtype)[None]
    cond = dit._time_cond(params, t, cfg, dtype)
    deltas = []
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda p: p[i], params["single"])
        x_new = dit.single_block(lp, x, cond, cfg)
        deltas.append(x_new - x)
        x = x_new
    return x, jnp.stack(deltas)  # crf, [L, B, S, D]


def run(out: str = "results/bench/fig4.json", interval: int = 5):
    cfg, params = B.get_model()
    x = jax.random.normal(jax.random.key(9),
                          (2, B.IMG_SIZE, B.IMG_SIZE, cfg.in_channels))
    ts = schedule.timesteps(B.N_STEPS)
    fwd = jax.jit(lambda lat, t: forward_with_residuals(
        params, lat, jnp.full((lat.shape[0],), t), cfg))
    full_fn, _ = dit.denoiser(cfg)

    pol = CachePolicy(kind="taylorseer", high_order=2)
    feat = None
    lw_state = crf_state = None
    h0 = None
    mse_lw, mse_crf, e_ref = [], [], []
    for i in range(B.N_STEPS):
        t_now, t_next = float(ts[i]), float(ts[i + 1])
        crf, deltas = fwd(x, t_now)
        if feat is None:
            feat = crf.shape
            lw_state = cache_lib.layerwise_init(pol, cfg.n_layers, feat)
            crf_state = cache_lib.init_state(pol, feat)
            h0 = crf - deltas.sum(0)    # embedding+pos part (t-invariant)
        if int(crf_state.n_valid) >= 3 and (i % interval) != 0:
            pred_lw = cache_lib.layerwise_predict(pol, lw_state, t_now, h0)
            pred_crf = cache_lib.predict(pol, crf_state, t_now)
            denom = float(jnp.mean(jnp.square(crf)))
            mse_lw.append(float(jnp.mean(jnp.square(pred_lw - crf))) / denom)
            mse_crf.append(float(jnp.mean(jnp.square(pred_crf - crf)))
                           / denom)
        else:
            lw_state = cache_lib.layerwise_update(pol, lw_state, deltas,
                                                  t_now)
            crf_state = cache_lib.update(pol, crf_state, crf, t_now)
        v, _ = full_fn(params, x, t_now)
        x = x + (t_next - t_now) * v

    rows = [{
        "variant": "layer-wise (2L tensors)",
        "rel_mse_mean": round(float(np.mean(mse_lw)), 5),
        "rel_mse_p90": round(float(np.percentile(mse_lw, 90)), 5),
    }, {
        "variant": "CRF (1 tensor)",
        "rel_mse_mean": round(float(np.mean(mse_crf)), 5),
        "rel_mse_p90": round(float(np.percentile(mse_crf, 90)), 5),
    }, {
        "variant": "CRF/layer-wise ratio",
        "rel_mse_mean": round(float(np.mean(mse_crf) / np.mean(mse_lw)), 3),
        "rel_mse_p90": round(float(np.percentile(mse_crf, 90)
                                   / np.percentile(mse_lw, 90)), 3),
    }]
    B.print_table("Fig 4 — prediction MSE: layer-wise vs CRF caching", rows)
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
