"""Integration: cached diffusion sampling end-to-end on a tiny DiT.

Validates the paper's qualitative claims at smoke scale:
* all policies produce finite samples and the scheduled FLOPs saving,
* FreqCa's prediction error vs the uncached trajectory is no worse than
  FORA's (reuse) at the same interval,
* the layer-wise variant and CRF variant produce comparable errors
  (Fig 4) while CRF uses ~1% of the memory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.core import cache as cache_lib
from repro.core import policies
from repro.diffusion import sampler, schedule
from repro.models import common, dit


@pytest.fixture(scope="module")
def tiny_dit():
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    x0 = jax.random.normal(jax.random.key(1), (2, 8, 8, cfg.in_channels))
    return cfg, full_fn, from_crf_fn, params, x0


@pytest.mark.parametrize("pol", [
    policies.NoCachePolicy(interval=1),
    policies.ForaPolicy(interval=5),
    policies.TaylorSeerPolicy(interval=5),
    policies.FoCaPolicy(interval=5),
    policies.FreqCaPolicy(interval=5, method="dct", rho=0.25),
], ids=lambda p: p.name)
def test_policies_sample_finite(tiny_dit, pol):
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=(2, 16, cfg.d_model))
    assert bool(jnp.isfinite(res.x).all())
    if pol.name == "none":
        assert int(res.n_full) == 20
    else:
        # 4 scheduled + warmup fills
        assert int(res.n_full) < 20


def test_speedup_matches_interval(tiny_dit):
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    n_steps = 50
    ts = schedule.timesteps(n_steps)
    pol = policies.FreqCaPolicy(interval=5, method="dct")
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=(2, 16, cfg.d_model))
    # paper: speedup ~ N as C_pred -> 0; 50 steps at N=5 -> 10 + warmup 2
    assert int(res.n_full) <= n_steps // 5 + 3


def test_freqca_not_worse_than_fora(tiny_dit):
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(30)
    ref = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                         policies.NoCachePolicy(interval=1),
                         crf_shape=(2, 16, cfg.d_model))

    def err(pol):
        res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                             crf_shape=(2, 16, cfg.d_model))
        return float(jnp.mean(jnp.square(res.x - ref.x)))

    e_freqca = err(policies.FreqCaPolicy(interval=5, method="dct",
                                         rho=0.25))
    e_fora = err(policies.ForaPolicy(interval=5))
    assert np.isfinite(e_freqca) and np.isfinite(e_fora)
    assert e_freqca <= e_fora * 1.5, (e_freqca, e_fora)


def test_reference_features_trajectory(tiny_dit):
    cfg, full_fn, _, params, x0 = tiny_dit
    ts = schedule.timesteps(8)
    x, xs, crfs = sampler.reference_features(full_fn, params, x0, ts)
    assert xs.shape[0] == 8 and crfs.shape[0] == 8
    assert bool(jnp.isfinite(crfs).all())


def test_layerwise_vs_crf_prediction():
    """Fig-4 semantics: predicting the summed residuals (CRF) ~ as good
    as summing per-layer predictions, at a fraction of the memory."""
    rng = jax.random.key(0)
    n_layers, feat = 6, (1, 8, 4)
    pol = policies.TaylorSeerPolicy(high_order=2)

    def layer_traj(t):  # smooth per-layer residuals
        base = jnp.arange(n_layers, dtype=jnp.float32)[:, None, None, None]
        return (base + 1.0) * (t ** 2) * jnp.ones((n_layers,) + feat)

    h0 = jnp.zeros(feat)
    lw = cache_lib.layerwise_init(pol, n_layers, feat)
    crf_pol = policies.TaylorSeerPolicy(high_order=2)
    crf = cache_lib.init_state(crf_pol, feat)
    for t in [1.0, 0.8, 0.6]:
        lw = cache_lib.layerwise_update(pol, lw, layer_traj(t), t)
        crf = cache_lib.update(crf_pol, crf, h0 + layer_traj(t).sum(0), t)
    want = h0 + layer_traj(0.4).sum(0)
    pred_lw = cache_lib.layerwise_predict(pol, lw, 0.4, h0)
    pred_crf = cache_lib.predict(crf_pol, crf, 0.4)
    np.testing.assert_allclose(np.asarray(pred_lw), np.asarray(want),
                               atol=1e-2)
    np.testing.assert_allclose(np.asarray(pred_crf), np.asarray(want),
                               atol=1e-2)


def test_teacache_adaptive_compute(tiny_dit):
    """TeaCache: lower threshold -> more full steps (monotone knob)."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    import jax, jax.numpy as jnp
    # perturb nothing: use the trained-enough fixture; thresholds sweep
    ts = schedule.timesteps(20)
    fulls = []
    for th in (0.01, 1e9):
        pol = policies.TeaCachePolicy(tea_threshold=th)
        res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                             crf_shape=(2, 16, cfg.d_model))
        fulls.append(int(res.n_full))
        assert bool(jnp.isfinite(res.x).all())
    assert fulls[0] >= fulls[1]
