"""End-to-end behaviour tests: training drives loss down, the serving
engine serves batches with the expected compute saving, checkpoints
round-trip, and the backbone-denoiser wrapping (FreqCa on assigned
architectures) works."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.checkpointing import checkpoint
from repro.core import policies
from repro.data import synthetic
from repro.diffusion import sampler, schedule, training
from repro.launch.train import train_dit, train_lm
from repro.models import common, dit
from repro.serving.engine import DiffusionEngine, DiffusionRequest


def test_dit_training_reduces_loss(tmp_path):
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    from repro.optim import adamw
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60)
    opt = adamw.init(opt_cfg, params)

    def apply_fn(p, x_t, t):
        return dit.dit_forward(p, x_t, t, cfg).velocity

    @jax.jit
    def step(params, opt, latents, rng):
        (l, m), g = jax.value_and_grad(
            lambda p: training.rf_loss(apply_fn, p, {"latents": latents},
                                       rng), has_aux=True)(params)
        params, opt, _ = adamw.update(opt_cfg, g, opt, params)
        return params, opt, l

    losses = []
    for i in range(60):
        latents = synthetic.shapes_batch(jax.random.key(i), 8, size=8,
                                         channels=cfg.in_channels)
        params, opt, l = step(params, opt, latents, jax.random.key(1000 + i))
        losses.append(float(l))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.8, losses[:3]


def test_lm_training_reduces_loss():
    cfg = config_lib.reduced(config_lib.get_config("yi-9b"))
    _, losses = train_lm(cfg, steps=15, batch=4, seq=32, ckpt_dir="")
    assert losses[-1] < losses[0]


def test_serving_engine_end_to_end():
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    eng = DiffusionEngine(full_fn, from_crf_fn, params,
                          (8, 8, cfg.in_channels),
                          (16, cfg.d_model),
                          policies.FreqCaPolicy(interval=5),
                          n_steps=20, max_batch=4)
    for i in range(6):
        eng.submit(DiffusionRequest(request_id=i, seed=i))
    out1 = eng.run_batch()
    out2 = eng.run_batch()
    assert len(out1) == 4 and len(out2) == 2
    assert all(jnp.isfinite(o.latents).all() for o in out1 + out2)
    assert out1[0].n_full_steps < 20  # compute actually skipped


def test_editing_request_denoises_from_reference():
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    eng = DiffusionEngine(full_fn, from_crf_fn, params,
                          (8, 8, cfg.in_channels),
                          (16, cfg.d_model),
                          policies.FreqCaPolicy(interval=3),
                          n_steps=10, max_batch=2)
    ref_img = synthetic.shapes_batch(jax.random.key(5), 1, size=8,
                                     channels=cfg.in_channels)[0]
    eng.submit(DiffusionRequest(request_id=0, seed=0, init_latents=ref_img,
                                edit_strength=0.4))
    out = eng.run_batch()
    assert jnp.isfinite(out[0].latents).all()


def test_backbone_denoiser_freqca():
    """FreqCa on an assigned architecture (mamba2) used as denoiser."""
    cfg = config_lib.reduced(config_lib.get_config("mamba2-370m"))
    params = common.init_params(dit.backbone_denoiser_specs(cfg),
                                jax.random.key(0))

    def full_fn(params, x, t, cond=()):
        tb = jnp.full((x.shape[0],), t)
        out = dit.backbone_denoiser_forward(params, x, tb, cfg)
        return out.velocity, out.crf

    def from_crf_fn(params, crf, t, cond=()):
        return dit.backbone_denoiser_from_crf(params, crf, cfg, 8, 8)

    x0 = jax.random.normal(jax.random.key(1), (2, 8, 8, 4))
    ts = schedule.timesteps(12)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                         policies.FreqCaPolicy(interval=4, rho=0.25),
                         crf_shape=(2, 16, cfg.d_model))
    assert bool(jnp.isfinite(res.x).all())
    assert int(res.n_full) < 12


def test_checkpoint_roundtrip(tmp_path):
    cfg = config_lib.reduced(config_lib.get_config("yi-9b"))
    from repro.models import transformer
    params = common.init_params(transformer.lm_specs(cfg), jax.random.key(0))
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 7, params, name="t")
    assert checkpoint.latest_step(d, "t") == 7
    restored = checkpoint.restore(d, 7, params, name="t")
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_lm_engine_generates():
    from repro.serving.engine import LMEngine
    cfg = config_lib.reduced(config_lib.get_config("yi-9b"))
    from repro.models import transformer
    params = common.init_params(transformer.lm_specs(cfg), jax.random.key(0))
    eng = LMEngine(params, cfg, max_len=32)
    prompt = jax.random.randint(jax.random.key(0), (2, 4), 0, cfg.vocab_size)
    out = eng.generate(prompt, n_new=6)
    assert out.shape == (2, 10)
