"""Image editing (FLUX.1-Kontext / Qwen-Image-Edit regime): start from a
partially-noised reference, denoise under FreqCa, measure fidelity vs
the uncached edit.

  PYTHONPATH=src python examples/edit_image.py
"""
import jax
import jax.numpy as jnp

import repro.configs as config_lib
from repro.core import policies
from repro.data import synthetic
from repro.diffusion import sampler, schedule
from repro.launch.train import train_dit
from repro.models import dit

cfg = config_lib.get_config("dit-small")
params = train_dit(cfg, steps=120, batch=16, ckpt_dir="", size=32)

full_fn, from_crf_fn = dit.denoiser(cfg)

ref = synthetic.shapes_batch(jax.random.key(3), 2, size=32,
                             channels=cfg.in_channels)
noise = jax.random.normal(jax.random.key(4), ref.shape)
tau = 0.6                                   # edit strength
x0 = schedule.add_noise(ref, noise, tau)
ts = schedule.timesteps(50) * tau           # resume from t = tau
crf_shape = (2, (32 // cfg.patch_size) ** 2, cfg.d_model)

full = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                      policies.NoCachePolicy(), crf_shape=crf_shape)
fast = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                      policies.FreqCaPolicy(interval=5, method="fft"),
                      crf_shape=crf_shape)
err = float(jnp.linalg.norm(fast.x - full.x) / jnp.linalg.norm(full.x))
print(f"edit with freqca: {int(fast.n_full)}/50 full steps, "
      f"rel err vs uncached edit {err:.4f}")
