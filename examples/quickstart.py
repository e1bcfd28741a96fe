"""Quickstart: train a small DiT on synthetic shapes, then sample with
FreqCa at 5x scheduled compute saving and compare with the uncached
output.

Cache policies are self-contained objects from ``repro.core.policies``:
construct them directly and pass them to the sampler.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

import repro.configs as config_lib
from repro.core import policies
from repro.diffusion import sampler, schedule
from repro.launch.train import train_dit
from repro.models import dit

print("cache policies:", ", ".join(policies.available()))

cfg = config_lib.get_config("dit-small")
params = train_dit(cfg, steps=120, batch=16, ckpt_dir="", size=32)

full_fn, from_crf_fn = dit.denoiser(cfg)

x0 = jax.random.normal(jax.random.key(0), (4, 32, 32, cfg.in_channels))
ts = schedule.timesteps(50)
crf_shape = (4, (32 // cfg.patch_size) ** 2, cfg.d_model)

full = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                      policies.NoCachePolicy(), crf_shape=crf_shape)
pol = policies.FreqCaPolicy(interval=5, method="dct", rho=0.0625)
freqca = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                        crf_shape=crf_shape)
err = float(jnp.linalg.norm(freqca.x - full.x) / jnp.linalg.norm(full.x))
print(f"uncached: {int(full.n_full)} full steps; "
      f"freqca: {int(freqca.n_full)} full steps "
      f"({50 / int(freqca.n_full):.2f}x scheduled compute saving)")
print(f"relative output error vs uncached: {err:.4f}")
