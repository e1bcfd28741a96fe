"""Training launcher.

Two modes:
* ``--arch dit-small`` (default): train the small DiT denoiser on the
  procedural shapes dataset with the rectified-flow loss — the model
  ``repro.launch.serve`` and the examples sample from.
* ``--arch <assigned-lm-arch> --reduced``: train the reduced variant of
  an assigned architecture on the synthetic LM stream (smoke-scale).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch dit-small --steps 300
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs as config_lib
from repro.checkpointing import checkpoint
from repro.configs.base import DiTConfig, ModelConfig
from repro.data import synthetic
from repro.diffusion import training as diff_training
from repro.models import common, dit, encdec, transformer
from repro.optim import adamw


def train_dit(cfg: DiTConfig, steps: int, batch: int, ckpt_dir: str,
              seed: int = 0, log_every: int = 20, size: int = 32):
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(seed),
                                jnp.dtype(cfg.dtype))
    opt_cfg = adamw.AdamWConfig(lr=2e-3, warmup_steps=50, total_steps=steps,
                                weight_decay=1e-4)
    opt_state = adamw.init(opt_cfg, params)

    def apply_fn(p, x_t, t):
        return dit.dit_forward(p, x_t, t, cfg).velocity

    @jax.jit
    def step_fn(params, opt_state, batch_latents, rng):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: diff_training.rf_loss(apply_fn, p,
                                            {"latents": batch_latents}, rng),
            has_aux=True)(params)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                             params)
        return params, opt_state, {**metrics, **om}

    t0 = time.time()
    for i in range(steps):
        rng = jax.random.key(seed * 7919 + i)
        latents = synthetic.shapes_batch(rng, batch, size=size,
                                         channels=cfg.in_channels)
        params, opt_state, metrics = step_fn(params, opt_state, latents,
                                             jax.random.fold_in(rng, 1))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({time.time() - t0:.1f}s)")
    if ckpt_dir:
        checkpoint.save(ckpt_dir, steps, params, name="dit")
        print("saved", ckpt_dir)
    return params


def restore_dit(cfg: DiTConfig, ckpt_dir: str):
    """The latest ``dit`` checkpoint in ``ckpt_dir``; None if it has none."""
    step = checkpoint.latest_step(ckpt_dir, "dit")
    if step < 0:
        return None
    like = common.abstract_params(dit.dit_specs(cfg), jnp.dtype(cfg.dtype))
    return checkpoint.restore(ckpt_dir, step, like, name="dit")


def _train_dit_child(conn, cfg, steps, batch, ckpt_dir, size):
    train_dit(cfg, steps, batch, ckpt_dir=ckpt_dir, size=size)
    conn.send(jax.default_backend())
    conn.close()


def train_dit_in_child(cfg: DiTConfig, steps: int, batch: int,
                       ckpt_dir: str, size: int = 32) -> str:
    """Train in a spawned child process that writes the checkpoint to
    ``ckpt_dir``, so the caller never touches a JAX device (the parent
    of a fleet must not hold the chip its workers need).  Returns the
    JAX platform the child trained on."""
    import multiprocessing as mp
    ctx = mp.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=_train_dit_child,
                       args=(child_conn, cfg, steps, batch, ckpt_dir, size),
                       name="train-dit")
    proc.start()
    child_conn.close()
    try:
        platform = parent_conn.recv()
    except EOFError:
        platform = None
    proc.join()
    if platform is None or proc.exitcode != 0:
        raise RuntimeError(f"training child exited with {proc.exitcode}")
    return platform


def train_lm(cfg: ModelConfig, steps: int, batch: int, seq: int,
             ckpt_dir: str, seed: int = 0, log_every: int = 5):
    if cfg.is_encdec:
        specs = encdec.encdec_specs(cfg)
        loss_fn = encdec.loss_fn
    else:
        specs = transformer.lm_specs(cfg)
        loss_fn = transformer.loss_fn
    params = common.init_params(specs, jax.random.key(seed),
                                jnp.dtype(cfg.dtype))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=steps)
    opt_state = adamw.init(opt_cfg, params)

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                             params)
        return params, opt_state, {**metrics, **om}

    losses = []
    for i in range(steps):
        b = synthetic.lm_batch(jax.random.key(seed * 104729 + i), batch, seq,
                               cfg.vocab_size)
        if cfg.is_encdec:
            b["frames"] = jax.random.normal(
                jax.random.key(i), (batch, seq, cfg.d_model)) * 0.1
        if cfg.n_prefix_tokens > 0:
            b["prefix_embeds"] = jax.random.normal(
                jax.random.key(i), (batch, cfg.n_prefix_tokens, cfg.d_model)
            ) * 0.1
        params, opt_state, metrics = step_fn(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d} loss {losses[-1]:.4f}")
    if ckpt_dir:
        checkpoint.save(ckpt_dir, steps, params, name=cfg.arch_id)
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dit-small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    cfg = config_lib.get_config(args.arch)
    if isinstance(cfg, DiTConfig):
        if args.reduced:
            cfg = config_lib.reduced(cfg)
        train_dit(cfg, args.steps, args.batch, args.ckpt)
    else:
        if args.reduced:
            cfg = config_lib.reduced(cfg)
        train_lm(cfg, args.steps, args.batch, args.seq, args.ckpt)


if __name__ == "__main__":
    main()
