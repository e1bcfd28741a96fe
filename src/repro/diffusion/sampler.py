"""Diffusion sampling loop, policy-agnostic with per-lane activation.

The whole sampler is one ``lax.scan`` over timesteps.  The cache policy
is a self-contained object from ``repro.core.policies`` (or a per-lane
sequence of them — one policy per batch lane), driven through the
four-method bank protocol; the sampler never dispatches on policy
names.

Each step the bank's ``decide`` returns a per-lane activation mask:

* batch-uniform mask (single non-adaptive policy) — scalar ``lax.cond``
  between the full branch (denoiser forward + cache update) and the
  cached branch (CRF prediction + final layer only): the seed fast
  path, one compiled program, full skip-compute win;
* lane-varying mask (adaptive policies / mixed banks) — the full
  forward runs iff *any* lane activates (``lax.cond``), and each lane's
  velocity and cache state are selected per lane with ``jnp.where``, so
  a mixed generation+editing batch never shares one global activation
  decision.  A lane behaves exactly as it would alone in the batch.

The denoiser is abstract: ``full_fn(params, x, t, cond) -> (velocity,
crf)`` and ``from_crf_fn(params, crf, t, cond) -> velocity``; both DiT
and backbone-wrapped assigned architectures plug in (repro.models.dit).
The weights travel as the ``params`` argument, never as closure
constants, so a jitted sampler takes them as inputs and its executable
embeds none of them.  ``cond`` is the batch's conditioning pytree (text
tokens, pooled vector, guidance, reference latents: ``dit.cond_kwargs``),
the same at every step and handed to both the full and the cached step;
``()`` for an unconditioned model, which adds no input to the program.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.policies import base as policy_base
from repro.core.policies import registry as policy_registry

PolicyArg = Union[policy_base.Policy, Sequence[policy_base.Policy]]

# name scopes of the two step bodies: every op of a full step (the
# denoiser forward and the cache update) or of a cached step (the CRF
# prediction and the final layer) carries one in its op metadata, so a
# device trace splits the sampler's time by step kind
FULL_STEP = "sampler.full_step"
CACHED_STEP = "sampler.cached_step"


class SampleResult(NamedTuple):
    x: jnp.ndarray                  # final latents
    n_full: jnp.ndarray             # [] — batch forwards (compute) count
    n_full_lanes: Optional[jnp.ndarray] = None   # [B] activated steps/lane
    trajectory: Optional[jnp.ndarray] = None
    # [B]-shaped realized-error report when any lane's policy consumes
    # error feedback (freqca_eb), else None
    feedback: Optional[policy_base.ErrorFeedback] = None


def sample(full_fn: Callable, from_crf_fn: Callable, params,
           x_init: jnp.ndarray, ts: jnp.ndarray, policy: PolicyArg,
           crf_shape: Tuple[int, ...], crf_dtype=jnp.float32,
           return_trajectory: bool = False, cond=()) -> SampleResult:
    """Euler rectified-flow sampling from t=1 to t=0 under a cache policy.

    ts: [n_steps+1] decreasing times.  crf_shape: [B, *feat] shape of the
    CRF feature (needed to build the static cache state).  ``policy``
    is a Policy object or a per-lane sequence of them (len == batch) for
    mixed-policy batches.  ``cond``: the batch's conditioning pytree,
    passed to every denoiser call.
    """
    n_steps = ts.shape[0] - 1
    batch = x_init.shape[0]
    feat_shape = tuple(crf_shape[1:])
    bank = policy_registry.bank(policy, batch)
    state0 = bank.init(feat_shape, crf_dtype,
                       latent_shape=x_init.shape[1:],
                       latent_dtype=x_init.dtype)

    def step(carry, inp):
        x, state = carry
        i, t_now, t_next = inp
        ctx = policy_base.StepContext(step_idx=i, t_now=t_now, x=x,
                                      batch=batch, feat_shape=feat_shape,
                                      crf_dtype=crf_dtype)
        state, mask = bank.decide(state, ctx)

        @jax.named_scope(FULL_STEP)
        def full_branch(op):
            x_, state_ = op
            v_full, crf = full_fn(params, x_, t_now, cond)
            if bank.uses_error_feedback:
                # score the prediction the cache WOULD have served for
                # this step (pre-update state) against the fresh CRF,
                # then feed it back after the push — the feedback loop
                # only costs ops for policies that opted in (static
                # flag), so everything else traces bit-identically
                err = bank.measure_error(state_, crf, ctx)
                state_ = bank.apply_update(state_, crf, ctx, mask)
                state_ = bank.observe(state_, err, ctx, mask)
            else:
                state_ = bank.apply_update(state_, crf, ctx, mask)
            if bank.scalar_decision:
                return v_full.astype(x_.dtype), state_
            # lanes that did not activate keep their own schedule: they
            # consume the cached prediction even though the batch paid
            # for a forward (quality decoupling across lanes)
            v_hat = from_crf_fn(params, bank.predict(state_, ctx), t_now,
                                cond)
            m = mask.reshape((batch,) + (1,) * (v_full.ndim - 1))
            v = jnp.where(m, v_full, v_hat.astype(v_full.dtype))
            return v.astype(x_.dtype), state_

        @jax.named_scope(CACHED_STEP)
        def cached_branch(op):
            x_, state_ = op
            v = from_crf_fn(params, bank.predict(state_, ctx), t_now, cond)
            return v.astype(x_.dtype), state_

        if bank.always_full:
            act = jnp.asarray(True)
            v, state = full_branch((x, state))
        else:
            act = mask[0] if bank.scalar_decision else jnp.any(mask)
            v, state = jax.lax.cond(act, full_branch, cached_branch,
                                    (x, state))
        dt = (t_next - t_now).astype(x.dtype)
        x_new = x + dt * v
        out = (x_new if return_trajectory else (),
               jnp.asarray(act, jnp.int32), mask.astype(jnp.int32))
        return (x_new, state), out

    idx = jnp.arange(n_steps)
    (x, state), (traj, fwd, used) = jax.lax.scan(step, (x_init, state0),
                                                 (idx, ts[:-1], ts[1:]))
    feedback = (bank.error_feedback(state)
                if bank.uses_error_feedback else None)
    return SampleResult(x=x, n_full=jnp.sum(fwd),
                        n_full_lanes=jnp.sum(used, axis=0),
                        trajectory=traj if return_trajectory else None,
                        feedback=feedback)


def reference_features(full_fn: Callable, params, x_init: jnp.ndarray,
                       ts: jnp.ndarray, cond=()):
    """Run the un-cached sampler, returning per-step (x, crf) trajectories."""
    def step(x, tt):
        t_now, t_next = tt
        v, crf = full_fn(params, x, t_now, cond)
        x_next = x + (t_next - t_now).astype(x.dtype) * v.astype(x.dtype)
        return x_next, (x_next, crf)

    x, (xs, crfs) = jax.lax.scan(step, x_init, (ts[:-1], ts[1:]))
    return x, xs, crfs
