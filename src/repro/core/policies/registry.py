"""Policy lookup + per-lane policy banks.

``available()`` names the policy classes the package exports (a
benchmark configuration selects a class by that ``name``); ``resolve``
checks that a value is a :class:`~repro.core.policies.base.Policy`
instance.

``bank(policy, batch)`` turns a policy — or a per-lane sequence of
policies — into a :class:`PolicyBank`, the object the sampler actually
drives.  A bank exposes the same four-method protocol batched over
lanes plus two static flags:

* ``scalar_decision`` — the mask is batch-uniform by construction
  (single non-adaptive policy), so the sampler may branch with a scalar
  ``lax.cond`` and skip the per-lane select entirely (the seed fast
  path, preserved bit-for-bit).
* ``always_full`` — every lane is the ``none`` policy; no branch at all.

Mixed banks hold one state pytree per lane (static tuple — fine at
serving batch sizes) so lanes with different policies, and therefore
different state *structures*, share one compiled executable.
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax.numpy as jnp

from repro.core.policies import base


def available() -> Tuple[str, ...]:
    """Sorted ``name``s of the policy classes the package exports."""
    from repro.core import policies
    return tuple(sorted(
        v.name for v in vars(policies).values()
        if isinstance(v, type) and issubclass(v, base.Policy)
        and v is not base.Policy))


def resolve(policy) -> base.Policy:
    """``policy`` itself if it is a Policy instance, else TypeError."""
    if not isinstance(policy, base.Policy):
        raise TypeError(f"expected a Policy object, got {policy!r}")
    return policy


def compatibility_key(policy: base.Policy) -> Tuple:
    """Batch-compatibility key of a policy (see
    :meth:`~repro.core.policies.base.Policy.compatibility_key`).  The
    scheduler groups requests by this key so every cut batch is
    policy-homogeneous."""
    return resolve(policy).compatibility_key()


# ---------------------------------------------------------------------------
# per-lane banks
# ---------------------------------------------------------------------------

class PolicyBank:
    """Per-lane policy assignment for one sampler batch (abstract)."""
    scalar_decision: bool
    always_full: bool
    # any lane consumes realized-error observations (static: the
    # sampler only adds the measure/observe ops when True, so banks
    # without feedback trace bit-identically to before)
    uses_error_feedback: bool = False
    batch: int

    def compatibility_key(self):
        """Single key when every lane is batch-compatible, else the
        per-lane key tuple (only ungrouped schedulers cut such banks)."""
        raise NotImplementedError

    def init(self, feat_shape, crf_dtype, latent_shape, latent_dtype):
        raise NotImplementedError

    def decide(self, state, ctx: base.StepContext):
        raise NotImplementedError

    def apply_update(self, state, crf, ctx: base.StepContext, mask):
        """Push ``crf`` and merge the result into the masked lanes."""
        raise NotImplementedError

    def predict(self, state, ctx: base.StepContext):
        raise NotImplementedError

    # --- error feedback ---------------------------------------------------
    def measure_error(self, state, crf, ctx: base.StepContext):
        """Per-lane realized-error measurement (pre-update state)."""
        raise NotImplementedError

    def observe(self, state, err, ctx: base.StepContext, mask):
        """Feed measurements back, merged into the masked lanes only
        (a lane alone would not have measured on a step it skipped)."""
        raise NotImplementedError

    def error_feedback(self, state):
        """[B]-shaped :class:`~repro.core.policies.base.ErrorFeedback`
        extracted from the final state, or ``None``."""
        return None


class UniformBank(PolicyBank):
    """Every lane runs the same policy; state is batched in one pytree."""

    def __init__(self, policy: base.Policy, batch: int):
        self.policy = policy
        self.batch = batch
        self.scalar_decision = not policy.per_lane
        self.always_full = policy.name == "none"
        self.uses_error_feedback = policy.uses_error_feedback

    def compatibility_key(self):
        return self.policy.compatibility_key()

    def init(self, feat_shape, crf_dtype, latent_shape, latent_dtype):
        return self.policy.init(self.batch, feat_shape, crf_dtype,
                                latent_shape=latent_shape,
                                latent_dtype=latent_dtype)

    def decide(self, state, ctx):
        return self.policy.decide(state, ctx)

    def apply_update(self, state, crf, ctx, mask):
        new = self.policy.update(state, crf, ctx)
        if self.scalar_decision:
            # the sampler only enters the full branch when the (uniform)
            # mask is True, so every lane activated — no select needed
            return new
        return base.lane_select(mask, new, state)

    def predict(self, state, ctx):
        return self.policy.predict(state, ctx)

    def measure_error(self, state, crf, ctx):
        return self.policy.measure_error(state, crf, ctx)

    def observe(self, state, err, ctx, mask):
        new = self.policy.observe(state, err, ctx)
        return base.lane_select(mask, new, state)

    def error_feedback(self, state):
        return self.policy.error_feedback(state)


class MixedBank(PolicyBank):
    """One policy per lane; state is a static tuple of lane-1 pytrees."""

    def __init__(self, policies: Sequence[base.Policy]):
        self.policies = tuple(policies)
        self.batch = len(self.policies)
        self.scalar_decision = False
        self.always_full = all(p.name == "none" for p in self.policies)
        self.uses_error_feedback = any(p.uses_error_feedback
                                       for p in self.policies)

    def compatibility_key(self):
        keys = tuple(p.compatibility_key() for p in self.policies)
        return keys[0] if all(k == keys[0] for k in keys) else keys

    def init(self, feat_shape, crf_dtype, latent_shape, latent_dtype):
        return tuple(p.init(1, feat_shape, crf_dtype,
                            latent_shape=latent_shape,
                            latent_dtype=latent_dtype)
                     for p in self.policies)

    def decide(self, state, ctx):
        states, masks = [], []
        for j, pol in enumerate(self.policies):
            st, m = pol.decide(state[j], ctx.lane(j))
            states.append(st)
            masks.append(m)
        return tuple(states), jnp.concatenate(masks)

    def apply_update(self, state, crf, ctx, mask):
        out = []
        for j, pol in enumerate(self.policies):
            new = pol.update(state[j], crf[j:j + 1], ctx.lane(j))
            out.append(base.lane_select(mask[j:j + 1], new, state[j]))
        return tuple(out)

    def predict(self, state, ctx):
        return jnp.concatenate([
            pol.predict(state[j], ctx.lane(j))
            for j, pol in enumerate(self.policies)])

    def measure_error(self, state, crf, ctx):
        # per-lane tuple: error shapes may differ across policies
        # (freqca_eb reports per-band pairs); None for lanes that
        # consume no feedback
        return tuple(
            pol.measure_error(state[j], crf[j:j + 1], ctx.lane(j))
            if pol.uses_error_feedback else None
            for j, pol in enumerate(self.policies))

    def observe(self, state, err, ctx, mask):
        out = []
        for j, pol in enumerate(self.policies):
            if pol.uses_error_feedback:
                new = pol.observe(state[j], err[j], ctx.lane(j))
                out.append(base.lane_select(mask[j:j + 1], new, state[j]))
            else:
                out.append(state[j])
        return tuple(out)

    def error_feedback(self, state):
        if not self.uses_error_feedback:
            return None
        parts = []
        for j, pol in enumerate(self.policies):
            fb = pol.error_feedback(state[j])
            if fb is None:
                fb = base.ErrorFeedback(
                    realized=jnp.zeros((1,), jnp.float32),
                    events=jnp.zeros((1,), jnp.int32))
            parts.append(fb)
        return base.ErrorFeedback(
            realized=jnp.concatenate([p.realized for p in parts]),
            events=jnp.concatenate([p.events for p in parts]))


def bank(policy: Union[base.Policy, Sequence[base.Policy]],
         batch: int) -> PolicyBank:
    """Policy or per-lane sequence of policies -> PolicyBank."""
    if isinstance(policy, (list, tuple)):
        lanes = tuple(resolve(p) for p in policy)
        if len(lanes) != batch:
            raise ValueError(f"got {len(lanes)} lane policies for "
                             f"batch {batch}")
        if all(p == lanes[0] for p in lanes):
            return UniformBank(lanes[0], batch)
        return MixedBank(lanes)
    return UniformBank(resolve(policy), batch)
