"""Policy-object tests: the package's policy names, golden equivalence
against the reference sampler over ``repro.core.cache``, per-lane
isolation in mixed batches, derived warm-up lengths, the FoCa
extension, policy-aware cache-bytes accounting, and the open-loop
Poisson arrival plan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.core import cache as cache_lib
from repro.core import policies
from repro.core.policies import base as policy_base
from repro.diffusion import sampler, schedule
from repro.models import common, dit


@pytest.fixture(scope="module")
def tiny_dit():
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    full_fn, from_crf_fn = dit.denoiser(cfg)

    x0 = jax.random.normal(jax.random.key(1), (2, 8, 8, cfg.in_channels))
    return cfg, full_fn, from_crf_fn, params, x0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_lists_policy_family():
    names = policies.available()
    for expected in ("freqca", "freqca_a", "taylorseer", "fora",
                     "teacache", "none", "foca"):
        assert expected in names


def test_available_names_every_policy_class_once():
    """``available()`` is exactly the names of the concrete policy
    classes in the package, each name one class: a benchmark
    configuration picks its class by this name."""
    import importlib
    import pkgutil
    for mod in pkgutil.iter_modules(policies.__path__):
        importlib.import_module(f"{policies.__name__}.{mod.name}")
    todo, classes = [policy_base.Policy], []
    while todo:
        cls = todo.pop()
        todo.extend(cls.__subclasses__())
        if (cls is not policy_base.Policy
                and cls.__module__.startswith(policies.__name__)):
            classes.append(cls)
    names = [cls.name for cls in classes]
    assert len(set(names)) == len(names), sorted(names)
    assert policies.available() == tuple(sorted(names))


def test_resolve_passes_objects_and_rejects_the_rest():
    pol = policies.FreqCaPolicy(interval=7, rho=0.25, high_order=3)
    assert policies.resolve(pol) is pol            # objects pass through
    assert policies.resolve(
        policies.FreqCaPolicy(interval=7, rho=0.25, high_order=3)) == pol

    class KindOnly:                                # a spec-shaped value
        kind = "freqca"
        interval = 7

    for bad in (42, KindOnly()):
        with pytest.raises(TypeError):
            policies.resolve(bad)
        with pytest.raises(TypeError):
            policies.bank(bad, 1)


def test_policy_metadata_matches_spec():
    assert policies.FreqCaPolicy().cache_units == 4
    assert policies.ForaPolicy().cache_units == 1
    assert policies.TaylorSeerPolicy().cache_units == 3
    assert policies.NoCachePolicy().cache_units == 0
    # warm-up length is derived from the predictor's history needs
    assert policies.FreqCaAdaptivePolicy().needed_history == 3
    assert policies.FreqCaAdaptivePolicy(high_order=4).needed_history == 5


def test_compatibility_keys():
    """Batch-compatibility grouping: static-schedule policies key by the
    activation schedule they produce (so mask-identical families share),
    adaptive policies key by full value (data-dependent masks only share
    with the identical policy)."""
    key = policies.compatibility_key
    none = policies.NoCachePolicy(interval=1)
    # value-equal objects -> identical keys
    assert key(policies.FreqCaPolicy(interval=5)) == \
        key(policies.FreqCaPolicy(interval=5))
    # same (interval, needed_history) static schedule -> one family,
    # across different predictors
    assert key(policies.FreqCaPolicy(interval=5)) == \
        key(policies.TaylorSeerPolicy(interval=5))
    assert key(policies.ForaPolicy(interval=1)) == key(none)
    # schedule differences split the family
    assert key(policies.ForaPolicy(interval=2)) != \
        key(policies.ForaPolicy(interval=3))
    assert key(policies.ForaPolicy(interval=5)) != \
        key(policies.FreqCaPolicy(interval=5))   # warmup differs
    # adaptive policies: value-keyed, never share with static schedules
    a1 = policies.FreqCaAdaptivePolicy(tea_threshold=0.3)
    a2 = policies.FreqCaAdaptivePolicy(tea_threshold=0.2)
    assert key(a1) == key(a1) != key(a2)
    assert key(a1) != key(policies.FreqCaPolicy())
    assert key(policies.TeaCachePolicy()) != key(a1)
    # banks expose the key too: uniform -> the policy's, mixed ->
    # collapsed when every lane is compatible
    assert policies.bank(a1, 2).compatibility_key() == key(a1)
    fam = policies.bank([policies.ForaPolicy(interval=1), none], 2)
    assert fam.compatibility_key() == key(none)
    mixed = policies.bank([a1, none], 2)
    assert mixed.compatibility_key() == (key(a1), key(none))


# ---------------------------------------------------------------------------
# golden equivalence vs the reference sampler
# ---------------------------------------------------------------------------

def _legacy_sample(full_fn, from_crf_fn, params, x_init, ts, policy,
                   crf_shape,
                   crf_dtype=jnp.float32):
    """Verbatim port of the seed sampler (dispatch on the policy's
    ``name`` + sampler-resident tea0 carries) over the reference state
    machines of ``repro.core.cache`` — the golden reference."""
    n_steps = ts.shape[0] - 1
    state0 = cache_lib.init_state(policy, crf_shape, crf_dtype)
    tea0 = (jnp.zeros((), jnp.float32), jnp.zeros_like(x_init),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))

    def step(carry, inp):
        x, state, tea = carry
        i, t_now, t_next = inp
        acc, prev_x, since, err_last = tea

        def full_branch(op):
            x_, state_ = op
            v, crf = full_fn(params, x_, t_now)
            if policy.name == "freqca_a":
                pred = cache_lib.predict(policy, state_, t_now)
                err = jnp.linalg.norm(
                    (pred - crf).astype(jnp.float32)) / jnp.maximum(
                    jnp.linalg.norm(crf.astype(jnp.float32)), 1e-6)
            else:
                err = jnp.zeros((), jnp.float32)
            return v, cache_lib.update(policy, state_, crf, t_now), 1, err

        def cached_branch(op):
            x_, state_ = op
            crf_hat = cache_lib.predict(policy, state_, t_now)
            return (from_crf_fn(params, crf_hat, t_now), state_, 0,
                    jnp.zeros((), jnp.float32))

        if policy.name == "teacache":
            rel = jnp.mean(jnp.abs(x - prev_x)) / jnp.maximum(
                jnp.mean(jnp.abs(prev_x)), 1e-6)
            acc = acc + rel.astype(jnp.float32)
            warm = state.n_valid < 1
            act = warm | (acc > policy.tea_threshold) | (i == 0)
            acc = jnp.where(act, 0.0, acc)
        elif policy.name == "freqca_a":
            warm = state.n_valid < 3
            projected = (since.astype(jnp.float32) + 1.0) * err_last
            act = warm | (projected > policy.tea_threshold)
        else:
            act = cache_lib.should_activate(policy, state, i)
        if policy.name == "none":
            v, state, used, err_new = full_branch((x, state))
        else:
            v, state, used, err_new = jax.lax.cond(
                act, full_branch, cached_branch, (x, state))
        since = jnp.where(jnp.asarray(used, bool), 0, since + 1)
        err_last = jnp.where(jnp.asarray(used, bool), err_new, err_last)
        dt = (t_next - t_now).astype(x.dtype)
        x_new = x + dt * v.astype(x.dtype)
        return (x_new, state, (acc, x, since, err_last)), \
            jnp.asarray(used, jnp.int32)

    idx = jnp.arange(n_steps)
    (x, _, _), used = jax.lax.scan(step, (x_init, state0, tea0),
                                   (idx, ts[:-1], ts[1:]))
    return x, jnp.sum(used)


SEED_CONFIGS = [
    pytest.param(policies.NoCachePolicy(), id="none-dct-5"),
    pytest.param(policies.ForaPolicy(interval=5), id="fora-dct-5"),
    pytest.param(policies.TaylorSeerPolicy(interval=5, high_order=2),
                 id="taylorseer-dct-5"),
    pytest.param(policies.FreqCaPolicy(interval=5, method="dct", rho=0.25),
                 id="freqca-dct-5"),
    pytest.param(policies.FreqCaPolicy(interval=3, method="fft",
                                       rho=0.0625), id="freqca-fft-3"),
    pytest.param(policies.FreqCaPolicy(interval=5, method="none"),
                 id="freqca-none-5"),
]


def _assert_golden(pol, got, want):
    """FreqCa's low band is now cached spectrally: mathematically the
    same projection as the legacy spatial cache, but a different matmul
    association — float tolerance for dct/fft.  ``method="none"`` (zero
    low band) and every non-decomposing policy stay BITWISE equal."""
    if pol.name.startswith("freqca") and pol.method != "none":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pol", SEED_CONFIGS)
def test_golden_equivalence_scheduled(tiny_dit, pol):
    """Registered policy objects match the legacy spatial-cache path on
    the seed configs (scheduled policies, batch > 1) — bitwise except
    for the spectral freqca low band (see _assert_golden)."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    crf_shape = (2, 16, cfg.d_model)
    want_x, want_full = _legacy_sample(full_fn, from_crf_fn, params, x0,
                                       ts, pol, crf_shape)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=crf_shape)
    _assert_golden(pol, res.x, want_x)
    assert int(res.n_full) == int(want_full)
    np.testing.assert_array_equal(np.asarray(res.n_full_lanes),
                                  int(want_full))


@pytest.mark.parametrize("pol", [
    policies.TeaCachePolicy(tea_threshold=0.05),
    policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25),
], ids=lambda p: p.name)
def test_golden_equivalence_adaptive_solo(tiny_dit, pol):
    """Adaptive policies match the legacy path at batch 1, where the
    legacy batch-global decision IS the lane decision.  (At batch > 1
    the new path is per-lane by design — covered below.)"""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    x0 = x0[:1]
    crf_shape = (1, 16, cfg.d_model)
    want_x, want_full = _legacy_sample(full_fn, from_crf_fn, params, x0,
                                       ts, pol, crf_shape)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=crf_shape)
    _assert_golden(pol, res.x, want_x)
    assert int(res.n_full_lanes[0]) == int(want_full)


# ---------------------------------------------------------------------------
# per-lane isolation
# ---------------------------------------------------------------------------

def test_mixed_batch_lane_matches_solo(tiny_dit):
    """A lane keeps its solo-batch behaviour inside a mixed-policy
    batch: the `none` lane matches its solo uncached run, the cached
    lane matches its solo cached run, and per-lane n_full decouple."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(16)
    mix = (policies.NoCachePolicy(interval=1),
           policies.FreqCaPolicy(interval=4, rho=0.25))
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, mix,
                         crf_shape=(2, 16, cfg.d_model))
    assert int(res.n_full_lanes[0]) == 16
    assert int(res.n_full_lanes[1]) < 16
    assert int(res.n_full) == 16        # forwards = union of activations
    for j, pol in enumerate(mix):
        solo = sampler.sample(full_fn, from_crf_fn, params, x0[j:j + 1],
                              ts, pol, crf_shape=(1, 16, cfg.d_model))
        assert int(solo.n_full_lanes[0]) == int(res.n_full_lanes[j])
        np.testing.assert_allclose(np.asarray(res.x[j]),
                                   np.asarray(solo.x[0]), atol=1e-5)


def test_uniform_adaptive_batch_is_per_lane(tiny_dit):
    """A single adaptive policy over a batch now decides per lane: each
    lane matches its solo run even when the other lane's content would
    have flipped the old batch-global decision."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    pol = policies.FreqCaAdaptivePolicy(tea_threshold=0.3, rho=0.25)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=(2, 16, cfg.d_model))
    for j in range(2):
        solo = sampler.sample(full_fn, from_crf_fn, params, x0[j:j + 1],
                              ts, pol, crf_shape=(1, 16, cfg.d_model))
        assert int(solo.n_full_lanes[0]) == int(res.n_full_lanes[j])
        np.testing.assert_allclose(np.asarray(res.x[j]),
                                   np.asarray(solo.x[0]), atol=1e-5)


# ---------------------------------------------------------------------------
# derived warm-up (satellite: no hard-coded `n_valid < 3`)
# ---------------------------------------------------------------------------

def test_freqca_a_warmup_follows_high_order(tiny_dit):
    """With an unbounded error budget freqca_a activates exactly its
    warm-up steps — which must track `high_order`, not the old
    hard-coded 3, so a bigger ring is never sampled underfilled."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    for high_order, want in [(2, 3), (4, 5)]:
        pol = policies.FreqCaAdaptivePolicy(
            tea_threshold=1e9, high_order=high_order, rho=0.25)
        res = sampler.sample(full_fn, from_crf_fn, params, x0[:1], ts, pol,
                             crf_shape=(1, 16, cfg.d_model))
        assert int(res.n_full_lanes[0]) == want, (high_order, want)


# ---------------------------------------------------------------------------
# FoCa (a policy added without touching the sampler)
# ---------------------------------------------------------------------------

def _ctx(t, batch=1, feat_shape=(4,)):
    return policy_base.StepContext(
        step_idx=jnp.asarray(0), t_now=jnp.asarray(t),
        x=jnp.zeros((batch, 1)), batch=batch, feat_shape=feat_shape)


def test_foca_calibrated_forecast():
    """FoCa = TaylorSeer forecast + per-lane gain calibration: exact on
    a linear trajectory (gain 1), gain-corrected under uniform drift."""
    pol = policies.FoCaPolicy(interval=3, high_order=1)
    traj = lambda t: jnp.full((1, 4), 2.0 - t)
    state = pol.init(1, (4,))
    for t in [1.0, 0.8, 0.6]:
        state = pol.update(state, traj(t), _ctx(t))
    np.testing.assert_allclose(np.asarray(state.gain), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pol.predict(state, _ctx(0.4))),
                               1.6, atol=1e-3)
    # trajectory jumps to 1.5x the forecast -> gain refits toward 1.5
    state = pol.update(state, 1.5 * traj(0.4), _ctx(0.4))
    assert abs(float(state.gain[0]) - 1.5) < 0.01
    # ... and is clipped to calib_clip under extreme drift
    state = pol.update(state, 100.0 * traj(0.2), _ctx(0.2))
    assert float(state.gain[0]) == pytest.approx(pol.calib_clip)


def test_foca_samples_end_to_end(tiny_dit):
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(20)
    res = sampler.sample(full_fn, from_crf_fn, params, x0, ts,
                         policies.FoCaPolicy(interval=5),
                         crf_shape=(2, 16, cfg.d_model))
    assert bool(jnp.isfinite(res.x).all())
    assert int(res.n_full) < 20


# ---------------------------------------------------------------------------
# cache-bytes accounting (satellite: dummy slots excluded)
# ---------------------------------------------------------------------------

def test_cache_bytes_excludes_dummy_low_slot():
    feat = (1, 32, 16)
    for pol in (policies.TaylorSeerPolicy(high_order=2),
                policies.FoCaPolicy(high_order=2), policies.ForaPolicy(),
                policies.TeaCachePolicy()):
        state = cache_lib.init_state(pol, feat)
        raw = cache_lib.cache_bytes(state)
        real = cache_lib.cache_bytes(state, pol)
        dummy = (state.low_hist.size * state.low_hist.dtype.itemsize
                 + state.ts_low.size * state.ts_low.dtype.itemsize)
        assert real == raw - dummy, pol.name
        # memory scales with cache_units, matching §4.4.1 accounting
        per_unit = (state.high_hist.size // pol.cache_units
                    * state.high_hist.dtype.itemsize)
        assert real >= per_unit * pol.cache_units, pol.name
    pol = policies.NoCachePolicy(interval=1)
    assert cache_lib.cache_bytes(cache_lib.init_state(pol, feat), pol) == 0
    # freqca uses both bands: nothing excluded
    pol = policies.FreqCaPolicy()
    state = cache_lib.init_state(pol, feat)
    assert cache_lib.cache_bytes(state, pol) == cache_lib.cache_bytes(state)
    # the new policy objects carry no dummy slots at all
    obj = policies.TaylorSeerPolicy(high_order=2)
    st = obj.init(1, feat)
    want = (np.prod((1, 3) + feat) * 4      # hist [B, K, *feat] f32
            + 3 * 4                          # ts [B, K]
            + 4                              # head [B] int32 (slot ptr)
            + 4)                             # n_valid [B] int32
    assert obj.state_bytes(st) == want


# ---------------------------------------------------------------------------
# slot-pointer ring (satellite: ring_push touches one slot, not the ring)
# ---------------------------------------------------------------------------

def _roll_push(vals, ts, v, t):
    """The old O(K·S·D) roll implementation — the regression oracle."""
    vals = jnp.roll(vals, -1, axis=1).at[:, -1].set(v)
    ts = jnp.roll(ts, -1, axis=1).at[:, -1].set(t)
    return vals, ts


def test_ring_pointer_matches_roll():
    """Pointer ring == roll ring through >K pushes (head wraps): the
    recency-ordered view, ring_last, and ring_predict are bit-equal."""
    from repro.core import hermite
    k, batch, feat = 3, 2, (4, 5)
    ring = policy_base.ring_init(batch, k, feat)
    rvals, rts = ring.vals, ring.ts
    rng = jax.random.key(7)
    for t in [1.0, 0.9, 0.8, 0.7, 0.6]:
        rng, sub = jax.random.split(rng)
        v = jax.random.normal(sub, (batch,) + feat)
        ring = policy_base.ring_push(ring, v, t)
        rvals, rts = _roll_push(rvals, rts, v, t)
        ts_o, vals_o = policy_base.ring_ordered(ring)
        np.testing.assert_array_equal(np.asarray(ts_o), np.asarray(rts))
        np.testing.assert_array_equal(np.asarray(vals_o), np.asarray(rvals))
        np.testing.assert_array_equal(
            np.asarray(policy_base.ring_last(ring)),
            np.asarray(rvals[:, -1]))
        want = jax.vmap(
            lambda a, b: hermite.predict(a, b, 0.5, 2))(rts, rvals)
        np.testing.assert_array_equal(
            np.asarray(policy_base.ring_predict(ring, 0.5, 2)),
            np.asarray(want))


def test_ring_slot_weights_permute_fold():
    """Slot-indexed folded weights applied to the raw (cyclic) ring
    reproduce the recency-ordered prediction."""
    k, batch, feat = 4, 2, (8,)
    ring = policy_base.ring_init(batch, k, feat)
    rng = jax.random.key(8)
    for t in [1.0, 0.8, 0.6, 0.5, 0.45, 0.4]:   # head wraps past K
        rng, sub = jax.random.split(rng)
        ring = policy_base.ring_push(
            ring, jax.random.normal(sub, (batch,) + feat), t)
    w = policy_base.ring_slot_weights(ring, 0.3, 2)
    got = jnp.einsum("bk,bk...->b...", w, ring.vals)
    want = policy_base.ring_predict(ring, 0.3, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# spectral low-band cache (tentpole)
# ---------------------------------------------------------------------------

def test_freqca_state_is_spectral_and_small():
    """The low ring holds kept_bins(S, rho) coefficient rows — ≥10x
    smaller than the spatial low ring at the paper's rho (ISSUE
    acceptance), with state_bytes reporting the real footprint."""
    from repro.core import frequency
    s, d, rho = 256, 64, 0.0625
    pol = policies.FreqCaPolicy(interval=5, method="dct", rho=rho)
    state = pol.init(2, (s, d))
    m = frequency.kept_bins(s, rho, "dct")
    assert state.low.vals.shape == (2, pol.k_low, m, d)
    assert state.high.vals.shape == (2, pol.k_high, s, d)
    low_bytes = sum(x.size * x.dtype.itemsize for x in state.low)
    spatial_low_bytes = 2 * pol.k_low * s * d * 4
    assert low_bytes * 10 <= spatial_low_bytes, (low_bytes,
                                                 spatial_low_bytes)
    assert pol.state_bytes(state) < (2 * (pol.k_low + pol.k_high)
                                     * s * d * 4)
    # freqca_a shares the spectral layout
    pol_a = policies.FreqCaAdaptivePolicy(rho=rho)
    st_a = pol_a.init(1, (s, d))
    assert st_a.low.vals.shape == (1, pol_a.k_low, m, d)


def test_spectral_predict_reconstructs_low_band():
    """update→predict round-trip: with a full ring, prediction equals
    synthesised low + Hermite high — and, for a band-limited constant
    trajectory, exactly the cached signal."""
    from repro.core import frequency
    s, d = 32, 8
    pol = policies.FreqCaPolicy(interval=5, method="dct", rho=0.25,
                                high_order=2)
    z = frequency.decompose(
        jax.random.normal(jax.random.key(9), (1, s, d)), 0.25, "dct").low
    state = pol.init(1, (s, d))
    for t in [1.0, 0.8, 0.6]:
        state = pol.update(state, z, _ctx(t, feat_shape=(s, d)))
    pred = pol.predict(state, _ctx(0.4, feat_shape=(s, d)))
    np.testing.assert_allclose(np.asarray(pred), np.asarray(z), atol=1e-3)


@pytest.mark.pallas
def test_sampler_pallas_dispatch_matches_xla(tiny_dit, monkeypatch):
    """Full sample() under REPRO_KERNELS=pallas (interpret) matches the
    XLA dispatch path — the CI guard that keeps the kernel-backed cache
    datapath from rotting."""
    cfg, full_fn, from_crf_fn, params, x0 = tiny_dit
    ts = schedule.timesteps(12)
    pol = policies.FreqCaPolicy(interval=4, method="dct", rho=0.25)
    crf_shape = (2, 16, cfg.d_model)
    monkeypatch.setenv("REPRO_KERNELS", "xla")
    want = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                          crf_shape=crf_shape)
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    got = sampler.sample(full_fn, from_crf_fn, params, x0, ts, pol,
                         crf_shape=crf_shape)
    assert int(got.n_full) == int(want.n_full)
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                               atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# Poisson arrival plan (satellite: open-loop client)
# ---------------------------------------------------------------------------

def test_poisson_stream_plan():
    from repro.launch.serve import poisson_stream
    plan = poisson_stream(200, rate=4.0, size=8, channels=4,
                          edit_every=5, seed=3)
    times = [r.arrival_s for r in plan]   # unified request API: the
    assert len(plan) == 200               # request carries its arrival
    assert all(b > a for a, b in zip(times, times[1:], strict=False))
    gaps = np.diff([0.0] + times)
    assert abs(float(np.mean(gaps)) - 0.25) < 0.06    # mean ~ 1/rate
    # deterministic for a fixed seed; different seed -> different plan
    again = poisson_stream(200, rate=4.0, size=8, channels=4,
                           edit_every=5, seed=3)
    assert [r.arrival_s for r in again] == times
    other = poisson_stream(200, rate=4.0, size=8, channels=4,
                           edit_every=5, seed=4)
    assert [r.arrival_s for r in other] != times
    # editing requests keep their cadence inside the plan
    assert all(plan[i].init_latents is not None
               for i in range(4, 200, 5))
    with pytest.raises(ValueError):
        poisson_stream(4, rate=0.0, size=8, channels=4)
