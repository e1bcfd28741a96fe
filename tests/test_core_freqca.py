"""Unit + property tests for the paper's core: frequency decomposition,
Hermite prediction, CRF caching, and the policy state machines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# property tests skip gracefully when hypothesis is absent (CI installs
# it via `pip install -e .[dev]`; the bare tier-1 env may not have it)
# while the deterministic tests below keep running either way; the
# shared "ci" profile and no-hypothesis shim live in hypothesis_compat
from hypothesis_compat import given, st  # noqa: E402

from repro.core import cache as cache_lib
from repro.core import frequency, hermite, policies

# ---------------------------------------------------------------------------
# frequency decomposition
# ---------------------------------------------------------------------------

@given(st.sampled_from(["fft", "dct"]),
       st.integers(min_value=4, max_value=64),
       st.floats(min_value=0.02, max_value=0.9),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_band_partition(method, s, rho, seed):
    """low + high == z exactly (the split is a partition) — paper eq. 1."""
    z = jax.random.normal(jax.random.key(seed), (2, s, 8))
    b = frequency.decompose(z, rho, method)
    np.testing.assert_allclose(np.asarray(b.low + b.high), np.asarray(z),
                               atol=1e-5)


@given(st.sampled_from(["fft", "dct"]),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_band_orthogonality(method, seed):
    """Low/high bands are orthogonal (Parseval: energies add up)."""
    z = jax.random.normal(jax.random.key(seed), (1, 32, 4))
    b = frequency.decompose(z, 0.25, method)
    e_low = float(jnp.sum(b.low.astype(jnp.float32) ** 2))
    e_high = float(jnp.sum(b.high.astype(jnp.float32) ** 2))
    e_tot = float(jnp.sum(z.astype(jnp.float32) ** 2))
    assert abs(e_low + e_high - e_tot) / e_tot < 1e-4


def test_constant_signal_is_all_low():
    z = jnp.ones((1, 32, 4)) * 3.0
    for method in ("fft", "dct"):
        b = frequency.decompose(z, 0.1, method)
        assert float(jnp.abs(b.high).max()) < 1e-5, method


def test_nyquist_signal_is_all_high():
    s = 32
    alt = jnp.tile(jnp.array([1.0, -1.0]), s // 2)[None, :, None]
    # FFT: the alternating signal is exactly the Nyquist bin -> zero low
    b = frequency.decompose(alt, 0.1, "fft")
    assert float(jnp.abs(b.low).max()) < 1e-4
    # DCT-II: it is *almost* the top basis vector (phase taper leaks a
    # little); low-band energy must still be tiny
    b = frequency.decompose(alt, 0.1, "dct")
    e_low = float(jnp.sum(b.low ** 2))
    e_tot = float(jnp.sum(alt ** 2))
    assert e_low / e_tot < 0.02


def test_low_pass_band_width_consistency():
    """FFT and DCT decompose the same band for the same rho: kept-bin
    counts agree within one bin (the FFT's conjugate-symmetry rounding —
    DC + whole ± pairs — rounds an even target up, never down).
    Regression: rho=0.5, n=8 used to keep 4 DCT bins but only 3 FFT
    bins, so the two methods split different bands."""
    for n, rho in [(8, 0.5), (16, 0.25), (32, 0.1), (64, 0.0625),
                   (7, 0.5), (8, 1.0)]:
        kept = {}
        for method in ("fft", "dct"):
            mask = frequency.low_pass_mask(n, rho, method)
            kept[method] = int(jnp.sum(mask))
            assert kept[method] == frequency.kept_bins(n, rho, method)
        m = min(max(int(round(n * rho)), 1), n)
        assert kept["dct"] == m
        assert abs(kept["fft"] - kept["dct"]) <= 1
        assert kept["fft"] >= kept["dct"]     # rounds up, never narrower


@given(st.integers(min_value=2, max_value=128),
       st.floats(min_value=0.01, max_value=1.0))
def test_low_pass_kept_fraction_agrees(n, rho):
    """Property: for any (n, rho) the FFT and DCT masks keep the same
    fraction of the spectrum within one bin."""
    kd = int(jnp.sum(frequency.low_pass_mask(n, rho, "dct")))
    kf = int(jnp.sum(frequency.low_pass_mask(n, rho, "fft")))
    assert kd == min(max(int(round(n * rho)), 1), n)
    assert abs(kf - kd) <= 1
    assert kd <= kf <= n


def test_low_band_basis_factorises_projection():
    """B: [m, n] orthonormal rows with L = BᵀB — the spectral cache
    representation spans exactly the masked-transform low band."""
    for method in ("fft", "dct"):
        for n, rho in [(16, 0.25), (64, 0.0625), (8, 0.5), (8, 1.0),
                       (7, 0.5)]:
            b = np.asarray(frequency._low_band_basis_np(n, rho, method))
            assert b.shape == (frequency.spectral_kept_bins(n, rho,
                                                            method), n)
            np.testing.assert_allclose(b @ b.T, np.eye(b.shape[0]),
                                       atol=1e-10)
            z = np.asarray(jax.random.normal(jax.random.key(3), (2, n, 4)))
            low = np.einsum("ms,bsd->bmd", b, z)
            recon = np.einsum("ms,bmd->bsd", b, low)
            bands = frequency.decompose(jnp.asarray(z), rho, method)
            np.testing.assert_allclose(recon, np.asarray(bands.low),
                                       atol=1e-5)
    # method="none": an all-zero basis row — empty low band, static shape
    b = np.asarray(frequency._low_band_basis_np(16, 0.25, "none"))
    assert b.shape == (1, 16) and not b.any()
    assert frequency.spectral_kept_bins(16, 0.25, "none") == 1


def test_decompose_idempotent():
    """Low band of the low band is the low band (projection)."""
    z = jax.random.normal(jax.random.key(0), (1, 64, 8))
    b = frequency.decompose(z, 0.25, "dct")
    b2 = frequency.decompose(b.low, 0.25, "dct")
    np.testing.assert_allclose(np.asarray(b2.low), np.asarray(b.low),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Hermite predictor
# ---------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=2),
       st.floats(min_value=-2, max_value=2),
       st.floats(min_value=-2, max_value=2),
       st.floats(min_value=-2, max_value=2))
def test_hermite_exact_on_polynomials(order, c0, c1, c2):
    """With K = order+1 points the fit reproduces any degree<=order poly."""
    coeffs = [c0, c1, c2][: order + 1]

    def poly(t):
        return sum(c * t ** i for i, c in enumerate(coeffs))

    ts = jnp.array([1.0, 0.8, 0.6][: order + 1])
    vals = jnp.stack([jnp.full((3, 3), poly(float(t))) for t in ts])
    pred = hermite.predict(ts, vals, 0.4, order=order)
    np.testing.assert_allclose(np.asarray(pred), poly(0.4), atol=5e-3)


def test_hermite_basis_recurrence():
    s = jnp.linspace(-1, 1, 7)
    b = hermite.hermite_basis(s, 3)
    np.testing.assert_allclose(np.asarray(b[:, 0]), 1.0)
    np.testing.assert_allclose(np.asarray(b[:, 1]), np.asarray(s), atol=1e-6)
    np.testing.assert_allclose(np.asarray(b[:, 2]), np.asarray(s * s - 1),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(b[:, 3]),
                               np.asarray(s ** 3 - 3 * s), atol=1e-5)


def test_hermite_interpolates_samples():
    """Evaluating at a cached timestamp returns the cached value."""
    ts = jnp.array([0.9, 0.6, 0.3])
    vals = jax.random.normal(jax.random.key(0), (3, 4, 4))
    for i, t in enumerate([0.9, 0.6, 0.3]):
        pred = hermite.predict(ts, vals, t, order=2)
        np.testing.assert_allclose(np.asarray(pred), np.asarray(vals[i]),
                                   atol=2e-3)


# ---------------------------------------------------------------------------
# cache policies
# ---------------------------------------------------------------------------

def _fill(policy, shape, traj, ts):
    st_ = cache_lib.init_state(policy, shape)
    for t in ts:
        st_ = cache_lib.update(policy, st_, traj(t), t)
    return st_


def test_fora_reuses_last():
    pol = policies.ForaPolicy(interval=3)
    shape = (1, 8, 4)
    traj = lambda t: jnp.full(shape, t)
    st_ = _fill(pol, shape, traj, [1.0, 0.8])
    np.testing.assert_allclose(np.asarray(cache_lib.predict(pol, st_, 0.6)),
                               0.8, atol=1e-6)


def test_taylorseer_extrapolates_quadratic():
    pol = policies.TaylorSeerPolicy(interval=3, high_order=2)
    shape = (1, 8, 4)
    traj = lambda t: jnp.full(shape, 1.0 + 2 * t - t * t)
    st_ = _fill(pol, shape, traj, [1.0, 0.8, 0.6])
    want = 1.0 + 2 * 0.4 - 0.16
    np.testing.assert_allclose(np.asarray(cache_lib.predict(pol, st_, 0.4)),
                               want, atol=1e-3)


def test_freqca_separates_bands():
    """Low band (constant) reused; high band (alternating) predicted."""
    s = 16
    pol = policies.FreqCaPolicy(interval=3, method="dct", rho=0.25,
                                high_order=2)
    alt = jnp.tile(jnp.array([1.0, -1.0]), s // 2)[None, :, None]
    alt = jnp.broadcast_to(alt, (1, s, 4))

    def traj(t):  # low: const 5t ; high: alternating with quadratic scale
        return jnp.full((1, s, 4), 5.0 * t) + alt * (t * t)

    st_ = _fill(pol, (1, s, 4), traj, [1.0, 0.8, 0.6])
    pred = cache_lib.predict(pol, st_, 0.4)
    # low-frequency part should be the REUSED value 5*0.6 = 3.0 …
    mean_part = float(jnp.mean(pred))
    assert abs(mean_part - 3.0) < 1e-2
    # … while the high band extrapolates t^2 -> 0.16
    high_amp = float(jnp.mean(pred * alt))
    assert abs(high_amp - 0.16) < 2e-2


def test_should_activate_schedule_and_warmup():
    pol = policies.FreqCaPolicy(interval=4, high_order=2)
    st_ = cache_lib.init_state(pol, (1, 4, 4))
    # no history yet -> always activate (warmup)
    assert bool(cache_lib.should_activate(pol, st_, jnp.asarray(1)))
    for t in [1.0, 0.9, 0.8]:
        st_ = cache_lib.update(pol, st_, jnp.zeros((1, 4, 4)), t)
    assert not bool(cache_lib.should_activate(pol, st_, jnp.asarray(1)))
    assert bool(cache_lib.should_activate(pol, st_, jnp.asarray(4)))


def test_cache_units_match_paper():
    """Paper §4.4.1: FreqCa = 1 + 3 = 4 units; layer-wise = 2(m+1)L."""
    pol = policies.FreqCaPolicy(low_order=0, high_order=2)
    assert pol.cache_units == 4
    assert policies.ForaPolicy().cache_units == 1
    assert policies.TaylorSeerPolicy(high_order=2).cache_units == 3


def test_cache_bytes_o1_vs_layerwise():
    feat = (2, 64, 32)
    pol = policies.FreqCaPolicy(high_order=2)
    crf_state = cache_lib.init_state(pol, feat)
    lw_state = cache_lib.layerwise_init(pol, n_layers=57, feat_shape=feat)
    crf_b = cache_lib.cache_bytes(crf_state)
    lw_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(lw_state))
    # paper: ~99% memory reduction vs layer-wise caching
    assert crf_b < 0.03 * lw_b
