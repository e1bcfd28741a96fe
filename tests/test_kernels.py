"""Pallas kernels vs pure-jnp oracles (interpret mode), swept over
shapes and dtypes per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frequency
from repro.kernels import dct as dct_kernel
from repro.kernels import freqca_fused, ops, ref, ssd_scan


@pytest.mark.parametrize("s,d", [(64, 32), (128, 128), (256, 64),
                                 (512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dct_kernel_matches_ref(s, d, dtype):
    x = jax.random.normal(jax.random.key(0), (2, s, d)).astype(dtype)
    basis = frequency.dct_basis(s)
    y = dct_kernel.token_basis_matmul(basis, x, block_s=64, block_d=32,
                                      block_k=64)
    y_ref = ref.token_basis_matmul_ref(basis, x)
    atol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)


@pytest.mark.pallas
@pytest.mark.parametrize("method", ["dct", "fft"])
@pytest.mark.parametrize("s,rho", [(64, 0.0625), (128, 0.125), (256, 0.25)])
def test_band_split_kernel_matches_decompose(method, s, rho):
    x = jax.random.normal(jax.random.key(1), (2, s, 32))
    low, high = dct_kernel.band_split(x, rho, method)
    low_r, high_r = ref.band_split_ref(x, rho, method)
    np.testing.assert_allclose(np.asarray(low), np.asarray(low_r), atol=5e-5)
    np.testing.assert_allclose(np.asarray(high), np.asarray(high_r),
                               atol=5e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("method", ["dct", "fft", "none"])
@pytest.mark.parametrize("s,rho", [(64, 0.0625), (128, 0.125), (256, 0.25)])
def test_band_split_spectral_matches_decompose(method, s, rho):
    """Fused (low_spec, high) kernel vs the pure decompose oracle: the
    synthesised low band and the high residual must both match, and
    low + high must still reconstruct the input."""
    x = jax.random.normal(jax.random.key(21), (2, s, 32))
    low_spec, high = dct_kernel.band_split_spectral(x, rho, method)
    assert low_spec.shape == (2, frequency.spectral_kept_bins(s, rho,
                                                              method), 32)
    bands = frequency.decompose(x, rho, method)
    basis = frequency.low_band_basis(s, rho, method)
    low = jnp.einsum("ms,bmd->bsd", basis, low_spec)
    np.testing.assert_allclose(np.asarray(low), np.asarray(bands.low),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(high), np.asarray(bands.high),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(low + high), np.asarray(x),
                               atol=5e-5)


@pytest.mark.pallas
def test_band_split_spectral_kernel_matches_ref():
    """Pallas kernel vs the jnp twin the XLA dispatch path runs."""
    x = jax.random.normal(jax.random.key(22), (2, 128, 64))
    for method in ("dct", "fft"):
        lk, hk = dct_kernel.band_split_spectral(x, 0.0625, method)
        lr, hr = ref.band_split_spectral_ref(x, 0.0625, method)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                                   atol=5e-5)
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                                   atol=5e-5)


def test_band_split_projection_idempotent():
    """L is a projection: L(Lx) == Lx (kernel-level invariant)."""
    x = jax.random.normal(jax.random.key(2), (1, 128, 16))
    low, _ = dct_kernel.band_split(x, 0.125, "dct")
    low2, _ = dct_kernel.band_split(low, 0.125, "dct")
    np.testing.assert_allclose(np.asarray(low2), np.asarray(low), atol=5e-5)


@pytest.mark.parametrize("k,order", [(2, 1), (3, 2), (4, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_predict_matches_ref(k, order, dtype):
    low = jax.random.normal(jax.random.key(3), (2, 128, 64)).astype(dtype)
    hist = jax.random.normal(jax.random.key(4), (k, 2, 128, 64)).astype(dtype)
    ts = jnp.linspace(1.0, 0.5, k)
    y = freqca_fused.freqca_predict_fused(low, hist, ts, 0.3, order,
                                          block_s=64, block_d=64)
    y_ref = ref.freqca_predict_ref(low, hist, ts, 0.3, order)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol,
                               rtol=rtol)


def test_fused_weights_equal_full_solve():
    """w = B G^{-1} b_q folding == explicit coefficient fit + eval."""
    from repro.core import hermite
    ts = jnp.array([1.0, 0.7, 0.4])
    vals = jax.random.normal(jax.random.key(5), (3, 8, 8))
    w = freqca_fused.hermite_eval_weights(ts, 0.2, 2)
    folded = jnp.einsum("k,k...->...", w, vals)
    direct = hermite.predict(ts, vals, 0.2, 2)
    np.testing.assert_allclose(np.asarray(folded), np.asarray(direct),
                               atol=1e-4)
    # fit_coefficients (solve-based, satellite bugfix) agrees with the
    # folded evaluation on multi-dim AND 1-d feature shapes
    coeffs = hermite.fit_coefficients(ts, vals, 2)
    via_fit = hermite.predict_from_coeffs(coeffs, ts, 0.2, 2)
    np.testing.assert_allclose(np.asarray(via_fit), np.asarray(direct),
                               atol=1e-4)
    c1 = hermite.fit_coefficients(ts, vals[:, 0, 0], 2)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(coeffs[:, 0, 0]),
                               atol=1e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("k,order", [(3, 2), (4, 2)])
def test_fused_spectral_predict_matches_ring(k, order):
    """Extended fused kernel (spectral low + synthesis basis + per-lane
    folded weights over the slot-ordered ring) vs ring_predict + add."""
    from repro.core.policies import base as policy_base
    s, d, rho, b = 64, 32, 0.125, 2
    ring = policy_base.ring_init(b, k, (s, d))
    rng = jax.random.key(30)
    # push k+1 values so the ring head wraps (slot order != recency)
    for i, t in enumerate(jnp.linspace(1.0, 0.4, k + 1)):
        rng, sub = jax.random.split(rng)
        ring = policy_base.ring_push(
            ring, jax.random.normal(sub, (b, s, d)), t)
    basis = frequency.low_band_basis(s, rho, "dct")
    low_spec = jax.random.normal(jax.random.key(31), (b, basis.shape[0], d))
    w = policy_base.ring_slot_weights(ring, 0.3, order)
    y = freqca_fused.freqca_predict_fused_spectral(
        low_spec, basis.T, ring.vals, w, block_s=32, block_d=32)
    want = (jnp.einsum("sm,bmd->bsd", basis.T, low_spec)
            + policy_base.ring_predict(ring, 0.3, order))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (128, 32),
                                     (64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_matches_naive(s, chunk, dtype):
    b, h, p, n = 2, 2, 16, 8
    xs = (jax.random.normal(jax.random.key(6), (b, s, h, p)) * 0.5)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(7), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.key(8), (h,)) * 0.3)
    B = jax.random.normal(jax.random.key(9), (b, s, n)) * 0.5
    C = jax.random.normal(jax.random.key(10), (b, s, n)) * 0.5
    y = ssd_scan.ssd_chunk_scan(xs.astype(dtype), dt, A, B, C, chunk)
    y_ref, _ = ref.ssd_naive_ref(xs, dt, A, B, C)
    atol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)


def test_ops_wrappers_jit():
    x = jax.random.normal(jax.random.key(0), (1, 128, 32))
    y = ops.dct_tokens(x)
    assert y.shape == x.shape
    lo, hi = ops.band_split(x, 0.125, "dct")
    np.testing.assert_allclose(np.asarray(lo + hi), np.asarray(x), atol=1e-5)


def test_ops_backend_read_lazily(monkeypatch):
    """Satellite: dispatch must honour REPRO_KERNELS flips without a
    module reimport (INTERPRET was frozen at import time before)."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    assert ops.backend() in ("pallas", "xla")
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    assert ops.backend() == "pallas" and ops.use_pallas()
    monkeypatch.setenv("REPRO_KERNELS", "xla")
    assert ops.backend() == "xla" and not ops.use_pallas()
    monkeypatch.setenv("REPRO_KERNELS", "cuda")
    with pytest.raises(ValueError):
        ops.backend()
    # INTERPRET is a lazy attribute now, driven by the env override
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    assert ops.INTERPRET is False
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "1")
    assert ops.INTERPRET is True


@pytest.mark.pallas
def test_ops_refuse_to_leave_the_kernels_on_a_tpu(monkeypatch):
    """On a TPU the kernels are the served path: forcing the XLA
    fallback or interpret mode there raises instead of passing
    silently."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    monkeypatch.delenv("REPRO_KERNELS_INTERPRET", raising=False)
    assert ops.backend() == "pallas" and not ops.interpret()
    monkeypatch.setenv("REPRO_KERNELS", "xla")
    with pytest.raises(ValueError, match="served path"):
        ops.backend()
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "1")
    with pytest.raises(ValueError, match="interpret"):
        ops.interpret()


def test_ops_band_split_spectral_backends_agree(monkeypatch):
    """The same call routed through both backends returns the same
    split (the pallas jits carry interpret/backend as static args, so
    flipping the env between calls cannot serve a stale executable)."""
    x = jax.random.normal(jax.random.key(40), (2, 128, 64))
    outs = {}
    for be in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNELS", be)
        outs[be] = ops.band_split_spectral(x, 0.125, "dct")
    for a, b in zip(outs["xla"], outs["pallas"], strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("s,hq,hkv,hd,blocks", [
    pytest.param(64, 4, 2, 16, (32, 32), id="64-4-2"),
    pytest.param(128, 8, 8, 16, (32, 32), id="128-8-8"),
    pytest.param(64, 6, 2, 16, (32, 32), id="64-6-2"),
    # q block != kv block, several kv steps, the served head sizes
    pytest.param(256, 4, 2, 72, (64, 32), id="256-4-2-hd72-q64-k32"),
    pytest.param(256, 2, 2, 128, (32, 128), id="256-2-2-hd128-q32-k128"),
    # the default tiles: (1024, 1024), one kv step
    pytest.param(1024, 2, 1, 72, (None, None), id="1024-2-1-hd72-tiles"),
    # head-dim-major blocks of whole lanes (the tokens), two kv steps
    pytest.param(256, 4, 2, 72, (128, 128), id="256-4-2-hd72-q128-k128"),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                           (False, 0)])
def test_flash_attention_matches_sdpa(s, hq, hkv, hd, blocks, causal,
                                      window):
    """f32 inputs: only the probabilities are rounded, to V's dtype
    (f32 here), so the kernel meets the f32 oracle at 5e-5."""
    from repro.kernels import flash_attention as fa
    from repro.models import attention as A
    b = 2
    q = jax.random.normal(jax.random.key(11), (b, s, hq, hd))
    k = jax.random.normal(jax.random.key(12), (b, s, hkv, hd))
    v = jax.random.normal(jax.random.key(13), (b, s, hkv, hd))
    if causal:
        mask = A.causal_mask(s, window=window)
    else:
        mask = jnp.ones((1, s, s), bool)
    ref_out = A._sdpa(q, k, v, mask, hq // hkv)
    out = fa.flash_attention(q, k, v, hq // hkv, causal=causal,
                             window=window, q_block=blocks[0],
                             kv_block=blocks[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=5e-5)


@pytest.mark.pallas
def test_dit_joint_attention_flash_routing(monkeypatch):
    """models.dit routes joint attention to the flash kernel above the
    threshold under REPRO_KERNELS=pallas — outputs must match the
    full-logits einsum path."""
    from repro.models import dit
    b, s, nh, hd = 1, 128, 2, 16
    q = jax.random.normal(jax.random.key(50), (b, s, nh, hd))
    k = jax.random.normal(jax.random.key(51), (b, s, nh, hd))
    v = jax.random.normal(jax.random.key(52), (b, s, nh, hd))
    p_out = jax.random.normal(jax.random.key(53), (nh, hd, nh * hd)) * 0.1
    monkeypatch.setenv("REPRO_KERNELS", "xla")
    want = dit._joint_attention(q, k, v, p_out, jnp.float32)
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setattr(dit, "_FLASH_MIN_SEQ", 64)
    got = dit._joint_attention(q, k, v, p_out, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    # below the threshold the einsum path serves even under pallas
    monkeypatch.setattr(dit, "_FLASH_MIN_SEQ", 4096)
    assert not dit._flash_ok(s)


@pytest.mark.parametrize("dtype,s,hd,causal,blocks", [
    pytest.param(jnp.float32, 64, 32, True, (32, 32), id="float32"),
    pytest.param(jnp.bfloat16, 64, 32, True, (32, 32), id="bfloat16"),
    # the default tiles, non-causal as served: (2048, 512) at S=2048 is
    # four kv steps
    pytest.param(jnp.float32, 2048, 128, False, (None, None),
                 id="float32-hd128-tiles"),
    pytest.param(jnp.bfloat16, 2048, 128, False, (None, None),
                 id="bfloat16-hd128-tiles"),
    pytest.param(jnp.bfloat16, 1024, 72, False, (None, None),
                 id="bfloat16-hd72-tiles"),
    # head-dim-major at (2048, 512): four kv steps
    pytest.param(jnp.bfloat16, 2048, 72, False, (None, None),
                 id="bfloat16-hd72-tiles-2048"),
    pytest.param(jnp.bfloat16, 512, 64, False, (256, 128),
                 id="bfloat16-hd64"),
    pytest.param(jnp.float32, 512, 80, False, (256, 128), id="float32-hd80"),
])
def test_flash_attention_dtypes(dtype, s, hd, causal, blocks):
    """f32 inputs meet the f32 oracle at 5e-5; bf16 inputs (the oracle
    takes the same rounded values) within 2^-7 of max|v|: the output is
    a convex mix of V rows, and bf16 probabilities and output move it by
    less than 2^-8 of max|v|."""
    from repro.kernels import flash_attention as fa
    from repro.models import attention as A
    b, hq, hkv = 1, 4, 2
    q = jax.random.normal(jax.random.key(1), (b, s, hq, hd)).astype(dtype)
    k = jax.random.normal(jax.random.key(2), (b, s, hkv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.key(3), (b, s, hkv, hd)).astype(dtype)
    mask = A.causal_mask(s) if causal else jnp.ones((1, s, s), bool)
    ref_out = A._sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32), mask, hq // hkv)
    out = fa.flash_attention(q, k, v, hq // hkv, causal=causal,
                             q_block=blocks[0], kv_block=blocks[1])
    assert out.dtype == dtype
    if dtype == jnp.float32:
        atol = 5e-5
    else:
        atol = 2 ** -7 * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out), atol=atol)
    if hd % fa.LANES == 0:
        # a head of whole lanes keeps the token-major kernel, bit for bit
        qb, kb = fa.tiles(s, s, hd, dtype) if blocks[0] is None else blocks
        same = fa._token_major(q, k, v, hq // hkv, causal, 0, qb, kb, True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(same))


# the two served attention shapes: (S, head size, the tiles the caps
# chosen from an on-chip sweep give there)
_SERVED_FLASH = [(4096, 128, (2048, 512)), (1024, 72, (1024, 1024))]


@pytest.mark.parametrize("s", [64, 100, 128, 256, 1000, 1024, 3072, 4096,
                               4608, 6144, 16384])
@pytest.mark.parametrize("hd", [72, 128])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_tiles_divide_the_sequence_and_fit_vmem(s, hd, dtype):
    from repro.kernels import flash_attention as fa
    bq, bk = fa.tiles(s, s, hd, dtype)
    assert s % bq == 0 and s % bk == 0
    assert bq <= min(s, fa.Q_CAP) and bq * bk <= fa.TILE_CAP
    assert fa.dispatch_ok(s)
    assert fa.vmem_bytes(bq, bk, hd, dtype) <= fa.VMEM_BUDGET
    for blk in (bq, bk):
        assert blk == s or blk % fa.LANES == 0


@pytest.mark.parametrize("s,hd,want", _SERVED_FLASH)
def test_flash_tiles_at_the_served_shapes(s, hd, want):
    """Both served shapes take the sweep's tiles, in bf16 and in f32,
    within v5e's default scoped VMEM."""
    from repro.kernels import flash_attention as fa
    for dtype in (jnp.bfloat16, jnp.float32):
        bq, bk = fa.tiles(s, s, hd, dtype)
        assert (bq, bk) == want
        assert fa.vmem_bytes(bq, bk, hd, dtype) <= fa.VMEM_BUDGET


def test_flash_dispatch_ok_keeps_every_former_length():
    """Every length the fixed 128-blocks served (``S % min(128, S) ==
    0``) is still served, so ``models/dit._flash_ok`` routes as before."""
    from repro.kernels import flash_attention as fa
    for s in range(1, 20000):
        if s % min(128, s) == 0:
            assert fa.dispatch_ok(s), s
