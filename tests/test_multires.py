"""Multi-resolution serving tests: (batch, shape) bucket signatures,
submit-time shape validation at engine / scheduler / router
boundaries, the shape-generic decode path, per-shape metrics on the
wire, the unbounded spectral-basis cache, and the non-power-of-two
bucket rule.

The engine e2e cases use a two-entry shape ladder (8px + 16px latents,
16 + 64 CRF tokens) through one shape-generic ``from_crf_fn`` — the
deployment shape the tentpole exists for — and pin the zero
steady-state recompile guarantee with the jit cache probe."""
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as config_lib
from repro.core import frequency, policies
from repro.serving import metrics as metrics_lib
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.serving.fleet import FleetRouter
from repro.serving.scheduler import (Scheduler, ShapeMismatchError,
                                     bucket_for, bucket_signature,
                                     resolve_shape_key,
                                     validate_request_shape)

N_STEPS = 6
SIZES = (8, 16)


@pytest.fixture(scope="module")
def multi_fns():
    from repro.models import common, dit
    cfg = config_lib.reduced(config_lib.get_config("dit-small"))
    params = common.init_params(dit.dit_specs(cfg), jax.random.key(0))
    # shape-generic: from_crf_fn recovers the image side from the token
    # count, so ONE callable serves every rung of the ladder
    full_fn, from_crf_fn = dit.denoiser(cfg)
    return cfg, full_fn, from_crf_fn, params


def shape_pair(cfg, size):
    return ((size, size, cfg.in_channels),
            ((size // cfg.patch_size) ** 2, cfg.d_model))


def make_multi_engine(multi_fns, max_batch=2, **kw):
    cfg, full_fn, from_crf_fn, params = multi_fns
    pairs = [shape_pair(cfg, s) for s in SIZES]
    return DiffusionEngine(full_fn, from_crf_fn, params, pairs[0][0],
                           pairs[0][1],
                           policies.FreqCaPolicy(interval=3),
                           n_steps=N_STEPS, max_batch=max_batch,
                           shapes=pairs[1:], **kw)


# ---------------------------------------------------------------------------
# engine: mixed-shape serving, zero steady recompiles, per-shape metrics
# ---------------------------------------------------------------------------

def test_multires_engine_serves_ladder_without_steady_recompiles(multi_fns):
    cfg = multi_fns[0]
    eng = make_multi_engine(multi_fns)
    assert eng.shapes == [shape_pair(cfg, s) for s in SIZES]
    eng.warmup()
    # warmed exactly the declared grid: shapes x buckets (one group)
    budget = eng.signature_budget()
    assert budget == len(SIZES) * 2          # buckets(2) = [1, 2]
    assert eng.compiled_buckets() == budget

    pre = eng.metrics_dict()["compile_misses"]
    for i, size in enumerate([8, 16, 8, 16, 8]):
        lat, crf = shape_pair(cfg, size)
        eng.submit(DiffusionRequest(request_id=i, seed=i,
                                    latent_shape=lat, crf_shape=crf))
    outs = eng.serve_until_drained()
    assert len(outs) == 5
    # the result tensors really are per-request resolution
    by_id = {o.request_id: o for o in outs}
    assert by_id[0].latents.shape == (8, 8, cfg.in_channels)
    assert by_id[1].latents.shape == (16, 16, cfg.in_channels)
    # zero steady-state recompiles across the whole mixed stream
    assert eng.metrics_dict()["compile_misses"] == pre
    assert eng.compiled_buckets() == budget

    s = eng.metrics.summary()
    assert s["shape_keys"] == len(SIZES)
    per = s["per_shape"]
    assert sum(v["requests"] for v in per.values()) == 5
    assert all(v["state_bytes_per_lane"] > 0 for v in per.values())


def test_multires_per_shape_state_bytes(multi_fns):
    cfg = multi_fns[0]
    eng = make_multi_engine(multi_fns)
    small = eng.state_bytes(1, *shape_pair(cfg, 8))
    large = eng.state_bytes(1, *shape_pair(cfg, 16))
    # 4x the pixels and tokens -> strictly more cache state
    assert large > small > 0


def test_undeclared_shape_rejected_at_submit(multi_fns):
    cfg = multi_fns[0]
    eng = make_multi_engine(multi_fns)
    bad_lat = (12, 12, cfg.in_channels)
    with pytest.raises(ShapeMismatchError):
        eng.submit(DiffusionRequest(request_id=0, seed=0,
                                    latent_shape=bad_lat))
    # the queue is untouched: nothing to drain, nothing half-submitted
    assert eng.scheduler.depth == 0
    # and a declared-but-inconsistent init_latents also fails fast
    lat, crf = shape_pair(cfg, 16)
    ref = np.zeros(shape_pair(cfg, 8)[0], np.float32)
    with pytest.raises(ShapeMismatchError):
        eng.submit(DiffusionRequest(request_id=1, seed=1, latent_shape=lat,
                                    crf_shape=crf, init_latents=ref,
                                    edit_strength=0.5))
    assert eng.scheduler.depth == 0


def test_declare_shape_after_construction(multi_fns):
    cfg = multi_fns[0]
    eng = make_multi_engine(multi_fns)
    lat, crf = shape_pair(cfg, 4)
    with pytest.raises(ShapeMismatchError):
        eng.submit(DiffusionRequest(request_id=0, seed=0, latent_shape=lat,
                                    crf_shape=crf))
    eng.declare_shape(lat, crf)
    # the scheduler shares the ladder by reference: now accepted
    eng.submit(DiffusionRequest(request_id=0, seed=0, latent_shape=lat,
                                crf_shape=crf))
    outs = eng.serve_until_drained()
    assert outs[0].latents.shape == lat


def test_partial_declaration_resolves_from_ladder(multi_fns):
    """A request naming only its latent shape completes to the unique
    ladder entry and serves at that resolution."""
    cfg = multi_fns[0]
    eng = make_multi_engine(multi_fns)
    eng.submit(DiffusionRequest(request_id=0, seed=0,
                                latent_shape=shape_pair(cfg, 16)[0]))
    outs = eng.serve_until_drained()
    assert outs[0].latents.shape == (16, 16, cfg.in_channels)


# ---------------------------------------------------------------------------
# shape-key resolution (pure helpers)
# ---------------------------------------------------------------------------

def test_resolve_shape_key_rules():
    a = ((8, 8, 4), (16, 64))
    b = ((16, 16, 4), (64, 64))
    ladder = {a, b}
    assert resolve_shape_key(None, None, a, ladder) == a
    assert resolve_shape_key(b[0], None, a, ladder) == b
    assert resolve_shape_key(None, b[1], a, ladder) == b
    # ambiguous half (shared crf shape) falls back to the default's half
    c = ((32, 32, 4), (64, 64))
    assert resolve_shape_key(None, b[1], a, {a, b, c}) == (a[0], b[1])
    # bare scheduler: nothing declared, nothing known
    assert resolve_shape_key(None, None, None, None) is None


def test_validate_request_shape_raises_outside_ladder():
    a = ((8, 8, 4), (16, 64))
    req = DiffusionRequest(request_id=0, seed=0, latent_shape=(9, 9, 4),
                           crf_shape=(16, 64))
    with pytest.raises(ShapeMismatchError):
        validate_request_shape(req, a, {a})
    assert validate_request_shape(
        DiffusionRequest(request_id=1, seed=1), a, {a}) == a


# ---------------------------------------------------------------------------
# bucket rule: non-power-of-two max_batch, signatures with a shape half
# ---------------------------------------------------------------------------

def test_bucket_rule_non_power_of_two():
    # the ladder is pow2 below max_batch, plus max_batch itself; a
    # request count between the last pow2 and max_batch lands on
    # max_batch (the smallest ladder rung >= n), never on a phantom
    # pow2 above it
    assert bucket_for(5, 6) == 6
    assert bucket_for(4, 6) == 4
    assert bucket_for(6, 6) == 6
    assert bucket_for(3, 6) == 4
    assert bucket_for(5, 7) == 7
    assert bucket_for(9, 12) == 12
    assert bucket_for(8, 12) == 8


def test_bucket_signature_carries_shape():
    shape = ((8, 8, 4), (16, 64))
    assert bucket_signature(3, 8) == (4, None)
    assert bucket_signature(3, 8, shape) == (4, shape)
    assert bucket_signature(5, 6, shape) == (6, shape)


# ---------------------------------------------------------------------------
# spectral basis cache: unbounded across a shape ladder
# ---------------------------------------------------------------------------

def test_low_band_basis_cache_is_unbounded():
    """Regression: a bounded LRU thrashed under a 20+-entry shape
    ladder — the basis for the first shape was evicted and rebuilt on
    every revisit.  Re-access of EVERY previously-built shape must be
    a cache hit."""
    frequency._low_band_basis_np.cache_clear()
    shapes = [16 + 4 * i for i in range(20)]
    for n in shapes:
        frequency._low_band_basis_np(n, 0.25, "dct")
    info = frequency._low_band_basis_np.cache_info()
    assert info.maxsize is None
    assert info.currsize >= len(shapes)
    misses = info.misses
    for n in shapes:                       # revisit in original order
        frequency._low_band_basis_np(n, 0.25, "dct")
    info = frequency._low_band_basis_np.cache_info()
    assert info.misses == misses           # zero rebuilds
    assert info.hits >= len(shapes)
    assert frequency._dct_basis_np.cache_info().maxsize is None


# ---------------------------------------------------------------------------
# per-shape metrics on the wire
# ---------------------------------------------------------------------------

def test_shape_metrics_roundtrip_and_merge():
    m = metrics_lib.ServeMetrics()
    m.observe_batch(2, 2, 0.1, 2, 6, shape_key="lat8x8x4/crf16x64")
    m.observe_batch(4, 3, 0.1, 2, 6, shape_key="lat16x16x4/crf64x64")
    m.observe_state_bytes(1000, shape_key="lat8x8x4/crf16x64")
    m.observe_state_bytes(4000, shape_key="lat16x16x4/crf64x64")
    r = metrics_lib.ServeMetrics.from_dict(m.to_dict())
    assert r.shape_batches == m.shape_batches
    assert r.state_bytes_by_shape == m.state_bytes_by_shape

    m2 = metrics_lib.ServeMetrics()
    m2.observe_batch(2, 1, 0.1, 2, 6, shape_key="lat8x8x4/crf16x64")
    m2.observe_state_bytes(1200, shape_key="lat8x8x4/crf16x64")
    merged = metrics_lib.ServeMetrics.merge([m.to_dict(), m2.to_dict()])
    sb = merged.shape_batches["lat8x8x4/crf16x64"]
    assert sb[0] == 2 and sb[1] == 3       # batches, requests summed
    # state bytes: max per shape across replicas, not a sum
    assert merged.state_bytes_by_shape["lat8x8x4/crf16x64"] == 1200
    assert merged.state_bytes_by_shape["lat16x16x4/crf64x64"] == 4000
    s = merged.summary()
    assert s["shape_keys"] == 2
    assert s["per_shape"]["lat8x8x4/crf16x64"]["requests"] == 3


def test_shape_metrics_tolerates_old_wire_format():
    """Snapshots from a pre-multires replica lack the per-shape dicts
    entirely; from_dict and merge must fill empties, not crash."""
    old = metrics_lib.ServeMetrics().to_dict()
    old.pop("shape_batches", None)
    old.pop("state_bytes_by_shape", None)
    r = metrics_lib.ServeMetrics.from_dict(old)
    assert r.shape_batches == {} and r.state_bytes_by_shape == {}
    merged = metrics_lib.ServeMetrics.merge(
        [old, {"shape_batches": {"k": [1, 1, 1.0]}}])
    assert merged.shape_batches == {"k": [1, 1, 1.0]}


# ---------------------------------------------------------------------------
# router boundary: fail fast before the counters move (unit, no procs)
# ---------------------------------------------------------------------------

class _FakeReplica:
    def __init__(self, idx=0):
        self.idx = idx
        self.inflight = {}
        self.healthy = True
        self.stopped = False
        self.probation = False
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


def _fake_router():
    router = FleetRouter(lambda: None, n_replicas=1)
    router.replicas = [_FakeReplica(0)]
    router.spill_slack = 4
    router._started = True
    return router


def test_router_rejects_bad_shape_before_counting():
    router = _fake_router()
    router._default_shape = ((8, 8, 4), (16, 64))
    router._shape_ladder = {((8, 8, 4), (16, 64)),
                            ((16, 16, 4), (64, 64))}
    before = dict(router.counters)
    with pytest.raises(ShapeMismatchError):
        router.submit(DiffusionRequest(request_id=0, seed=0,
                                       latent_shape=(9, 9, 4),
                                       crf_shape=(16, 64)))
    # synchronous rejection: no counter moved, nothing reached a
    # replica, so submitted == resolved + failed still holds trivially
    assert dict(router.counters) == before
    assert not router.replicas[0].sent
    assert not router.replicas[0].inflight


def test_router_validation_skipped_for_legacy_workers():
    """Workers predating shape metadata report no ladder: the router
    must not invent one (validation is a no-op, replicas still reject
    engine-side)."""
    router = _fake_router()
    assert router._shape_ladder is None and router._default_shape is None
    router._validate_shape(
        DiffusionRequest(request_id=0, seed=0, latent_shape=(9, 9, 4)))


# ---------------------------------------------------------------------------
# scheduler-level validation without an engine
# ---------------------------------------------------------------------------

def test_bare_scheduler_accepts_anything():
    # no declared default, no ladder: the pre-multires behavior
    sched = Scheduler(max_batch=4, max_wait_s=0.0, clock=lambda: 0.0)
    sched.submit(DiffusionRequest(request_id=0, seed=0,
                                  latent_shape=(9, 9, 4),
                                  crf_shape=(17, 3)), now=0.0)
    assert sched.depth == 1


def test_scheduler_with_ladder_rejects():
    a = ((8, 8, 4), (16, 64))
    sched = Scheduler(max_batch=4, max_wait_s=0.0, clock=lambda: 0.0,
                      default_shape=a, allowed_shapes={a})
    with pytest.raises(ShapeMismatchError):
        sched.submit(DiffusionRequest(request_id=0, seed=0,
                                      latent_shape=(9, 9, 4)), now=0.0)
    assert sched.depth == 0


# ---------------------------------------------------------------------------
# async engine: validation surfaces at submit, not inside a future
# ---------------------------------------------------------------------------

def test_async_submit_bad_shape_raises_no_orphan_future(multi_fns):
    from repro.serving.async_engine import AsyncDiffusionEngine
    cfg = multi_fns[0]
    pairs = [shape_pair(cfg, s) for s in SIZES]
    eng = AsyncDiffusionEngine(make_multi_engine(multi_fns))
    eng.start()
    try:
        with pytest.raises(ShapeMismatchError):
            eng.submit(DiffusionRequest(request_id=0, seed=0,
                                        latent_shape=(9, 9, 4)))
        assert not eng._futures            # no orphan future leaked
        fut = eng.submit(DiffusionRequest(
            request_id=1, seed=1, latent_shape=pairs[1][0],
            crf_shape=pairs[1][1]))
        assert isinstance(fut, Future)
        out = fut.result(timeout=60)
        assert out.latents.shape == pairs[1][0]
    finally:
        eng.shutdown()
