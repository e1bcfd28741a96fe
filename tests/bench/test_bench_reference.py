"""The plain reference against the served path at a tiny size: its own
weights equal the program's, and the engine's latents, per request, in
buckets 1 and 4 with padding, agree with it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import cell as cell_lib
from bench import loadgen
from bench.references import dit as ref_dit

LAT = (16, 16, 4)             # 128 px: 64 tokens


def _model(**kw):
    return dict(bench_tiny.MODEL, **kw)


@pytest.mark.parametrize("extra", [{}, {"n_double": 1, "text_dim": 32,
                                        "n_text_tokens": 8}])
def test_reference_weights_are_the_programs(extra):
    model = _model(**extra)
    prog = cell_lib.program("dit")
    got = prog.weights(model, "tiny", 1234, jax.devices()[0])
    want = ref_dit.make_weights(model, 1234)
    assert set(want) == set(got) - {"double", "text_proj"}
    leaves_w = jax.tree.leaves_with_path(want)
    flat_g = dict(jax.tree.leaves_with_path(got))
    for path, w in leaves_w:
        g = flat_g[path]
        assert g.dtype == w.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_reference_schedule_runs_12_of_50_full_steps():
    sched = ref_dit.schedule(bench_tiny.FREQCA, 50)
    assert sum(sched) == 12
    assert [i for i, f in enumerate(sched) if f][:5] == [0, 1, 2, 5, 10]
    assert all(ref_dit.schedule({"name": "none"}, 50))


def test_extrapolation_is_exact_on_quadratics():
    ts = [0.9, 0.8, 0.7]
    w = ref_dit.extrapolation_weights(ts, 0.66, 2)
    f = lambda t: 3 * t * t - 2 * t + 0.5   # noqa: E731
    assert float(np.dot(w, [f(t) for t in ts])) == pytest.approx(f(0.66))


@pytest.mark.parametrize("policy", [bench_tiny.FREQCA, {"name": "none"}])
def test_engine_latents_match_the_reference(policy):
    from repro.serving.async_engine import AsyncDiffusionEngine
    from repro.serving.engine import DiffusionEngine
    from repro.serving.scheduler import DiffusionRequest

    model = _model(dtype="float32")
    prog = cell_lib.program("dit")
    full_fn, from_crf_fn = prog.denoiser(model, "tiny")
    params = prog.weights(model, "tiny", 99, jax.devices()[0])
    eng = DiffusionEngine(full_fn, from_crf_fn, params, LAT,
                          (64, model["d_model"]), prog.policy(policy),
                          n_steps=50, max_batch=4, max_wait_s=0.2)
    plan = loadgen.make_plan({"backlog": 4, "rate_per_s": 1.0,
                              "edit_every": 2}, 5, 1)[:4]
    ref_lat = {a.index: loadgen.edit_reference(a, LAT) for a in plan}

    def req(a):
        return DiffusionRequest(
            request_id=a.index, seed=a.seed,
            init_latents=ref_lat[a.index] if a.edit else None,
            edit_strength=0.5 if a.edit else 0.0)

    with AsyncDiffusionEngine(eng) as aeng:
        lone = aeng.submit(req(plan[0])).result()          # bucket 1
        futs = [aeng.submit(req(a)) for a in plan[1:]]     # bucket 4
        outs = [lone] + [f.result() for f in futs]
    assert [o.bucket for o in outs] == [1, 4, 4, 4]
    ref = ref_dit.Reference(model, policy, 50, LAT)
    w = ref_dit.make_weights(model, 99)
    for a, o in zip(plan, outs, strict=True):
        x0 = ref.x_init(a.seed, ref_lat[a.index] if a.edit else None, 0.5)
        x, n_full = ref.sample(w, x0)
        assert o.n_full_steps == n_full
        # f32 against f32 at highest precision: round-off only
        assert ref_dit.rel_err(o.latents, x) < 1e-4
