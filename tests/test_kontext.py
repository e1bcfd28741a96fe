"""FLUX.1-Kontext conditioning on the served path, at a tiny size.

d 64, 2 heads of 32, 1 dual-stream + 2 single-stream blocks, 16 text
tokens, 8x8x4 latents (16 image + 16 reference tokens), seeded weights,
float32: the served denoiser, the engine and the scheduler against the
plain reference ``bench/references/flux.py``, the ``dit`` family's path
against ``bench/references/dit.py``, and the ``flux`` family's counts at
published widths against a hand count."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell as cell_lib  # noqa: E402
from bench import loadgen  # noqa: E402
from repro.core import policies  # noqa: E402
from repro.diffusion import sampler, schedule  # noqa: E402
from repro.models import dit  # noqa: E402
from repro.serving.engine import DiffusionEngine  # noqa: E402
from repro.serving.scheduler import (BatchPlan, DiffusionRequest,  # noqa: E402
                                     Scheduler)

MODEL = {"n_double": 1, "n_layers": 2, "d_model": 64, "n_heads": 2,
         "d_ff": 128, "patch_size": 2, "in_channels": 4, "text_dim": 32,
         "n_text_tokens": 16, "vec_in_dim": 24, "guidance_embed": True,
         "rope_axes": [8, 12, 12], "rope_theta": 10000.0,
         "time_embed_dim": 256, "norm_eps": 1e-6, "dtype": "float32"}
FREQCA = {"name": "freqca", "interval": 5, "method": "dct", "rho": 0.0625,
          "low_order": 0, "high_order": 2}
LAT = (8, 8, 4)
S_IMG = 16
N_STEPS = 12
SEED = 2 ** 40 + 3

PROG = cell_lib.program("flux")
REF = cell_lib.reference("flux")


def _cell(**model):
    return cell_lib.Cell(
        name="tiny-kontext", chips=1,
        config={"name": "tiny-kontext", "family": "flux",
                "model": dict(MODEL, **model),
                "engine": {"max_batch": 4, "max_wait_s": 0.05,
                           "n_steps": N_STEPS}},
        traffic={"image_px": 64, "policy": FREQCA, "rate_per_s": 20.0,
                 "backlog": 0, "edit_every": 0, "guidance": [2.5, 4.0]},
        limits={"latent_rel_err_max": 0.02, "check_requests": 2},
        metrics=[])


CELL = _cell()
CFG = PROG.config(CELL.model, "tiny-kontext")


@pytest.fixture(scope="module")
def params():
    return dit.random_params(CFG, 11)


def _arrival(i):
    return loadgen.Arrival(index=i, due_s=0.0,
                           seed=loadgen.fold(SEED, f"request{i}"),
                           edit=False)


def _batched(conds):
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *conds)


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64)
                                - np.asarray(want, np.float64))
                 / np.linalg.norm(np.asarray(want, np.float64)))


# --- weights, forward, reference ----------------------------------------

def test_program_and_reference_draw_the_same_weights(params):
    want = REF.make_weights(CELL.model, 11)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_program_and_reference_draw_the_same_conditioning():
    for i in range(3):
        a = _arrival(i)
        got = PROG.request(CELL, a, LAT).cond
        want = REF.inputs(REF.Reference(CELL.model, FREQCA, N_STEPS, LAT),
                          CELL, a)["cond"]
        assert sorted(got) == sorted(want) == sorted(dit.COND_KEYS)
        for k in got:
            np.testing.assert_array_equal(got[k], want[k])
        assert 2.5 <= float(got["guidance"]) <= 4.0
        assert got["txt"].shape == (16, 32) and got["vec"].shape == (24,)
        assert got["ref_latents"].shape == LAT


def test_conditioned_forward_matches_the_reference(params):
    """Velocity and CRF of ``dit_forward`` with text, pooled vector,
    guidance and reference latents against the float32 reference at
    ``HIGHEST``.  Both run in float32; what differs is the order of the
    sums (XLA's fused LayerNorm and matmul reductions against the
    reference's einsums) and the RoPE angles (float32 here, float64 in
    the reference), each near float32's 6e-8 per operation, so 1e-4 of
    the norm leaves two orders of room."""
    conds = [PROG.request(CELL, _arrival(i), LAT).cond for i in range(2)]
    x = jax.random.normal(jax.random.key(5), (2,) + LAT)
    full_fn, from_crf_fn = dit.denoiser(CFG)
    v, crf = full_fn(params, x, 0.6, _batched(conds))
    ref = REF.Reference(CELL.model, FREQCA, N_STEPS, LAT)
    w = REF.make_weights(CELL.model, 11)
    for j in range(2):
        c = {k: jnp.asarray(a, jnp.float32) for k, a in conds[j].items()}
        v_ref, crf_ref = ref._full(w, x[j], jnp.float32(0.6), c)
        assert crf.shape == (2, S_IMG, 64)
        assert _rel(v[j], v_ref) < 1e-4
        assert _rel(crf[j], crf_ref) < 1e-4
    # the cached step: the final layer over the CRF, modulated by the
    # same vec of time, guidance and pooled text
    v2 = from_crf_fn(params, crf, 0.6, _batched(conds))
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v), atol=1e-5)


def test_every_conditioning_input_moves_the_output(params):
    cond = _batched([PROG.request(CELL, _arrival(0), LAT).cond])
    x = jax.random.normal(jax.random.key(5), (1,) + LAT)
    full_fn, _ = dit.denoiser(CFG)
    v0, _ = full_fn(params, x, 0.4, cond)
    for k in dit.COND_KEYS:
        moved = dict(cond, **{k: cond[k] + 0.5})
        v1, _ = full_fn(params, x, 0.4, moved)
        assert _rel(v1, v0) > 1e-3, k


def test_a_flux_configuration_refuses_missing_conditioning(params):
    full_fn, _ = dit.denoiser(CFG)
    cond = _batched([PROG.request(CELL, _arrival(0), LAT).cond])
    x = jnp.zeros((1,) + LAT)
    for k in ("guidance", "vec"):
        with pytest.raises(ValueError, match="guidance|pooled"):
            full_fn(params, x, 0.5, {j: v for j, v in cond.items()
                                     if j != k})
    with pytest.raises(ValueError, match="unknown conditioning"):
        full_fn(params, x, 0.5, dict(cond, mask=cond["vec"]))


# --- joint attention once ----------------------------------------------

def _double_block_two_calls(p, img, txt, cond, cfg, rope):
    """The dual-stream block as it was: the joint attention computed
    once per stream, each call projected by that stream's ``wo``."""
    streams = {"img": img, "txt": txt}
    qkvs, mods = {}, {}
    for name in ("img", "txt"):
        mods[name] = dit._modulation(p[name]["mod"], cond, 6)
        sh1, sc1 = mods[name][:2]
        h = dit.common.layernorm(streams[name], cfg.norm_eps) * (1 + sc1) \
            + sh1
        qkvs[name] = dit._qkv_heads(p[name]["attn"], h, cfg.n_heads)
    s_txt = txt.shape[1]
    q, k, v = (jnp.concatenate([qkvs["txt"][i], qkvs["img"][i]], axis=1)
               for i in range(3))
    q, k = dit.apply_rope(q, rope), dit.apply_rope(k, rope)
    outs = {}
    for name in ("img", "txt"):
        _, _, g1, sh2, sc2, g2 = mods[name]
        attn = dit._joint_attention(q, k, v, p[name]["attn"]["wo"],
                                    img.dtype)
        part = attn[:, s_txt:] if name == "img" else attn[:, :s_txt]
        x = streams[name] + g1 * part
        h = dit.common.layernorm(x, cfg.norm_eps) * (1 + sc2) + sh2
        y = jax.nn.gelu(h @ p[name]["mlp"]["wi"])
        outs[name] = x + g2 * (y @ p[name]["mlp"]["wo"])
    return outs["img"], outs["txt"]


def test_double_block_attends_once_and_matches_the_two_call_form(
        params, monkeypatch):
    layer = jax.tree.map(lambda a: a[0], params["double"])
    keys = jax.random.split(jax.random.key(9), 3)
    img = jax.random.normal(keys[0], (2, 2 * S_IMG, 64))
    txt = jax.random.normal(keys[1], (2, 16, 64))
    cond = jax.random.normal(keys[2], (2, 64))
    rope = dit.rope_tables(dit.token_ids(16, (4, 4), (4, 4)),
                           CFG.rope_axes, CFG.rope_theta)
    calls = []
    attention = dit._attention
    monkeypatch.setattr(dit, "_attention",
                        lambda *a: calls.append(1) or attention(*a))
    got = dit.double_block(layer, img, txt, cond, CFG, rope)
    assert len(calls) == 1
    monkeypatch.setattr(dit, "_attention", attention)
    want = _double_block_two_calls(layer, img, txt, cond, CFG, rope)
    for g, w in zip(got, want, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6,
                                   rtol=1e-6)


# --- RoPE ---------------------------------------------------------------

def test_token_ids_and_rope_by_hand():
    ids = dit.token_ids(2, (1, 2), (2, 1))
    assert ids.tolist() == [[0, 0, 0], [0, 0, 0],          # text
                            [0, 0, 0], [0, 0, 1],          # image (0, h, w)
                            [1, 0, 0], [1, 1, 0]]          # reference (1, h, w)
    cos, sin = dit.rope_tables(ids, (2, 2, 2), 10.0)
    # one pair per axis, angle = id · theta^0
    np.testing.assert_allclose(np.asarray(cos[5]), np.cos([1.0, 1.0, 0.0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[3]), np.sin([0.0, 0.0, 1.0]),
                               rtol=1e-6)
    # pair (x0, x1) = (1, 0) rotated by the angle: (cos, sin)
    x = jnp.tile(jnp.array([1.0, 0.0]), 3)[None, None, None]   # [1,1,1,6]
    rot = dit.apply_rope(jnp.broadcast_to(x, (1, 6, 1, 6)), (cos, sin))
    np.testing.assert_allclose(np.asarray(rot[0, 5, 0]),
                               [math.cos(1), math.sin(1), math.cos(1),
                                math.sin(1), 1.0, 0.0], rtol=1e-6)


def test_reference_ids_tell_the_reference_from_the_image(params,
                                                         monkeypatch):
    """With the reference tokens at index 1 the output differs from the
    one they give at the image's own ids (0, h, w), where a reference
    equal to the image would be indistinguishable from it."""
    cond = _batched([PROG.request(CELL, _arrival(0), LAT).cond])
    x = jax.random.normal(jax.random.key(5), (1,) + LAT)
    full_fn, _ = dit.denoiser(CFG)
    v1, _ = full_fn(params, x, 0.5, cond)
    ids = dit.token_ids

    def index_zero(s_txt, grid, ref_grid=None):
        out = ids(s_txt, grid, ref_grid)
        out[:, 0] = 0
        return out

    monkeypatch.setattr(dit, "token_ids", index_zero)
    v0, _ = full_fn(params, x, 0.5, cond)
    assert _rel(v0, v1) > 1e-3


# --- the engine and the scheduler --------------------------------------

def _engine(params, max_batch=4, n_steps=N_STEPS):
    full_fn, from_crf_fn = dit.denoiser(CFG)
    return DiffusionEngine(full_fn, from_crf_fn, params, LAT, (S_IMG, 64),
                           PROG.policy(FREQCA), n_steps=n_steps,
                           max_batch=max_batch)


def test_freqca_trajectory_through_the_engine_matches_the_reference(
        params):
    """The served FreqCa trajectory (full and cached steps, both
    conditioned) against the reference sampler: float32 on both sides,
    the error of one forward (1e-4 of the norm at most, above) carried
    through 12 Euler steps."""
    eng = _engine(params)
    reqs = [PROG.request(CELL, _arrival(i), LAT) for i in range(2)]
    out = eng.run_batch(reqs)
    ref = REF.Reference(CELL.model, FREQCA, N_STEPS, LAT)
    w = REF.make_weights(CELL.model, 11)
    for r, a in zip(out, (_arrival(0), _arrival(1)), strict=True):
        x, n_full = ref.sample(w, **REF.inputs(ref, CELL, a))
        assert r.n_full_steps == n_full < N_STEPS
        assert _rel(r.latents, x) < 1e-4
        assert r.cond_host_s > 0
    assert eng.metrics.cond_bytes == 2 * sum(
        np.asarray(v).nbytes for v in reqs[0].cond.values())


def test_lanes_with_different_conditioning_are_independent(params):
    """Three lanes (padded to a bucket of 4) give each lane what serving
    it alone gives."""
    eng = _engine(params)
    reqs = [PROG.request(CELL, _arrival(i), LAT) for i in range(3)]
    batch = eng.run_batch(reqs)
    assert {r.bucket for r in batch} == {4}
    for r in reqs:
        alone = eng.run_batch([dataclasses.replace(r)])[0]
        assert alone.bucket == 1
        got = next(b for b in batch if b.request_id == r.request_id)
        np.testing.assert_allclose(np.asarray(got.latents),
                                   np.asarray(alone.latents), atol=1e-5,
                                   rtol=1e-5)


def test_padded_lanes_copy_the_first_lanes_conditioning():
    reqs = [PROG.request(CELL, _arrival(i), LAT) for i in range(3)]
    plan = BatchPlan(requests=reqs, bucket=4, formed_at=0.0)
    cond = DiffusionEngine.build_cond(plan)
    assert cond["txt"].shape == (4, 16, 32)
    np.testing.assert_array_equal(cond["txt"][3], reqs[0].cond["txt"])
    np.testing.assert_array_equal(cond["guidance"][:3],
                                  [r.cond["guidance"] for r in reqs])


def test_different_conditioning_shapes_are_never_cut_together():
    sched = Scheduler(max_batch=4, max_wait_s=0.0)
    short = _cell(n_text_tokens=8)
    for i in range(6):
        c = short if i % 2 else CELL
        sched.submit(PROG.request(c, _arrival(i), LAT), now=0.0)
    cuts = []
    while len(sched):
        cuts.append(sched.form_batch(now=1.0, flush=True))
    assert [len(p.requests) for p in cuts] == [3, 3]
    for p in cuts:
        assert len({r.cond["txt"].shape for r in p.requests}) == 1
        assert {r.request_id % 2 for r in p.requests} in ({0}, {1})
    # one conditioning shape, no shape declared: one cut
    same = Scheduler(max_batch=4, max_wait_s=0.0)
    for i in range(4):
        same.submit(PROG.request(CELL, _arrival(i), LAT), now=0.0)
    assert len(same.form_batch(now=1.0).requests) == 4


def test_warmup_compiles_the_conditioned_signature(params):
    eng = _engine(params, max_batch=2, n_steps=6)
    lane = jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                                       np.result_type(a)),
                        PROG.request(CELL, _arrival(0), LAT).cond)
    eng.warmup(cond=lane)
    misses = eng.metrics.compile_misses
    eng.run_batch([PROG.request(CELL, _arrival(i), LAT) for i in range(2)])
    assert eng.metrics.compile_misses == misses


# --- the dit family, unconditioned --------------------------------------

DIT = {"n_double": 1, "n_layers": 2, "d_model": 64, "n_heads": 4,
       "d_ff": 128, "patch_size": 2, "in_channels": 4, "text_dim": 32,
       "n_text_tokens": 8, "time_embed_dim": 256, "norm_eps": 1e-6,
       "dtype": "float32"}


def test_dit_family_is_unchanged_with_an_empty_conditioning():
    """The ``dit`` family's denoiser and engine with ``cond=()``: the same
    forward as the unchanged ``bench/references/dit.py`` (dual-stream
    weights held, unused), and an engine program with no input beyond
    the weights and the latents."""
    dit_prog, dit_ref = cell_lib.program("dit"), cell_lib.reference("dit")
    cfg = dit_prog.config(DIT, "tiny-dit")
    assert (cfg.vec_in_dim, cfg.guidance_embed, cfg.rope_axes) == \
        (0, False, ())
    params = dit.random_params(cfg, 4)
    full_fn, from_crf_fn = dit.denoiser(cfg)
    x = jax.random.normal(jax.random.key(2), (2,) + LAT)
    v, crf = full_fn(params, x, 0.3, ())
    v3, crf3 = full_fn(params, x, 0.3)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v3))
    w = dit_ref.make_weights(DIT, 4)
    for j in range(2):
        v_ref, crf_ref = dit_ref._forward(w, x[j], jnp.float32(0.3), DIT,
                                          dit_ref._operands(None))
        assert _rel(v[j], v_ref) < 1e-4 and _rel(crf[j], crf_ref) < 1e-4
    eng = DiffusionEngine(full_fn, from_crf_fn, params, LAT, (S_IMG, 64),
                          policies.FreqCaPolicy(interval=5), n_steps=4,
                          max_batch=2)
    xs = jax.ShapeDtypeStruct((2,) + LAT, jnp.float32)
    args = eng._jit_run.trace(params, xs, eng.policy,
                              eng.crf_shape).jaxpr.in_avals
    assert len(args) == len(jax.tree.leaves(params)) + 1
    res = eng.run_batch([DiffusionRequest(request_id=0, seed=3)])
    assert res[0].cond_host_s == 0.0 and eng.metrics.cond_bytes == 0
    want = sampler.sample(full_fn, from_crf_fn, params,
                          jax.random.normal(jax.random.key(3), LAT)[None],
                          schedule.timesteps(4),
                          policies.FreqCaPolicy(interval=5),
                          crf_shape=(1, S_IMG, 64))
    np.testing.assert_allclose(np.asarray(res[0].latents),
                               np.asarray(want.x[0]), atol=1e-6)


# --- the flux family's counts, at published widths ----------------------

def test_flux_family_counts_at_published_widths():
    cell = cell_lib.load("flux1-kontext-dev-cut.freqca-kontext-1024.sat",
                         True)
    m = cell.model
    assert PROG.attention_tokens(m, 4096) == 512 + 4096 + 4096 == 8704
    assert PROG.flash_calls(m) == 4 + 8 == 12
    d, f, L = 3072, 12288, 8704
    linear = L * (2 * 4 * d * d + 2 * 2 * d * f)   # Q, K, V, O and MLP
    attn = 4 * 24 * L * L * 128                    # QK^T and PV
    block = linear + attn
    embed = (2 * 8192 * 64 * d + 2 * 512 * 4096 * d
             + 2 * 2 * (256 * d + d * d) + 2 * (768 * d + d * d))
    mods = 4 * 2 * 2 * d * 6 * d + 8 * 2 * d * 6 * d
    final = 2 * d * 2 * d + 2 * 4096 * d * 64
    hand = 12 * block + embed + mods + final
    assert PROG.forward_flops(m, 4096) == pytest.approx(hand, rel=1e-12)
    assert PROG.forward_flops(m, 4096) == pytest.approx(3.48e13, rel=0.01)
    assert attn / block == pytest.approx(0.32, abs=0.01)
