"""A configuration family is its two files.

A stub family whose requests all carry conditioning is served through
``serve.serve`` and ``AsyncDiffusionEngine`` on the CPU and checked by
the harness's own ``check``, loaded in place of the family files with no
file under ``bench/`` edited for it.  The ``dit`` family's requests,
engine arguments, work counts and check inputs are pinned as numbers:
what the harness's inline code gave before the family functions."""
import dataclasses
import hashlib
import time
import types

import jax
import numpy as np
import pytest

import bench_tiny
from bench import cell as cell_lib
from bench import loadgen, serve, work

ROOT = bench_tiny.ROOT
RUN = cell_lib.load_module(ROOT / "bench" / "run.py")
PEAK = cell_lib.peaks()["TPU v5 lite"]
SEED = 2 ** 40 + 5
STRENGTH = 0.75
PROGRAM = ("denoiser", "weights", "policy", "request", "engine",
           "attention_tokens", "flash_calls", "forward_flops")
REFERENCE = ("make_weights", "Reference", "inputs", "rel_err")


def digest(x) -> str:
    a = np.ascontiguousarray(np.asarray(x, np.float32))
    return hashlib.blake2b(a.tobytes(), digest_size=8).hexdigest()


# --- a family whose every request carries conditioning ---------------------

def _conditioning(a, lat, salt):
    rng = np.random.RandomState(loadgen.fold(a.seed, salt))
    return rng.standard_normal(lat).astype(np.float32)


def _stub_family(ref_salt):
    """Program and reference modules of a family ``stub``: the dit
    family's functions, but every request starts from seeded latents at
    ``STRENGTH``; the engine logs what it serves and the reference what
    it is given.  The reference draws with ``ref_salt``."""
    from repro.serving.scheduler import DiffusionRequest
    dit, dit_ref = cell_lib.program("dit"), cell_lib.reference("dit")
    served, given = {}, {}

    def request(cell, a, lat):
        return DiffusionRequest(request_id=a.index, seed=a.seed,
                                init_latents=_conditioning(a, lat, "stub"),
                                edit_strength=STRENGTH)

    def engine(cell, *args):
        eng = dit.engine(cell, *args)
        execute = eng.execute_plan

        def logged(plan):
            for r in plan.requests:
                served[r.request_id] = (r.init_latents, r.edit_strength)
            return execute(plan)

        eng.execute_plan = logged
        return eng

    def inputs(ref, cell, a):
        cond = _conditioning(a, ref.lat_shape, ref_salt)
        given[a.index] = cond
        return {"x": ref.x_init(a.seed, cond, STRENGTH)}

    prog = types.SimpleNamespace(**{
        **{f: getattr(dit, f) for f in PROGRAM}, "request": request,
        "engine": engine})
    ref = types.SimpleNamespace(**{
        **{f: getattr(dit_ref, f) for f in REFERENCE}, "inputs": inputs})
    return prog, ref, served, given


def test_the_dit_family_defines_every_function_the_harness_calls():
    assert all(callable(getattr(cell_lib.program("dit"), f)) for f in PROGRAM)
    assert all(callable(getattr(cell_lib.reference("dit"), f))
               for f in REFERENCE)


@pytest.mark.parametrize("ref_salt,correct", [("stub", True),
                                              ("another", False)])
def test_a_conditioned_family_is_served_and_checked(monkeypatch, ref_salt,
                                                    correct):
    prog, ref, served, given = _stub_family(ref_salt)
    program, reference = cell_lib.program, cell_lib.reference
    monkeypatch.setattr(cell_lib, "program", lambda f: prog if f == "stub"
                        else program(f))
    monkeypatch.setattr(cell_lib, "reference", lambda f: ref if f == "stub"
                        else reference(f))
    cell = bench_tiny.cell(rate_per_s=40.0, edit_every=0,
                           limits_of="flux1-dev-cut.freqca-1024.sat")
    cell.config = dict(cell.config, family="stub")
    cell.limits = dict(cell.limits, check_requests=6)
    line = RUN.measure(cell, SEED, 1.0, False, jax.devices(), PEAK,
                       time.perf_counter())
    lat = loadgen.latent_shape(cell.traffic, cell.model["in_channels"])
    plan = {a.index: a for a in loadgen.make_plan(cell.traffic, SEED, 1.0)}
    # every request the engine ran, warm-up and window, was conditioned
    assert len([i for i in served if i < 0]) == 4
    assert len([i for i in served if i >= 0]) >= 8
    for i, (init, strength) in served.items():
        assert strength == STRENGTH
        if i >= 0:
            np.testing.assert_array_equal(
                init, _conditioning(plan[i], lat, "stub"))
    # the check's reference was given the arrival's conditioning
    assert len(given) == 6 and set(given) <= set(served)
    for i, cond in given.items():
        assert np.array_equal(cond, served[i][0]) is correct
    assert line["correct"] is correct
    assert line["compiles_in_window"] == 0 and line["failed"] == 0


# --- the dit family, pinned ------------------------------------------------

# bench_tiny.cell(backlog=2, edit_every=2), seed 2**40 + 77, the plan's
# first six arrivals at 64 px: (request_id, seed, edit_strength, digest
# of init_latents) and the digest of the reference's start latents
PIN_SEED = 2 ** 40 + 77
PIN_REQUESTS = [(0, 1349900033, 0.5, "cfc6ebaad54d9fe4"),
                (1, 2143045774, 0.5, "c59f06a1c2704640"),
                (2, 1259328069, 0.0, None),
                (3, 1996113771, 0.5, "2ae8afb55fee0a01"),
                (4, 931544591, 0.5, "6eb61e985fa573bc"),
                (5, 1004098821, 0.0, None)]
PIN_X0 = ["1ffad9719c780430", "a91feb4fe9044eed", "e0dbbe8e71687bba",
          "6883c5062963363c", "48d766a9829d809e", "319ff82388f8c22b"]
PIN_WARM = [(-1, 2045344064, 0.0, None), (-2, 359696698, 0.0, None),
            (-3, 1886710315, 0.0, None), (-4, 1101862381, 0.5,
                                          "3a06024261ae1de6")]


def _pinned(r):
    from repro.serving.scheduler import DiffusionRequest
    bare = dataclasses.replace(r, init_latents=None)
    assert bare == DiffusionRequest(request_id=r.request_id, seed=r.seed,
                                    edit_strength=r.edit_strength)
    return (r.request_id, r.seed, r.edit_strength,
            None if r.init_latents is None else digest(r.init_latents))


def _pin_cell():
    cell = bench_tiny.cell(backlog=2, edit_every=2)
    lat = loadgen.latent_shape(cell.traffic, cell.model["in_channels"])
    return cell, lat, loadgen.make_plan(cell.traffic, PIN_SEED, 1.0)[:6]


def test_dit_requests_are_the_inline_ones():
    cell, lat, plan = _pin_cell()
    prog = cell_lib.program("dit")
    assert [_pinned(prog.request(cell, a, lat)) for a in plan] == PIN_REQUESTS
    warm = serve._warm_plan(prog, cell, 4, lat)
    assert warm.bucket == 4
    assert [_pinned(r) for r in warm.requests] == PIN_WARM


def test_dit_check_inputs_are_the_inline_ones():
    cell, lat, plan = _pin_cell()
    ref_mod = cell_lib.reference("dit")
    ref = ref_mod.Reference(cell.model, cell.policy, 50, lat)
    got = [ref_mod.inputs(ref, cell, a) for a in plan]
    assert [set(g) for g in got] == [{"x"}] * len(plan)
    assert [digest(g["x"]) for g in got] == PIN_X0


CELLS = {"flux1-dev-cut.freqca-1024.sat": {
             "lat": (128, 128, 16), "crf": (4096, 3072), "max_batch": 4,
             "buckets": [1, 2, 4], "flash_calls": 8,
             "forward": 9075156320256.0, "n_full": 12,
             "image": 109367742431232.0,
             "flash10": (16492674416640.0, 8053063680.0)},
         "dit-xl2-512.none.sat": {
             "lat": (64, 64, 4), "crf": (1024, 1152), "max_batch": 8,
             "buckets": [1, 2, 4, 8], "flash_calls": 28,
             "forward": 1049038848000.0, "n_full": 50,
             "image": 52451942400000.0,
             "flash10": (1352914698240.0, 2642411520.0)}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_dit_engine_arguments_are_the_inline_ones(name):
    pin = CELLS[name]
    cell = cell_lib.load(name, False)
    prog = cell_lib.program(cell.family)
    lat = loadgen.latent_shape(cell.traffic, cell.model["in_channels"])
    run = serve.Run(cell=cell, seed=0, seconds=1.0, peak={}, chips=1)
    crf = (run.tokens, cell.model["d_model"])
    assert (lat, crf) == (pin["lat"], pin["crf"])
    full_fn, from_crf_fn, params = object(), object(), object()
    pol = prog.policy(cell.policy)
    eng = prog.engine(cell, full_fn, from_crf_fn, params, lat, crf, pol)
    assert (eng.full_fn, eng.from_crf_fn, eng.params, eng.policy) == \
        (full_fn, from_crf_fn, params, pol)
    assert (eng.latent_shape, eng.crf_shape) == (pin["lat"], pin["crf"])
    assert (eng.n_steps, eng.max_batch, eng.scheduler.max_wait_s) == \
        (50, pin["max_batch"], 0.05)
    assert eng.buckets == pin["buckets"] and eng.mesh is None
    assert eng.crf_dtype == np.float32 and eng.shapes == [(lat, crf)]
    assert (eng.scheduler.pad_to_max, eng.group_policies,
            eng.scheduler.shed_depth) == (False, True, None)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_dit_work_counts_are_the_inline_ones(name):
    pin = CELLS[name]
    cell = cell_lib.load(name, True)
    m = cell.model
    s = pin["crf"][0]
    prog = cell_lib.program(cell.family)
    assert prog.attention_tokens(m, s) == s
    assert prog.flash_calls(m) == pin["flash_calls"]
    assert prog.forward_flops(m, s) == pin["forward"]
    assert work.image_flops(prog.forward_flops(m, s), m, s, cell.policy,
                            pin["n_full"], 50) == pin["image"]
    # the readers, end to end, over a window of one second
    done = [types.SimpleNamespace(result=types.SimpleNamespace(
        n_full_steps=pin["n_full"]))]
    trace = types.SimpleNamespace(window_s=1.0,
                                  kernel_time=lambda names: (1.0, 2))
    run = types.SimpleNamespace(cell=cell, tokens=s, trace=trace, chips=1,
                                peak=PEAK, full_lane_steps=10,
                                total_lane_steps=10, program=prog,
                                completed=lambda: done)
    flops, nbytes = pin["flash10"]
    least, _ = work.roofline_s(work.Work(flops, nbytes), PEAK)
    assert cell_lib.reader("flash_roofline").read(run)["value"] == \
        100.0 * least
    assert cell_lib.reader("mfu").read(run) == \
        100.0 * pin["image"] / PEAK["bf16_flops_per_s"]
