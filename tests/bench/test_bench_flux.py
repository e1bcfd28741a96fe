"""The ``flux`` family (FLUX.1-Kontext) through the harness on the CPU: a
tiny cell served by ``serve.serve`` and checked against
``bench/references/flux.py``; its float8 control and a served path that
drops the conditioning read incorrect; the ``cond_host_ms`` reader; and
the family's refusal of a tree whose requests cannot carry
conditioning."""
import dataclasses
import json
import time
import types

import jax
import numpy as np
import pytest

import bench_tiny
from bench import cell as cell_lib
from bench import loadgen

ROOT = bench_tiny.ROOT
RUN = cell_lib.load_module(ROOT / "bench" / "run.py")
CONTROL = cell_lib.load_module(ROOT / "bench" / "control.py")
PEAK = cell_lib.peaks()["TPU v5 lite"]
SEED = 2 ** 40 + 21
CELL = "flux1-kontext-dev-cut.freqca-kontext-1024.sat"
MODEL = {"n_double": 1, "n_layers": 2, "d_model": 64, "n_heads": 2,
         "d_ff": 128, "patch_size": 2, "in_channels": 4, "text_dim": 32,
         "n_text_tokens": 16, "vec_in_dim": 24, "guidance_embed": True,
         "rope_axes": [8, 12, 12], "rope_theta": 10000.0,
         "time_embed_dim": 256, "norm_eps": 1e-6, "dtype": "bfloat16"}


def tiny_cell(check_requests=6):
    """The new cell's traffic, limits and engine at 64 px (16 image + 16
    reference + 16 text tokens), offered above what the CPU serves from
    the open, so cuts of every bucket."""
    real = cell_lib.load(CELL, False)
    return cell_lib.Cell(
        name="tiny-kontext", chips=1,
        config=dict(real.config, model=MODEL),
        traffic=dict(real.traffic, image_px=64, rate_per_s=40.0,
                     backlog=0),
        limits=dict(real.limits, check_requests=check_requests),
        metrics=real.metrics)


def measure(cell):
    return RUN.measure(cell, SEED, 1.0, False, jax.devices(), PEAK,
                       time.perf_counter())


def test_a_kontext_cell_is_served_conditioned_and_correct():
    line = measure(tiny_cell())
    assert line["correct"] is True
    assert line["compiles_in_window"] == 0
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert set(line["metrics"]) >= {"images_per_s", "setup_s"}
    assert line["checks"]["full_steps_off"]["value"] == 0


def test_a_served_path_without_its_conditioning_is_incorrect(monkeypatch):
    """The text zeroed in the engine: the check's reference, given the
    arrival's text, reads the latents as wrong."""
    from repro.serving.engine import DiffusionEngine
    build = DiffusionEngine.build_cond

    def no_text(plan):
        cond = build(plan)
        return dict(cond, txt=0 * cond["txt"])

    monkeypatch.setattr(DiffusionEngine, "build_cond",
                        staticmethod(no_text))
    assert measure(tiny_cell())["correct"] is False


def test_the_float8_control_is_incorrect():
    cell = tiny_cell(check_requests=3)
    out = CONTROL.readings(cell, SEED, 1.0)
    assert out["correct"] is False
    assert out["checks"]["full_steps_off"]["value"] == 0


def _run(results):
    done = [types.SimpleNamespace(result=r) for r in results]
    return types.SimpleNamespace(in_window=lambda: done)


def test_cond_host_ms_is_the_mean_over_batches():
    read = cell_lib.reader("cond_host_ms").read
    r = [types.SimpleNamespace(batch=b, cond_host_s=s)
         for b, s in ((3, 0.010), (3, 0.010), (4, 0.030))]
    assert read(_run(r)) == pytest.approx(20.0)
    # unconditioned batches, or results that carry no such time
    assert read(_run([types.SimpleNamespace(batch=1, cond_host_s=0.0)])) \
        is None
    assert read(_run([types.SimpleNamespace(batch=1)])) is None
    assert read(_run([])) is None


def test_a_tree_without_conditioned_requests_is_refused(monkeypatch):
    from repro.serving import scheduler

    @dataclasses.dataclass
    class Bare:
        request_id: int
        seed: int

    monkeypatch.setattr(scheduler, "DiffusionRequest", Bare)
    prog = cell_lib.program("flux")
    with pytest.raises(SystemExit, match="cond"):
        prog.denoiser(MODEL, "tiny")


# the cell's knee on a TPU v5e: the full-bucket throughput that
# bench/tools/sweep.py measured, 4 images over an 8.308 s batch
# (images/s; PERF.md §4)
KNEE = 0.4815


def test_the_kontext_mix_offers_a_full_bucket_every_batch():
    """At 1.5x the knee with a backlog of three buckets, every batch the
    chip starts by the window's close finds ``max_batch`` requests due:
    every cut is the one bucket warmed."""
    cell = cell_lib.load(CELL, False)
    traffic, mb = cell.traffic, cell.engine["max_batch"]
    assert traffic["backlog"] >= 3 * mb and traffic["edit_every"] == 0
    assert traffic["rate_per_s"] == pytest.approx(1.5 * KNEE, rel=0.01)
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    batch_s = mb / KNEE
    due = np.array([a.due_s for a in loadgen.make_plan(traffic, SEED,
                                                       seconds)])
    starts = np.arange(0.0, seconds + batch_s, batch_s)
    for k, t in enumerate(starts):
        assert np.sum(due <= t) - k * mb >= mb, (t, k)
