"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false, once for each fault a served
diffusion cell can have."""
import time

import jax

import bench_tiny
from bench import cell as cell_lib

ROOT = bench_tiny.ROOT
RUN = cell_lib.load_module(ROOT / "bench" / "run.py")
PEAK = cell_lib.peaks()["TPU v5 lite"]
SEED = 2 ** 40 + 3


def measure(**traffic):
    # arrivals from the open at more than the CPU serves, so cuts of any
    # size; the check compares every answer against a real cell's limits
    cell = bench_tiny.cell(rate_per_s=40.0,
                           limits_of="flux1-dev-cut.freqca-1024.sat",
                           **traffic)
    cell.limits = dict(cell.limits, check_requests=64)
    return RUN.measure(cell, SEED, 1.0, False, jax.devices(), PEAK,
                       time.perf_counter())


def _patch_sampler(monkeypatch, edit):
    from repro.diffusion import sampler
    orig = sampler.sample

    def broken(full_fn, from_crf_fn, params, x_init, *a, **k):
        res = orig(full_fn, from_crf_fn, params, x_init, *a, **k)
        return res._replace(x=edit(res.x, x_init))

    monkeypatch.setattr(sampler, "sample", broken)


def test_sound_run_is_correct():
    line = measure()
    assert line["correct"] is True
    assert line["compiles_in_window"] == 0
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert set(line["metrics"]) >= {"images_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


def test_steps_that_leave_the_state_unchanged(monkeypatch):
    _patch_sampler(monkeypatch, lambda x, x0: x0)
    assert measure()["correct"] is False


def test_half_the_batch_left_out(monkeypatch):
    def half(x, x0):
        return x.at[x.shape[0] // 2:].set(x0[x.shape[0] // 2:])
    _patch_sampler(monkeypatch, half)
    assert measure()["correct"] is False


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.serving.engine import DiffusionEngine
    orig = DiffusionEngine.execute_plan

    def altered(self, plan):
        out = orig(self, plan)
        return [out[0]._replace(latents=out[0].latents + 1.0)] + out[1:]

    monkeypatch.setattr(DiffusionEngine, "execute_plan", altered)
    assert measure()["correct"] is False
