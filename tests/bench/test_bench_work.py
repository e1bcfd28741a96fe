"""The benchmark's operation and byte counts against published and
hand-worked figures."""
import json

import pytest

import bench_tiny
from bench import work

CONFIGS = bench_tiny.ROOT / "bench" / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_dit_xl2_forward_matches_the_paper():
    # DiT paper, Table 4: 524.6 GFLOPs for XL/2 at 512 px, counted as
    # multiply-adds; 1024 tokens
    m = model("dit-xl2-512")
    assert work.image_tokens(m, 512) == 1024
    assert work.forward_flops(m, 1024) == pytest.approx(2 * 524.6e9,
                                                        rel=2e-3)


def test_flux_cut_forward_at_4096_tokens():
    # 8 single blocks at d=3072, d_ff=12288, S=4096: 1.1338 TFLOP each
    m = model("flux1-dev-cut")
    s = work.image_tokens(m, 1024)
    assert s == 4096
    assert work.forward_flops(m, s) == pytest.approx(9.07e12, rel=2e-3)


def test_flash_call_at_flux_bucket_4():
    w = work.flash(4, 4096, 24, 128, "bfloat16")
    assert w.flops == pytest.approx(0.8246e12, rel=1e-3)
    assert w.bytes == 4 * 4 * 4096 * 24 * 128 * 2


@pytest.mark.parametrize("flops,nbytes,bound", [
    (197e12, 1e9, "compute"), (1e9, 819e9, "memory")])
def test_roofline_names_its_bound(flops, nbytes, bound):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    secs, which = work.roofline_s(work.Work(flops, nbytes), peak)
    assert which == bound
    assert secs == pytest.approx(1.0, rel=1e-2)


def test_cached_step_is_a_sliver_of_a_full_step():
    m = model("flux1-dev-cut")
    pol = bench_tiny.FREQCA
    forward = work.forward_flops(m, 4096)
    full = work.full_step_flops(forward, m, 4096, pol)
    cached = work.cached_step_flops(m, 4096, pol)
    assert 0 < cached < 0.01 * full
    img = work.image_flops(forward, m, 4096, pol, n_full=12, n_steps=50)
    assert img == pytest.approx(12 * full + 38 * cached)


def test_band_split_and_predict_bytes_at_the_config_dtype():
    s, d, m = 4096, 3072, 256
    split = work.band_split(4, s, d, m, "bfloat16")
    assert split.bytes == (2 * 4 * s * d + 4 * m * d) * 2
    pred = work.freqca_predict(4, s, d, m, 3, "bfloat16", calls=2)
    assert pred.bytes == (4 * 3 * s * d + 4 * m * d + 2 * s * m
                          + 4 * s * d) * 2
