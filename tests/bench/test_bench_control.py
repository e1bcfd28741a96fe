"""The control of the correctness check at a size a test run holds: the
plain reference computed with float8 operands, put in the program's
place and judged by the harness's own check, comes out as not correct
under each real cell's limits."""
import pytest

import bench_tiny
from bench import cell as cell_lib

CONTROL = cell_lib.load_module(bench_tiny.ROOT / "bench" / "control.py")
SEEDS = (2 ** 40 + 11, 2 ** 40 + 12, 2 ** 40 + 13)


@pytest.mark.parametrize("policy,limits_of", [
    (bench_tiny.FREQCA, "flux1-dev-cut.freqca-1024.sat"),
    ({"name": "none"}, "dit-xl2-512.none.sat")])
def test_float8_control_is_not_correct(policy, limits_of):
    cell = bench_tiny.cell(policy=policy, limits_of=limits_of)
    for seed in SEEDS:
        got = CONTROL.readings(cell, seed, 1.0)
        assert got["correct"] is False, got
        err = got["checks"]["latent_rel_err_max"]
        assert err["value"] > err["limit"]
        assert got["checks"]["full_steps_off"]["value"] == 0
