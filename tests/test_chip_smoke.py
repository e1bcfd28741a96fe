"""``chip_smoke.py``: refuses to run off the chip, and its phases pass
end to end on the CPU at a tiny size (the rehearsal of the chip run:
same code paths, Pallas kernels in interpret mode)."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import repro.configs as config_lib
from repro.models import dit

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=_env(),
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "platform cpu" in out.stdout


def test_flux_cut_keeps_widths_and_ratio(smoke):
    full, cfg = smoke.flux_cut()
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.patch_size,
            cfg.in_channels, cfg.dtype) == (3072, 24, 12288, 2, 16,
                                            "bfloat16")
    assert cfg.n_layers == 2 * cfg.n_double
    assert full.n_layers == 2 * full.n_double


def _tiny():
    cfg = config_lib.reduced(config_lib.get_config("flux1-dev"))
    return cfg, dit.random_params(cfg, 0)


def test_phases_pass_on_cpu_at_tiny_size(smoke, monkeypatch):
    # no chip here: the kernels run interpreted, so no custom call
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setattr(smoke, "require_custom_call", lambda *a: None)
    smoke.kernel_phase(batch=2, s=256, d=256, heads=2, n_text=128)
    monkeypatch.delenv("REPRO_KERNELS")
    cfg, params = _tiny()
    smoke.forward_phase(cfg, params, size=8, n_text=8, seed=0)
    smoke.serving_phase(cfg, params, size=8, n_steps=10, interval=5,
                        max_batch=2, n_requests=4)


_FLEET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json, sys
import repro.configs as config_lib
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
cfg = config_lib.reduced(config_lib.get_config("flux1-dev"))
# a tiny request takes milliseconds here: arrivals come at once so
# that requests overlap and spread over the replicas, as on the chip
smoke.fleet_phase(cfg, size=8, n_steps=6, interval=3, n_requests=8, seed=0,
                  rate=1e4)
print(json.dumps({"ok": True}))
"""


def test_fleet_phase_on_four_cpu_devices():
    """Four thread replicas, one CPU device each, against one replica:
    the same stream, the same per-request latents."""
    out = subprocess.run([sys.executable, "-c", _FLEET,
                          str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=_env(),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {"ok": True}
    assert "devices [[0], [1], [2], [3]]" in out.stdout, out.stdout
