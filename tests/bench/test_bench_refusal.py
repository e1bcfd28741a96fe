"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny

ROOT = bench_tiny.ROOT


def _run(cwd, workload="dit-xl2-512.none.sat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 40), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_cpu_run_refuses_to_report_device_metrics():
    proc = _run(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_bare_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_device_check_refuses_the_cpu():
    from bench import cell as cell_lib
    run = cell_lib.load_module(ROOT / "bench" / "run.py")
    with pytest.raises(SystemExit, match="no TPU"):
        run.devices(1)
