"""Shared benchmark plumbing: one trained dit-small reused by every
paper-table benchmark, image metrics (PSNR/SSIM), policy sweep runner."""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as config_lib
from repro.checkpointing import checkpoint
from repro.core.cache import CachePolicy
from repro.diffusion import sampler, schedule
from repro.launch.train import restore_dit, train_dit, train_dit_in_child

# --smoke (benchmarks/run.py) shrinks everything via these env knobs.
# Read at *call* time, never at import: the fleet router (and run.py
# itself) set the knobs after this module may already be imported, and
# an import-frozen read would silently pin full-scale settings — the
# same bug class as the PR-4 INTERPRET freeze (see repro.analysis's
# env-read-at-import rule).  The legacy module-level names (B.IMG_SIZE
# etc.) still work via the PEP 562 __getattr__ below, which re-reads
# the environment on every attribute access.


def reduced() -> bool:
    return os.environ.get("BENCH_REDUCED", "") == "1"


def ckpt_dir() -> str:
    return "results/bench_ckpt_smoke" if reduced() else "results/bench_ckpt"


def img_size() -> int:
    return int(os.environ.get("BENCH_IMG_SIZE", "32"))


def train_steps() -> int:
    return int(os.environ.get("BENCH_TRAIN_STEPS", "200"))


def sample_steps() -> int:
    return int(os.environ.get("BENCH_SAMPLE_STEPS", "50"))


def bench_batch() -> int:
    return int(os.environ.get("BENCH_BATCH", "4"))


_ENV_ATTRS = {
    "REDUCED": reduced, "CKPT_DIR": ckpt_dir, "IMG_SIZE": img_size,
    "TRAIN_STEPS": train_steps, "N_STEPS": sample_steps,
    "BATCH": bench_batch,
}


def __getattr__(name: str):
    fn = _ENV_ATTRS.get(name)
    if fn is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    return fn()


def bench_config():
    """The small DiT config the benches serve (reduced under --smoke)."""
    cfg = config_lib.get_config("dit-small")
    return config_lib.reduced(cfg) if reduced() else cfg


def get_model():
    """Train (once) and cache the small DiT used by the quality benches."""
    cfg = bench_config()
    params = restore_dit(cfg, ckpt_dir())
    if params is None:
        params = train_dit(cfg, train_steps(), 16, ckpt_dir=ckpt_dir(),
                           size=img_size())
    return cfg, params


def ensure_checkpoint() -> None:
    """Train the bench checkpoint in a child process if it is missing,
    so a fleet bench's parent never touches a JAX device before its
    workers (which restore it) boot."""
    if checkpoint.latest_step(ckpt_dir(), "dit") < 0:
        train_dit_in_child(bench_config(), train_steps(), 16, ckpt_dir(),
                           size=img_size())


def router_capacity(router, max_batch: int, rounds: int = 2) -> float:
    """Requests/s of one warm fleet replica serving one full bucket,
    best of ``rounds`` (the requests share a group, so affinity keeps
    them on one replica; the replica cuts them by age, so no drain
    tick is timed)."""
    from repro.serving.engine import DiffusionRequest
    best = 0.0
    for _ in range(rounds):
        t0 = time.perf_counter()
        futs = [router.submit(DiffusionRequest(request_id=-1 - i, seed=i))
                for i in range(max_batch)]
        for f in futs:
            f.result()
        best = max(best, max_batch / max(time.perf_counter() - t0, 1e-9))
    return best


def denoiser_flops_per_step(cfg) -> float:
    """Analytic FLOPs of one denoiser forward (batch 1)."""
    s = (img_size() // cfg.patch_size) ** 2
    per_layer = (4 * cfg.d_model ** 2 + 2 * cfg.d_model * cfg.d_ff
                 ) * 2 * s + 2 * 2 * s * s * cfg.d_model
    return (cfg.n_layers + 2 * cfg.n_double) * per_layer


def psnr(a, b, data_range: float = 2.0) -> float:
    mse = float(jnp.mean(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
    if mse == 0:
        return float("inf")
    return float(10.0 * np.log10(data_range ** 2 / mse))


def ssim(a, b, data_range: float = 2.0) -> float:
    """Global-statistics SSIM per channel (adequate at 32x32 bench scale)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    c1, c2 = (0.01 * data_range) ** 2, (0.03 * data_range) ** 2
    mu_a, mu_b = a.mean(), b.mean()
    va, vb = a.var(), b.var()
    cov = ((a - mu_a) * (b - mu_b)).mean()
    return float(((2 * mu_a * mu_b + c1) * (2 * cov + c2))
                 / ((mu_a ** 2 + mu_b ** 2 + c1) * (va + vb + c2)))


def run_policy(cfg, full_fn, from_crf_fn, params, policy: CachePolicy,
               x0: jnp.ndarray, n_steps: Optional[int] = None,
               time_it: bool = True) -> Dict:
    if n_steps is None:
        n_steps = sample_steps()
    ts = schedule.timesteps(n_steps)
    n_tok = (img_size() // cfg.patch_size) ** 2
    crf_shape = (x0.shape[0], n_tok, cfg.d_model)

    fn = jax.jit(lambda p, x: sampler.sample(full_fn, from_crf_fn, p, x,
                                             ts, policy,
                                             crf_shape=crf_shape))
    res = fn(params, x0)
    res.x.block_until_ready()
    wall = None
    if time_it:
        t0 = time.perf_counter()
        res = fn(params, x0)
        res.x.block_until_ready()
        wall = time.perf_counter() - t0
    n_full = int(res.n_full)
    flops = n_full * denoiser_flops_per_step(cfg) * x0.shape[0]
    return {"x": res.x, "n_full": n_full, "wall_s": wall,
            "flops": flops,
            "flops_speedup": n_steps / max(n_full, 1)}


def quality_row(name: str, res: Dict, ref_x, base_wall: float,
                base_flops: float) -> Dict:
    wall = res["wall_s"] or 0.0
    return {
        "method": name,
        "latency_s": round(wall, 3),
        "speed": round(base_wall / wall, 2) if wall else 0.0,
        "flops_speedup": round(base_flops / max(res["flops"], 1), 2),
        "n_full": res["n_full"],
        "psnr": round(psnr(res["x"], ref_x), 2),
        "ssim": round(ssim(res["x"], ref_x), 3),
        "rel_err": round(float(
            jnp.linalg.norm((res["x"] - ref_x).astype(jnp.float32))
            / jnp.linalg.norm(ref_x.astype(jnp.float32))), 4),
    }


def print_table(title: str, rows: List[Dict]):
    if not rows:
        return
    cols = list(rows[0].keys())
    print(f"\n### {title}")
    print(" | ".join(cols))
    print(" | ".join(["---"] * len(cols)))
    for r in rows:
        print(" | ".join(str(r[c]) for c in cols))


def save_rows(path: str, rows: List[Dict]):
    import json
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
