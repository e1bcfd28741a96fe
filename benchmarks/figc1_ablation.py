"""Paper Fig 7 / C1: decomposition x prediction-order ablation.

Sweeps {none, fft, dct} x (low_order, high_order) at several intervals;
the paper's finding to validate: (low=reuse/0, high=2) with a real
decomposition dominates; no-decomposition degrades at large N.
"""
from __future__ import annotations

import jax

from benchmarks import common as B
from repro.core.cache import CachePolicy
from repro.models import dit


def run(out: str = "results/bench/figc1.json"):
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    x0 = jax.random.normal(jax.random.key(11),
                           (B.BATCH, B.IMG_SIZE, B.IMG_SIZE,
                            cfg.in_channels))
    base = B.run_policy(cfg, full_fn, from_crf_fn, params,
                        CachePolicy(kind="none"), x0)

    rows = []
    grids = [
        ("none", [(0, 0), (0, 2)]),       # no decomposition: reuse / taylor
        ("fft", [(0, 2), (0, 1), (1, 2), (2, 2), (0, 0)]),
        ("dct", [(0, 2), (0, 1), (1, 2), (2, 2), (0, 0)]),
    ]
    # rho (low-band fraction) sweep at the paper-default orders
    for n in (5, 10):
        for method in ("fft", "dct"):
            for rho in (0.0625, 0.125, 0.25, 0.5):
                pol = CachePolicy(kind="freqca", interval=n, method=method,
                                  rho=rho, low_order=0, high_order=2)
                res = B.run_policy(cfg, full_fn, from_crf_fn, params, pol, x0,
                                   time_it=False)
                res["wall_s"] = 0.0
                row = B.quality_row(f"{method}/rho={rho}/N={n}", res,
                                    base["x"], 1.0, base["flops"])
                row.pop("latency_s")
                row.pop("speed")
                rows.append(row)
    for n in (5, 10):
        for method, orders in grids:
            for lo, hi in orders:
                if method == "none":
                    kind = "fora" if (lo, hi) == (0, 0) else "taylorseer"
                    pol = CachePolicy(kind=kind, interval=n, high_order=hi)
                    name = f"none/({lo},{hi})/N={n}"
                else:
                    pol = CachePolicy(kind="freqca", interval=n,
                                      method=method, rho=0.0625,
                                      low_order=lo, high_order=hi)
                    name = f"{method}/({lo},{hi})/N={n}"
                res = B.run_policy(cfg, full_fn, from_crf_fn, params, pol, x0,
                                   time_it=False)
                res["wall_s"] = 0.0
                row = B.quality_row(name, res, base["x"], 1.0,
                                    base["flops"])
                row.pop("latency_s")
                row.pop("speed")
                rows.append(row)
    B.print_table("Fig C1 — decomposition x prediction-order ablation",
                  rows)
    B.save_rows(out, rows)
    return rows


def main():
    run()


if __name__ == "__main__":
    main()
