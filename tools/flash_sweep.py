"""Flash-attention tile sweep at the served attention shapes, on the chip.

    env PYTHONPATH=src python -m tools.flash_sweep --sweep 512x512,1024x512

Times the non-causal bf16 flash kernel at each served shape
(``SERVED_ATTENTION``), first at ``flash_attention.tiles``' blocks (the
first row of each shape) and then at every listed (q_block, kv_block)
that divides the sequence.  One JSON row per timing goes to stdout and
all of them to ``results/bench/BENCH_flash_sweep.json``: host wall time
of a call, the work ``bench/work.flash`` counts and, on a chip listed
in ``bench/peaks.json``, the share of that work's roofline.  The caps
``Q_CAP``/``TILE_CAP`` in ``kernels/flash_attention.py`` are chosen
from it.  Off the chip the kernel runs in interpret mode and the times
mean nothing (``interpret`` is true in each row).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp

from bench import cell, work
from repro.kernels import flash_attention as fa
from repro.kernels import ops

# the served attention shapes: (cell's config, B, S, heads, hd)
SERVED_ATTENTION = (("flux1-dev-cut", 4, 4096, 24, 128),
                    ("dit-xl2-512", 8, 1024, 16, 72),
                    ("flux1-kontext-dev-cut", 4, 8704, 24, 128))
OUT = "results/bench/BENCH_flash_sweep.json"


def _wall(fn, *args, reps: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def attention_row(batch: int, s: int, heads: int, hd: int,
                  blocks: tuple[int, int], dtype=jnp.bfloat16) -> dict:
    """The flash kernel at one shape and one (q, kv) block pair."""
    q, k, v = (jax.random.normal(kk, (batch, s, heads, hd)).astype(dtype)
               for kk in jax.random.split(jax.random.key(2), 3))
    qb, kb = blocks
    flash = jax.jit(functools.partial(
        fa.flash_attention, q_per_kv=1, causal=False, q_block=qb,
        kv_block=kb, interpret=ops.interpret()))
    wall = _wall(flash, q, k, v, reps=5)
    need = work.flash(batch, s, heads, hd, jnp.dtype(dtype).name)
    share = bound = None
    peak = cell.peaks().get(jax.devices()[0].device_kind)
    if not ops.interpret() and peak is not None:
        least, bound = work.roofline_s(need, peak)
        share = round(100.0 * least / wall, 3)
    return {
        "batch": batch, "tokens": s, "heads": heads, "head_dim": hd,
        "dtype": jnp.dtype(dtype).name, "q_block": qb, "kv_block": kb,
        "grid_steps": batch * heads * (s // qb) * (s // kb),
        "flops": need.flops, "bytes": need.bytes,
        "wall_flash_ms": round(1e3 * wall, 3),
        # host clock around the call: an upper bound on device time
        "flash_roofline_pct": share, "bound": bound,
        "interpret": ops.interpret(),
    }


def sweep(blocks, out: str = OUT) -> list:
    """Each served shape at ``tiles``' blocks, then at every pair of
    ``blocks`` (each capped at the sequence) that divides its length."""
    rows = []
    for config, batch, s, heads, hd in SERVED_ATTENTION:
        seen = set()
        for qb, kb in [fa.tiles(s, s, hd, jnp.bfloat16), *blocks]:
            qb, kb = min(qb, s), min(kb, s)
            if s % qb or s % kb or (qb, kb) in seen:
                continue
            seen.add((qb, kb))
            row = {"config": config,
                   **attention_row(batch, s, heads, hd, (qb, kb))}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sweep", metavar="QxK,...", required=True,
                    help="(q_block x kv_block) pairs to time besides "
                         "the default tiles, e.g. 512x512,1024x512")
    args = ap.parse_args(argv)
    sweep([tuple(int(n) for n in b.split("x"))
           for b in args.sweep.split(",")])


if __name__ == "__main__":
    main()
