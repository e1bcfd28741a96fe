"""Whether the timed path's outputs are correct.

After the window closes and the program's state is off the device, a
sample of the requests the window completed, drawn from the seed, is
served again by the plain float32 reference of the configuration's
family, one image at a time, from the weight seed and each request's
inputs as the family's reference file draws them from the arrival
(``inputs``: the same seeds, edit references and conditioning the
program file's ``request`` served).  Compared, each with its limit from
``bench/limits/<cell>.json``:

  latent_rel_err_max  largest ||x − x_ref|| / ||x_ref|| of the final
                      latents over the sample
  full_steps_off      sampled requests whose count of full steps is not
                      the reference schedule's (exact: limit 0)
  nonfinite_latents   completed requests with a non-finite latent
                      (exact: limit 0)
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from bench import loadgen


def sample(run, k: int) -> List[loadgen.Arrival]:
    """``k`` completed requests drawn from the seed: an edit where one
    completed, then requests of buckets not yet drawn, then any."""
    rng = np.random.RandomState(loadgen.fold(run.seed, "check"))
    pool = sorted(run.completed(), key=lambda a: a.index)
    order = [pool[i] for i in rng.permutation(len(pool))]
    picked: List[loadgen.Arrival] = []
    edits = [a for a in order if a.edit]
    if edits:
        picked.append(edits[0])
    seen = {a.result.bucket for a in picked}
    for a in order:
        if len(picked) < k and a not in picked and a.result.bucket not in seen:
            picked.append(a)
            seen.add(a.result.bucket)
    for a in order:
        if len(picked) < k and a not in picked:
            picked.append(a)
    return picked


def compare(run) -> Dict[str, dict]:
    """The compared numbers, each ``{"value": v, "limit": l}``."""
    from bench import cell as cell_lib
    cell = run.cell
    ref_mod = cell_lib.reference(cell.family)
    lat = loadgen.latent_shape(cell.traffic, cell.model["in_channels"])
    ref = ref_mod.Reference(cell.model, cell.policy,
                            cell.engine["n_steps"], lat)
    weights = ref_mod.make_weights(cell.model,
                                   loadgen.fold(run.seed, "weights"))
    errs, off = [], 0
    for a in sample(run, cell.limits["check_requests"]):
        x, n_full = ref.sample(weights, **ref_mod.inputs(ref, cell, a))
        errs.append(ref_mod.rel_err(run.latents[a.index], x))
        off += int(a.result.n_full_steps != n_full)
    bad = sum(int(not np.isfinite(x).all()) for x in run.latents.values())
    lim = cell.limits
    return {"latent_rel_err_max": {"value": max(errs),
                                   "limit": lim["latent_rel_err_max"]},
            "full_steps_off": {"value": off, "limit": 0},
            "nonfinite_latents": {"value": bad, "limit": 0}}


def run_check(run) -> tuple:
    """-> (correct, checks, seconds)."""
    t0 = time.perf_counter()
    checks = compare(run)
    correct = bool(run.completed()) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks, time.perf_counter() - t0


def print_checks(checks: Dict[str, dict], correct: bool) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct {correct}", file=sys.stderr, flush=True)
