"""Multi-resolution serving: one engine (and a 2-replica fleet) over a
mixed-shape Poisson stream.

One deployment declares a three-entry shape ladder (half / primary /
double image size — e.g. 64/256/1024 tokens at the default bench
scale) and serves a mixed-resolution Poisson arrival stream through
the (batch-bucket, shape-bucket) signature path:

* **multires_poisson** — open-loop replay through the single warmed
  engine.  Asserted: zero steady-state recompiles, every cut
  shape-pure (checked on every ``execute_plan`` call), compiled
  signatures <= shapes x groups x buckets (``signature_budget``), and
  a submit carrying an undeclared shape rejected with
  ``ShapeMismatchError`` before it touches the queue.
* **multires_fleet** — the same plan through a ``FleetRouter`` over 2
  replicas, each warming the full ladder.  It runs first, so this
  process holds no JAX device state when the workers spawn; its
  capacity probe sets the stream's arrival rate.  Asserted: nothing
  dropped,
  ``submitted == resolved + failed`` (a bad-shape submit through the
  router fails fast and leaves the counters in step), zero
  steady-state recompiles on every replica.
* **multires_closed vs three_singles** — closed-loop drain of the
  mixed stream through the one multi-shape engine vs the sum of three
  single-shape engines each draining its own sub-stream (the
  deployment the shape ladder replaces).  The req/s ratio is recorded
  (not hard-asserted: it measures consolidation overhead, which is
  host-dependent), the executable counts are.

Emits ``results/bench/BENCH_serve_multires.json``.  Run directly
(``python -m benchmarks.serve_multires``) or via
``benchmarks/run.py --smoke``; the ``__main__`` guard is mandatory —
the spawn start method re-imports this module in every fleet worker.
"""
from __future__ import annotations

import dataclasses
import functools
import time

from benchmarks import common as B
from repro.core.policies import FreqCaPolicy
from repro.launch.serve import poisson_stream, serve_fleet_open_loop, \
    serve_open_loop
from repro.models import dit
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.serving.fleet import FleetRouter
from repro.serving.scheduler import ShapeMismatchError


def ladder_sizes():
    """Half / primary / double the bench image size."""
    s = B.img_size()
    return (s // 2, s, 2 * s)


def shape_pairs(cfg, sizes):
    return [((s, s, cfg.in_channels),
             ((s // cfg.patch_size) ** 2, cfg.d_model)) for s in sizes]


def multires_engine(max_batch: int, interval: int, max_wait_s: float,
                    sizes=None):
    """Worker-side engine builder — module-level so its
    ``functools.partial`` pickles under spawn.  ``from_crf_fn`` is
    shape-generic (image side recovered from the token count), so one
    callable serves the whole ladder."""
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    sizes = list(sizes) if sizes else [B.img_size()]
    pairs = shape_pairs(cfg, sizes)
    return DiffusionEngine(full_fn, from_crf_fn, params, pairs[0][0],
                           pairs[0][1],
                           FreqCaPolicy(interval=interval, method="dct"),
                           n_steps=B.N_STEPS, max_batch=max_batch,
                           max_wait_s=max_wait_s, shapes=pairs[1:])


def _count_pure_cuts(eng):
    """Wrap ``execute_plan`` to assert every cut is shape-pure (all
    lanes resolve to one shape key) and count the cuts."""
    counter = [0]
    orig = eng.execute_plan

    def checked(plan):
        cut_shapes = {eng.scheduler.shape_of(r) for r in plan.requests}
        assert len(cut_shapes) == 1, f"mixed-shape cut: {cut_shapes}"
        counter[0] += 1
        return orig(plan)

    eng.execute_plan = checked
    return counter


def run(out: str = "results/bench/BENCH_serve_multires.json",
        n_requests: int = 18, max_batch: int = 4, interval: int = 5,
        title: str = "Multi-resolution serving — one (batch, shape) "
                     "bucketed engine"):
    cfg = B.bench_config()
    sizes = ladder_sizes()
    pairs = shape_pairs(cfg, sizes)
    rows = []

    # --- leg 3 (run first): 2-replica fleet, the mixed stream ----------
    # this process touches no device before the workers have booted
    B.ensure_checkpoint()
    factory = functools.partial(multires_engine, max_batch, interval,
                                0.02, sizes)
    router = FleetRouter(factory, n_replicas=2)
    try:
        router.start()
        # capacity probe (primary shape) on one warm replica: sets an
        # arrival rate an engine can sustain without the open-loop
        # replay dragging on for minutes
        rate = 2.0 * B.router_capacity(router, max_batch)
        plan = poisson_stream(n_requests, rate, B.img_size(),
                              cfg.in_channels, edit_every=0, shapes=pairs)
        bad = DiffusionRequest(request_id=-1, seed=0,
                               latent_shape=(B.img_size() + 2,) * 2
                               + (cfg.in_channels,))
        fplan = [dataclasses.replace(r, submit_time=0.0) for r in plan]
        f_outs, f_wall = serve_fleet_open_loop(router, fplan, clients=4)
        # bad-shape submit through the router: synchronous rejection,
        # counters stay in step (submitted never incremented)
        try:
            router.submit(dataclasses.replace(bad))
            fleet_bad_rejected = False
        except ShapeMismatchError:
            fleet_bad_rejected = True
        fm = router.fleet_metrics()
        rt = router.status()["counters"]
    finally:
        router.shutdown(drain=True)
    s = fm.summary()
    fleet_steady = {idx: pr["steady_recompiles"]
                    for idx, pr in s["per_replica"].items()}
    fleet_row = {
        "leg": "multires_fleet",
        "replicas": 2,
        "shapes": len(pairs),
        "submitted": n_requests,
        "served": len(f_outs),
        "dropped": n_requests - len(f_outs),
        "unresolved": rt["submitted"] - rt["resolved"] - rt["failed"],
        "wall_s": round(f_wall, 3),
        "req_per_s": round(len(f_outs) / max(f_wall, 1e-9), 3),
        "steady_recompiles": fleet_steady,
        "bad_shape_rejected": fleet_bad_rejected,
        "shape_keys": s["fleet"].get("shape_keys", 0),
    }


    # --- leg 1: one engine, mixed-shape Poisson stream ------------------
    eng = multires_engine(max_batch, interval, 0.02, sizes=sizes)
    eng.warmup()
    budget = eng.signature_budget()
    warm_sigs = eng.compiled_buckets()

    pre = eng.metrics_dict()["compile_misses"]
    pure_cuts = _count_pure_cuts(eng)
    outs, wall = serve_open_loop(eng, plan)
    steady = eng.metrics_dict()["compile_misses"] - pre

    # bad-shape submit: rejected at the API boundary, queue untouched
    try:
        eng.submit(bad)
        bad_rejected = False
    except ShapeMismatchError:
        bad_rejected = eng.scheduler.depth == 0

    served_shapes = {}
    for o in outs:
        k = tuple(o.latents.shape)
        served_shapes[k] = served_shapes.get(k, 0) + 1
    rows.append({
        "leg": "multires_poisson",
        "shapes": len(pairs),
        "submitted": n_requests,
        "served": len(outs),
        "dropped": n_requests - len(outs),
        "wall_s": round(wall, 3),
        "req_per_s": round(len(outs) / max(wall, 1e-9), 3),
        "shape_pure_cuts": pure_cuts[0],
        "steady_recompiles": steady,
        "compiled_signatures": eng.compiled_buckets(),
        "signature_budget": budget,
        "bad_shape_rejected": bad_rejected,
        "served_per_shape": {str(k): v for k, v in
                             sorted(served_shapes.items())},
    })

    # --- leg 2: closed-loop, one multi-shape engine vs three singles ----
    replay = [dataclasses.replace(r, arrival_s=0.0, submit_time=0.0)
              for r in plan]
    t0 = time.perf_counter()
    for r in replay:
        eng.submit(r)
    m_outs = eng.serve_until_drained()
    multires_wall = time.perf_counter() - t0

    singles_wall, singles_served, singles_sigs = 0.0, 0, 0
    for s, pair in zip(sizes, pairs, strict=True):
        se = multires_engine(max_batch, interval, 0.02, sizes=[s])
        se.warmup()
        singles_sigs += se.compiled_buckets()
        sub = [dataclasses.replace(r, arrival_s=0.0, submit_time=0.0)
               for r in plan if r.latent_shape == pair[0]]
        t0 = time.perf_counter()
        for r in sub:
            se.submit(r)
        singles_served += len(se.serve_until_drained())
        singles_wall += time.perf_counter() - t0
        del se
    m_rps = len(m_outs) / max(multires_wall, 1e-9)
    s_rps = singles_served / max(singles_wall, 1e-9)
    rows.append({
        "leg": "multires_closed_vs_singles",
        "shapes": len(pairs),
        "served_multires": len(m_outs),
        "served_singles": singles_served,
        "multires_wall_s": round(multires_wall, 3),
        "singles_wall_s": round(singles_wall, 3),
        "multires_req_per_s": round(m_rps, 3),
        "singles_req_per_s": round(s_rps, 3),
        "rps_vs_singles": round(m_rps / max(s_rps, 1e-9), 3),
        "multires_signatures": eng.compiled_buckets(),
        "singles_signatures_total": singles_sigs,
    })
    del eng
    rows.append(fleet_row)

    # rows carry per-leg schemas: one table per leg
    for r in rows:
        B.print_table(f"{title} — {r['leg']}",
                      [{k: v for k, v in r.items()
                        if not isinstance(v, dict)}])

    # hard invariants (the CI multires guard re-checks these from the
    # emitted json): compile-free steady state, bounded signatures,
    # shape-pure cuts, fail-fast validation, conservation
    poisson, closed, fleet = rows
    assert poisson["dropped"] == 0 and poisson["steady_recompiles"] == 0
    assert poisson["compiled_signatures"] <= poisson["signature_budget"]
    assert poisson["shape_pure_cuts"] > 0
    assert poisson["bad_shape_rejected"]
    assert len(poisson["served_per_shape"]) == len(pairs)
    assert closed["served_multires"] == n_requests
    assert closed["multires_signatures"] <= poisson["signature_budget"]
    assert fleet["dropped"] == 0 and fleet["unresolved"] == 0
    assert all(v == 0 for v in fleet["steady_recompiles"].values())
    assert fleet["bad_shape_rejected"]
    B.save_rows(out, rows)
    return rows


if __name__ == "__main__":
    run()
