"""Fleet serving: 1 vs 2 engine replicas on the identical Poisson
arrival stream.

Both rows boot a ``FleetRouter`` over N worker processes (each worker
restores the shared bench checkpoint, builds its own engine, and warms
its bucket ladder), then replay the *same* timestamped arrival plan
(same seed, same rate) through threaded clients.  The arrival rate is
set well above one engine's drained capacity, so the single-replica
row is server-bound and the two-replica row measures real horizontal
scaling: on a host with cores to spare the 2-replica row must reach
>= 1.5x the 1-replica req/s (asserted in CI), with zero dropped or
unresolved futures and zero steady-state recompiles on every replica
— warmup per process, never per request.

On a host without enough cores to run two jax processes concurrently
(``os.cpu_count() < 3``: two busy workers + the router would timeshare
one core) the scaling assertion is recorded but not enforced —
``host_limited`` marks the row so CI guards key off the flag instead
of silently passing.  Emits ``results/bench/BENCH_serve_fleet.json``.

Run directly (``python -m benchmarks.serve_fleet``) or via
``benchmarks/run.py --smoke``; the ``__main__`` guard is mandatory —
the spawn start method re-imports this module in every worker.
"""
from __future__ import annotations

import functools
import os

from benchmarks import common as B
from repro.core.policies import FreqCaPolicy
from repro.launch.serve import poisson_stream, serve_fleet_open_loop
from repro.serving.engine import DiffusionEngine
from repro.serving.fleet import FleetRouter
from repro.models import dit


def fleet_engine(max_batch: int, interval: int, max_wait_s: float):
    """Worker-side engine builder — module-level so its
    ``functools.partial`` pickles under spawn.  Each worker restores
    the checkpoint ``B.ensure_checkpoint()`` wrote before the spawn."""
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    n_tok = (B.IMG_SIZE // cfg.patch_size) ** 2
    return DiffusionEngine(full_fn, from_crf_fn, params,
                           (B.IMG_SIZE, B.IMG_SIZE, cfg.in_channels),
                           (n_tok, cfg.d_model),
                           FreqCaPolicy(interval=interval, method="dct"),
                           n_steps=B.N_STEPS, max_batch=max_batch,
                           max_wait_s=max_wait_s)


def run(out: str = "results/bench/BENCH_serve_fleet.json",
        n_requests: int = 16, max_batch: int = 4, interval: int = 5,
        clients: int = 4,
        title: str = "Fleet serving — 1 vs 2 replicas, same stream"):
    factory = functools.partial(fleet_engine, max_batch, interval, 0.02)
    B.ensure_checkpoint()       # trained in a child, before any spawn

    # capacity probe on a separate warmed replica, so both rows start
    # from equally fresh replicas: set the arrival rate far enough above
    # it that one replica is saturated and two have headroom to show
    # scaling
    probe = FleetRouter(factory, n_replicas=1)
    try:
        probe.start()
        rate = 3.0 * B.router_capacity(probe, max_batch)
    finally:
        probe.shutdown(drain=True)

    host_cpus = os.cpu_count() or 1
    host_limited = host_cpus < 3
    rows = []
    for n_replicas in (1, 2):
        router = FleetRouter(factory, n_replicas=n_replicas)
        try:
            router.start()
            # identical arrival plan both rows: same seed, same rate
            plan = poisson_stream(n_requests, rate, B.IMG_SIZE,
                                  B.bench_config().in_channels,
                                  edit_every=0)
            outs, wall = serve_fleet_open_loop(router, plan,
                                               clients=clients)
            fm = router.fleet_metrics()
        finally:
            router.shutdown(drain=True)
        s = fm.summary()
        fleet, rt = s["fleet"], s["routing"]
        steady = {idx: pr["steady_recompiles"]
                  for idx, pr in s["per_replica"].items()}
        rows.append({
            "replicas": n_replicas,
            "submitted": n_requests,
            "served": len(outs),
            "dropped": n_requests - len(outs),
            "unresolved": rt["submitted"] - rt["resolved"] - rt["failed"],
            "arrival_rate": round(rate, 3),
            "wall_s": round(wall, 3),
            "req_per_s": round(len(outs) / max(wall, 1e-9), 3),
            "latency_p50_s": fleet["request_latency_p50_s"],
            "latency_p95_s": fleet["request_latency_p95_s"],
            "mean_occupancy": fleet["mean_occupancy"],
            "steady_recompiles": steady,
            "affinity_hits": rt["affinity_hits"],
            "spills": rt["spills"],
            "requeued": rt["requeued"],
            "replicas_lost": rt["replicas_lost"],
            "host_cpus": host_cpus,
            "host_limited": host_limited,
        })

    one, two = rows
    two["rps_vs_1replica"] = round(
        two["req_per_s"] / max(one["req_per_s"], 1e-9), 3)
    B.print_table(title, rows)

    # hard invariants on every host: nothing dropped, nothing left
    # unresolved, no replica ever recompiles once warm, no losses
    for r in rows:
        assert r["served"] == r["submitted"] and r["dropped"] == 0, r
        assert r["unresolved"] == 0, r
        assert all(v == 0 for v in r["steady_recompiles"].values()), r
        assert r["replicas_lost"] == 0 and r["requeued"] == 0, r
    # the scaling claim needs cores: router + 2 busy workers.  CI
    # runners have them; a 1-core dev box records host_limited instead
    if not host_limited:
        assert two["rps_vs_1replica"] >= 1.5, rows
    else:
        print(f"host_limited: {host_cpus} cpus — 2-replica scaling "
              f"({two['rps_vs_1replica']}x) recorded, not asserted")
    B.save_rows(out, rows)
    return rows


if __name__ == "__main__":
    run()
