"""Availability under faults: kill 1 of 2 replicas mid-stream.

Two rows over the same request load on a 2-replica fleet:

* ``no_fault`` — the control: both replicas serve two waves cleanly
  (0 losses, 0 restarts, 0 steady-state recompiles);
* ``kill_one_of_two`` — a scripted ``FaultInjector`` SIGKILLs replica
  0 the moment its 2nd submit arrives (the pipe just EOFs, exactly
  like a real crash).  The router requeues the orphans onto the
  survivor, the supervisor restarts the slot, and a second wave runs
  after the rejoin.

The availability invariants (asserted here and guarded in CI from
``BENCH_serve_chaos.json``): every submitted future resolves exactly
once (served == submitted, 0 dropped, 0 unresolved), the fault row
records ``replicas_lost >= 1`` and ``restarts >= 1``, the restarted
replica serves post-rejoin work, fleet-wide in-flight never exceeded
``replicas x max_inflight``, and steady-state recompiles are 0 on
every replica — the restarted worker re-warms at boot, so a restart
costs downtime, never a compile in the serving path.

Run directly (``python -m benchmarks.serve_chaos``) or via
``benchmarks/run.py --smoke``; the ``__main__`` guard is mandatory —
the spawn start method re-imports this module in every worker.
"""
from __future__ import annotations

import functools
import time

from benchmarks import common as B
from repro.core.policies import FreqCaPolicy
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.serving.fleet import FaultInjector, FleetRouter
from repro.models import dit

MAX_BATCH = 4
MAX_INFLIGHT = 16
REJOIN_TIMEOUT_S = 300.0


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


def _wave(router, start_rid: int, n: int):
    """Submit ``n`` requests and return their futures."""
    return [router.submit(DiffusionRequest(request_id=start_rid + i,
                                           seed=start_rid + i))
            for i in range(n)]


def _wait_rejoin(router, want: int, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if router.status()["healthy_replicas"] >= want:
            return True
        time.sleep(0.25)
    return False


def run(out: str = "results/bench/BENCH_serve_chaos.json",
        n_requests: int = 12,
        title: str = "Chaos — kill 1 of 2 replicas mid-stream"):
    factory = functools.partial(fleet_engine, MAX_BATCH, 5, 0.02)
    B.ensure_checkpoint()       # trained in a child, before any spawn

    rows = []
    for scenario in ("no_fault", "kill_one_of_two"):
        faults = None
        if scenario == "kill_one_of_two":
            # replica 0's first incarnation dies on its 2nd submit;
            # later incarnations (the restart) run clean
            faults = FaultInjector(seed=0).kill_after_submits(
                2, slot=0, start_n=0)
        router = FleetRouter(factory, n_replicas=2,
                             max_inflight=MAX_INFLIGHT,
                             max_restarts=2,
                             restart_backoff_base_s=0.2,
                             fault_injector=faults)
        try:
            router.start()
            t0 = time.perf_counter()
            futs = _wave(router, 0, n_requests)
            router.drain()
            rejoined = _wait_rejoin(router, want=2,
                                    timeout_s=REJOIN_TIMEOUT_S)
            # post-rejoin wave: the restarted replica must take real
            # work again, with zero steady-state recompiles
            futs += _wave(router, n_requests, n_requests)
            router.drain()
            wall = time.perf_counter() - t0
            outs = [f.result(timeout=60.0) for f in futs]
            fm = router.fleet_metrics()
            status = router.status()
        finally:
            router.shutdown(drain=True)
        s = fm.summary()
        rt = s["routing"]
        steady = {idx: pr["steady_recompiles"]
                  for idx, pr in s["per_replica"].items()}
        submitted = 2 * n_requests
        sup = status.get("supervisor", {})
        rows.append({
            "scenario": scenario,
            "submitted": submitted,
            "served": len(outs),
            "dropped": submitted - len(outs),
            "unresolved": rt["submitted"] - rt["resolved"] - rt["failed"],
            "wall_s": round(wall, 3),
            "replicas_lost": rt["replicas_lost"],
            "restarts": sup.get("restarts", 0),
            "boot_failures": sup.get("boot_failures", 0),
            "replicas_retired": sup.get("replicas_retired", 0),
            "rejoined": rejoined,
            "requeued": rt["requeued"],
            "duplicate_results": rt["duplicate_results"],
            "poison_quarantined": rt["poison_quarantined"],
            "peak_inflight": rt["peak_inflight"],
            "inflight_bound": 2 * MAX_INFLIGHT,
            "steady_recompiles": steady,
            "restarted_replica_requests": (
                s["per_replica"].get(0, {}).get("requests", 0)
                if scenario == "kill_one_of_two" else None),
        })
    B.print_table(title, rows)

    # availability invariants — the CI guard re-checks these from the
    # emitted JSON, so keep the field names stable
    for r in rows:
        assert r["served"] == r["submitted"] and r["dropped"] == 0, r
        assert r["unresolved"] == 0, r
        assert r["poison_quarantined"] == 0, r
        assert r["peak_inflight"] <= r["inflight_bound"], r
        assert all(v == 0 for v in r["steady_recompiles"].values()), r
    control, chaos = rows
    assert control["replicas_lost"] == 0 and control["restarts"] == 0, rows
    assert chaos["replicas_lost"] >= 1, rows
    assert chaos["restarts"] >= 1 and chaos["rejoined"], rows
    assert chaos["requeued"] >= 1, rows
    # the restarted incarnation actually served post-rejoin traffic
    assert chaos["restarted_replica_requests"] > 0, rows
    B.save_rows(out, rows)
    return rows


if __name__ == "__main__":
    run()
