"""Serving throughput: continuous-batching bucketed engine vs the seed
pad-to-max engine on the same mixed-size request stream, plus an
open-loop Poisson client, a mixed-policy per-lane case, and the
threaded async submit path vs the single-thread open-loop replay
(``run_async`` -> ``BENCH_serve_async.json``, asserted in CI).

Closed loop: both engines run the identical FreqCa policy and trained
DiT; the only difference is batch formation — power-of-two bucket
signatures vs the seed's fixed pad-to-``max_batch`` signature.  Both
are warmed up first, so the timed phase measures steady-state serving
(the recompile counter must stay at zero).  The bucketed engine is then
re-run under an open-loop Poisson arrival process (rate scaled off its
closed-loop throughput) so the age-based batch former is exercised
under real queueing, not only drained bursts.  Emits
``results/bench/BENCH_serve.json``.

``run_mixed`` serves the same mixed-policy stream (freqca / fora /
freqca_a cycling) through two batch formers:

* **ungrouped** (the pre-grouping baseline): mixed-lane batches with
  per-lane activation — per-request ``n_full_steps`` must differ
  across policies, and every distinct lane-policy mix is its own jit
  signature;
* **grouped** (policy-homogeneous formation, the default engine mode):
  every cut is policy-pure, the compiled-signature count is capped at
  policy-groups x buckets (probed via ``compiled_buckets()`` and
  reported as ``compiled_signatures``), the skip-compute fraction
  rises (scheduled lanes stop paying for adaptive lanes' activations),
  and req/s must hold the ungrouped baseline on the identical stream.

Both serve with zero steady-state recompiles once warm.  Emits
``results/bench/BENCH_serve_mixed.json`` (asserted in CI).
"""
from __future__ import annotations

import time

from benchmarks import common as B
from repro.core.policies import (ForaPolicy, FreqCaAdaptivePolicy,
                                 FreqCaPolicy)
from repro.launch.serve import (mixed_stream, poisson_stream,
                                serve_open_loop, serve_stream,
                                serve_threaded_open_loop)
from repro.serving import metrics as metrics_lib
from repro.serving.engine import DiffusionEngine, DiffusionRequest
from repro.models import dit


def _engine(full_fn, from_crf_fn, params, cfg, policy, max_batch,
            pad_to_max=False, max_wait_s=0.0, group_policies=False):
    n_tok = (B.IMG_SIZE // cfg.patch_size) ** 2
    return DiffusionEngine(full_fn, from_crf_fn, params,
                           (B.IMG_SIZE, B.IMG_SIZE, cfg.in_channels),
                           (n_tok, cfg.d_model), policy,
                           n_steps=B.N_STEPS, max_batch=max_batch,
                           pad_to_max=pad_to_max, max_wait_s=max_wait_s,
                           group_policies=group_policies)


def run(out: str = "results/bench/BENCH_serve.json",
        n_requests: int = 24, max_batch: int = 8, interval: int = 5,
        title: str = "Serving throughput — bucketed vs pad-to-max"):
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    policy = FreqCaPolicy(interval=interval, method="dct")

    def row(name, eng, outs, wall, warm, warm_misses):
        assert len(outs) == n_requests
        s = eng.metrics.summary()
        return {
            "engine": name,
            "requests": n_requests,
            "wall_s": round(wall, 3),
            "req_per_s": round(metrics_lib.throughput(eng.metrics, wall), 3),
            "mean_occupancy": s["mean_occupancy"],
            "mean_bucket": s["mean_bucket"],
            "latency_p50_s": s["request_latency_p50_s"],
            "latency_p95_s": s["request_latency_p95_s"],
            "full_step_fraction": s["full_step_fraction"],
            "request_full_p50": s["request_full_p50"],
            "warmup_s": round(warm, 2),
            "warmup_compiles": warm_misses,
            "steady_recompiles": s["compile_misses"] - warm_misses,
            "cache_state_bytes_per_lane": s["cache_state_bytes_per_lane"],
        }

    rows = []
    for name, pad in [("pad_to_max (seed)", True), ("bucketed", False)]:
        eng = _engine(full_fn, from_crf_fn, params, cfg, policy, max_batch,
                      pad_to_max=pad)
        # pad-to-max only ever sees one signature; bucketed precompiles
        # the whole ladder — both amortised over the process lifetime
        warm = eng.warmup(buckets=[max_batch] if pad else None)
        warm_misses = eng.metrics_dict()["compile_misses"]
        bursts = mixed_stream(n_requests, B.IMG_SIZE, cfg.in_channels,
                              edit_every=4)
        outs, wall = serve_stream(eng, bursts)
        rows.append(row(name, eng, outs, wall, warm, warm_misses))

    # open-loop Poisson client against the bucketed engine: arrivals at
    # ~75% of its closed-loop throughput, batches cut by queue pressure
    rate = max(0.75 * rows[-1]["req_per_s"], 0.5)
    eng = _engine(full_fn, from_crf_fn, params, cfg, policy, max_batch,
                  max_wait_s=0.02)
    warm = eng.warmup()
    warm_misses = eng.metrics_dict()["compile_misses"]
    plan = poisson_stream(n_requests, rate, B.IMG_SIZE, cfg.in_channels,
                          edit_every=4)
    outs, wall = serve_open_loop(eng, plan)
    rows.append(row(f"bucketed+poisson({rate:.2f}/s)", eng, outs, wall,
                    warm, warm_misses))

    base = rows[0]
    for r in rows:
        r["speedup_vs_padmax"] = round(
            r["req_per_s"] / max(base["req_per_s"], 1e-9), 2)
    B.print_table(title, rows)
    bucketed = rows[1]
    print(f"bucketed vs pad-to-max: {bucketed['speedup_vs_padmax']}x "
          f"req/s, steady-state recompiles: "
          f"{bucketed['steady_recompiles']}")
    B.save_rows(out, rows)
    return rows


def run_mixed(out: str = "results/bench/BENCH_serve_mixed.json",
              n_requests: int = 12, max_batch: int = 4, interval: int = 5,
              title: str = "Mixed-policy serving — grouped vs ungrouped"):
    from repro.core.policies import registry as policy_registry
    from repro.launch.serve import _make_request
    from repro.serving.scheduler import bucket_sizes

    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    default = FreqCaPolicy(interval=interval, method="dct")
    policies = [default,
                ForaPolicy(interval=max(interval // 2, 1)),
                FreqCaAdaptivePolicy(method="dct", rho=0.25,
                                     tea_threshold=0.3)]
    n_groups = len({policy_registry.compatibility_key(p)
                    for p in policies})
    budget = n_groups * len(bucket_sizes(max_batch))

    def stream():
        # one burst, policies cycling: the ungrouped former cuts mixed
        # FIFO windows; the grouped former cuts one pure batch per
        # policy from the same queue — identical requests either way
        return [[_make_request(rid, B.IMG_SIZE, cfg.in_channels,
                               edit_every=4, policies=policies)
                 for rid in range(n_requests)]]

    rows = []
    for name, grouped in [("ungrouped (per-mix sigs)", False),
                          ("grouped (policy-pure)", True)]:
        eng = _engine(full_fn, from_crf_fn, params, cfg, default, max_batch,
                      group_policies=grouped)
        # grouped: one uniform ladder per compatibility group covers
        # every signature a policy-pure former can cut.  Ungrouped: the
        # first serving pass mints each mixed-lane signature; the timed
        # second pass must be all hits either way.
        eng.warmup(policies=policies if grouped else ())
        serve_stream(eng, stream())
        warm_misses = eng.metrics_dict()["compile_misses"]
        outs, wall = serve_stream(eng, stream())
        s = eng.metrics.summary()
        fulls = {}
        for pol in policies:
            f = [o.n_full_steps for o in outs
                 if policies[o.request_id % len(policies)] == pol]
            fulls[pol.name] = round(sum(f) / max(len(f), 1), 2)
        rows.append({
            "engine": name,
            "grouped": grouped,
            "requests": len(outs),
            "wall_s": round(wall, 3),
            "req_per_s": round(len(outs) / max(wall, 1e-9), 3),
            "steady_recompiles": s["compile_misses"] - warm_misses,
            "compiled_signatures": s["compiled_signatures"],
            "signature_budget": budget,
            "policy_groups": s["policy_groups"],
            "skip_compute_fraction": s["skip_compute_fraction"],
            "max_lane_full_spread": s["max_lane_full_spread"],
            "mean_full_steps": fulls,
            "n_steps": B.N_STEPS,
        })

    ung, grp = rows
    grp["rps_vs_ungrouped"] = round(
        grp["req_per_s"] / max(ung["req_per_s"], 1e-9), 3)
    B.print_table(title, rows)
    # ungrouped: per-lane activation must actually decouple the lanes
    assert ung["max_lane_full_spread"] > 0, ung
    assert ung["mean_full_steps"]["fora"] != \
        ung["mean_full_steps"]["freqca_a"], ung
    # both formers serve compile-free once warm
    assert all(r["steady_recompiles"] == 0 for r in rows), rows
    # grouping caps the signature count at groups x buckets and raises
    # the skip-compute fraction (no cross-policy activation coupling) …
    assert grp["compiled_signatures"] <= budget, grp
    assert grp["policy_groups"] == n_groups, grp
    assert grp["skip_compute_fraction"] > ung["skip_compute_fraction"], rows
    # … while holding the ungrouped baseline's throughput on the same
    # stream (0.97: same tolerance as the async CI guard)
    assert grp["rps_vs_ungrouped"] >= 0.97, rows
    B.save_rows(out, rows)
    return rows


def run_async(out: str = "results/bench/BENCH_serve_async.json",
              n_requests: int = 14, max_batch: int = 4, interval: int = 5,
              clients: int = 4,
              title: str = "Async serving — threaded clients vs "
                           "single-thread open loop"):
    """Same Poisson arrival plan, same engine config, two clients:

    * single-thread open-loop replay (the PR-2 baseline): one thread
      interleaves submits with engine turns, so a busy engine delays
      every later arrival's submission;
    * N client threads through ``AsyncDiffusionEngine``: ``submit``
      returns a future immediately and the worker overlaps the clients.

    The arrival rate is set above the engine's drained capacity so the
    run is server-bound — the async path must reach at least the
    single-thread req/s with zero steady-state recompiles and every
    submitted future resolved.  (Throughput on one device is
    work-conserving either way; the async edge is structural — clients
    signal completion, so the tail batch is drained instead of aging
    out ``max_wait_s``, on top of the p95/TTFR latency win.)
    """
    cfg, params = B.get_model()
    full_fn, from_crf_fn = dit.denoiser(cfg)
    policy = FreqCaPolicy(interval=interval, method="dct")

    # n_requests deliberately NOT a multiple of max_batch: under
    # overload the stream ends in a partial batch, which the sync
    # replay must age out (max_wait_s) while the async client drains it
    if n_requests % max_batch == 0:
        n_requests += 1

    def fresh_engine():
        eng = _engine(full_fn, from_crf_fn, params, cfg, policy, max_batch,
                      max_wait_s=0.15)
        eng.warmup()
        return eng, eng.metrics_dict()["compile_misses"]

    # capacity probe on a warmed engine: drain one full bucket, so the
    # arrival rate can be set above what the server can absorb
    probe, _ = fresh_engine()
    t0 = time.perf_counter()
    for i in range(max_batch):
        probe.submit(DiffusionRequest(request_id=i, seed=i))
    probe.serve_until_drained()
    capacity = max_batch / max(time.perf_counter() - t0, 1e-9)
    rate = 1.5 * capacity

    rows = []
    for name, threaded in [("open_loop_1thread", False),
                           (f"async_threaded(clients={clients})", True)]:
        eng, warm_misses = fresh_engine()
        # identical arrival plan (same seed), fresh request objects
        plan = poisson_stream(n_requests, rate, B.IMG_SIZE,
                              cfg.in_channels, edit_every=4)
        if threaded:
            outs, wall = serve_threaded_open_loop(eng, plan,
                                                  clients=clients)
        else:
            outs, wall = serve_open_loop(eng, plan)
        s = eng.metrics.summary()
        rows.append({
            "engine": name,
            "clients": clients if threaded else 1,
            "submitted": n_requests,
            "served": len(outs),
            "arrival_rate": round(rate, 3),
            "wall_s": round(wall, 3),
            "req_per_s": round(metrics_lib.throughput(eng.metrics, wall), 3),
            "latency_p50_s": s["request_latency_p50_s"],
            "latency_p95_s": s["request_latency_p95_s"],
            "time_to_first_result_s": s["time_to_first_result_s"],
            "max_queue_depth": s["max_queue_depth"],
            "steady_recompiles": s["compile_misses"] - warm_misses,
        })

    single, threaded_row = rows
    ratio = round(threaded_row["req_per_s"]
                  / max(single["req_per_s"], 1e-9), 3)
    threaded_row["rps_vs_single_thread"] = ratio
    B.print_table(title, rows)
    # every submitted future resolved; nothing lost or double-served
    for r in rows:
        assert r["served"] == r["submitted"], r
        assert r["steady_recompiles"] == 0, r
    # the threaded async client must keep up with the sync replay
    assert ratio >= 0.97, rows
    B.save_rows(out, rows)
    return rows


def main():
    run()
    run_mixed()
    run_async()


if __name__ == "__main__":
    main()
