"""Run every paper-table benchmark. One function per paper table/figure.

Prints markdown tables + a final ``name,us_per_call,derived`` CSV line
per benchmark (latency of the headline FreqCa config; derived = its
quality metric).

``--smoke`` shrinks the shared DiT (reduced dit-small, 16px latents,
few train/sample steps) and runs a representative subset so a CPU CI
job finishes in minutes; artifacts land in ``results/bench/BENCH_*``.
"""
from __future__ import annotations

import argparse
import os


def _enable_smoke() -> None:
    # must run before ``benchmarks.common`` is imported anywhere
    os.environ.setdefault("BENCH_REDUCED", "1")
    os.environ.setdefault("BENCH_IMG_SIZE", "16")
    os.environ.setdefault("BENCH_TRAIN_STEPS", "30")
    os.environ.setdefault("BENCH_SAMPLE_STEPS", "12")
    os.environ.setdefault("BENCH_BATCH", "2")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + few steps; CI-sized subset")
    args = ap.parse_args(argv)
    if args.smoke:
        _enable_smoke()

    from benchmarks import (fig2_freq_analysis, fig4_crf_mse, figc1_ablation,
                            kernel_bench, roofline, serve_chaos, serve_fleet,
                            serve_multires, serve_quality, serve_throughput,
                            table1_flux, table2_qwen, table3_kontext,
                            table4_qwen_edit, table5_memory)
    csv = ["name,us_per_call,derived"]

    def headline(rows, pick="freqca(N=5)", metric="psnr"):
        for r in rows:
            if r.get("method") == pick:
                lat = r.get("latency_s", 0.0) or 0.0
                return f"{lat * 1e6:.0f}", f"{metric}={r[metric]}"
        return "0", ""

    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    # the fleet benches first: their workers need this process to hold
    # no JAX device state when they spawn
    svf = serve_fleet.run(
        n_requests=16 if args.smoke else 24,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_fleet,0,rps_vs_1replica=%s"
               % svf[-1]["rps_vs_1replica"])
    svc = serve_chaos.run(n_requests=8 if args.smoke else 12)
    csv.append("serve_chaos,0,restarts=%s" % svc[-1]["restarts"])
    svr = serve_multires.run(
        n_requests=18 if args.smoke else 24,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_multires,0,rps_vs_singles=%s"
               % svr[1]["rps_vs_singles"])
    t1 = table1_flux.run()
    csv.append("table1_flux,%s,%s" % headline(t1))
    if not args.smoke:
        table2_qwen.main()
        t3 = table3_kontext.run()
        csv.append("table3_kontext,%s,%s" % headline(t3))
        table4_qwen_edit.main()
    t5 = table5_memory.run()
    csv.append("table5_memory,0,freqca_pct=%s"
               % t5[-1]["pct_of_layerwise"])
    kb = kernel_bench.run()
    csv.append("kernel_bench,0,low_ring_compression=%s"
               % kb[0]["low_ring_compression"])
    if not args.smoke:
        # fig2's low-band-similarity property only holds at the realistic
        # model scale, not the reduced smoke DiT
        f2 = fig2_freq_analysis.run()
        csv.append("fig2_freq_analysis,0,rows=%d" % len(f2))
    f4 = fig4_crf_mse.run()
    csv.append("fig4_crf_mse,0,crf_over_layerwise=%s"
               % f4[-1]["rel_mse_mean"])
    if not args.smoke:
        fc1 = figc1_ablation.run()
        csv.append("figc1_ablation,0,rows=%d" % len(fc1))
    sv = serve_throughput.run(
        n_requests=12 if args.smoke else 24,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_throughput,0,bucketed_speedup=%s"
               % sv[1]["speedup_vs_padmax"])
    svm = serve_throughput.run_mixed(
        n_requests=12 if args.smoke else 24,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_mixed,0,grouped_rps_ratio=%s"
               % svm[1]["rps_vs_ungrouped"])
    sva = serve_throughput.run_async(
        n_requests=14 if args.smoke else 26,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_async,0,rps_vs_single_thread=%s"
               % sva[-1]["rps_vs_single_thread"])
    svq = serve_quality.run(
        n_requests=12 if args.smoke else 24,
        max_batch=4 if args.smoke else 8)
    csv.append("serve_quality,0,shed_rps_ratio=%s"
               % svq[-1]["rps_vs_no_shed"])
    try:
        rl = roofline.run()
        csv.append("roofline,0,combos=%d" % len(rl))
    except Exception as e:  # dryrun results may not exist yet
        csv.append("roofline,0,skipped(%s)" % type(e).__name__)

    print("\n=== CSV ===")
    for line in csv:
        print(line)
    from benchmarks import common as B
    B.save_rows("results/bench/BENCH_summary.json",
                [{"line": line} for line in csv])


if __name__ == "__main__":
    main()
