"""The load generator's plans and clients, and the harness finding each
cell's files by name."""
import concurrent.futures
import json

import numpy as np
import pytest

import bench_tiny
from bench import cell as cell_lib
from bench import loadgen

BENCH = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())
TRAFFIC = {"image_px": 64, "policy": bench_tiny.FREQCA, "rate_per_s": 2.0,
           "backlog": 5, "edit_every": 8, "edit_strength": 0.5}
BIG = 2 ** 40 + 12345


def test_same_seed_same_plan():
    a = loadgen.make_plan(TRAFFIC, BIG, 30)
    b = loadgen.make_plan(TRAFFIC, BIG, 30)
    assert [(x.due_s, x.seed, x.edit) for x in a] == \
        [(x.due_s, x.seed, x.edit) for x in b]


def test_every_seed_gets_one_arrival_trace_and_its_own_requests():
    a = loadgen.make_plan(TRAFFIC, 1, 30)
    b = loadgen.make_plan(TRAFFIC, BIG, 30)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert [x.due_s for x in a[:5]] == [0.0] * 5
    assert [x.seed for x in a] != [x.seed for x in b]
    assert [x.edit for x in a] != [x.edit for x in b]
    assert sum(x.edit for x in a) == sum(x.edit for x in b) > 0
    assert all(0 <= x.seed < 2 ** 31 for x in a + b)
    # Poisson gaps at the rate: the exponential quantiles, shuffled
    gaps = np.diff([x.due_s for x in a[4:]])
    assert np.mean(gaps) == pytest.approx(1 / 2.0, rel=0.05)
    assert not np.all(np.diff(gaps) >= 0)
    assert np.median(gaps) == pytest.approx(np.log(2) / 2.0, rel=0.05)


def test_edit_reference_is_seeded_unit_scale_data():
    a = loadgen.make_plan(TRAFFIC, 7, 30)[0]
    r1 = loadgen.edit_reference(a, (8, 8, 4))
    r2 = loadgen.edit_reference(a, (8, 8, 4))
    assert r1.dtype == np.float32 and r1.shape == (8, 8, 4)
    assert np.array_equal(r1, r2) and 0.3 < np.std(r1) < 1.5


def test_open_loop_submits_at_due_times_and_closes_on_a_completion():
    plan = loadgen.make_plan(dict(TRAFFIC, rate_per_s=40.0, backlog=2),
                             3, 0.5)
    pool = concurrent.futures.ThreadPoolExecutor(1)

    def submit(a):
        return pool.submit(lambda: a.index)

    loop = loadgen.OpenLoop(plan, submit)
    loop.start()
    close = loop.wait_close(0.2, timeout_s=10)
    loop.stop()
    pool.shutdown(wait=True)
    assert close >= 0.2
    sub = loop.submitted()
    assert sub and all(a.submit_s >= a.due_s - 1e-6 for a in sub)
    assert max(a.submit_s - a.due_s for a in sub) < 0.2
    done = [a for a in sub if a.result is not None]
    assert done and all(a.result == a.index for a in done)


@pytest.mark.parametrize("backlog,warmed", [(0, [1, 2, 4]), (8, [4])])
def test_warm_up_covers_the_buckets_the_traffic_cuts(backlog, warmed):
    # a backlog keeps every cut full; without one any bucket is cut
    from bench import serve
    cell = bench_tiny.cell(backlog=backlog)
    assert serve.warm_buckets(cell, [1, 2, 4]) == warmed


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name, trace):
    cell = cell_lib.load(name, trace)
    assert cell.config["family"] and cell.traffic["policy"]["name"]
    assert cell.limits["check_requests"] >= 1
    assert cell.metrics
    for m in cell.metrics:
        assert callable(cell_lib.reader(m["name"]).read)
    assert cell_lib.program(cell.family)
    assert cell_lib.reference(cell.family).Reference


def test_every_metric_config_and_mix_is_a_file_of_its_own():
    root = bench_tiny.ROOT
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (root / "bench" / "metrics" / f"{m['name']}.py").is_file()
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for w in BENCH["workloads"]:
        assert (root / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


def test_a_peak_table_entry_names_its_source():
    for kind, p in cell_lib.peaks().items():
        assert p["bf16_flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
        assert p["source"]


# each saturating cell's knee on a TPU v5e, the full-bucket throughput
# bench/tools/sweep.py measured (images/s; PERF.md §4)
KNEES = {"flux1-dev-cut.freqca-1024.sat": 1.369,
         "dit-xl2-512.none.sat": 2.402}


@pytest.mark.parametrize("name", sorted(KNEES))
def test_a_saturating_mix_offers_a_full_bucket_every_batch(name):
    """At 1.5x the knee with a backlog of three buckets, every batch the
    chip starts by the window's close finds ``max_batch`` requests due,
    served a full bucket per ``max_batch / knee`` seconds from the open:
    every cut is the one bucket warmed."""
    cell = cell_lib.load(name, False)
    traffic, mb = cell.traffic, cell.engine["max_batch"]
    assert traffic["backlog"] >= 3 * mb
    assert traffic["rate_per_s"] == pytest.approx(1.5 * KNEES[name],
                                                  rel=0.01)
    batch_s = mb / KNEES[name]
    due = np.array([a.due_s for a in loadgen.make_plan(
        traffic, BIG, BENCH["run_seconds"])])
    starts = np.arange(0.0, BENCH["run_seconds"] + batch_s, batch_s)
    for k, t in enumerate(starts):
        assert np.sum(due <= t) - k * mb >= mb, (t, k)
