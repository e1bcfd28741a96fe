"""The trace reduction against a slice of a trace recorded on one TPU v5e
(the FLUX cut at bucket 4: the last flash call of a full step, the band
split that fills the cache, two cached steps and the next flash call),
and against small hand-made traces."""
import types

import pytest
from jax.profiler import ProfileData

import bench_tiny
from bench import cell as cell_lib
from bench import trace, work

SLICE = bench_tiny.ROOT / "tests/bench/data/flux_window_slice.pbtxt"
FLASH = ("_flash",)
SPLIT = ("_band_split_spectral_pallas",)
PREDICT = ("_freqca_predict_spectral_pallas",)


@pytest.fixture(scope="module")
def chip_slice():
    return trace.reduce_profile(ProfileData.from_text_proto(SLICE.read_text()),
                                "bench.window")


def test_chip_slice_window_busy_and_self_times(chip_slice):
    r = chip_slice
    assert r.window_s == pytest.approx(0.25)
    # the sampler's while loop spans the whole slice
    assert r.busy_s == pytest.approx(0.25)
    # self times partition the nested ops: they sum to the busy time
    assert sum(s for s, _ in r.ops.values()) == pytest.approx(r.busy_s)
    assert r.ops[("while.13", "while")][1] == 1


@pytest.mark.parametrize("names,seconds", [
    (FLASH, 0.2023), (SPLIT, 0.000895), (PREDICT, 0.002406)])
def test_chip_slice_finds_each_pallas_kernel(chip_slice, names, seconds):
    secs, calls = chip_slice.kernel_time(names)
    assert calls == 2
    assert secs == pytest.approx(seconds, rel=1e-3)


def test_chip_slice_breakdown(chip_slice):
    ops = chip_slice.breakdown["device_ops"]
    assert len(ops) == trace.TOP
    assert ops[0][0] == "_flash.5 (tpu_custom_call)"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert chip_slice.breakdown["idle_gaps"] == []


def test_flash_roofline_of_the_chip_slice(chip_slice):
    # the reader counts lanes as full lane-steps x blocks: one lane-step
    # of 8 blocks stands for the slice's two bucket-4 calls
    cell = cell_lib.load("flux1-dev-cut.freqca-1024.sat", True)
    run = types.SimpleNamespace(cell=cell, trace=chip_slice, tokens=4096,
                                full_lane_steps=1, total_lane_steps=1,
                                peak=cell_lib.peaks()["TPU v5 lite"],
                                program=cell_lib.program(cell.family))
    got = cell_lib.reader("flash_roofline").read(run)
    least = work.flash(8, 4096, 24, 128, "bfloat16").flops / 197e12
    assert got["bound"] == "compute" and got["calls"] == 2
    assert got["value"] == pytest.approx(100 * least / 0.2023, rel=1e-3)
    assert 0 < got["value"] < 100


@pytest.mark.parametrize("hlo,key", [
    ('%_flash.5 = bf16[96,32,128,128]{3,2,1,0:T(8,128)(2,1)} custom-call('
     'bf16[96,32,128,128]{3,2,1,0} %bitcast.308), custom_call_target='
     '"tpu_custom_call"', ("_flash.5", "tpu_custom_call")),
    ('%while.13 = (s32[]{:T(128)}, f32[4,128,128,16]{3,2,1,0:T(8,128)}) '
     'while((s32[], f32[4,128,128,16]) %tuple.3), condition=%c, body=%b',
     ("while.13", "while")),
    ('%fusion.11 = f32[4,4096,64]{1,2,0:T(8,128)S(1)} fusion(f32[4,4096,3072]'
     ' %_freqca_predict_spectral_pallas.2), kind=kOutput, calls=%f.16',
     ("fusion.11", "fusion"))])
def test_op_key(hlo, key):
    assert trace.op_key(hlo) == key


HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 3000000000 duration_ps: 1000000000 }
    events { metadata_id: 3 offset_ps: 8000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = f32[4] while(f32[4] %a)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[4] fusion(f32[4] %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%_flash.3 = f32[4] custom-call(f32[4] %fusion.2), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 6100000000 duration_ps: 1500000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
}
'''


def test_hand_made_trace_nesting_gaps_and_host_labels():
    r = trace.reduce_profile(ProfileData.from_text_proto(HAND), "bench.window")
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s == pytest.approx(0.007)
    # the while loop's own time excludes the fusion and the kernel in it
    assert r.ops[("while.1", "while")] == (pytest.approx(0.003), 1)
    assert r.kernel_time(FLASH) == (pytest.approx(0.002), 2)
    # only Pallas custom calls count as kernels
    assert r.kernel_time(("fusion",)) == (0.0, 0)
    gaps = r.breakdown["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.submit", "no host span"]
    assert [g[1] for g in gaps] == [pytest.approx(0.002), pytest.approx(0.001)]


def test_a_trace_without_a_device_plane_is_refused():
    host_only = HAND[HAND.index("planes { id: 2"):]
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce_profile(ProfileData.from_text_proto(host_only),
                             "bench.window")
