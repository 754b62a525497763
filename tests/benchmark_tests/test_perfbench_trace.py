"""The reduction from a trace to numbers: by hand on intervals, and on a
small trace recorded on a TPU v5e and kept with the benchmark."""
import os

import pytest

from benchmark import trace
from benchmark.files import HERE

KERNEL = ('%wave_histogram_pallas_t.12 = f32[128,96] custom-call(u8[8,64] %x), '
          'custom_call_target="tpu_custom_call"')
RECORDED = os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb")


def test_union_of_overlapping_intervals():
    assert trace.union_seconds([]) == 0.0
    assert trace.union_seconds([(0, 1), (2, 3)]) == 2.0
    assert trace.union_seconds([(0, 2), (1, 3), (2.5, 2.75)]) == 3.0
    assert trace.union_seconds([(5, 6), (0, 10)]) == 10.0


def test_gaps_are_what_no_interval_covers():
    assert trace.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert trace.gaps([(0, 5)], 0, 5) == []
    assert trace.gaps([], 1, 2) == [(1, 2)]


def test_reduce_planes_by_hand():
    planes = {
        "devices": {"/device:TPU:0": [
            ("%while.2 = (s32[]) while(...)", 1.0, 4.0),
            ("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0), (KERNEL, 2.0, 4.0),
            ("%fusion.1 = f32[8] fusion(...)", 5.0, 5.5), (KERNEL, 6.0, 7.0),
            ("%before_window = f32[] copy(...)", -3.0, -2.0)]},
        "spans": [("bench_update", 0.0, 4.25), ("bench_sync", 4.25, 8.0)]}
    out = trace.reduce_planes(planes)
    assert out["window_s"] == 8.0
    assert out["busy_s"] == pytest.approx(4.5)
    # the while holds a fusion and a kernel: its own time is nothing
    assert out["device_ops"] == [["wave_histogram_pallas_t.12", 3.0],
                                 ["fusion.1", 1.5], ["while.2", 0.0]]
    assert out["hist_kernel_s"] == 3.0 and out["hist_kernel_launches"] == 2
    assert out["mosaic_s"] == 3.0
    # idle: 0-1 under update; 4-5, 5.5-6 and 7-8 mostly under sync
    assert out["idle_gaps"] == [["bench_sync", pytest.approx(2.5)],
                                ["bench_update", pytest.approx(1.0)]]


def test_self_time_of_nested_operations():
    ops = [("outer", 0.0, 10.0), ("a", 1.0, 3.0), ("inner", 4.0, 8.0),
           ("b", 5.0, 6.0), ("after", 10.0, 11.0)]
    assert dict(trace.self_times(ops)) == {
        "outer": 4.0, "a": 2.0, "inner": 3.0, "b": 1.0, "after": 1.0}
    assert trace.short_name("%fusion.3 = f32[2]{0} fusion(%x)") == "fusion.3"


def test_two_device_planes_are_averaged():
    planes = {"devices": {"/device:TPU:0": [("a", 0.0, 1.0)],
                          "/device:TPU:1": [("a", 0.0, 0.5)]},
              "spans": [("bench_update", 0.0, 1.0)]}
    out = trace.reduce_planes(planes, chips=2)
    assert out["busy_s"] == pytest.approx(0.75)
    assert out["device_ops"] == [["a", 0.75]]


def test_no_device_plane_reads_nothing():
    out = trace.reduce_planes({"devices": {}, "spans": []})
    assert out["busy_s"] == 0.0 and out["hist_kernel_s"] is None


def test_recorded_trace_from_the_chip():
    planes = trace.read_planes(RECORDED)
    assert list(planes["devices"]) == ["/device:TPU:0"]
    names = {name for name, _, _ in planes["spans"]}
    assert {"bench_update", "bench_sync"} <= names
    out = trace.reduce_planes(planes)
    assert 0.0 < out["busy_s"] <= out["window_s"]
    # self times add up to the union of what ran
    total = sum(s for _, s in out["device_ops"])
    assert total == pytest.approx(out["busy_s"], rel=1e-3)
    assert out["device_ops"][0][0].startswith("wave_histogram_pallas_t")
    assert out["hist_kernel_launches"] >= 1
    assert out["mosaic_s"] is not None and 0 < out["mosaic_s"] <= out["busy_s"]
    assert out["idle_gaps"] and out["idle_gaps"][0][1] > 0
