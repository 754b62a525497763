"""The program's spans on the trace's clock (`benchmark/spans.py`, PR 37):
the idle gaps of `trace.reduce_planes` cut at the program's span bounds
and named by the innermost span open over each piece, every other number
as it was; and the two rate readers of the grow loop's row counters,
`hist_kernel_ns_per_row` and `slab_move_ns_per_row`."""
import os

import pytest

from benchmark import spans, trace
from benchmark.files import HERE, ROOT, load_json, load_module
from lightgbm_tpu.obs import timers

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["hist_kernel_ns_per_row", "slab_move_ns_per_row"]
RECORDED = os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb")
RECORDED_SPANS = os.path.join(HERE, "testdata", "tiny_v5e_spans.xplane.pb")


def _without_program_spans(planes):
    return {k: v for k, v in planes.items() if k != "program_spans"}


# ------------------------------------------------------------ by hand

def _planes(program=None):
    # device busy 1-2, 3-4, 6-7 in a window 0-8: idle 0-1, 2-3, 4-6, 7-8
    planes = {"devices": {"/device:TPU:0": [("a", 1.0, 2.0), ("b", 3.0, 4.0),
                                            ("c", 6.0, 7.0)]},
              "spans": [("bench_update", 0.0, 5.0), ("bench_sync", 5.0, 8.0)]}
    if program is not None:
        planes["program_spans"] = program
    return planes


def test_the_innermost_program_span_names_each_piece():
    out = spans.idle_spans(_planes([
        ("lgbm_iteration", 0.5, 4.5),
        ("lgbm_gradients", 0.5, 1.0),       # nested: wins over iteration
        ("lgbm_dispatch", 2.5, 3.0),
        ("lgbm_score_update", 4.0, 4.5)]))
    assert dict(out) == pytest.approx({
        "lgbm_gradients": 0.5,              # 0.5-1
        "lgbm_iteration": 0.5,              # 2-2.5
        "lgbm_dispatch": 0.5,               # 2.5-3
        "lgbm_score_update": 0.5,           # 4-4.5
        "bench_update": 0.5,                # 0-0.5
        "bench_sync": 2.5})                 # 4.5-6 (mostly sync's), 7-8
    assert out[0][0] == "bench_sync"


def test_a_piece_under_no_program_span_goes_to_the_bench_span_over_it():
    # the gap 4-6 straddles the two bench spans: cut at 4.5 by the program
    # span, its pieces 4.5-6 lie mostly under bench_sync
    out = dict(spans.idle_spans(_planes([("lgbm_iteration", 3.5, 4.5)])))
    old = dict(trace.reduce_planes(_planes())["idle_gaps"])
    assert out["lgbm_iteration"] == pytest.approx(0.5)
    assert out["bench_sync"] == pytest.approx(2.5)      # 4.5-6, 7-8
    assert out["bench_update"] == pytest.approx(2.0)    # 0-1, 2-3
    # whole, the gap 4-6 lies as much under one bench span as the other
    assert old == pytest.approx({"bench_update": 4.0, "bench_sync": 1.0})
    assert sum(out.values()) == pytest.approx(sum(old.values()), abs=1e-9)


def test_two_planes_are_averaged_as_idle_gaps_are():
    planes = _planes([("lgbm_dispatch", 0.0, 1.0)])
    planes["devices"]["/device:TPU:1"] = [("a", 0.0, 8.0)]   # never idle
    out = dict(spans.idle_spans(planes, chips=2))
    gaps = dict(trace.reduce_planes(planes, chips=2)["idle_gaps"])
    assert out["lgbm_dispatch"] == pytest.approx(0.5)
    assert sum(out.values()) == pytest.approx(sum(gaps.values()), abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(2.5)


@pytest.mark.parametrize("planes", [
    _planes(), _planes([]),
    {"devices": {}, "spans": []},
    {"devices": {"/device:TPU:0": [("a", 1.0, 2.0)]}, "spans": []}],
    ids=["no-key", "empty", "nothing", "no-bench-span"])
def test_without_program_spans_idle_spans_are_idle_gaps(planes):
    assert spans.idle_spans(planes) == trace.reduce_planes(planes)["idle_gaps"]
    out = spans.reduce_planes(planes)
    assert out["idle_spans"] == out["idle_gaps"]


def test_the_old_recorded_trace_reads_as_it_did():
    planes = spans.read_planes(RECORDED)
    old = trace.reduce_planes(trace.read_planes(RECORDED))
    out = spans.reduce_planes(planes)
    assert {k: out[k] for k in old} == old
    if not planes["program_spans"]:
        assert out["idle_spans"] == out["idle_gaps"]


# ------------------------------------------------------------ recorded

def test_recorded_trace_keeps_every_old_number_and_names_its_idle():
    planes = spans.read_planes(RECORDED_SPANS)
    old = trace.reduce_planes(_without_program_spans(planes))
    assert trace.reduce_planes(planes) == old
    assert old == trace.reduce_planes(trace.read_planes(RECORDED_SPANS))
    out = spans.reduce_planes(planes)
    assert {k: out[k] for k in old} == old
    assert sum(s for _, s in out["idle_spans"]) == pytest.approx(
        sum(s for _, s in out["idle_gaps"]), abs=1e-9)
    named = [k for k, s in out["idle_spans"]
             if k.startswith(spans.PROGRAM_PREFIX) and s > 0]
    assert named


def test_recorded_trace_holds_the_staged_chain_spans():
    names = [n for n, _, _ in spans.program_spans(RECORDED_SPANS)]
    assert names.count("lgbm_iteration") == 3
    for name in ("lgbm_gradients", "lgbm_grow_args", "lgbm_score_update"):
        assert names.count(name) == 1, name
    assert names.count("lgbm_dispatch") == 3
    assert os.path.getsize(RECORDED_SPANS) < 2 * 1024 * 1024


# ------------------------------------------------------------ the readers

# 10 s busy: the kernel 4 s, the slice reading its output 0.5 (named
# after it in its text, not by its own name), the slab's gather 2 s and
# sort 0.5 s under wave_compact, the rest 3
HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sort.3 = f32[8]{0} sort(%p), metadata={op_name="jit(step)/jit(grow)/while/body/wave_compact/sort"}
  %fusion.83 = f32[8]{0} fusion(%p), kind=kCustom, calls=%a, metadata={op_name="jit(step)/jit(grow)/while/body/wave_compact/jit(_take)/gather"}
  %wave_histogram_pallas_t.5 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/pallas_call"}
  ROOT %slice.9 = f32[8]{0} slice(%wave_histogram_pallas_t.5), metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/slice"}
}
'''
DEVICE_OPS = [["wave_histogram_pallas_t.5", 4.0], ["fusion.348", 3.0],
              ["fusion.83", 2.0], ["sort.3", 0.5], ["slice.9", 0.5]]


def _tree(it, shards, **fields):
    return {"kind": "count", "name": "tree", "seq": 900 + it, "cause": None,
            "t": 99_000_000_000,
            "fields": dict(it=it, tree=0, shards=shards, **fields)}


@pytest.fixture(params=[1, 4], ids=["one-chip", "mesh"])
def filled(request):
    """Two window trees (and an older one) whose counters sum, under a
    mesh, every shard's rows."""
    shards = request.param
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.register_device_scopes(HLO)
    with timers._ring_lock:
        timers._ring.extend([
            _tree(1, shards, kernel_rows=1, slab_rows_moved=1),
            _tree(2, shards, kernel_rows=12_000_000 * shards,
                  slab_rows_moved=13_000_000 * shards),
            _tree(3, shards, kernel_rows=8_000_000 * shards,
                  slab_rows_moved=7_000_000 * shards)])
    yield shards
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


def _run(ops=DEVICE_OPS):
    return {"trace": {"device_ops": ops, "busy_s": 10.0, "window_s": 10.2},
            "trees": [{"num_leaves": 255}] * 2}


def _read(name, run):
    return load_module("metrics", name).read(run)


def test_kernel_nanoseconds_a_row_read_the_kernel_by_its_own_name(filled):
    # 4 s (not 4.5: the slice is not the kernel) over 20 M rows a shard
    assert _read("hist_kernel_ns_per_row", _run()) == pytest.approx(200.0)
    pct = [["wave_partition_hist_pallas_ct.10", 4.0], ["slice.9", 0.5]]
    assert _read("hist_kernel_ns_per_row", _run(pct)) == pytest.approx(200.0)


def test_slab_move_nanoseconds_a_row_read_the_scope(filled):
    # wave_compact 2.5 s over 20 M rows moved a shard
    assert _read("slab_move_ns_per_row", _run()) == pytest.approx(125.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_without_its_records(name):
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    try:
        assert _read(name, _run()) is None          # no ring, no table
        assert _read(name, {"trace": None, "trees": []}) is None
    finally:
        timers._scopes.update(saved)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_the_trace_lacks_its_seconds(filled, name):
    assert _read(name, _run([["fusion.348", 3.0]])) is None
    assert _read(name, dict(_run(), trace={"device_ops": [],
                                           "busy_s": 0.0})) is None


def test_a_step_without_the_slab_has_no_move_to_read(filled):
    with timers._ring_lock:
        for record in timers._ring:
            record["fields"]["slab_rows_moved"] = 0
    assert _read("slab_move_ns_per_row", _run()) is None
    assert _read("hist_kernel_ns_per_row", _run()) is not None


@pytest.mark.parametrize("name", NEW)
def test_new_entries_list_the_two_wide_cells_after_every_accepted_one(name):
    names = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][names.index(name)]
    assert entry["workloads"] == ["epsilon_2000_train", "bosch_968_train"]
    assert entry["unit"] == "ns" and entry["better"] == "lower"
    assert entry["moves"] == "iters_per_s"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if m["name"] not in NEW}
    assert names.index(name) > names.index("bundled_wave_slot_fill_pct")
