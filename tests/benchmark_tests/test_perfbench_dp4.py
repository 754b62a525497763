"""The four-chip cell's five readers (PR 28), each on a made-up run plus a
filled ring and table: the hand-computed value, nothing where the program
lacks the scope or the counter (the parent of the PR that brought them),
and the entries that name them."""
import os

import pytest

from benchmark import shapes
from benchmark.files import ROOT, load_json, load_module
from lightgbm_tpu.obs import timers

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "epsilon_2000_dp4_train"
NEW = ["collective_ms", "collective_time_pct", "allreduce_gb_per_s",
       "mesh_iter_mfu", "shard_hbm_skew_pct"]

HLO = '''HloModule jit_grow, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %all-reduce.1 = f32[8]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(grow)/jit(main)/jit(shmap_body)/hist_allreduce/psum"}
  %all-reduce.7 = f32[8]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(grow)/jit(main)/jit(shmap_body)/while/body/hist_allreduce/psum"}
  %wave_histogram_pallas_t.5 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(grow)/jit(main)/jit(shmap_body)/while/body/wave_histogram/jit(wave_histogram_pallas_t)/pallas_call"}
  ROOT %fusion.348 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, metadata={op_name="jit(grow)/jit(main)/jit(shmap_body)/while/body/split_search/vmap()/mul"}
}
'''
# 10 s busy on each chip (the trace's mean): the all-reduces 0.05 + 0.35
DEVICE_OPS = [["wave_histogram_pallas_t.5", 7.0], ["fusion.348", 2.6],
              ["all-reduce.7", 0.35], ["all-reduce.1", 0.05]]
TREE = {"num_leaves": 3, "left_child": [1, -1], "right_child": [-3, -2],
        "leaf_count": [300, 500, 200], "internal_count": [1000, 800]}
ROWS, COLUMNS, KIND = 1000, 40, "TPU v5 lite"
EXPECTED = {"collective_ms": 1e3 * 0.4 / 2,
            "collective_time_pct": 4.0,
            # two trees, 6.0e8 B a shard each, over 0.4 s
            "allreduce_gb_per_s": 1.2e9 / 0.4 / 1e9,
            "shard_hbm_skew_pct": 100.0 * (4.9e9 / 4.7e9 - 1.0)}


def _tree(it, **fields):
    return {"kind": "count", "name": "tree", "seq": 900 + it, "cause": None,
            "t": 99_000_000_000, "fields": dict(it=it, tree=0, **fields)}


def _memory(seq, peaks):
    return {"kind": "count", "name": "mesh_memory", "seq": seq,
            "cause": None, "t": 99_000_000_000,
            "fields": {"peak_bytes_in_use": [int(p) for p in peaks]}}


def _fill(shards):
    timers.clear()
    timers._scopes.clear()
    timers.register_device_scopes(HLO)
    with timers._ring_lock:
        timers._ring.extend(
            _tree(it, waves=12, rows=ROWS, shards=shards,
                  allreduce_bytes=600_000_000 if shards > 1 else 0)
            for it in (1, 2, 3))
        if shards > 1:      # an older batch's record, then the window's
            timers._ring.extend(_memory(seq, peaks) for seq, peaks in (
                (950, [3e9, 1e9, 1e9, 1e9]),
                (951, [4.9e9, 4.7e9, 4.8e9, 4.8e9])))


@pytest.fixture
def filled():
    saved = dict(timers._scopes)
    _fill(4)
    yield
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


def _run(trees=2):
    return {"trace": {"device_ops": DEVICE_OPS, "busy_s": 10.0,
                      "window_s": 10.2},
            "window_s": 10.2, "trees": [TREE] * trees, "rows": ROWS,
            "columns": COLUMNS, "device_kind": KIND}


def _read(name, run):
    return load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_value(filled, name):
    assert _read(name, _run()) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("shards", [1, 4])
def test_mesh_iter_mfu_is_iter_mfu_over_the_shards(filled, shards):
    _fill(shards)
    one_chip = _read("iter_mfu", _run())
    nbytes, ops = shapes.iteration_work([TREE] * 2, COLUMNS, ROWS)
    least, _ = shapes.least_seconds(nbytes, ops, shapes.peaks_for(KIND))
    assert one_chip == pytest.approx(100.0 * least / 10.2)
    assert _read("mesh_iter_mfu", _run()) == pytest.approx(one_chip / shards)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_its_source_is_empty(name):
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    try:
        assert _read(name, {"trace": None, "trees": []}) is None
        assert _read(name, _run()) is None          # no ring, no table
    finally:
        timers._scopes.update(saved)


def test_the_parent_program_reads_as_nothing(filled, monkeypatch):
    """The parent of this PR registers no table for the staged chain,
    its tree records carry neither `shards` nor `allreduce_bytes` and it
    writes no `mesh_memory` record: the readers leave their metric out,
    and none raises; so does a one-device step whose table names no
    all-reduce."""
    with timers._ring_lock:
        kept = [r for r in timers._ring if r["name"] == "tree"]
        timers._ring.clear()
        timers._ring.extend(kept)
        for record in kept:
            for key in ("shards", "allreduce_bytes"):
                record["fields"].pop(key)
    assert _read("shard_hbm_skew_pct", _run()) is None
    assert _read("allreduce_gb_per_s", _run()) is None
    assert _read("mesh_iter_mfu", _run()) is None
    timers._scopes.clear()
    assert _read("collective_ms", _run()) is None
    assert _read("collective_time_pct", _run()) is None
    timers.register_device_scopes(HLO.replace("hist_allreduce", "other"))
    assert _read("collective_ms", _run()) is None
    assert _read("collective_time_pct", _run()) is None
    # and a program without the ring at all
    for attr in ("snapshot", "device_scopes", "device_time_by_scope"):
        monkeypatch.delattr(timers, attr)
    for name in NEW:
        assert _read(name, _run()) is None


@pytest.mark.parametrize("peaks", [[4.9e9], [0, 0, 0, 0]],
                         ids=["one device", "no allocator counter"])
def test_a_record_with_nothing_to_compare_reads_as_nothing(filled, peaks):
    with timers._ring_lock:
        timers._ring.append(_memory(960, peaks))
    assert _read("shard_hbm_skew_pct", _run()) is None


def test_a_run_off_the_chip_has_no_peak_to_take_a_share_of(filled):
    off_chip = dict(_run(), trace={"device_ops": [], "busy_s": 0.0},
                    device_kind="cpu")
    assert _read("mesh_iter_mfu", off_chip) is None


@pytest.mark.parametrize("name", NEW)
def test_new_entries_name_the_new_cell_alone(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    older = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    assert entry["layer"] in older | {"collectives"}
    assert entry["moves"] == ("peak_hbm_gib" if name == "shard_hbm_skew_pct"
                              else "iters_per_s")
    assert entry["unit"] in ("ms", "%", "GB/s")


def test_the_cell_is_the_one_four_chip_cell_and_joins_no_accepted_reader():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="epsilon_2000_dp4", traffic="train",
                        chips=4)
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [CELL]
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]]
    assert listed == NEW            # in this order, after every older one
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("collective_ms") > names.index("wave_compacted_pct")


def test_the_configuration_is_the_one_chip_files_plus_the_learner():
    one = load_json(os.path.join(ROOT, "benchmark/configs/epsilon_2000.json"))
    dp4 = load_json(os.path.join(ROOT,
                                 "benchmark/configs/epsilon_2000_dp4.json"))
    assert dp4["params"] == dict(one["params"], tree_learner="data")
    assert dp4["rows"] == 4 * one["rows"] and dp4["columns"] == one["columns"]
    assert dp4["rows"] % (4 * dp4["shard_rows"]) == 0
    for key in ("environment", "reference", "published", "reduced",
                "weighted_share"):
        assert dp4[key] == one[key]
    assert set(dp4["limits"]) == set(one["limits"])


def test_a_root_rounded_once_under_exact_waves_is_what_loss_gap_read(
        drive, tiny_config, monkeypatch):
    """Why the mesh read `loss_gap` 3-4 x one chip's before its root took
    two products: with the chip's rounding of the root's weights put on
    (the CPU's contraction is exact), hi/lo kernels under a root rounded
    once read hundreds of times what they read under a hi/lo root."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram

    real = histogram._onehot_accumulate
    once = {"root": True}

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    def as_on_the_chip(binned, w, num_bins, chunk, logical_cols=0,
                       hilo=False):
        if hilo and not once["root"]:
            hi = bf16(w)
            return (real(binned, hi, num_bins, chunk, logical_cols)
                    + real(binned, bf16(w - hi), num_bins, chunk,
                           logical_cols))
        return real(binned, bf16(w), num_bins, chunk, logical_cols)

    monkeypatch.setattr(histogram, "_onehot_accumulate", as_on_the_chip)
    tiny_config["params"].update(
        tpu_histogram_mode="pallas_t", tpu_pallas_interpret=True,
        tpu_hist_precision="hilo")
    gaps = {}
    for once["root"] in (True, False):
        jax.clear_caches()      # a root traced before would be served
        gaps[once["root"]] = drive(3, seconds=0.0, config=tiny_config)[
            "checks"]["loss_gap"]["value"]
    assert gaps[True] > 100 * gaps[False] > 0
