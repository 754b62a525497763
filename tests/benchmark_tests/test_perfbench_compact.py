"""The two readers of the row slab (PR 27): `compact_time_pct` from the
`wave_compact` scope and `wave_compacted_pct` from the grow loop's
`compacted` counter.  Each on a made-up run plus a filled ring and table
gives the hand-computed value, nothing where its source is empty, and
nothing on a program that has neither the scope nor the counter."""
import os

import pytest

from benchmark.files import ROOT, load_json, load_module
from lightgbm_tpu.obs import timers

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["compact_time_pct", "wave_compacted_pct"]

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sort.3 = f32[8]{0} sort(%p), metadata={op_name="jit(step)/jit(grow)/while/body/cond/branch_1_fun/wave_compact/sort"}
  %fusion.83 = f32[8]{0} fusion(%p), kind=kCustom, calls=%a, metadata={op_name="jit(step)/jit(grow)/while/body/cond/branch_1_fun/wave_compact/jit(_take)/gather"}
  %wave_histogram_pallas_t.5 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/cond/branch_1_fun/wave_histogram/jit(wave_histogram_pallas_t)/pallas_call"}
  ROOT %fusion.348 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/jit(grow)/while/body/split_search/vmap()/mul"}
}
'''
# 10 s busy: the slab's sort 0.5 and gather 2.5, the kernel 5, the rest 2
DEVICE_OPS = [["wave_histogram_pallas_t.5", 5.0], ["fusion.83", 2.5],
              ["fusion.348", 2.0], ["sort.3", 0.5]]
EXPECTED = {"compact_time_pct": 30.0,
            # two trees: 12 of 12 and 9 of 12 waves read the slab
            "wave_compacted_pct": 100.0 * 21 / 24}


def _tree(it, **fields):
    return {"kind": "count", "name": "tree", "seq": 900 + it, "cause": None,
            "t": 99_000_000_000, "fields": dict(it=it, tree=0, **fields)}


@pytest.fixture
def filled():
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.register_device_scopes(HLO)
    records = [
        _tree(1, waves=5, compacted=0, rows=1000, kernel_rows=5000),
        _tree(2, waves=12, compacted=12, rows=1000, kernel_rows=3400),
        _tree(3, waves=12, compacted=9, rows=1000, kernel_rows=5600),
    ]
    with timers._ring_lock:
        timers._ring.extend(records)
    yield
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


def _run(trees=2):
    return {"trace": {"device_ops": DEVICE_OPS, "busy_s": 10.0,
                      "window_s": 10.2},
            "trees": [{"num_leaves": 255}] * trees}


def _read(name, run):
    return load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_hand_computed_value(filled, name):
    assert _read(name, _run()) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_its_source_is_empty(name):
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    try:
        assert _read(name, _run()) is None          # no ring, no table
        assert _read(name, {"trace": None, "trees": []}) is None
    finally:
        timers._scopes.update(saved)


def test_the_parent_program_reads_as_nothing(filled, monkeypatch):
    """The parent of this PR has the ring and the table, no `wave_compact`
    among its scopes and no `compacted` in a tree's record: both readers
    leave their metric out (a share of 0 would claim the slab never
    ran), and neither raises."""
    monkeypatch.setattr(timers, "SCOPES", tuple(
        s for s in timers.SCOPES if s != "wave_compact"))
    assert _read("compact_time_pct", _run()) is None
    with timers._ring_lock:
        for record in timers._ring:
            for key in ("compacted", "kernel_rows"):
                record["fields"].pop(key)
    assert _read("wave_compacted_pct", _run()) is None
    # and a program without the ring at all
    for attr in ("device_time_by_scope", "snapshot", "device_scopes",
                 "self_seconds"):
        monkeypatch.delattr(timers, attr)
    for name in NEW:
        assert _read(name, _run()) is None


def test_a_window_longer_than_the_ring_reads_as_nothing(filled):
    assert _read("wave_compacted_pct", _run(trees=4)) is None
    no_trace = dict(_run(), trace={"device_ops": [], "busy_s": 0.0})
    assert _read("compact_time_pct", no_trace) is None


@pytest.mark.parametrize("name", NEW)
def test_new_entries_name_both_cells_and_the_grow_program(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["epsilon_2000_train", "bosch_968_train"]
    assert entry["unit"] == "%" and entry["moves"] == "iters_per_s"
    assert entry["layer"] == "grow program"
    assert entry["source"] == ("device_trace" if name == "compact_time_pct"
                               else "program_counter")
    assert [m["name"] for m in BENCH["per_layer"]][-2:] == NEW


def test_tiny_traced_run_counts_every_wave_as_compacted(drive, tiny_config):
    """Through the driver, tiny on the CPU, on the grower the cells run
    (wave, interpreted pallas_t kernel, fused step, all weights 1): every
    wave reads the slab, and the kept rows are a larger share of the rows
    visited than one full pass a wave would leave them."""
    from benchmark import phases

    timers.clear()
    tiny_config["params"].update(
        tpu_growth="wave", tpu_histogram_mode="pallas_t",
        tpu_pallas_interpret=True, tpu_fused_iter="on", tpu_wave_width=8)
    out = drive(11, trace=True, config=tiny_config)
    run = out["run"]
    assert out["correct"]
    assert _read("wave_compacted_pct", run) == 100.0
    assert _read("compact_time_pct", run) is None   # no device plane here
    total = phases.window_counters(run)
    rows = total["rows"] // len(run["trees"])           # of one tree
    assert total["rows_visited"] == total["rows"] + total["kernel_rows"]
    assert total["hist_rows"] <= total["kernel_rows"] \
        < total["waves"] * rows * 0.75
    full_passes = total["rows"] + total["waves"] * rows
    assert _read("hist_rows_useful_pct", run) > \
        100.0 * total["hist_rows"] / full_passes
