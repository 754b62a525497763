"""The readers of what the program records from inside (benchmark/phases.py):
each on a made-up run plus a filled ring and table gives the hand-computed
value, and nothing where its source is empty."""
import os

import pytest

from benchmark import phases
from benchmark.files import ROOT, load_json, load_module
from lightgbm_tpu.obs import timers

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["split_search_time_pct", "partition_time_pct", "root_hist_time_pct",
       "row_pass_time_pct", "unscoped_time_pct", "wave_slot_fill_pct",
       "hist_rows_useful_pct", "dispatch_ms", "upload_host_s",
       "first_iter_host_s"]
MS = 1_000_000

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%a, metadata={op_name="jit(step)/gradients/mul"}
  %select_add_fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%b, metadata={op_name="jit(step)/jit(grow)/root_histogram/add"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/jit(grow)/while/body/wave_partition/select_n"}
  %wave_histogram_pallas_t.12 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/jit(wave_histogram_pallas_t)/pallas_call"}
  %fusion.348 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/jit(grow)/while/body/split_search/vmap()/mul"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%e, metadata={op_name="jit(step)/jit(grow)/while/body/tree_commit/scatter"}
  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/score_update/add"}
  ROOT %copy.7 = f32[8]{0} copy(%p)
}
'''

# 20 s busy: kernel 16, split search 1.5, partition 0.5 + commit 0.25, root
# 0.75, gradients 0.25 + score update 0.25, unnamed 0.5
DEVICE_OPS = [["wave_histogram_pallas_t.12", 16.0], ["fusion.348", 1.5],
              ["select_add_fusion.2", 0.75], ["fusion.3", 0.5],
              ["copy.7", 0.375], ["fusion.5", 0.25], ["fusion.1", 0.25],
              ["fusion.6", 0.25], ["convert.9", 0.125]]
EXPECTED = {"split_search_time_pct": 7.5, "partition_time_pct": 3.75,
            "root_hist_time_pct": 3.75, "row_pass_time_pct": 2.5,
            "unscoped_time_pct": 2.5,
            # two trees: 254 + 200 committed over 2 x 24 x 32 slots
            "wave_slot_fill_pct": 100.0 * 454 / 1536,
            # 4,000,000 + 3,500,000 rows kept over 2 x 25 x 1,200,128
            "hist_rows_useful_pct": 100.0 * 7_500_000 / 60_006_400,
            "dispatch_ms": 3.0,         # median of 2, 3, 9 ms
            "upload_host_s": 0.0625,    # 40 + 10 + 7.5 + 5 ms
            "first_iter_host_s": 1.5}   # 100 + 200 + 1200 ms (load inside)


def _span(name, seq, cause, t0, t1, **ids):
    return {"kind": "span", "name": name, "seq": seq, "cause": cause,
            "t0": int(t0 * MS), "t1": int(t1 * MS), "ids": ids}


def _tree(it, **fields):
    return {"kind": "count", "name": "tree", "seq": 900 + it, "cause": 80,
            "t": 99_000 * MS, "fields": dict(it=it, tree=0, **fields)}


@pytest.fixture
def filled():
    """A ring as one benchmark run leaves it (times in ms) and the step's
    table; the ring holds an older booster's records too."""
    timers.clear()
    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.register_device_scopes(HLO)
    records = [
        # an earlier booster in this process: never the one read
        _span("host_copy", 1, 2, 1, 901, shard=0),
        _span("booster_init", 2, None, 0, 1000),
        _span("trace", 3, 4, 1100, 1900, it=0, entry="step"),
        _span("iteration", 4, None, 1000, 2000, it=0),
        # this run's set-up
        _span("host_copy", 10, 12, 5000, 5040, shard=0),
        _span("h2d", 11, 12, 5040, 5050, shard=0),
        _span("upload_shard", 12, 13, 5000, 5051, shard=0),
        _span("concat", 14, 13, 5051, 5058.5),
        _span("upload", 13, 15, 5000, 5060),
        _span("transpose_xt", 16, 15, 5100, 5105),
        _span("learner_build", 15, 17, 4990, 5200),
        _span("booster_init", 17, None, 4980, 5300),
        # its first iteration: trace, lower, compile with the load inside
        _span("trace", 20, 24, 6000, 6100, it=0, entry="step"),
        _span("lower", 21, 24, 6100, 6300, it=0, entry="step"),
        _span("cache_load", 22, 23, 6400, 7400, it=0),
        _span("compile", 23, 24, 6300, 7500, it=0, entry="step"),
        _span("dispatch", 24, 25, 5990, 7510, it=0),
        _span("iteration", 25, None, 5980, 7520, it=0),
        # a warm-up step, then the window's three
        _span("dispatch", 30, 31, 8000, 8050, it=1),
        _span("iteration", 31, None, 8000, 8051, it=1),
        _span("dispatch", 40, 41, 9000, 9002, it=2),
        _span("iteration", 41, None, 9000, 9003, it=2),
        _span("dispatch", 50, 51, 9100, 9103, it=3),
        _span("iteration", 51, None, 9100, 9104, it=3),
        _span("dispatch", 60, 61, 9200, 9209, it=4),
        _span("iteration", 61, None, 9200, 9210, it=4),
        _tree(2, waves=9, slots=288, attempted=99, committed=99,
              hist_rows=1, rows=1_200_128, rows_visited=12_001_280),
        _tree(3, waves=24, slots=768, attempted=254, committed=254,
              hist_rows=4_000_000, rows=1_200_128, rows_visited=30_003_200),
        _tree(4, waves=24, slots=768, attempted=210, committed=200,
              hist_rows=3_500_000, rows=1_200_128, rows_visited=30_003_200),
        _span("materialize", 80, None, 99_000, 99_100),
    ]
    with timers._ring_lock:
        timers._ring.extend(records)
    yield
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


def _run(trees=2, ops=DEVICE_OPS):
    return {"trace": {"device_ops": ops, "busy_s": 20.0, "window_s": 20.5},
            "trees": [{"num_leaves": 255}] * trees, "spans": {},
            "iter_seconds": [3.1] * trees}


def _read(name, run):
    return load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_hand_computed_value(filled, name):
    run = _run(trees=3) if name == "dispatch_ms" else _run()
    assert _read(name, run) == pytest.approx(EXPECTED[name], rel=1e-9)


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


def test_device_readers_need_the_trace_and_counters_the_trees(filled):
    no_trace = dict(_run(), trace={"device_ops": [], "busy_s": 0.0})
    for name in NEW[:5]:
        assert _read(name, no_trace) is None
    assert _read("wave_slot_fill_pct", _run(trees=0)) is None
    # more trees than the ring has records for: not the window's, nothing
    assert _read("hist_rows_useful_pct", _run(trees=4)) is None
    assert _read("dispatch_ms", _run(trees=9)) is None


def test_a_program_without_the_ring_reads_as_nothing(filled, monkeypatch):
    """The parent of the PR that brought the ring has `obs.timers` and no
    spans in it: every new reader leaves its metric out, none raises."""
    for attr in ("device_time_by_scope", "snapshot", "device_scopes",
                 "self_seconds"):
        monkeypatch.delattr(timers, attr)
    assert phases.timers() is None
    for name in NEW:
        assert _read(name, _run()) is None


def test_scopes_and_table_account_for_the_whole_window(filled):
    seconds = phases.device_seconds(_run())
    assert sum(seconds.values()) == pytest.approx(20.0)
    assert seconds["wave_histogram"] == 16.0
    assert seconds[timers.UNSCOPED] == 0.5


def test_the_table_that_names_most_of_the_window_is_the_steps(filled):
    timers.register_device_scopes(
        'HloModule jit_other\n\nENTRY %main () -> f32[] {\n'
        '  %fusion.348 = f32[] fusion(), kind=kLoop, calls=%z, '
        'metadata={op_name="jit(other)/score_update/add"}\n}\n')
    assert phases.scope_pct(_run(), "split_search") == pytest.approx(7.5)


def test_window_counters_are_the_last_trees_summed(filled):
    total = phases.window_counters(_run())
    assert total["committed"] == 454 and total["attempted"] == 464
    assert total["waves"] == 48 and total["slots"] == 1536
    assert "it" not in total and "tree" not in total


@pytest.mark.parametrize("name", NEW)
def test_new_entries_name_both_cells_and_a_layer_that_exists(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["epsilon_2000_train", "bosch_968_train"]
    assert entry["unit"] in ("%", "ms", "s")
    older = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    assert entry["layer"] in older
    assert entry["moves"] == ("setup_s" if name.endswith("_s")
                              else "iters_per_s")


def test_new_entries_come_last_and_the_old_ones_stand():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == NEW
    assert names[:9] == ["construct_s", "booster_init_s", "first_iter_s",
                         "iter_p50_ms", "waves_per_tree", "mosaic_time_pct",
                         "hist_roofline", "iter_mfu", "device_idle_pct"]


def test_tiny_traced_run_reports_the_program_metrics(drive, tiny_config):
    """Through the driver, tiny on the CPU, on the grower the cells run
    (wave, interpreted kernel, fused step): counters and spans read; the
    device shares have no device plane to read here and are left out."""
    timers.clear()
    tiny_config["params"].update(
        tpu_growth="wave", tpu_histogram_mode="pallas_t",
        tpu_pallas_interpret=True, tpu_fused_iter="on", tpu_wave_width=8)
    out = drive(11, trace=True, config=tiny_config)
    run = out["run"]
    assert out["correct"]
    assert len(run["trees"]) == 2
    assert _read("split_search_time_pct", run) is None
    fill = _read("wave_slot_fill_pct", run)
    useful = _read("hist_rows_useful_pct", run)
    assert 0.0 < fill <= 100.0 and 0.0 < useful < 100.0
    total = phases.window_counters(run)
    assert total["committed"] == sum(t["num_leaves"] - 1
                                     for t in run["trees"])
    assert 0.0 < _read("dispatch_ms", run) < 1e4
    assert 0.0 < _read("upload_host_s", run) <= run["spans"]["booster_init_s"]
    assert 0.0 < _read("first_iter_host_s", run) <= \
        run["spans"]["first_iter_s"]
