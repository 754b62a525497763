"""The tall, narrow cell (`higgs_28_train`, PR 33): its configuration's
file against its entry, the cell through the harness's own lookup, its
five readers, and the reference against the program on the path the cell
takes on the chip (`pallas_ct`, here through the interpreter), sound and
under a fault."""
import copy
import os

import pytest

from benchmark import faults, trace
from benchmark.files import HERE, ROOT, load_json, load_module
from benchmark.run import metric_names, resolve_cell

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "higgs_28_train"
READERS = ["tall_iter_mfu", "ct_hist_roofline", "ct_kernel_time_pct",
           "tall_row_pass_time_pct", "tall_device_idle_pct"]
RECORDED = os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb")


def _read(name, run):
    return load_module("metrics", name).read(run)


def test_the_configuration_is_the_published_shape_with_rows_raised():
    cell, entry, config, traffic = resolve_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("higgs_28", "train", 1)
    assert entry["file"] == "benchmark/configs/higgs_28.json"
    assert entry["reduced"] == config["reduced"] == ["rows"]
    assert config["published"] == {"rows": 10_500_000, "columns": 28}
    assert config["columns"] == 28
    # raised to fill a chip, and a multiple of 2^21: there the parent of
    # the PR that brought the cell compiles in seconds too
    assert config["rows"] % (1 << 21) == 0
    assert config["rows"] >= 3.9 * config["published"]["rows"]
    assert config["rows"] % config["shard_rows"] == 0
    wide = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "epsilon_2000.json"))
    for key in ("params", "environment", "reference", "weighted_share"):
        assert config[key] == wide[key], key
    assert traffic["driver"] == "train_loop"


def test_the_cell_reports_its_own_five_readers_and_no_other():
    names = [m["name"] for m in metric_names(
        BENCH, "per_layer", CELL, {"iters_per_s", "peak_hbm_gib",
                                   "setup_s"})]
    assert names == READERS
    assert [m["name"] for m in BENCH["per_layer"]][-5:] == READERS
    for m in BENCH["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "iters_per_s"
        assert m["unit"] == "%"


# 10 s busy in a 10.1 s window: the fused kernel 7, the root's pass 1,
# gradients 0.5 and the score kernel 0.25, the split search 0.75, a
# slice of the kernel's output that names the kernel only as its operand
OPS = [["wave_partition_hist_pallas_ct.12", 7.0], ["select_add_fusion.2", 1.0],
       ["fusion.20", 0.75], ["fusion.1", 0.5], ["slice.3", 0.5],
       ["score_update_pallas.1", 0.25]]
HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%a, metadata={op_name="jit(step)/gradients/mul"}
  %select_add_fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%b, metadata={op_name="jit(step)/jit(grow)/root_histogram/add"}
  %wave_partition_hist_pallas_ct.12 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/jit(wave_partition_hist_pallas_ct)/pallas_call"}
  %slice.3 = f32[8]{0} slice(%wave_partition_hist_pallas_ct.12), slice={[0:8]}, metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/slice"}
  %fusion.20 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/jit(grow)/while/body/split_search/mul"}
  ROOT %score_update_pallas.1 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/score_update/jit(_update_score_pallas)/score_update/pallas_call"}
}
'''
# one tree of two leaves over 1,000,000 rows, the smaller child 300,000
TREE = {"num_leaves": 2, "left_child": [-1], "right_child": [-2],
        "leaf_count": [700_000, 300_000], "internal_count": [1_000_000]}


def _made_up_run():
    return {"trace": {"device_ops": OPS, "busy_s": 10.0, "window_s": 10.1},
            "trees": [TREE], "rows": 1_000_000, "columns": 28,
            "window_s": 10.1, "device_kind": "TPU v5 lite"}


@pytest.fixture
def step_table():
    from lightgbm_tpu.obs import timers

    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.register_device_scopes(HLO)
    yield
    timers._scopes.clear()
    timers._scopes.update(saved)


def test_readers_give_the_hand_computed_values(step_table):
    run = _made_up_run()
    # 1,300,000 rows x (28 + 8) B over 819 GB/s against the kernel's 7 s;
    # the slice that only names the kernel as its operand is not counted
    least = 1_300_000 * 36 / 819e9
    assert _read("ct_hist_roofline", run) == pytest.approx(
        100 * least / 7.0, rel=1e-9)
    assert _read("ct_kernel_time_pct", run) == pytest.approx(70.0)
    assert _read("tall_row_pass_time_pct", run) == pytest.approx(7.5)
    assert _read("tall_device_idle_pct", run) == pytest.approx(
        100 * (1 - 10.0 / 10.1))
    assert _read("tall_iter_mfu", run) == pytest.approx(
        100 * (1_300_000 * 36 + 16 * 1_000_000) / 819e9 / 10.1, rel=1e-9)
    for name in ("ct_hist_roofline", "tall_iter_mfu"):
        assert 0 < _read(name, run) < 100


def test_readers_on_the_trace_recorded_on_the_chip(step_table):
    """The stored trace is a `pallas_t` step's: the window readers read
    it, the fused kernel's two find no launch of theirs and say nothing
    (what the harness then leaves out), they never read 0."""
    reduced = trace.reduce_planes(trace.read_planes(RECORDED))
    run = dict(_made_up_run(), trace=reduced, window_s=reduced["window_s"])
    assert 0 < _read("tall_device_idle_pct", run) < 100
    assert 0 < _read("tall_iter_mfu", run)
    assert _read("tall_row_pass_time_pct", run) is not None
    assert _read("ct_hist_roofline", run) is None
    assert _read("ct_kernel_time_pct", run) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_says_nothing(name):
    assert _read(name, {}) is None
    assert _read(name, {"trace": {"device_ops": [], "busy_s": 0.0,
                                  "window_s": 0.0}, "trees": []}) is None


# ---- the reference against the program, on the cell's own path

@pytest.fixture
def narrow_config():
    """The cell's configuration at 20,000 rows, with the kernel the chip
    takes by itself forced through the interpreter."""
    _, _, config, _ = resolve_cell(BENCH, CELL)
    config = copy.deepcopy(config)
    config.update(rows=20000, shard_rows=8192)
    config["params"].update(num_leaves=31, tpu_growth="wave",
                            tpu_histogram_mode="pallas_ct",
                            tpu_pallas_interpret=True)
    return config


def test_the_reference_follows_the_fused_kernels_trees(drive, narrow_config):
    out = drive(seed=2 ** 31 + 33, config=narrow_config)
    values = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], values
    assert values["count_mismatch"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_a_fault_on_the_fused_kernels_path_is_caught(drive, narrow_config):
    with faults.FAULTS["half_batch"]():
        out = drive(seed=33, config=narrow_config)
    assert not out["correct"]
    check = out["checks"]["leaf_value_gap_step0"]
    assert check["value"] > check["limit"]
