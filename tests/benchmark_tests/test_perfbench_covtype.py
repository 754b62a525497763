"""The multiclass cell (`covtype_54_train`): its configuration's file
against its entry, the cell through the harness's own lookup, the
generator's class shares and groups, its readers (hand-made run,
nothing), and the plain softmax reference against the program: sound,
under a state left unchanged, an altered answer and the control's half
batch."""
import copy
import os
import re
import time

import numpy as np
import pytest

from benchmark import control_multiclass, faults, gen, gen_multiclass
from benchmark.files import HERE, ROOT, load_json, load_module
from benchmark.run import metric_names, resolve_cell

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "covtype_54_train"
READERS = ["class_tree_ms", "class_gradient_time_pct",
           "class_gradient_roofline", "class_kernel_time_pct",
           "class_hist_roofline", "class_device_idle_pct",
           "class_root_hist_time_pct", "class_split_search_time_pct",
           "class_waves_per_tree", "class_wave_slot_fill_pct",
           "class_hist_kernel_ns_per_row"]
# accepted readers whose lists no accepted test pins: the cell joins them
SETUP_READERS = ["construct_s", "booster_init_s", "first_iter_s"]
COVERTYPE_ROWS = [211840, 283301, 35754, 2747, 9493, 17367, 20510]


def _read(name, run):
    return load_module("metrics", name).read(run)


def test_the_configuration_is_covertype_with_rows_raised():
    cell, entry, config, traffic = resolve_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("covtype_54", "train_multiclass", 1)
    assert entry["file"] == "benchmark/configs/covtype_54.json"
    assert entry["reduced"] == config["reduced"] == ["rows"]
    assert entry["source"] == config["source"]
    assert config["published"]["rows"] == 581_012
    assert config["published"]["columns"] == config["columns"] == 54
    assert config["published"]["classes"] == config["classes"] == 7
    assert config["published"]["class_rows"] == COVERTYPE_ROWS
    assert sum(COVERTYPE_ROWS) == 581_012
    assert config["rows"] == 48_234_496 == 23 << 21
    assert config["rows"] % config["shard_rows"] == 0
    # ten numerical columns, then the 4 wilderness and 40 soil levels
    from benchmark import gen_onehot
    cols = gen_onehot.source_columns(config)
    assert cols == [(63, False)] * 10 + [(4, True), (40, True)]
    assert config["class_shares"] == COVERTYPE_ROWS
    assert not [k for k in config if k.endswith("_seed")]
    for key in ("rows", "source_columns", "zipf_exponent", "labels",
                "groups", "data", "recipe"):
        assert config["assumed"][key], key
    wide = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "epsilon_2000.json"))
    assert config["environment"] == wide["environment"]
    recipe = dict(wide["params"], objective="multiclass", num_class=7)
    assert config["params"] == recipe
    assert (config["generator"], config["reference"]) == \
        ("gen_multiclass", "gbdt_multiclass_plain")
    assert traffic["driver"] == "train_multiclass_loop"
    plain = load_json(os.path.join(HERE, "traffic", "train.json"))
    for key in ("check_steps", "check_nodes", "warmup_iterations",
                "trace_iterations"):
        assert traffic[key] == plain[key], key


def test_the_entries_come_after_the_accepted_ones():
    configs = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert configs.index("covtype_54") > configs.index("expo_700")
    assert cells.index(CELL) > cells.index("expo_700_train")
    entry = BENCH["configs"][configs.index("covtype_54")]
    cell = BENCH["workloads"][cells.index(CELL)]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [m["name"] for m in metric_names(
        BENCH, "per_layer", CELL, {"iters_per_s", "peak_hbm_gib",
                                   "setup_s"})]
    assert names == READERS
    every = [m["name"] for m in BENCH["per_layer"]]
    at = every.index(READERS[0])
    assert every[at:at + len(READERS)] == READERS
    assert at > every.index("bundled_wave_slot_fill_pct")
    for m in BENCH["per_layer"][at:at + len(READERS)]:
        assert m["workloads"] == [CELL] and m["moves"] == "iters_per_s"
    # no accepted reader's list gained the cell: the set-up readers'
    # lists end in `expo_700_train`, which an accepted test pins
    for m in BENCH["per_layer"][:at]:
        assert CELL not in m.get("workloads", [CELL])
    assert all(m["workloads"][-1] == "expo_700_train"
               for m in BENCH["per_layer"] if m["name"] in SETUP_READERS)


# ---- the generator

@pytest.fixture
def covtype_config():
    _, _, config, _ = resolve_cell(BENCH, CELL)
    config = copy.deepcopy(config)
    config.update(rows=60000, shard_rows=16384)
    config["params"]["num_leaves"] = 15
    return config


def test_the_generator_draws_covertypes_shares_and_twelve_groups(
        covtype_config, tmp_path):
    made = gen_multiclass.generate(covtype_config, 4000000017,
                                   str(tmp_path), log=lambda *a: None)
    # the ten numerical columns a group each, the two one-hot blocks a
    # bundle each: 4 levels in 5 bins, 40 in 41
    assert made["groups"] == 12
    assert made["group_bins"] == [63] * 10 + [5, 41]
    want = np.asarray(COVERTYPE_ROWS) / sum(COVERTYPE_ROWS)
    assert np.abs(np.asarray(made["shares"]) - want).max() < 0.01
    _, label = gen.open_shards(str(tmp_path))
    assert set(np.unique(label)) == set(range(7))
    # the rows are gen_onehot's: its source values decode to these bytes
    from benchmark import gen_onehot
    values = gen_onehot.source_values(covtype_config, 4000000017)
    shards, _ = gen.open_shards(str(tmp_path))
    stored = np.concatenate([np.asarray(s) for s in shards], axis=1)
    np.testing.assert_array_equal(stored[:10], values[:10])
    assert (stored[10] == values[10] + 1).all()     # one level a row
    assert (stored[11] == values[11] + 1).all()


def test_the_class_biases_meet_the_shares_they_are_fitted_to():
    rng = np.random.default_rng(3)
    values = rng.integers(0, 5, size=(2, 5000))
    tables = [rng.normal(size=(3, 5)), rng.normal(size=(3, 5))]
    shares = np.array([0.7, 0.25, 0.05])
    bias = gen_multiclass.fit_bias(values, tables, shares)
    z = gen_multiclass._logits(values, tables, bias)
    p = np.exp(z - z.max(axis=0))
    p /= p.sum(axis=0)
    np.testing.assert_allclose(p.mean(axis=1), shares, rtol=1e-6)


# ---- the readers

TREES = 12


def _made_up_run():
    # 10 s busy in 10.4: the kernel 8 in 24 launches, the softmax 0.5,
    # the root's pass 1.25, the split search 0.25; 2 iterations of a step
    # whose scan grows 6 of 7 classes: 12 trees of two leaves over
    # 1,000,000 rows, the smaller child 300,000
    tree = {"num_leaves": 2, "left_child": [-1], "right_child": [-2],
            "leaf_count": [700_000, 300_000], "internal_count": [1_000_000]}
    ops = [["wave_partition_hist_pallas_ct.4", 8.0], ["fusion.1", 0.5],
           ["select_add_fusion.2", 1.25], ["fusion.3", 0.25]]
    return {"trace": {"device_ops": ops, "busy_s": 10.0, "window_s": 10.4,
                      "hist_kernel_launches": 24},
            "trees": [tree] * TREES, "rows": 1_000_000, "columns": 12,
            "classes": 7, "iter_seconds": [5.0, 5.0], "window_s": 10.4,
            "device_kind": "TPU v5 lite"}


HLO = '''HloModule jit_class_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%a, metadata={op_name="jit(class_step)/gradients/exp"}
  %select_add_fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%b, metadata={op_name="jit(class_step)/while/body/jit(grow)/root_histogram/add"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(class_step)/while/body/jit(grow)/while/body/split_search/max"}
  ROOT %wave_partition_hist_pallas_ct.4 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(class_step)/while/body/jit(grow)/while/body/wave_histogram/pallas_call"}
}
'''


@pytest.fixture
def program_records():
    """The class step's scope table, its `class_trees` record and a
    `tree` record a class tree, as a run of the multiclass step leaves
    them; the program's state put back."""
    from lightgbm_tpu.obs import timers

    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.clear()
    timers.register_device_scopes(HLO)
    timers.count("class_trees", classes=7, trained=6)
    for _ in range(TREES):
        timers.count("tree", committed=1, slots=32, kernel_rows=1_000_000)
    yield
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


def test_readers_give_the_hand_computed_values(program_records):
    run = _made_up_run()
    # 10 s of busy time over the window's 12 class trees
    assert _read("class_tree_ms", run) == pytest.approx(1000 * 10 / 12)
    assert _read("class_gradient_time_pct", run) == pytest.approx(5.0)
    # 2 x 1,000,000 x (12 x 7 + 4) B over 819 GB/s against 0.5 s
    assert _read("class_gradient_roofline", run) == pytest.approx(
        100 * 2 * 1_000_000 * 88 / 819e9 / 0.5, rel=1e-9)
    assert _read("class_kernel_time_pct", run) == pytest.approx(80.0)
    assert _read("class_hist_roofline", run) == pytest.approx(
        100 * TREES * 1_300_000 * 20 / 819e9 / 8.0, rel=1e-9)
    assert _read("class_device_idle_pct", run) == pytest.approx(
        100 * (1 - 10.0 / 10.4))
    assert _read("class_root_hist_time_pct", run) == pytest.approx(12.5)
    assert _read("class_split_search_time_pct", run) == pytest.approx(2.5)
    assert _read("class_waves_per_tree", run) == pytest.approx(2.0)
    # one committed split of 32 slots a tree
    assert _read("class_wave_slot_fill_pct", run) == pytest.approx(
        100 / 32)
    # 8 s of the kernel over 12 trees x 1,000,000 rows visited
    assert _read("class_hist_kernel_ns_per_row", run) == pytest.approx(
        1e9 * 8.0 / (TREES * 1_000_000))
    for name in ("class_gradient_roofline", "class_hist_roofline"):
        assert 0 < _read(name, run) < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_says_nothing(name):
    from lightgbm_tpu.obs import timers

    timers.clear()
    assert _read(name, {}) is None
    assert _read(name, {"trace": {"device_ops": [], "busy_s": 0.0,
                                  "window_s": 0.0}, "trees": [],
                        "iter_seconds": []}) is None


def test_a_program_without_the_class_step_says_nothing_of_it(
        program_records):
    """The parent of the PR that brought the cell grows the class trees
    through the staged chain: no `class_trees` record in its ring.  No
    reader leans on that record: the window's trees are the driver's."""
    from lightgbm_tpu.obs import timers

    records = [r for r in timers.snapshot() if r["name"] != "class_trees"]
    timers.clear()
    for r in records:
        timers.count(r["name"], **r["fields"])
    assert not [r for r in timers.snapshot() if r["name"] == "class_trees"]
    assert _read("class_tree_ms", _made_up_run()) == \
        pytest.approx(1000 * 10 / 12)
    assert _read("class_kernel_time_pct", _made_up_run()) == \
        pytest.approx(80.0)
    assert _read("class_wave_slot_fill_pct", _made_up_run()) == \
        pytest.approx(100 / 32)


# ---- the reference against the program

@pytest.fixture
def drive_covtype(covtype_config):
    import jax

    traffic = load_json(os.path.join(HERE, "traffic",
                                     "train_multiclass.json"))
    traffic.update(check_nodes=8, trace_iterations=2)

    def go(seed, seconds=0.3, trace=False, config=None, **extra):
        driver = load_module("drivers", traffic["driver"])
        return driver.run(dict({
            "t0": time.time(), "cell": {}, "config": config or covtype_config,
            "traffic": traffic, "seed": seed, "seconds": seconds,
            "trace": trace, "devices": jax.devices()[:1], "root": ROOT},
            **extra), log=lambda *a: None)

    return go


def test_the_reference_follows_the_program_to_float32(drive_covtype,
                                                      covtype_config):
    """Every leaf of the 21 trees counted exactly, step 0's leaves and
    scores to float32 accuracy, on the CPU's default precision; the
    traced run hands the readers every class tree of its window."""
    out = drive_covtype(seed=4000000018, trace=True)
    values = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], values
    assert values["count_mismatch"] == 0
    assert values["leaf_value_gap_step0"] < 1e-3
    assert values["score_gap_step0"] < 1e-3
    assert values["split_gain_gap_step0"] < 1e-3
    assert values["loss_gap"] < 1e-5
    run = out["run"]
    assert run["classes"] == 7 and run["columns"] == 12
    assert len(run["trees"]) == 7 * out["attempted"]
    # and it takes nothing of the program's
    with open(os.path.join(HERE, "references",
                           "gbdt_multiclass_plain.py")) as f:
        assert not re.search(r"^\s*(import|from)\s+(lightgbm_tpu|benchmark)",
                             f.read(), re.M)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "loss_gap"),
    ("answer_altered", "count_mismatch")])
def test_a_fault_on_the_class_step_is_caught(drive_covtype, covtype_config,
                                             fault, number):
    config = copy.deepcopy(covtype_config)
    config["params"]["tpu_fused_iter"] = "on"
    with faults.FAULTS[fault]():
        out = drive_covtype(seed=4000000019, config=config)
    assert not out["correct"]
    check = out["checks"][number]
    assert check["value"] > check["limit"]


def test_half_a_batch_reaches_the_class_step(drive_covtype, covtype_config):
    """The control's `half_batch` for a multiclass booster: the softmax's
    gradients lose every odd row in every class, inside the fused class
    step.  A leaf's value is a ratio of two sums and hardly moves; a
    split's gain is a sum over the rows, and the roots' recorded gains
    fall to about half the reference's: `split_gain_gap_step0` catches it
    at a test's size as at the cell's (PERF.md section 4)."""
    config = copy.deepcopy(covtype_config)
    config["params"]["tpu_fused_iter"] = "on"
    sound = drive_covtype(seed=4000000019, config=config)
    with control_multiclass.half_batch():
        broken = drive_covtype(seed=4000000019, config=config)
    assert sound["correct"] and not broken["correct"]
    gap = "split_gain_gap_step0"
    assert sound["checks"][gap]["value"] < 1e-3
    assert broken["checks"][gap]["value"] > 0.3 > \
        broken["checks"][gap]["limit"]
