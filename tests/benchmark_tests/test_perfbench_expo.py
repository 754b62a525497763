"""The bundled cell (`expo_700_train`, PR 35): its configuration's file
against its entry, the cell through the harness's own lookup, its
readers (hand-made run, the trace recorded on the chip, nothing), the
driver's column count and how little of it differs from `train_loop`'s,
and the reference that decodes the bundles itself against the program:
sound, on a store whose one group is shifted by a byte, and under two
faults."""
import copy
import difflib
import inspect
import json
import os
import re
import time

import numpy as np
import pytest

from benchmark import faults, gen, gen_onehot, trace
from benchmark.files import HERE, ROOT, load_json, load_module
from benchmark.run import metric_names, resolve_cell

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "expo_700_train"
READERS = ["bundled_iter_mfu", "bundled_hist_roofline",
           "bundled_kernel_time_pct", "bundle_view_time_pct",
           "bundle_pad_fill_pct", "bundled_device_idle_pct",
           "bundled_root_hist_time_pct", "bundled_split_search_time_pct",
           "bundled_wave_slot_fill_pct"]
# accepted readers whose lists no accepted test pins: the cell joins them
SETUP_READERS = ["construct_s", "booster_init_s", "first_iter_s"]
RECORDED = os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb")


def _read(name, run):
    return load_module("metrics", name).read(run)


def test_the_configuration_is_the_published_width_with_rows_raised():
    cell, entry, config, traffic = resolve_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("expo_700", "train_bundled", 1)
    assert entry["file"] == "benchmark/configs/expo_700.json"
    assert entry["reduced"] == config["reduced"] == ["rows"]
    assert entry["source"] == config["source"]
    assert config["published"] == {"rows": 11_000_000, "columns": 700}
    assert config["columns"] == 700
    assert config["rows"] % (1 << 21) == 0
    assert config["rows"] >= 83_886_080
    assert config["rows"] % config["shard_rows"] == 0
    # the six categorical source columns one-hot coded and two numerical
    # ones make the 700; every one of them is said to be assumed
    cols = gen_onehot.source_columns(config)
    assert [card for card, cat in cols if cat] == [12, 31, 7, 22, 313, 313]
    assert [card for card, cat in cols if not cat] == [63, 63]
    assert len(gen_onehot.feature_table(cols)) == 700
    assert config["zipf_exponent"] == 1.0
    # the seed draws everything but the frequencies, as in gen.py: no
    # key of the file fixes the label's weights or the binning sample
    assert not [k for k in config if k.endswith("_seed")]
    for key in ("rows", "source_columns", "zipf_exponent", "groups",
                "data", "weighted_share"):
        assert config["assumed"][key], key
    wide = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "epsilon_2000.json"))
    for key in ("params", "environment", "weighted_share"):
        assert config[key] == wide[key], key
    assert "enable_bundle" not in config["params"]
    assert not [k for k in config["params"] if k.startswith(("tpu_", "obs_"))]
    assert config["reference"] == "gbdt_bundled_plain"
    assert traffic["driver"] == "train_bundled_loop"
    plain = load_json(os.path.join(HERE, "traffic", "train.json"))
    for key in ("check_steps", "check_nodes", "warmup_iterations",
                "trace_iterations"):
        assert traffic[key] == plain[key], key


def test_the_entries_come_after_the_accepted_ones_and_keep_their_lengths():
    """After, not last: a pin on the tail of a list fails by construction
    as soon as a later PR appends to it (PERF.md section 7, third c)."""
    configs = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert configs.index("expo_700") > configs.index("higgs_28")
    assert cells.index(CELL) > cells.index("higgs_28_train")
    entry = BENCH["configs"][configs.index("expo_700")]
    cell = BENCH["workloads"][cells.index(CELL)]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [m["name"] for m in metric_names(
        BENCH, "per_layer", CELL, {"iters_per_s", "peak_hbm_gib",
                                   "setup_s"})]
    assert names == SETUP_READERS + READERS
    every = [m["name"] for m in BENCH["per_layer"]]
    at = every.index(READERS[0])
    assert every[at:at + len(READERS)] == READERS
    assert at > every.index("tall_device_idle_pct")
    for m in BENCH["per_layer"][at:at + len(READERS)]:
        assert m["workloads"] == [CELL] and m["moves"] == "iters_per_s"
        assert m["unit"] == "%"
    # of the accepted readers' lists only the three set-up ones gained
    # the cell, at their end
    for m in BENCH["per_layer"][:at]:
        if m["name"] in SETUP_READERS:
            assert m["workloads"][-1] == CELL and m["moves"] == "setup_s"
        else:
            assert CELL not in m.get("workloads", [CELL])


# 10 s busy in a 10.1 s window: the fused kernel 7, the root's pass 1,
# the split search 0.75 of which the bundle view 0.25, gradients 0.5, a
# slice that names the kernel only as its operand
OPS = [["wave_partition_hist_pallas_ct.10", 7.0], ["select_add_fusion.2", 1.0],
       ["fusion.20", 0.5], ["gather.5", 0.25], ["fusion.1", 0.5],
       ["slice.3", 0.5], ["score_update_pallas.1", 0.25]]
HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%a, metadata={op_name="jit(step)/gradients/mul"}
  %select_add_fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%b, metadata={op_name="jit(step)/jit(grow)/root_histogram/add"}
  %wave_partition_hist_pallas_ct.10 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/jit(wave_partition_hist_pallas_ct)/pallas_call"}
  %slice.3 = f32[8]{0} slice(%wave_partition_hist_pallas_ct.10), slice={[0:8]}, metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/slice"}
  %gather.5 = f32[8]{0} gather(%p), metadata={op_name="jit(step)/jit(grow)/while/body/split_search/split_search/vmap(bundle_view)/gather"}
  %fusion.20 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, metadata={op_name="jit(step)/jit(grow)/while/body/split_search/mul"}
  ROOT %score_update_pallas.1 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/score_update/jit(_update_score_pallas)/score_update/pallas_call"}
}
'''
# one tree of two leaves over 1,000,000 rows, the smaller child 300,000
TREE = {"num_leaves": 2, "left_child": [-1], "right_child": [-2],
        "leaf_count": [700_000, 300_000], "internal_count": [1_000_000]}


def _made_up_run(kernel=None):
    ops = [[kernel, s] if name.startswith("wave_partition_hist")
           and kernel else [name, s] for name, s in OPS]
    return {"trace": {"device_ops": ops, "busy_s": 10.0, "window_s": 10.1},
            "trees": [TREE], "rows": 1_000_000, "columns": 10,
            "window_s": 10.1, "device_kind": "TPU v5 lite"}


@pytest.fixture
def program_records():
    """The step's scope table and one `bundle` record, as a run on a
    bundled dataset leaves them; the program's own state put back."""
    from lightgbm_tpu.obs import timers

    saved = dict(timers._scopes)
    timers._scopes.clear()
    timers.clear()
    timers.register_device_scopes(HLO)
    timers.count("bundle", bundle_groups=10, bundled_features=698,
                 group_bins_used=832, group_bins_padded=2560)
    timers.count("tree", it=1, tree=0, waves=13, slots=416, committed=254)
    yield
    timers.clear()
    timers._scopes.clear()
    timers._scopes.update(saved)


@pytest.mark.parametrize("kernel", ["wave_partition_hist_pallas_ct.10",
                                    "wave_histogram_pallas_t.7"])
def test_readers_give_the_hand_computed_values(program_records, kernel):
    run = _made_up_run(kernel)
    # 1,300,000 rows x (10 group bytes + 8) B over 819 GB/s against the
    # kernel's 7 s, whichever of the two kernels ran; the slice that only
    # names the kernel as its operand is not counted
    least = 1_300_000 * 18 / 819e9
    assert _read("bundled_hist_roofline", run) == pytest.approx(
        100 * least / 7.0, rel=1e-9)
    assert _read("bundled_kernel_time_pct", run) == pytest.approx(70.0)
    assert _read("bundle_view_time_pct", run) == pytest.approx(2.5)
    assert _read("bundle_pad_fill_pct", run) == pytest.approx(32.5)
    assert _read("bundled_device_idle_pct", run) == pytest.approx(
        100 * (1 - 10.0 / 10.1))
    assert _read("bundled_iter_mfu", run) == pytest.approx(
        100 * (1_300_000 * 18 + 16 * 1_000_000) / 819e9 / 10.1, rel=1e-9)
    for name in ("bundled_hist_roofline", "bundled_iter_mfu"):
        assert 0 < _read(name, run) < 100
    # the split search's own share does not hold the view twice
    assert _read("bundled_split_search_time_pct", run) == pytest.approx(5.0)
    assert _read("bundled_root_hist_time_pct", run) == pytest.approx(10.0)
    # one tree of 13 waves: 254 splits in 13 x 32 slots
    assert _read("bundled_wave_slot_fill_pct", run) == pytest.approx(
        100 * 254 / 416)


def test_seven_hundred_columns_would_read_seventy_times_too_high(
        program_records):
    """Why `run["columns"]` is the group count: with the model's 700
    there the same run reads (700 + 8) / (10 + 8) times the share."""
    run = _made_up_run()
    wide = dict(run, columns=700)
    assert _read("bundled_hist_roofline", wide) == pytest.approx(
        _read("bundled_hist_roofline", run) * 708 / 18)


def test_readers_on_the_trace_recorded_on_the_chip(program_records):
    """The stored trace is an unbundled `pallas_t` step's: the window's
    readers and the kernel's two read it (the slab kernel is one of the
    two names), the scope finds no time of its own."""
    reduced = trace.reduce_planes(trace.read_planes(RECORDED))
    run = dict(_made_up_run(), trace=reduced, window_s=reduced["window_s"])
    assert 0 < _read("bundled_device_idle_pct", run) < 100
    assert 0 < _read("bundled_iter_mfu", run)
    assert 0 < _read("bundled_kernel_time_pct", run) < 100
    assert 0 < _read("bundled_hist_roofline", run)
    assert _read("bundle_view_time_pct", run) == 0.0
    assert _read("bundle_pad_fill_pct", run) == pytest.approx(32.5)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_says_nothing(name):
    from lightgbm_tpu.obs import timers

    timers.clear()
    assert _read(name, {}) is None
    assert _read(name, {"trace": {"device_ops": [], "busy_s": 0.0,
                                  "window_s": 0.0}, "trees": []}) is None


def test_a_program_without_the_scope_or_the_record_says_nothing(
        program_records, monkeypatch):
    """The parent of the PR that brought the cell: no `bundle_view` among
    its scopes, no `bundle` record in its ring."""
    from lightgbm_tpu.obs import timers

    monkeypatch.setattr(timers, "SCOPES", tuple(
        s for s in timers.SCOPES if s != "bundle_view"))
    timers.clear()
    run = _made_up_run()
    assert _read("bundle_view_time_pct", run) is None
    assert _read("bundle_pad_fill_pct", run) is None
    assert _read("bundled_kernel_time_pct", run) == pytest.approx(70.0)


# ---- the driver and the reference against the program

def test_the_driver_is_train_loops_run_but_for_three_places():
    """`train_bundled_loop.run` is a copy of the accepted `train_loop.run`
    (an accepted file may not be edited, PERF.md section 7, third h): line
    for line the same but for the generator's import, its call, and the
    column count, so a later change to one shows here until the two fold."""
    base = inspect.getsource(load_module("drivers", "train_loop").run)
    mine = inspect.getsource(load_module("drivers", "train_bundled_loop").run)
    delta = [line for line in difflib.ndiff(base.splitlines(),
                                            mine.splitlines())
             if line[:2] in ("- ", "+ ")]
    assert [d[2:].strip() for d in delta if d[0] == "-"] == [
        "from benchmark import gen, trace as trace_mod",
        'log("generated: %s" % gen.generate(config, ctx["seed"], data_dir))',
        'info = {"rows": int(ds._handle.num_data),',
        '"columns": int(ds._handle.num_total_features)}']
    assert [d[2:].strip() for d in delta if d[0] == "+"] == [
        "from benchmark import gen_onehot, trace as trace_mod",
        'log("generated: %s" % gen_onehot.generate(',
        'config, ctx["seed"], data_dir, log))',
        "# the store's byte columns: the groups, not the model's features",
        'with open(os.path.join(data_dir, "header.json")) as f:',
        'groups = int(json.load(f)["num_columns"])',
        'info = {"rows": int(ds._handle.num_data), "columns": groups,',
        '"features": int(ds._handle.num_total_features)}']


@pytest.fixture
def bundled_config():
    _, _, config, _ = resolve_cell(BENCH, CELL)
    config = copy.deepcopy(config)
    config.update(rows=20000, shard_rows=8192)
    config["params"]["num_leaves"] = 31
    return config


@pytest.fixture
def drive_bundled(bundled_config):
    import jax

    traffic = load_json(os.path.join(HERE, "traffic", "train_bundled.json"))
    traffic.update(check_nodes=8, trace_iterations=2)

    def go(seed, seconds=0.5, trace=False, **extra):
        driver = load_module("drivers", traffic["driver"])
        return driver.run(dict({
            "t0": time.time(), "cell": {}, "config": bundled_config,
            "traffic": traffic, "seed": seed, "seconds": seconds,
            "trace": trace, "devices": jax.devices()[:1], "root": ROOT},
            **extra), log=lambda *a: None)

    return go


def test_the_run_counts_the_stores_groups_as_its_columns(
        drive_bundled, bundled_config, tmp_path):
    made = gen_onehot.generate(bundled_config, 35, str(tmp_path),
                               log=lambda *a: None)
    out = drive_bundled(seed=35, trace=True, data_dir=str(tmp_path))
    values = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], values
    assert values["count_mismatch"] == 0
    assert out["attempted"] == 2 and out["failed"] == 0
    assert out["run"]["columns"] == made["groups"] < 16
    assert out["run"]["rows"] == 20000 and len(out["run"]["trees"]) == 2
    # the directory handed in is the caller's: it stands
    assert os.path.isfile(os.path.join(str(tmp_path), "header.json"))


def test_a_group_shifted_by_one_byte_fails_the_reference(bundled_config,
                                                         tmp_path):
    """The reference decodes the bundles by itself: on a store whose
    bundles have every written byte one too high (offsets wrong by one)
    the trees the program grew on the right store no longer fit."""
    import lightgbm_tpu as lgb

    config = bundled_config
    config["params"]["min_sum_hessian_in_leaf"] = 1.0
    made = gen_onehot.generate(config, 3504, str(tmp_path),
                               log=lambda *a: None)
    driver = load_module("drivers", "train_bundled_loop")
    reference = load_module("references", "gbdt_bundled_plain")
    bst = lgb.Booster(dict(config["params"]), lgb.Dataset.from_binned(
        str(tmp_path), params=dict(config["params"])))
    scores = []
    for _ in range(3):
        bst.update()
        scores.append(np.array(bst._gbdt.train_score[0], np.float32))
    bst._gbdt._materialize()
    trees = [driver.tree_dict(m) for m in bst._gbdt.models[:3]]
    shards, label = gen.open_shards(str(tmp_path))
    with open(os.path.join(str(tmp_path), "header.json")) as f:
        header = json.load(f)
    sound = reference.follow(trees, scores, shards, label, config["params"],
                             header, 8, 3504)
    assert sound["count_mismatch"] == 0
    assert sound["split_gain_gap_step0"] < 1e-5
    bundles = [g for g, feats in enumerate(header["bundle_groups"])
               if len(feats) > 1]
    assert len(bundles) >= 4 and max(made["group_bins"]) == 256
    shifted = []
    for s in shards:
        s = np.array(s)
        s[bundles] = np.where(s[bundles] > 0,
                              np.minimum(s[bundles].astype(np.int32) + 1,
                                         255), 0).astype(np.uint8)
        shifted.append(s)
    broken = reference.follow(trees, scores, shifted, label,
                              config["params"], header, 8, 3504)
    assert broken["count_mismatch"] > 0
    assert broken["split_gain_gap_step0"] > config["limits"][
        "split_gain_gap_step0"]
    # and it takes nothing of the program's
    for name in ("gbdt_bundled_plain.py", "gbdt_plain.py"):
        with open(os.path.join(HERE, "references", name)) as f:
            assert not re.search(r"^\s*(import|from)\s+(lightgbm_tpu|benchmark)",
                                 f.read(), re.M), name


@pytest.mark.parametrize("fault,number", [
    ("half_batch", "leaf_value_gap_step0"),
    ("answer_altered", "count_mismatch")])
def test_a_fault_on_the_bundled_path_is_caught(drive_bundled, fault, number):
    with faults.FAULTS[fault]():
        out = drive_bundled(seed=36)
    assert not out["correct"]
    check = out["checks"][number]
    assert check["value"] > check["limit"]
