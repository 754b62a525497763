"""The bundled store end to end (`expo_700`, PR 35): a table of the
configuration's own eight source columns, 20,000 rows, written by
`benchmark/gen_onehot.py` (the program's own EFB decides the groups),
opened by `from_binned`, trained three rounds, and held to the plain
reference that decodes the bundles itself
(`benchmark/references/gbdt_bundled_plain.py`): on the CPU's default path
and, at a row count the interpreter finishes in seconds, through each
Pallas wave kernel at a 256-bin pad with the in-kernel bundle remap.

Two more ties.  The bundle is a store and not a model: the same table
written as 700 byte columns grows the same first tree.  And the generator
writes what the program would: its group bytes are
`io/bundle.py bin_rows_grouped` of the per-feature bins, conflict rows
(two features of one group set in a row the EFB sample never saw)
included.
"""
import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import lightgbm_tpu as lgb                                    # noqa: E402
from benchmark import gen, gen_onehot                         # noqa: E402
from benchmark.files import load_json, load_module            # noqa: E402
from lightgbm_tpu.io.binned_format import BinnedWriter        # noqa: E402
from lightgbm_tpu.io.binning import BinMapper                 # noqa: E402
from lightgbm_tpu.io.bundle import (bin_rows_grouped,         # noqa: E402
                                    build_layout, local_bins_np)

STEPS = 3


def _config(rows):
    config = copy.deepcopy(load_json(os.path.join(
        ROOT, "benchmark", "configs", "expo_700.json")))
    config.update(rows=rows, shard_rows=8192)
    config["params"].update(num_leaves=31, min_sum_hessian_in_leaf=1.0)
    return config


def _header(path):
    with open(os.path.join(path, "header.json")) as f:
        return json.load(f)


def _train(path, params):
    """Three rounds on the directory: the trees and each step's score."""
    driver = load_module("drivers", "train_bundled_loop")
    ds = lgb.Dataset.from_binned(path, params=dict(params))
    bst = lgb.Booster(dict(params), ds)
    scores = []
    for _ in range(STEPS):
        bst.update()
        scores.append(np.array(bst._gbdt.train_score[0], np.float32))
    bst._gbdt._materialize()
    return ([driver.tree_dict(m) for m in bst._gbdt.models[:STEPS]], scores,
            bst)


def _follow(path, trees, scores, params, seed):
    reference = load_module("references", "gbdt_bundled_plain")
    shards, label = gen.open_shards(path)
    return reference.follow(trees, scores, shards, label, params,
                            _header(path), 8, seed)


def _sound(gaps):
    assert gaps["count_mismatch"] == 0
    for name in ("leaf_value_gap_step0", "score_gap_step0",
                 "split_gain_gap_step0"):
        assert gaps[name] < 1e-5, (name, gaps[name])


@pytest.mark.parametrize("seed", [35, 2 ** 31 + 35, 3500000035])
def test_the_reference_follows_the_program_on_a_bundled_table(tmp_path,
                                                              seed):
    config = _config(20000)
    made = gen_onehot.generate(config, seed, str(tmp_path),
                               log=lambda *a: None)
    # 700 features in a few byte columns, one of them full
    assert made["columns"] == 700 and made["groups"] < 16
    assert max(made["group_bins"]) == 256
    trees, scores, bst = _train(str(tmp_path), config["params"])
    assert bst._gbdt.learner.bundle_arrays is not None
    assert int(bst._gbdt.learner.X.shape[1]) == made["groups"]
    # the trees split on the model's features, not on the byte columns
    assert max(int(t["split_feature"].max()) for t in trees) >= made["groups"]
    gaps = _follow(str(tmp_path), trees, scores, config["params"], seed)
    _sound(gaps)
    assert gaps["loss_gap"] < config["limits"]["loss_gap"]


@pytest.mark.parametrize("mode", ["pallas_ct", "pallas_t"])
def test_each_wave_kernel_at_a_256_bin_pad_with_the_bundle_remap(
        tmp_path, mode):
    """The kernels the chip takes at 10 and at 11 groups, through the
    interpreter: `bp=256`, `bundled=True`."""
    config = _config(2048)
    config["params"].update(num_leaves=8, tpu_growth="wave",
                            tpu_histogram_mode=mode,
                            tpu_pallas_interpret=True)
    made = gen_onehot.generate(config, 3501, str(tmp_path),
                               log=lambda *a: None)
    assert max(made["group_bins"]) == 256
    trees, scores, bst = _train(str(tmp_path), config["params"])
    plan = bst._gbdt.learner.plan
    assert (plan.hist_mode, plan.pallas_interpret) == (mode, True)
    _sound(_follow(str(tmp_path), trees, scores, config["params"], 3501))


def _unbundled_twin(path, out):
    """The directory at `path` again with one byte column a FEATURE: what
    the stored group bytes hold of each feature (the program's own
    decode), the same mappers and labels, no bundle."""
    header = _header(path)
    mappers = [BinMapper.from_dict(d) for d in header["bin_mappers"]]
    num_bin = np.array([m.num_bin for m in mappers], np.int32)
    default = np.array([m.default_bin for m in mappers], np.int32)
    layout = build_layout(header["bundle_groups"], num_bin, default)
    shards, label = gen.open_shards(path)
    stored = np.concatenate([np.asarray(s) for s in shards], axis=1)
    columns = np.stack(
        [local_bins_np(stored[layout.group_of[f]], f, layout,
                       int(default[f])) for f in range(len(mappers))],
        axis=1).astype(np.uint8)
    writer = BinnedWriter(out, len(mappers), np.uint8)
    writer.append(columns)

    class _Meta:
        weights = query_boundaries = init_score = None
    _Meta.label = label
    writer.finalize(
        num_total_features=len(mappers),
        used_feature_idx=header["used_feature_idx"],
        feature_names=header["feature_names"], max_bin=header["max_bin"],
        bin_mappers=mappers, bundle_groups=None, metadata=_Meta)
    return columns


def test_bundled_and_unbundled_stores_grow_the_same_first_tree(tmp_path):
    config = _config(8192)
    bundled, plain = str(tmp_path / "bundled"), str(tmp_path / "plain")
    gen_onehot.generate(config, 3502, bundled, log=lambda *a: None)
    _unbundled_twin(bundled, plain)
    params = dict(config["params"], enable_bundle=False)
    trees_b, scores_b, _ = _train(bundled, config["params"])
    trees_p, scores_p, bst = _train(plain, params)
    assert bst._gbdt.learner.bundle_arrays is None
    assert int(bst._gbdt.learner.X.shape[1]) == 700
    # step 0's gradients are exact: split for split, count for count
    for key in ("split_feature", "threshold_bin", "dbz", "left_child",
                "right_child", "leaf_count", "internal_count"):
        np.testing.assert_array_equal(trees_b[0][key], trees_p[0][key], key)
    np.testing.assert_allclose(trees_b[0]["leaf_value"],
                               trees_p[0]["leaf_value"], rtol=1e-5)
    # later trees: each store's, inside the reference's limits
    for path, trees, scores in ((bundled, trees_b, scores_b),
                                (plain, trees_p, scores_p)):
        gaps = _follow(path, trees, scores, params, 3502)
        _sound(gaps)
        assert gaps["loss_gap"] < config["limits"]["loss_gap"]
    np.testing.assert_allclose(scores_b[-1], scores_p[-1], atol=1e-4)


def test_the_generator_writes_what_the_program_would(tmp_path):
    config = _config(20000)
    seed = 3503
    gen_onehot.generate(config, seed, str(tmp_path), log=lambda *a: None)
    header = _header(str(tmp_path))
    cols = gen_onehot.source_columns(config)
    bins = gen_onehot.feature_bins(gen_onehot.source_values(config, seed),
                                   cols)
    num_bin = np.array([d["num_bin"] for d in header["bin_mappers"]],
                       np.int32)
    default = np.array([d["default_bin"] for d in header["bin_mappers"]],
                       np.int32)
    layout = build_layout(header["bundle_groups"], num_bin, default)
    want = bin_rows_grouped(bins, layout, default)            # (rows, G)
    shards, _ = gen.open_shards(str(tmp_path))
    got = np.concatenate([np.asarray(s) for s in shards], axis=1).T
    np.testing.assert_array_equal(got, want)
    # rows in which two features of one bundle are set: the sample that
    # made the groups never saw them, the later feature's byte stands
    set_ = bins != default[None, :]
    conflicts = sum(int((set_[:, g].sum(axis=1) > 1).sum())
                    for g in header["bundle_groups"] if len(g) > 1)
    assert conflicts > 0
    # every level of every column is there
    assert set_.any(axis=0).all()
