"""Multi-device data-parallel training on the virtual 8-device CPU mesh —
the reference's OpenCL-on-CPU / single-process-MPI trick (SURVEY.md §4)."""
import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.parallel.mesh import (DataParallelTreeLearner,
                                        make_data_mesh)
from lightgbm_tpu.ops.learner import SerialTreeLearner
from lightgbm_tpu.utils.config import Config


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    n, f = 1003, 8   # deliberately not divisible by 8 (padding path)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_data_parallel_tree_matches_serial(data):
    X, y = data
    cfg = Config({"num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1})
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    g = (1.0 / (1.0 + np.exp(-np.zeros(len(y)))) - y).astype(np.float32)
    h = np.full(len(y), 0.25, dtype=np.float32)

    serial = SerialTreeLearner(cfg, td)
    tree_s, leaf_s = serial.train(g, h)

    mesh = make_data_mesh(jax.devices())
    dp = DataParallelTreeLearner(cfg, td, mesh)
    tree_dev, leaf_d = dp.train_device(g, h)
    tree_d = dp.materialize(tree_dev)

    # identical structure and outputs (psum changes reduction order, so
    # float32 sums can differ in the last ulps -> identical splits expected
    # on well-separated gains)
    assert tree_d.num_leaves == tree_s.num_leaves
    np.testing.assert_array_equal(tree_d.split_feature[:tree_d.num_leaves - 1],
                                  tree_s.split_feature[:tree_s.num_leaves - 1])
    np.testing.assert_array_equal(tree_d.threshold_in_bin[:tree_d.num_leaves - 1],
                                  tree_s.threshold_in_bin[:tree_s.num_leaves - 1])
    np.testing.assert_allclose(tree_d.leaf_value[:tree_d.num_leaves],
                               tree_s.leaf_value[:tree_s.num_leaves],
                               rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(leaf_d), np.asarray(leaf_s))


def test_data_parallel_wave_with_several_chunks_per_shard():
    """A shard holding more rows than one chunk makes the root one-hot,
    the wave pass and the no-cache rehist accumulate in a lax.scan, whose
    zero carry must start out shard-varying (ops/grow.py vary_like).  The
    default 16,384-row chunk never loops at test sizes; the first
    four-chip run (500,000 rows a shard) failed to trace here.  A
    histogram pool too small for the cache puts all three scans in one
    program."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(4096, 8))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "tpu_growth": "wave",
              "tpu_histogram_mode": "onehot", "tpu_wave_chunk": 256,
              "histogram_pool_size": 0.001}
    trees = {}
    for learner in ("serial", "data"):
        p = dict(params, tree_learner=learner)
        bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=1)
        assert not bst._gbdt.learner.cache_hists
        bst._gbdt._materialize()
        trees[learner] = bst._gbdt.models[0]
    assert trees["data"].num_leaves == trees["serial"].num_leaves == 7
    assert (trees["data"].split_feature[0], trees["data"].threshold_in_bin[0]) \
        == (trees["serial"].split_feature[0],
            trees["serial"].threshold_in_bin[0])


def test_end_to_end_data_parallel_training(data):
    X, y = data
    train = lgb.Dataset(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "binary", "metric": "auc",
                     "tree_learner": "data", "verbose": -1,
                     "num_leaves": 15, "min_data_in_leaf": 5},
                    train, num_boost_round=20, valid_sets=[train],
                    evals_result=evals, verbose_eval=False)
    assert evals["training"]["auc"][-1] > 0.97
    p = bst.predict(X)
    assert (((p > 0.5) == (y > 0)).mean()) > 0.9


def test_voting_alias_and_feature_alias(data):
    X, y = data
    for ltype in ("feature", "voting"):
        train = lgb.Dataset(X, label=y)
        bst = lgb.train({"objective": "binary", "tree_learner": ltype,
                         "verbose": -1, "num_leaves": 7,
                         "min_data_in_leaf": 5},
                        train, num_boost_round=5, verbose_eval=False)
        assert bst.num_trees() > 0


def _tree_signature(t):
    nl = t.num_leaves
    return (nl, t.split_feature[:nl - 1].tolist(),
            t.threshold_in_bin[:nl - 1].tolist(),
            np.round(t.leaf_value[:nl], 6).tolist())


@pytest.fixture(scope="module")
def wide_data():
    rng = np.random.default_rng(3)
    n, f = 1500, 23   # f not divisible by 8 (feature-padding path)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.7 * X[:, 1] * X[:, 2]
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    cfg = Config({"num_leaves": 31, "min_data_in_leaf": 10, "verbose": -1,
                  "top_k": 64})
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    g = (0.5 - y).astype(np.float32)
    h = np.full(n, 0.25, dtype=np.float32)
    return cfg, td, g, h


def test_feature_parallel_exact_match(wide_data):
    """Feature-sharded search must reproduce the serial tree bit-for-bit:
    same scans run, only the argmax-reduce location differs
    (feature_parallel_tree_learner.cpp:52-76)."""
    from lightgbm_tpu.parallel.mesh import FeatureParallelTreeLearner
    cfg, td, g, h = wide_data
    tree_s, leaf_s = SerialTreeLearner(cfg, td).train(g, h)
    fp = FeatureParallelTreeLearner(cfg, td)
    tree_f, leaf_f = fp.train(g, h)
    assert _tree_signature(tree_f) == _tree_signature(tree_s)
    np.testing.assert_array_equal(np.asarray(leaf_f), np.asarray(leaf_s))


def test_voting_parallel_exact_when_topk_covers(wide_data):
    """top_k >= num_features selects every feature, so voting must equal
    serial exactly (modulo psum reduction order)."""
    from lightgbm_tpu.parallel.mesh import VotingParallelTreeLearner
    cfg, td, g, h = wide_data
    tree_s, _ = SerialTreeLearner(cfg, td).train(g, h)
    vt = VotingParallelTreeLearner(cfg, td)
    tree_v = vt.materialize(vt.train_device(g, h)[0])
    assert _tree_signature(tree_v) == _tree_signature(tree_s)


def test_voting_parallel_topk_approximation(wide_data):
    """Small top_k still grows a full, useful tree (PV-Tree regime)."""
    from lightgbm_tpu.parallel.mesh import VotingParallelTreeLearner
    cfg, td, g, h = wide_data
    cfg_small = Config({"num_leaves": 31, "min_data_in_leaf": 10,
                        "verbose": -1, "top_k": 5})
    vt = VotingParallelTreeLearner(cfg_small, td)
    tree_v = vt.materialize(vt.train_device(g, h)[0])
    assert tree_v.num_leaves == 31


def test_end_to_end_voting_parallel_training(data):
    X, y = data
    train = lgb.Dataset(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "binary", "metric": "auc",
                     "tree_learner": "voting", "top_k": 3, "verbose": -1,
                     "num_leaves": 15, "min_data_in_leaf": 5},
                    train, num_boost_round=20, valid_sets=[train],
                    evals_result=evals, verbose_eval=False)
    assert evals["training"]["auc"][-1] > 0.97


def test_end_to_end_feature_parallel_training(data):
    X, y = data
    train = lgb.Dataset(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "binary", "metric": "auc",
                     "tree_learner": "feature", "verbose": -1,
                     "num_leaves": 15, "min_data_in_leaf": 5},
                    train, num_boost_round=20, valid_sets=[train],
                    evals_result=evals, verbose_eval=False)
    assert evals["training"]["auc"][-1] > 0.97


def test_feature_parallel_never_packs_nibbles():
    """max_bin<=15 + tpu_bin_pack=auto must NOT pack under the
    feature-parallel learner (its base ctor runs with psum_axis=None but
    a pre-sharded device matrix; packing there would shard nibble bytes
    as if they were bin columns). The tree must still match serial."""
    from lightgbm_tpu.parallel.mesh import FeatureParallelTreeLearner
    rng = np.random.default_rng(5)
    n, f = 1200, 11
    X = rng.normal(size=(n, f))
    y = (X[:, 0] - 0.5 * X[:, 3] > 0).astype(np.float64)
    cfg = Config({"num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1,
                  "max_bin": 15, "tree_learner": "feature",
                  "enable_bundle": False})
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    g = (0.5 - y).astype(np.float32)
    h = np.full(n, 0.25, dtype=np.float32)
    fp = FeatureParallelTreeLearner(cfg, td)
    assert fp.packed_cols == 0
    cfg_s = Config({"num_leaves": 15, "min_data_in_leaf": 5, "verbose": -1,
                    "max_bin": 15, "enable_bundle": False})
    td_s = TrainingData.from_matrix(X, label=y, config=cfg_s)
    tree_s, _ = SerialTreeLearner(cfg_s, td_s).train(g, h)
    tree_f, _ = fp.train(g, h)
    assert _tree_signature(tree_f) == _tree_signature(tree_s)
