"""Exact-order waves: tpu_wave_order=exact commits, per sweep, exactly the
prefix of candidates the reference's leaf-wise order would have produced
(serial_tree_learner.cpp:203 argmax-per-split), rolling back the rest.
Histograms are reduction-order-identical across wave widths, so the
resulting trees must equal tpu_wave_width=1 — which is pinned to the
leaf-wise order — BIT FOR BIT, at any W, on any data."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _model_string(params, X, y, extra=None, rounds=5):
    p = dict(params, **(extra or {}))
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=rounds)
    return bst.model_to_string()


def _data(seed, n=2500, f=8, kind="binary"):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    X[:, -1] = rng.integers(0, 6, size=n)          # a categorical-ish col
    if kind == "binary":
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2] > 0).astype(np.float64)
    else:
        y = X[:, 0] + 0.3 * X[:, 1] ** 2 + 0.1 * rng.normal(size=n)
    return X, y


BASE = {"verbose": -1, "num_leaves": 31, "min_data_in_leaf": 5,
        "tpu_growth": "wave", "tpu_wave_order": "exact"}


@pytest.mark.parametrize("width", [4, 8, 30])
def test_exact_order_matches_w1_binary(width):
    X, y = _data(1)
    params = dict(BASE, objective="binary")
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": width})
    assert mw == m1


def test_exact_order_matches_w1_regression_and_depth():
    X, y = _data(2, kind="regression")
    params = dict(BASE, objective="regression", max_depth=4)
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1


def test_exact_order_matches_w1_categorical():
    X, y = _data(3)
    params = dict(BASE, objective="binary",
                  categorical_feature=[7])
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1


def test_exact_order_matches_w1_goss_dart():
    """Order-sensitive boosting variants — the configs exact order exists
    for — must also match W=1 exactly (same row_mult per iteration)."""
    X, y = _data(4)
    for boosting in ("goss", "dart"):
        params = dict(BASE, objective="binary", boosting=boosting,
                      bagging_seed=7, drop_seed=9)
        m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
        mw = _model_string(params, X, y, {"tpu_wave_width": 8})
        assert mw == m1, boosting


def test_exact_order_auto_defaults():
    """auto wave order resolves exact ONLY for order-sensitive configs;
    auto width then keeps the ladder instead of collapsing to W=1."""
    from lightgbm_tpu.ops.plan import (resolve_wave_order,
                                       resolve_wave_width)
    from lightgbm_tpu.utils.config import Config

    plain = Config({"objective": "binary", "verbose": -1})
    rank = Config({"objective": "lambdarank", "verbose": -1})
    dart = Config({"objective": "binary", "boosting": "dart",
                   "verbose": -1})
    assert resolve_wave_order(plain) == "batched"
    assert resolve_wave_order(rank) == "exact"
    assert resolve_wave_order(dart) == "exact"
    # widths: exact order carries the ladder for order-sensitive configs
    assert resolve_wave_width(rank, 255, "exact") == 32
    assert resolve_wave_width(rank, 255, "batched") == 1
    assert resolve_wave_width(plain, 255, "batched") == 32


def test_exact_order_data_parallel_matches_w1():
    """Under the data mesh, exact-order W=8 must match data-parallel W=1
    bit-for-bit (identical shard-local reductions + psum order).  Serial
    vs mesh differs by psum reduction order — the accepted drift class —
    so the exactness pin is within the same sharding."""
    X, y = _data(5, n=3000)
    params = dict(BASE, objective="binary", tree_learner="data")
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1


def test_exact_order_sparse_store_matches_w1():
    """Exact order over the sparse coordinate store (tpu_sparse=true +
    explicit wave growth): segment_sum histograms are per-segment
    reductions in row order — W-invariant — so trees must match W=1."""
    rng = np.random.default_rng(6)
    n, f = 3000, 30
    X = np.zeros((n, f))
    nnz = int(n * f * 0.05)
    X[rng.integers(0, n, nnz), rng.integers(0, f, nnz)] = \
        rng.normal(size=nnz)
    y = (X[:, 0] + X[:, 1] > 0.01).astype(np.float64)
    params = dict(BASE, objective="binary", tpu_sparse=True,
                  num_leaves=15)
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1


def test_exact_order_bundled_matches_w1():
    """EFB-bundled data exercises the split table's group remap columns
    (goff/adjust/span) — exact order must stay W-invariant there too."""
    rng = np.random.default_rng(7)
    n = 2400
    parts = []
    for k in (4, 5, 6):                      # one-hot blocks -> bundles
        codes = rng.integers(0, k, size=n)
        parts.append(np.eye(k)[codes])
    dense = rng.normal(size=(n, 3))
    X = np.concatenate(parts + [dense], axis=1)
    y = (dense[:, 0] + (X[:, 0] > 0) - 0.5 * (X[:, 6] > 0)
         > 0.2).astype(np.float64)
    params = dict(BASE, objective="binary", num_leaves=23)
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1
    # sanity: the dataset actually bundled (EFB engaged)
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y, params=dict(params))
    ds.construct()
    assert ds._handle.bundle is not None


@pytest.mark.parametrize("lookup", ["compact", "gather"])
def test_exact_order_with_lookup_modes(lookup):
    """Exact-order commit/rollback composes with every partition-lookup
    strategy: trees still equal tpu_wave_width=1 bit-for-bit."""
    X, y = _data(7)
    params = dict(BASE, objective="binary", tpu_wave_lookup=lookup)
    m1 = _model_string(params, X, y, {"tpu_wave_width": 1})
    mw = _model_string(params, X, y, {"tpu_wave_width": 8})
    assert mw == m1
