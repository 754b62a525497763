"""HistogramPool budget policy (feature_histogram.hpp:398-565 analog).

When the (L, F, B, 3) per-leaf cache exceeds histogram_pool_size, the
learner disables the cache and recomputes larger children instead of
obtaining them by subtraction — the model must be IDENTICAL either way,
for both growth engines.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _fit(growth, pool_mb):
    rng = np.random.default_rng(17)
    X = rng.normal(size=(3000, 10))
    y = (X[:, 0] + np.sin(X[:, 3] * 2) + 0.3 * rng.normal(size=3000) > 0.3)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 3, "verbose": -1, "tpu_growth": growth,
              "histogram_pool_size": pool_mb}
    bst = lgb.train(params,
                    lgb.Dataset(X, label=y.astype(np.float64),
                                params=params),
                    num_boost_round=4)
    return bst, X


@pytest.mark.parametrize("growth", ["exact", "wave"])
def test_pool_fallback_identical_model(growth):
    # 31 leaves x 10 cols x 64 bins x 3 x 4B ~ 0.24 MB -> 0.01 MB budget
    # forces the no-cache recompute path
    b_cache, X = _fit(growth, -1.0)
    b_nocache, _ = _fit(growth, 0.01)
    assert b_cache._gbdt.learner.cache_hists is True
    assert b_nocache._gbdt.learner.cache_hists is False
    # recompute vs parent-minus-sibling subtraction differ in f32 low bits,
    # which can flip near-tie split choices (the reference's pool eviction
    # has the same property) — the contract is equal-quality training, not
    # bit-identical trees
    p_c, p_n = b_cache.predict(X), b_nocache.predict(X)
    np.testing.assert_allclose(p_c, p_n, atol=5e-3)
    for b in (b_cache, b_nocache):
        assert all(t.num_leaves == 31 for t in b._gbdt.models)


def test_pool_auto_budget_boundaries():
    """The auto budget admits Higgs- and Epsilon-shaped caches (both fit a
    16 GB chip alongside the data) but rejects unbounded growth, and an
    explicit histogram_pool_size always wins."""
    from lightgbm_tpu.ops.plan import hist_cache_enabled
    from lightgbm_tpu.utils.config import Config
    cfg = Config({"verbose": -1})
    assert hist_cache_enabled(cfg, 255, 28, 64, 4)        # Higgs: 5.5 MB
    assert hist_cache_enabled(cfg, 255, 2000, 255, 4)     # Epsilon: 1.6 GB
    assert not hist_cache_enabled(cfg, 255, 8000, 255, 4)   # 6.2 GB: no
    tight = Config({"verbose": -1, "histogram_pool_size": 512.0})
    assert not hist_cache_enabled(tight, 255, 2000, 255, 4)
