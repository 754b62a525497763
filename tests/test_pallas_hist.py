"""Pallas histogram kernel vs the scatter oracle (interpret mode on the CPU
mesh — the reference's OpenCL-on-CPU trick, SURVEY.md §4)."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import leaf_histogram_scatter
from lightgbm_tpu.ops.pallas_hist import leaf_histogram_pallas


@pytest.mark.parametrize("n,f,B", [(1000, 5, 16), (3000, 13, 63)])
def test_pallas_matches_scatter(n, f, B):
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.integers(0, B, size=(n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32))
    leaf_id = jnp.asarray(rng.integers(0, 3, size=n).astype(np.int32))
    rm = jnp.asarray(rng.uniform(0, 2, size=n).astype(np.float32))

    ref = leaf_histogram_scatter(X, g, h, leaf_id, 1, rm, num_bins=B)
    got = leaf_histogram_pallas(X, g, h, leaf_id, 1, rm, num_bins=B)
    assert got.shape == (f, B, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_pallas_no_row_mult():
    rng = np.random.default_rng(1)
    n, f, B = 777, 3, 8     # odd sizes exercise both pad paths
    X = jnp.asarray(rng.integers(0, B, size=(n, f)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    leaf_id = jnp.zeros(n, jnp.int32)
    ref = leaf_histogram_scatter(X, g, h, leaf_id, 0, None, num_bins=B)
    got = leaf_histogram_pallas(X, g, h, leaf_id, 0, None, num_bins=B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_grow_with_pallas_hist_mode():
    """hist_mode='pallas' grows the same tree as 'scatter'."""
    from lightgbm_tpu.io.dataset import TrainingData
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.learner import build_split_params
    from lightgbm_tpu.ops.split_finder import FeatureMeta
    from lightgbm_tpu.utils.config import Config
    import jax

    rng = np.random.default_rng(2)
    n, f = 600, 4
    Xr = rng.normal(size=(n, f))
    y = (Xr[:, 0] > 0).astype(np.float64)
    cfg = Config({"num_leaves": 7, "min_data_in_leaf": 5, "verbose": -1})
    td = TrainingData.from_matrix(Xr, label=y, config=cfg)
    meta = FeatureMeta(num_bin=jnp.asarray(td.num_bin_arr),
                       default_bin=jnp.asarray(td.default_bin_arr),
                       is_categorical=jnp.asarray(td.is_categorical_arr))
    B = int(td.num_bin_arr.max())
    args = (jnp.asarray(td.binned),
            jnp.asarray((0.5 - y).astype(np.float32)),
            jnp.full(n, 0.25, jnp.float32),
            jnp.ones(n, jnp.float32),
            jnp.ones(f, dtype=bool))
    trees = {}
    for mode in ("scatter", "pallas"):
        grow = make_grow_fn(cfg.num_leaves, B, meta, build_split_params(cfg),
                            cfg.max_depth, hist_mode=mode)
        tree, _ = jax.jit(grow)(*args)
        trees[mode] = tree
    assert int(trees["pallas"].num_leaves) == int(trees["scatter"].num_leaves)
    np.testing.assert_array_equal(
        np.asarray(trees["pallas"].split_feature),
        np.asarray(trees["scatter"].split_feature))
    np.testing.assert_array_equal(
        np.asarray(trees["pallas"].threshold_bin),
        np.asarray(trees["scatter"].threshold_bin))
