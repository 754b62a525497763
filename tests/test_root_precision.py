"""The root's one-hot pass makes two bf16 products a weight where the wave
kernels do (PR 28): under a mesh the kernels are hi/lo, and a root rounded
once left its whole error to the leaf at the end of every `larger = parent
- smaller` chain."""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import histogram, wave


def _table(n=3000, f=5, bins=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, bins, size=(n, f)).astype(np.uint8)
    score = rng.normal(scale=0.3, size=n)
    p = 1.0 / (1.0 + np.exp(-score))
    w = np.stack([p - (rng.uniform(size=n) < 0.5), p * (1 - p),
                  np.ones(n)], axis=-1).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), bins


@pytest.mark.parametrize("chunk", [4096, 1024], ids=["one chunk", "scan"])
def test_two_products_add_up_to_the_one_pass(chunk):
    x, w, bins = _table()
    one = histogram._onehot_accumulate(x, w, bins, chunk)
    two = histogram._onehot_accumulate(x, w, bins, chunk, hilo=True)
    assert one.shape == two.shape == (5, bins, 3)
    np.testing.assert_allclose(two, one, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(two[..., 2], one[..., 2])    # the counts


def test_float8_weights_leave_the_second_product_nothing():
    """The control rounds the weights before the pass (benchmark/faults.py
    patches this argument): float8 is exact in bf16, so what the rounding
    left is zero and the control reads the same with either root."""
    x, w, bins = _table()
    w8 = w.astype(jnp.float8_e4m3fn).astype(w.dtype)
    np.testing.assert_array_equal(
        histogram._onehot_accumulate(x, w8, bins, 1024, hilo=True),
        histogram._onehot_accumulate(x, w8, bins, 1024))


@pytest.mark.parametrize("params,two_products", [
    ({"tpu_histogram_mode": "pallas_t", "tpu_pallas_interpret": True,
      "tpu_hist_precision": "hilo"}, True),
    ({"tpu_histogram_mode": "pallas_t", "tpu_pallas_interpret": True,
      "tpu_hist_precision": "bf16"}, False),       # the one-chip cells
    ({"tree_learner": "data", "tpu_histogram_mode": "onehot"}, False),
], ids=["hilo kernels", "bf16 kernels", "no kernel"])
def test_the_root_follows_the_wave_kernels_products(monkeypatch, params,
                                                    two_products):
    seen = []
    real = wave.leaf_histogram_onehot

    def spy(*args, **kw):
        seen.append(kw.get("hilo", False))
        return real(*args, **kw)

    monkeypatch.setattr(wave, "leaf_histogram_onehot", spy)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(600, 6))
    y = (X[:, 0] + 0.3 * rng.normal(size=600) > 0).astype(np.float64)
    bst = lgb.Booster(dict({"objective": "binary", "num_leaves": 7,
                            "max_bin": 15, "min_data_in_leaf": 5,
                            "verbose": -1, "tpu_growth": "wave",
                            "tpu_fused_iter": "off"}, **params),
                      lgb.Dataset(X, y))
    bst.update()
    assert seen and set(seen) == {two_products}
