"""Rank-encoded device bulk prediction (ops/predict.py RankedPredictor):
leaf ROUTING must be bit-equal to the host f64 predictor — the ranks
encode every f64 threshold compare — including the zero-range default
redirect, NaN-goes-right, and integer-cast categorical equality; scores
match the host f64 sums to f32 rounding."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import predict as dev_predict


def _train(X, y, params, rounds=10):
    p = dict({"verbose": -1, "num_leaves": 15, "min_data_in_leaf": 5},
             **params)
    return lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=rounds)


def _routing_and_scores(bst, Xq):
    g = bst._gbdt
    g._materialize()
    k = g.num_tree_per_iteration
    rp = dev_predict.build_ranked_predictor(g.models, k, Xq.shape[1])
    V, D = dev_predict.rank_encode(rp, Xq)
    import jax.numpy as jnp
    leaves = np.asarray(dev_predict.ranked_leaf_indices_device(
        rp.dev, jnp.asarray(V), jnp.asarray(D)))
    score = np.asarray(dev_predict.ranked_predict_device(
        rp.dev, jnp.asarray(V), jnp.asarray(D), k))
    host_leaves = np.stack(
        [t.predict_leaf_index(np.asarray(Xq, np.float64))
         for t in g.models], axis=1)
    host_raw = np.zeros((len(Xq), k))
    for t, tree in enumerate(g.models):
        host_raw[:, t % k] += tree.predict(np.asarray(Xq, np.float64))
    return leaves, host_leaves, score, host_raw


def test_routing_bit_equal_binary_with_zeros_and_nan():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(4000, 6))
    X[rng.random(X.shape) < 0.2] = 0.0          # exercise zero redirect
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    bst = _train(X, y, {"objective": "binary"})
    Xq = X.copy()
    Xq[rng.random(Xq.shape) < 0.05] = np.nan    # NaN -> right
    Xq[rng.random(Xq.shape) < 0.05] = 0.0
    leaves, host_leaves, score, host_raw = _routing_and_scores(bst, Xq)
    np.testing.assert_array_equal(leaves, host_leaves)
    np.testing.assert_allclose(score, host_raw, rtol=2e-6, atol=2e-6)


def test_routing_bit_equal_categorical_multiclass():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(3000, 5))
    X[:, 0] = rng.integers(0, 12, size=3000)
    X[:, 1] = rng.integers(0, 5, size=3000)
    y = rng.integers(0, 3, size=3000).astype(np.float64)
    bst = _train(X, y, {"objective": "multiclass", "num_class": 3,
                        "categorical_feature": [0, 1]}, rounds=5)
    Xq = X.copy()
    Xq[:50, 0] = 99.0                           # unseen category
    leaves, host_leaves, score, host_raw = _routing_and_scores(bst, Xq)
    np.testing.assert_array_equal(leaves, host_leaves)
    np.testing.assert_allclose(score, host_raw, rtol=2e-6, atol=2e-6)


def test_bulk_predict_engages_and_matches(monkeypatch):
    """tpu_predict=true forces the device path through Booster.predict;
    results match the host path (tpu_predict=false) to f32 rounding."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(2500, 6))
    y = X[:, 0] * 2 + X[:, 2] + 0.1 * rng.normal(size=2500)
    bst = _train(X, y, {"objective": "regression"})
    g = bst._gbdt
    g.config = g.config.copy_with(tpu_predict="true")
    p_dev = bst.predict(X)
    calls = {"n": 0}
    orig_one = dev_predict.ranked_predict_device
    orig_sh = dev_predict.ranked_predict_sharded

    def spy_one(*a, **kw):
        calls["n"] += 1
        return orig_one(*a, **kw)

    def spy_sh(*a, **kw):
        calls["n"] += 1
        return orig_sh(*a, **kw)
    # multi-device backends route through the sharded program instead
    monkeypatch.setattr(dev_predict, "ranked_predict_device", spy_one)
    monkeypatch.setattr(dev_predict, "ranked_predict_sharded", spy_sh)
    g.config = g.config.copy_with(tpu_predict="true")
    g._ranked_pred_key = None
    p_dev2 = bst.predict(X)
    assert calls["n"] >= 1, "device path did not engage"
    g.config = g.config.copy_with(tpu_predict="false")
    p_host = bst.predict(X)
    np.testing.assert_allclose(p_dev, p_host, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(p_dev2, p_host, rtol=2e-6, atol=2e-6)


def test_loaded_model_device_predict(tmp_path):
    """A Booster loaded from a model FILE (real-valued thresholds only)
    routes identically on device."""
    rng = np.random.default_rng(6)
    X = rng.normal(size=(2000, 5))
    y = (X[:, 0] - 0.3 * X[:, 4] > 0).astype(np.float64)
    bst = _train(X, y, {"objective": "binary"})
    fn = str(tmp_path / "m.txt")
    bst.save_model(fn)
    loaded = lgb.Booster(model_file=fn)
    g = loaded._gbdt
    g._materialize()
    rp = dev_predict.build_ranked_predictor(
        g.models, g.num_tree_per_iteration, X.shape[1])
    V, D = dev_predict.rank_encode(rp, X)
    import jax.numpy as jnp
    leaves = np.asarray(dev_predict.ranked_leaf_indices_device(
        rp.dev, jnp.asarray(V), jnp.asarray(D)))
    host_leaves = np.stack(
        [t.predict_leaf_index(np.asarray(X, np.float64))
         for t in g.models], axis=1)
    np.testing.assert_array_equal(leaves, host_leaves)

def test_sharded_predict_matches_single_device():
    """ranked_predict_sharded over the 8-device CPU mesh is bit-identical
    to the single-device program — prediction is pure data parallelism
    (rows shard, trees replicate, zero collectives)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    X = rng.normal(size=(1003, 6))          # deliberately not %8 == 0
    X[rng.random(X.shape) < 0.1] = 0.0
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    bst = _train(X, y, {"objective": "binary"})
    g = bst._gbdt
    g._materialize()
    k = g.num_tree_per_iteration
    rp = dev_predict.build_ranked_predictor(g.models, k, X.shape[1])
    V, D = dev_predict.rank_encode(rp, X)
    single = np.asarray(dev_predict.ranked_predict_device(
        rp.dev, jnp.asarray(V), jnp.asarray(D), k))
    sharded, nrows = dev_predict.ranked_predict_sharded(
        rp, V, D, k, devices=jax.devices()[:8])
    assert nrows == len(X)
    np.testing.assert_array_equal(np.asarray(sharded)[:nrows], single)
    # ctx is cached: a second call reuses the replicated tree stack
    ctx1 = rp._shard_ctx
    sharded2, _ = dev_predict.ranked_predict_sharded(
        rp, V, D, k, devices=jax.devices()[:8])
    assert rp._shard_ctx is ctx1
    np.testing.assert_array_equal(np.asarray(sharded2), np.asarray(sharded))


def test_sharded_predict_through_booster(monkeypatch):
    """tpu_predict=true on a multi-device backend routes Booster.predict
    through the sharded program and matches the host predictor."""
    rng = np.random.default_rng(12)
    X = rng.normal(size=(2000, 5))
    y = X[:, 0] - X[:, 3] + 0.1 * rng.normal(size=2000)
    bst = _train(X, y, {"objective": "regression"})
    g = bst._gbdt
    calls = {"n": 0}
    orig = dev_predict.ranked_predict_sharded

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)
    monkeypatch.setattr(dev_predict, "ranked_predict_sharded", spy)
    g.config = g.config.copy_with(tpu_predict="true")
    p_dev = bst.predict(X)
    assert calls["n"] >= 1, "sharded path did not engage"
    g.config = g.config.copy_with(tpu_predict="false")
    p_host = bst.predict(X)
    np.testing.assert_allclose(p_dev, p_host, rtol=2e-6, atol=2e-6)


def test_chunked_pipeline_predict_matches(monkeypatch):
    """The one-deep chunk pipeline assembles multi-chunk predictions in
    the right slots (chunk forced tiny so several chunks flow through a
    single predict call)."""
    from lightgbm_tpu.models import gbdt as gbdt_mod
    rng = np.random.default_rng(13)
    X = rng.normal(size=(1500, 5))
    y = X[:, 0] - 0.4 * X[:, 2] + 0.05 * rng.normal(size=1500)
    bst = _train(X, y, {"objective": "regression"})
    g = bst._gbdt
    g.config = g.config.copy_with(tpu_predict="false")
    host = bst.predict(X)
    calls = {"n": 0}
    real_encode = dev_predict.rank_encode

    def spy(rp, part):
        calls["n"] += 1
        return real_encode(rp, part)
    monkeypatch.setattr(dev_predict, "rank_encode", spy)
    monkeypatch.setattr(gbdt_mod.GBDT, "_predict_chunk_rows",
                        staticmethod(lambda nf, nd: 400))
    g.config = g.config.copy_with(tpu_predict="true")
    g._ranked_pred_key = None
    piped = bst.predict(X)
    assert calls["n"] == 4, calls     # 1500 rows / 400-row chunks
    np.testing.assert_allclose(piped, host, rtol=2e-6, atol=2e-6)


def test_score_update_pallas_bit_equal():
    """tpu_score_update=pallas (compare-select kernel) must be BIT-equal
    to the XLA gather form — same clipped f32 leaf values selected and
    added once per row (ops/predict.py)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import (_update_score_gather,
                                          _update_score_pallas,
                                          kMaxTreeOutput)
    rng = np.random.default_rng(11)
    for n, L in [(5000, 255), (8192, 31), (777, 7)]:
        score = rng.normal(size=n).astype(np.float32)
        # include out-of-range sentinels: both engines clamp to [0, L-1]
        lid = rng.integers(-1, L + 1, size=n).astype(np.int32)
        lv = rng.normal(size=L).astype(np.float32) * 60  # hits the clamp
        scale = np.float32(1.7)
        want = _update_score_gather(jnp.asarray(score), jnp.asarray(lid),
                                    jnp.asarray(lv), jnp.asarray(scale))
        vals = jnp.clip(jnp.asarray(lv) * scale,
                        -kMaxTreeOutput, kMaxTreeOutput)
        got = _update_score_pallas(jnp.asarray(score), jnp.asarray(lid),
                                   vals, interpret=True)
        assert np.array_equal(np.asarray(want), np.asarray(got)), (n, L)


def test_score_update_pallas_needs_one_device(monkeypatch):
    """A Mosaic kernel outside shard_map cannot be partitioned: with the
    row->leaf map sharded over a mesh (the data-parallel learner on real
    chips) the dispatch must take the XLA gather.  Found on the way to
    the first four-chip run: lowered for tpu with a sharded leaf_id the
    kernel raises NotImplementedError."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from lightgbm_tpu.ops import predict as dev_predict
    from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_data_mesh
    n, L = 4096, 31
    rng = np.random.default_rng(12)
    score = jnp.asarray(rng.normal(size=n).astype(np.float32))
    lv = jnp.asarray(rng.normal(size=L).astype(np.float32))
    lid = rng.integers(0, L, size=n).astype(np.int32)
    sharded = jax.device_put(
        lid, NamedSharding(make_data_mesh(jax.devices()[:4]), P(DATA_AXIS)))
    with pytest.raises(NotImplementedError, match="shard_map"):
        dev_predict._update_score_pallas.trace(score, sharded, lv).lower(
            lowering_platforms=("tpu",))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        dev_predict, "_update_score_pallas",
        lambda *a, **k: pytest.fail("pallas engine on a sharded leaf_id"))
    got = dev_predict.update_score_from_partition(
        score, sharded, lv, jnp.float32(0.1), engine="pallas")
    want = dev_predict._update_score_gather(score, jnp.asarray(lid), lv,
                                            jnp.float32(0.1))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_score_update_engine_validation():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    import lightgbm_tpu as lgb
    import pytest
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        lgb.train({"objective": "binary", "num_boost_round": 1,
                   "tpu_score_update": "vmem", "verbose": -1},
                  lgb.Dataset(X, label=y))
    # explicit gather trains
    bst = lgb.train({"objective": "binary", "num_boost_round": 2,
                     "tpu_score_update": "gather", "verbose": -1},
                    lgb.Dataset(X, label=y))
    assert bst.predict(X).shape == (300,)
    # round-5 promoted auto (BENCH_NOTES.md "Armed decks", measured
    # bit-equal + faster at the 10.5M flagship): auto resolves to the
    # pallas engine — the dispatch in ops/predict.py still falls back
    # to the gather off-TPU / at num_leaves>512 / on f64 scores, so
    # training on CPU must keep working
    bst2 = lgb.train({"objective": "binary", "num_boost_round": 2,
                      "verbose": -1}, lgb.Dataset(X, label=y))
    assert bst2._gbdt._score_engine == "pallas"
    assert bst2.predict(X).shape == (300,)
