"""Wave growth engine (ops/wave.py) correctness.

* wave_width=1 must reproduce the exact leaf-wise grower bit for bit
  (same argmax order, same node numbering) — serial and under the data
  mesh.
* wave_width>1 batches the top-W frontier: the tree differs only in split
  scheduling, so row accounting and quality must hold.
* data-parallel wave == serial wave, exact structure (the psum'd wave
  histogram block must reproduce single-shard histograms).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.ops.grow import make_grow_fn
from lightgbm_tpu.ops.learner import build_split_params
from lightgbm_tpu.ops.split_finder import FeatureMeta
from lightgbm_tpu.ops.wave import make_wave_grow_fn
from lightgbm_tpu.utils.config import Config

N, F, L = 6000, 8, 31


def test_wave_width_auto_policy():
    """tpu_wave_width=-1 scales with num_leaves; explicit values win."""
    from lightgbm_tpu.ops.plan import resolve_wave_width
    assert resolve_wave_width(Config({"verbose": -1}), 15) == 8
    assert resolve_wave_width(Config({"verbose": -1}), 63) == 16
    assert resolve_wave_width(Config({"verbose": -1}), 255) == 32
    cfg = Config({"verbose": -1, "tpu_wave_width": 1})
    assert resolve_wave_width(cfg, 255) == 1


def test_tile_plan_covers_the_measured_band_cells():
    """The 18-30 MB band escape (BENCH_NOTES.md r4/r5: epsilon W16 43x
    slower, bosch W32 10.8x, yahoo's W=64 'escape' itself 3.2x slower)
    was deleted — root cause was the row-tile planner ignoring the
    VMEM-resident accumulator block (ops/pallas_wave.py::_tile_plan).
    Every measured cell the band encoded must land right under the
    live-set accounting: the slow cells are pathological under the old
    plan and fixed under the new one, and the cells that measured fine
    (yahoo W32, the flagship) keep their full row tile."""
    from lightgbm_tpu.ops.pallas_wave import tile_plan_vmem_report
    for fc, bp, k in [(2000, 64, 16), (968, 64, 32)]:   # epsilon, bosch
        rep = tile_plan_vmem_report(6000, fc, bp, k)
        assert rep["pathological_old"] and not rep["pathological_new"]
    for fc, bp, k in [(699, 64, 32), (28, 64, 32)]:     # yahoo, flagship
        rep = tile_plan_vmem_report(1 << 20, fc, bp, k)
        assert not rep["pathological_old"]
        assert rep["c_new"] == rep["c_old"]


def test_auto_width_no_longer_bent_in_serial_learner(monkeypatch):
    """With the band escape gone the learner's AUTO width is exactly the
    resolve_wave_width ladder even where the pallas wave kernel will run
    (faked TPU backend; the 1200-col 255-leaf shape used to bend
    32 -> 64), and an explicit width still passes through untouched."""
    import jax
    from lightgbm_tpu.ops.learner import SerialTreeLearner
    from lightgbm_tpu.ops.wave import make_wave_core, make_wave_jit

    rng = np.random.default_rng(23)
    Xw = rng.normal(size=(600, 1200))
    yw = (Xw[:, 0] > 0).astype(np.float64)
    cfg = Config({"num_leaves": 255, "verbose": -1, "max_bin": 63,
                  "enable_bundle": False})
    td = TrainingData.from_matrix(Xw, label=yw, config=cfg)
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        lrn = SerialTreeLearner(cfg, td)
        assert lrn.hist_mode == "pallas_t"       # wide-F kernel
        assert lrn.wave_width == 32              # raw ladder, no bend
        cfg2 = Config({"num_leaves": 255, "verbose": -1, "max_bin": 63,
                       "enable_bundle": False, "tpu_wave_width": 16})
        lrn2 = SerialTreeLearner(cfg2, td)
        assert lrn2.wave_width == 16             # explicit width wins
    finally:
        monkeypatch.undo()
        make_wave_core.cache_clear(); make_wave_jit.cache_clear()


def _setup(categorical=False, efb=False):
    rng = np.random.default_rng(11)
    X = rng.normal(size=(N, F))
    if categorical:
        X[:, 0] = rng.integers(0, 9, size=N)
    if efb:   # two near-exclusive sparse features bundle together
        m = rng.random(N) < 0.5
        X[:, 2] = np.where(m, X[:, 2], 0.0)
        X[:, 3] = np.where(~m, X[:, 3], 0.0)
    y = (X[:, 1] + np.cos(X[:, 4] * 2) + 0.4 * rng.normal(size=N) > 0.5)
    cfg = Config({"num_leaves": L, "min_data_in_leaf": 3, "max_bin": 63,
                  "verbose": -1, "enable_bundle": efb,
                  "categorical_feature": "0" if categorical else ""})
    td = TrainingData.from_matrix(X, label=y.astype(np.float64), config=cfg)
    meta = FeatureMeta(num_bin=jnp.asarray(td.num_bin_arr),
                       default_bin=jnp.asarray(td.default_bin_arr),
                       is_categorical=jnp.asarray(td.is_categorical_arr))
    grad = jnp.asarray((0.5 - y).astype(np.float32))
    hess = jnp.full(N, 0.25, jnp.float32)
    return cfg, td, meta, grad, hess


def _trees_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.split_feature),
                                  np.asarray(b.split_feature))
    np.testing.assert_array_equal(np.asarray(a.threshold_bin),
                                  np.asarray(b.threshold_bin))
    np.testing.assert_array_equal(np.asarray(a.left_child),
                                  np.asarray(b.left_child))
    np.testing.assert_array_equal(np.asarray(a.right_child),
                                  np.asarray(b.right_child))
    np.testing.assert_array_equal(np.asarray(a.leaf_count),
                                  np.asarray(b.leaf_count))
    np.testing.assert_allclose(np.asarray(a.leaf_value),
                               np.asarray(b.leaf_value), rtol=1e-5)


@pytest.mark.parametrize("categorical", [False, True])
def test_wave1_is_exact_leafwise(categorical):
    cfg, td, meta, grad, hess = _setup(categorical)
    params = build_split_params(cfg)
    nb = int(td.num_bin_arr.max())
    ones = jnp.ones(N, jnp.float32)
    fm = jnp.ones(td.num_features, dtype=bool)
    args = (jnp.asarray(td.binned), grad, hess, ones, fm)
    tg, lg = jax.jit(make_grow_fn(L, nb, meta, params, -1,
                                  hist_mode="scatter",
                                  row_capacities=()))(*args)
    tw, lw = jax.jit(make_wave_grow_fn(L, nb, meta, params, -1,
                                       wave_width=1,
                                       hist_mode="scatter"))(*args)
    assert int(tg.num_leaves) == int(tw.num_leaves)
    _trees_equal(tg, tw)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))


def test_wave1_is_exact_leafwise_efb():
    cfg, td, meta, grad, hess = _setup(efb=True)
    assert td.bundle is not None, "EFB bundle expected for this fixture"
    from lightgbm_tpu.ops.learner import build_bundle_arrays
    bundle, group_bins = build_bundle_arrays(td)
    params = build_split_params(cfg)
    nb = int(td.num_bin_arr.max())
    ones = jnp.ones(N, jnp.float32)
    fm = jnp.ones(td.num_features, dtype=bool)
    args = (jnp.asarray(td.binned), grad, hess, ones, fm)
    tg, lg = jax.jit(make_grow_fn(L, nb, meta, params, -1,
                                  hist_mode="scatter", bundle=bundle,
                                  group_bins=group_bins,
                                  row_capacities=()))(*args)
    tw, lw = jax.jit(make_wave_grow_fn(L, nb, meta, params, -1,
                                       wave_width=1, hist_mode="scatter",
                                       bundle=bundle,
                                       group_bins=group_bins))(*args)
    _trees_equal(tg, tw)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))


def test_wave_batched_accounting_and_depth():
    cfg, td, meta, grad, hess = _setup()
    params = build_split_params(cfg)
    nb = int(td.num_bin_arr.max())
    ones = jnp.ones(N, jnp.float32)
    fm = jnp.ones(td.num_features, dtype=bool)
    args = (jnp.asarray(td.binned), grad, hess, ones, fm)
    tw, lw = jax.jit(make_wave_grow_fn(L, nb, meta, params, 4,
                                       wave_width=8,
                                       hist_mode="scatter"))(*args)
    nl = int(tw.num_leaves)
    assert nl > 8
    lc = np.asarray(tw.leaf_count)[:nl]
    assert lc.sum() == N and (lc >= 3).all()
    assert (np.asarray(tw.leaf_depth)[:nl] <= 4).all()
    # leaf_id agrees with leaf_count
    ids, cnts = np.unique(np.asarray(lw), return_counts=True)
    assert set(ids.tolist()) <= set(range(nl))
    got = dict(zip(ids.tolist(), cnts.tolist()))
    for i in range(nl):
        assert got.get(i, 0) == lc[i]


def test_wave_quality_close_to_exact():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(20000, 10))
    w = rng.normal(size=10)
    y = ((X @ w + 0.5 * rng.normal(size=20000)) > 0).astype(np.float64)
    out = {}
    for mode, ww in (("exact", 1), ("wave", 8)):
        params = {"objective": "binary", "num_leaves": 63, "max_bin": 63,
                  "learning_rate": 0.2, "min_data_in_leaf": 5,
                  "verbose": -1, "metric": "auc", "tpu_growth": mode,
                  "tpu_wave_width": ww}
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.train(params, ds, num_boost_round=15)
        p = bst.predict(X)
        order = np.argsort(p)
        ranks = np.empty(len(p)); ranks[order] = np.arange(1, len(p) + 1)
        npos = y.sum(); nneg = len(y) - npos
        out[mode] = (ranks[y > 0].sum() - npos * (npos + 1) / 2) / (
            npos * nneg)
    assert abs(out["wave"] - out["exact"]) < 5e-3, out


def test_wave_data_parallel_matches_serial():
    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device mesh")
    cfg, td, meta, grad, hess = _setup()
    from lightgbm_tpu.parallel.mesh import (DataParallelTreeLearner,
                                            make_data_mesh)
    cfg2 = cfg.copy_with(tpu_growth="wave", tpu_wave_width=8)
    serial_cfg = cfg2
    from lightgbm_tpu.ops.learner import SerialTreeLearner
    sl = SerialTreeLearner(serial_cfg, td)
    dp = DataParallelTreeLearner(cfg2, td,
                                 make_data_mesh(jax.devices()[:4]))
    assert sl.growth == "wave" and dp.growth == "wave"
    g = np.asarray(grad, np.float32)
    h = np.asarray(hess, np.float32)
    ts, _ = sl.train_device(g, h)
    tdp, _ = dp.train_device(g, h)
    assert int(ts.num_leaves) == int(tdp.num_leaves)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tdp.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(tdp.threshold_bin))
    np.testing.assert_array_equal(np.asarray(ts.leaf_count),
                                  np.asarray(tdp.leaf_count))


@pytest.mark.parametrize("boosting,extra", [
    ("gbdt", {"bagging_fraction": 0.6, "bagging_freq": 1}),
    ("goss", {}),
    ("dart", {"drop_rate": 0.3}),
])
def test_wave_with_row_weighted_boosters(boosting, extra):
    """Wave growth must honor row multipliers (bagging masks, GOSS
    amplification, DART drops) exactly as the exact engine does."""
    rng = np.random.default_rng(17)
    X = rng.normal(size=(8000, 6))
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    out = {}
    for mode in ("exact", "wave"):
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  "verbose": -1, "boosting": boosting,
                  "bagging_seed": 3, "tpu_growth": mode,
                  "tpu_wave_width": 1, **extra}
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=6)
        out[mode] = bst.model_to_string()
    # W=1 wave == exact leaf-wise, so the full booster stack must produce
    # structurally identical models (float-valued fields may differ in the
    # last ulp from histogram accumulation order)
    structural = ("split_feature=", "threshold=", "left_child=",
                  "right_child=", "leaf_count=", "num_leaves=",
                  "decision_type=")
    pick = lambda s: [l for l in s.splitlines()
                      if l.startswith(structural)]
    assert pick(out["wave"]) == pick(out["exact"])


def test_wave_width_auto_ranking_quality_gate():
    """Auto wave width resolves to 1 (the reference's exact split order)
    for ranking objectives — PARITY_TRAINING.md measured -6.4e-3 NDCG@10
    at W=8, so the auto policy is gated on quality, not only speed."""
    from lightgbm_tpu.ops.plan import resolve_wave_width
    cfg = Config({"verbose": -1, "objective": "lambdarank"})
    assert resolve_wave_width(cfg, 255) == 1
    # explicit values still win
    cfg2 = Config({"verbose": -1, "objective": "lambdarank",
                   "tpu_wave_width": 16})
    assert resolve_wave_width(cfg2, 255) == 16
    # DART/InfiniteBoost re-weighting compounds the order approximation
    # (PARITY_TRAINING: +2.7e-2 / +2.5e-2 logloss at W=8) -> W=1 on auto
    assert resolve_wave_width(Config({"verbose": -1, "objective": "binary",
                                      "boosting_type": "dart"}), 255) == 1
    assert resolve_wave_width(
        Config({"verbose": -1, "boosting_type": "infiniteboost"}), 255) == 1
    assert resolve_wave_width(
        Config({"verbose": -1, "boosting_type": "goss"}), 255) == 1
    # plain GBDT keeps the speed ladder
    assert resolve_wave_width(Config({"verbose": -1,
                                      "objective": "binary"}), 255) == 32


def test_wave_lookup_modes_identical_trees():
    """The three partition-lookup strategies (onehot / compact / gather)
    are algebraically identical — each row's split row r is the same
    exact f32 vector — so full trainings must produce byte-identical
    models, including under EFB bundling and at several widths."""
    rng = np.random.default_rng(23)
    X = rng.normal(size=(4000, 10))
    X[rng.random(X.shape) < 0.15] = 0.0
    y = (X[:, 0] - 0.5 * X[:, 3] + 0.2 * rng.normal(size=4000) > 0)
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 5, "tpu_growth": "wave"}
    for width in (4, 8):
        models = {}
        for lk in ("onehot", "compact", "gather"):
            p = dict(base, tpu_wave_width=width, tpu_wave_lookup=lk)
            bst = lgb.train(p, lgb.Dataset(X, label=y.astype(np.float64),
                                           params=p), num_boost_round=8)
            models[lk] = bst.model_to_string()
        assert models["compact"] == models["onehot"], \
            "compact lookup diverged at W=%d" % width
        assert models["gather"] == models["onehot"], \
            "gather lookup diverged at W=%d" % width


def test_wave_lookup_validation():
    p = {"objective": "binary", "verbose": -1, "tpu_growth": "wave",
         "tpu_wave_lookup": "bogus"}
    X = np.random.default_rng(0).normal(size=(200, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)


def test_wave_auto_width_quality_envelope():
    """Every width the auto ladder can resolve to (8/16/32 at its
    num_leaves breakpoints) trains within epsilon of the exact W=1 order
    on a fixed dataset.  Pins VERDICT r3 Weak #4: W=128 measurably
    degrades AUC (0.9319 vs 0.9362 at the 1M on-chip A/B,
    tools/AB_RESULTS.md 11:30 block); the ladder caps at 32 to stay off
    that cliff, and a future ladder change that ships a quality-losing
    width must fail here."""
    from lightgbm_tpu.ops.plan import resolve_wave_width
    from lightgbm_tpu.utils.config import Config

    # the ladder must never resolve past the measured-safe 32
    for leaves in (31, 127, 255, 1023, 4095):
        w = resolve_wave_width(Config({"verbose": -1,
                                       "objective": "binary"}), leaves)
        assert w <= 32, "auto ladder shipped W=%d at %d leaves" % (w,
                                                                   leaves)

    rng = np.random.default_rng(11)
    X = rng.normal(size=(20000, 10))
    wvec = rng.normal(size=10)
    y = ((X @ wvec + 0.5 * rng.normal(size=20000)) > 0).astype(np.float64)

    def auc_of(params, rounds=12):
        ds = lgb.Dataset(X, label=y, params=params)
        p = lgb.train(params, ds, num_boost_round=rounds).predict(X)
        order = np.argsort(p)
        ranks = np.empty(len(p)); ranks[order] = np.arange(1, len(p) + 1)
        npos = y.sum(); nneg = len(y) - npos
        return (ranks[y > 0].sum() - npos * (npos + 1) / 2) / (npos * nneg)

    # one (num_leaves -> auto width) point per ladder rung
    for leaves, expect_w in ((31, 8), (127, 16), (255, 32)):
        base = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
                "learning_rate": 0.2, "min_data_in_leaf": 5, "verbose": -1}
        cfg = Config(dict(base, tpu_growth="wave", tpu_wave_width=-1))
        assert resolve_wave_width(cfg, leaves) == expect_w
        auc_exact = auc_of(dict(base, tpu_growth="exact"))
        auc_wave = auc_of(dict(base, tpu_growth="wave", tpu_wave_width=-1))
        assert auc_wave > auc_exact - 5e-3, \
            "auto W=%d at %d leaves lost %.2e AUC vs exact" % (
                expect_w, leaves, auc_exact - auc_wave)


def test_wave_lookup_validated_under_exact_growth_too():
    """A typo'd tpu_wave_lookup must be fatal even when growth resolves
    to exact (where the value is never applied) — like
    tpu_histogram_mode, validation is unconditional (ADVICE r3)."""
    p = {"objective": "binary", "verbose": -1, "tpu_growth": "exact",
         "tpu_wave_lookup": "bogus"}
    X = np.random.default_rng(0).normal(size=(200, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
