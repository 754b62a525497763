"""Wave-histogram Pallas kernels vs the XLA oracle (interpret mode, CPU).

Covers the shipped kernel layouts (v1 row-major `pallas`, v2 transposed
`pallas_t`, v5 fused compact-table row-vector `pallas_ct`) and the
4-bit packed input path of each.  The v3/v4 fused kernels and their
tests were deleted in round 4 (measured losers — BENCH_NOTES.md).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.pack import pack4_host
from lightgbm_tpu.ops.pallas_wave import (wave_histogram_pallas,
                                          wave_histogram_pallas_t,
                                          wave_histogram_reference)


def _data(n=3000, f=7, b=14, k=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.integers(0, b, size=(n, f), dtype=np.uint8)
    leaf_id = rng.integers(0, 2 * k, size=n).astype(np.int32)
    w3 = rng.normal(size=(n, 3)).astype(np.float32)
    cid = np.array([0, 2, 4, -1, 7], dtype=np.int32)[:k]
    return X, leaf_id, w3, cid, b


@pytest.mark.parametrize("layout", ["v1", "v2"])
def test_kernel_matches_oracle(layout):
    X, leaf_id, w3, cid, b = _data()
    want = np.array(wave_histogram_reference(
        jnp.asarray(X), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), b))
    want[np.asarray(cid) < 0] = 0.0
    if layout == "v1":
        got = wave_histogram_pallas(
            jnp.asarray(X), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True)
    else:
        got = wave_histogram_pallas_t(
            jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("layout", ["v1", "v2", "v5"])
def test_kernel_bf16_precision_mode(layout):
    """tpu_hist_precision=bf16 (single round-to-nearest product, half the
    MXU work — the reference GPU's gpu_use_dp=false analog): sums must
    stay within the 2^-9-per-product class of the exact oracle, measured
    against the histogram's scale (signed gradients make tiny individual
    cells legitimately high-relative-error)."""
    X, leaf_id, w3, cid, b = _data()
    want = np.array(wave_histogram_reference(
        jnp.asarray(X), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), b))
    want[np.asarray(cid) < 0] = 0.0
    if layout == "v1":
        got = wave_histogram_pallas(
            jnp.asarray(X), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True, hilo=False)
    elif layout == "v2":
        got = wave_histogram_pallas_t(
            jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True, hilo=False)
    else:
        from lightgbm_tpu.ops.pallas_wave import (
            wave_partition_hist_pallas_ct)
        # inactive table: no splits commit, histograms of cid as-is
        cols = np.zeros((4, 10), np.float32)
        psrc = np.full(4, -3, np.int32)
        _, got = wave_partition_hist_pallas_ct(
            jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc), b,
            interpret=True, hilo=False)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= 5e-3 * scale


@pytest.mark.parametrize("mode", ["pallas_t", "pallas_ct"])
def test_pallas_wave_data_parallel_constructs(mode):
    """tree_learner=data + a wave-only pallas mode must reach the mesh
    wave branch (the base constructor's exact-engine fallback maps these
    modes to onehot instead of crashing) and train."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(2)
    X = rng.normal(size=(1600, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "tree_learner": "data", "tpu_histogram_mode": mode}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2)
    assert bst.predict(X).shape == (1600,)


@pytest.mark.parametrize("mode", ["pallas_t", "pallas_ct"])
def test_pallas_wave_mode_plumbing(mode):
    """Wave-only pallas modes resolve to wave growth and train (falling
    back to the einsum path off-TPU); exact growth rejects them."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.log import LightGBMError

    rng = np.random.default_rng(1)
    X = rng.normal(size=(1200, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "tpu_histogram_mode": mode}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=3)
    assert bst._gbdt.learner.growth == "wave"
    p = bst.predict(X)
    assert p.shape == (1200,)

    bad = dict(params, tpu_growth="exact")
    with pytest.raises(LightGBMError):
        lgb.train(bad, lgb.Dataset(X, label=y, params=bad),
                  num_boost_round=1)


@pytest.mark.parametrize("layout", ["v1", "v2"])
def test_kernel_packed_matches_oracle(layout):
    X, leaf_id, w3, cid, b = _data(f=9, b=15, seed=3)
    want = np.array(wave_histogram_reference(
        jnp.asarray(X), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), b))
    want[np.asarray(cid) < 0] = 0.0
    packed = pack4_host(X)
    if layout == "v1":
        got = wave_histogram_pallas(
            jnp.asarray(packed), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True, logical_cols=X.shape[1])
    else:
        got = wave_histogram_pallas_t(
            jnp.asarray(packed.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), b, interpret=True, logical_cols=X.shape[1])
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-4, atol=5e-4)


def _route_numpy(X, leaf_id, tbl, bundled=False):
    """Numpy replica of the wave partition routing (ops/wave.py)."""
    r = tbl[np.clip(leaf_id, 0, tbl.shape[0] - 1)]
    r = np.where((leaf_id >= 0)[:, None], r, 0.0)
    active = r[:, 0] > 0.5
    cj = r[:, 1].astype(np.int32)
    colv = X[np.arange(len(X)), np.clip(cj, 0, X.shape[1] - 1)].astype(
        np.int32)
    if bundled:
        goff = r[:, 7].astype(np.int32)
        span = r[:, 9].astype(np.int32)
        in_range = (colv >= goff) & (colv < goff + span)
        colv = np.where(in_range, colv - goff + r[:, 8].astype(np.int32),
                        r[:, 4].astype(np.int32))
    thr = r[:, 2].astype(np.int32)
    cat = r[:, 3] > 0.5
    gl = np.where(cat, colv == thr, colv <= thr)
    gl = np.where(colv == r[:, 4].astype(np.int32), r[:, 5] > 0.5, gl)
    return np.where(active & ~gl, r[:, 6].astype(np.int32), leaf_id)




def test_auto_hist_mode_resolution(monkeypatch):
    """tpu_histogram_mode=auto picks the measured winner per backend:
    on TPU, when the wave engine will run it, pallas_ct for narrow
    shapes (ncols * bin_pad <= 2048) and pallas_t for wider
    VMEM-feasible ones; onehot on TPU otherwise; scatter on CPU
    (tools/AB_RESULTS.md, tools/BENCH_SUITE.md higgs_ct)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.learner import SerialTreeLearner
    from lightgbm_tpu.io.dataset import TrainingData
    from lightgbm_tpu.utils.config import Config

    rng = np.random.default_rng(3)
    X = rng.normal(size=(600, 5))
    y = (X[:, 0] > 0).astype(np.float64)

    def learner_for(**over):
        cfg = Config(dict({"objective": "binary", "num_leaves": 7,
                           "verbose": -1}, **over))
        td = TrainingData.from_matrix(X, label=y, config=cfg)
        return SerialTreeLearner(cfg, td)

    # CPU truth (this process): scatter; auto precision stays hi/lo
    # off-TPU (no pallas kernel ever runs the bf16 product there)
    assert learner_for().hist_mode == "scatter"
    assert learner_for().hist_hilo is True

    # tpu_hist_precision is validated unconditionally (like
    # tpu_histogram_mode); bf16 resolves the kernels' hilo flag off
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        learner_for(tpu_hist_precision="f64")
    assert learner_for(tpu_hist_precision="bf16").hist_hilo is False
    assert learner_for(tpu_hist_precision="hilo").hist_hilo is True

    # fake the TPU backend: resolution must flip to pallas_t / onehot.
    # Clear the wave-core caches before AND after — cores built under the
    # fake bake use_pallas_hist=True into lru_cache entries whose static
    # keys later CPU tests could hit (dispatching real Pallas on CPU).
    from lightgbm_tpu.ops.wave import make_wave_core, make_wave_jit
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # narrow-F under the fused-kernel bound (5 cols * 256-pad = 1280
    # <= 2048): the round-4 promoted pallas_ct (measured winner at
    # 10.5M x 28 and 1M x 28 — learner.py auto block)
    assert learner_for().hist_mode == "pallas_ct"
    assert learner_for(tpu_growth="exact").hist_mode == "onehot"
    # round-5 promoted auto precision (BENCH_NOTES.md "Armed decks"):
    # auto -> single-bf16-product where the pallas wave kernel runs;
    # exact growth (parity anchor) and explicit hilo stay hi/lo.  A
    # refactor reverting the auto resolution must fail here, not ship
    # a silent 1.63x flagship slowdown.
    assert learner_for().hist_hilo is False
    assert learner_for(tpu_growth="exact").hist_hilo is True
    assert learner_for(tpu_hist_precision="hilo").hist_hilo is True
    # ...and scoped to serial execution: the DP learner (psum_axis set)
    # keeps hi/lo — every bf16 gate was a single-chip serial arm
    cfg_dp = Config({"objective": "binary", "num_leaves": 7,
                     "verbose": -1})
    td_dp = TrainingData.from_matrix(X, label=y, config=cfg_dp)
    assert SerialTreeLearner(cfg_dp, td_dp,
                             psum_axis="d").hist_hilo is True
    # inside the round-5 widened fused-kernel bound (40 cols * 64-pad =
    # 2560 <= 8768): ct measured a 15% win at exactly this shape
    # (tools/BENCH_SUITE.md expo_ct 4.07 vs expo_cat 3.53 it/s)
    Xm = rng.normal(size=(600, 40))
    ym = (Xm[:, 0] > 0).astype(np.float64)
    cfgm = Config({"objective": "binary", "num_leaves": 7,
                   "max_bin": 63, "verbose": -1})
    tdm = TrainingData.from_matrix(Xm, label=ym, config=cfgm)
    assert SerialTreeLearner(cfgm, tdm).hist_mode == "pallas_ct"
    # past the bound but inside the VMEM gate: pallas_t stays (200 cols
    # * 64-pad = 12800 > 8768 — epsilon-class wide-F measured ct 5.6x
    # SLOWER, tools/BENCH_SUITE.md epsilon_ct)
    Xm2 = rng.normal(size=(600, 200))
    ym2 = (Xm2[:, 0] > 0).astype(np.float64)
    tdm2 = TrainingData.from_matrix(Xm2, label=ym2, config=cfgm)
    assert SerialTreeLearner(cfgm, tdm2).hist_mode == "pallas_t"
    assert learner_for(tpu_use_dp=True).hist_mode == "onehot"
    sp = learner_for(tpu_sparse=True)
    assert sp.hist_mode == "sparse"    # sparse store keeps its own path
    assert learner_for(tree_learner="voting").hist_mode == "onehot"

    # VMEM feasibility: a wide/high-bin shape whose in-VMEM histogram
    # block (ncols * bin_pad * 3W * 4B) exceeds the kernels' budget must
    # keep the HBM-streaming onehot engine (800 cols * 256-pad * 3 * 64
    # * 4B ~= 157 MB > 64 MB; 700 rows bin to >128 levels -> pad 256)
    Xw = rng.normal(size=(700, 800))
    yw = (Xw[:, 0] > 0).astype(np.float64)
    cfg = Config({"objective": "binary", "num_leaves": 255,
                  "max_bin": 255, "tpu_wave_width": 64, "verbose": -1})
    tdw = TrainingData.from_matrix(Xw, label=yw, config=cfg)
    assert SerialTreeLearner(cfg, tdw).hist_mode == "onehot"
    # wipe cores built under the fake before later CPU tests can hit them
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()


def test_with_xt_grow_signature_matches():
    """make_wave_grow_fn(with_xt=True) takes Xt positionally and produces
    the identical tree off-TPU (where the kernel is bypassed and Xt is
    ignored) — the mesh learner's per-booster-Xt plumbing contract."""
    from lightgbm_tpu.ops.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.learner import build_split_params
    from lightgbm_tpu.ops.split_finder import FeatureMeta
    from lightgbm_tpu.io.dataset import TrainingData
    from lightgbm_tpu.utils.config import Config
    import jax

    rng = np.random.default_rng(21)
    X = rng.normal(size=(900, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config({"num_leaves": 15, "verbose": -1})
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    meta = FeatureMeta(num_bin=jnp.asarray(td.num_bin_arr),
                       default_bin=jnp.asarray(td.default_bin_arr),
                       is_categorical=jnp.asarray(td.is_categorical_arr))
    common = dict(wave_width=4, hist_mode="pallas_t")
    g = (0.5 - y).astype(np.float32)
    h = np.full(len(y), 0.25, np.float32)
    args = (jnp.asarray(td.binned), jnp.asarray(g), jnp.asarray(h),
            jnp.ones(len(y), jnp.float32),
            jnp.ones(td.num_features, dtype=bool))
    grow0 = make_wave_grow_fn(15, int(td.num_bin_arr.max()), meta,
                              build_split_params(cfg), -1, **common)
    grow1 = make_wave_grow_fn(15, int(td.num_bin_arr.max()), meta,
                              build_split_params(cfg), -1, with_xt=True,
                              **common)
    t0, l0 = grow0(*args)
    t1, l1 = grow1(*args, jnp.transpose(args[0]))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    np.testing.assert_array_equal(np.asarray(t0.leaf_value),
                                  np.asarray(t1.leaf_value))


def test_mesh_precomputes_xt_for_transposed_kernels(monkeypatch):
    """Under the data mesh with a transposed pallas mode on (a faked) TPU
    backend, the learner materializes the (F, N) transposed matrix ONCE
    per booster with a column sharding — not per tree inside the grow."""
    import jax
    from lightgbm_tpu.parallel.mesh import (DataParallelTreeLearner,
                                            make_data_mesh)
    from lightgbm_tpu.io.dataset import TrainingData
    from lightgbm_tpu.utils.config import Config

    rng = np.random.default_rng(22)
    X = rng.normal(size=(1100, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config({"num_leaves": 15, "verbose": -1, "tree_learner": "data",
                  "tpu_histogram_mode": "pallas_t"})
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    mesh = make_data_mesh(jax.devices()[:4])

    from lightgbm_tpu.ops.wave import make_wave_core, make_wave_jit
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp = DataParallelTreeLearner(cfg, td, mesh)
    assert dp._Xt is not None
    n_pad = dp.X.shape[0]
    assert dp._Xt.shape == (td.binned.shape[1], n_pad)
    # column-sharded: each device holds the transpose of its row shard
    spec = dp._Xt.sharding.spec
    assert tuple(spec) == (None, "data")
    # and the grow program TRACES under shard_map with the varying-axes
    # check on: each pallas_call declares how its outputs vary over the
    # mesh (ops/grow.py vma_struct).  Off-TPU the mesh path runs the XLA
    # engine, so only a faked backend reaches the kernels' out_shape.
    jaxpr = jax.make_jaxpr(dp._grow)(*dp.grow_args(
        dp._ones, dp._ones, dp._ones, dp.sample_feature_mask()))
    assert "pallas_call" in str(jaxpr)

    # off-TPU (real backend): no Xt is pinned.  Cache-clear first: the
    # faked-backend cores above share static keys with real-CPU ones.
    monkeypatch.undo()
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    dp2 = DataParallelTreeLearner(cfg, td, mesh)
    assert dp2._Xt is None


def test_tile_plan_block_legality():
    """Pallas TPU block rule: the row-tile c (the transposed kernels'
    LANES dim) must be a multiple of 128 unless it equals the padded
    array dim (c == n fallthrough).  fc=2000 (epsilon's width) caught
    the un-aligned 2096 tile on chip."""
    from lightgbm_tpu.ops.pallas_wave import _tile_plan, _bin_pad
    for fc in (28, 137, 968, 2000):
        for n in (513, 8192, 999424, 2_270_000):
            for row_tile in (1000, 8192):     # non-128-multiple too
                bsub, c = _tile_plan(n, fc, _bin_pad(64), row_tile)
                assert c % 128 == 0 or c == n, (fc, n, bsub, c)
                assert _bin_pad(64) % bsub == 0


def test_kernel_wide_shape_epsilon_width():
    """F=2000 (epsilon's width): the widest headline shape runs the
    transposed kernel end-to-end in interpret mode.  Pins the wide-fc
    tile plan + output reshape path that the on-chip epsilon failure
    exposed (tools/ab_err_suite_epsilon.log); auto resolves epsilon to
    pallas_t (49 MB hist block < the 64 MB gate), so this is the shape's
    production kernel."""
    rng = np.random.default_rng(0)
    n, f, b, k = 512, 2000, 63, 8
    X = rng.integers(0, b, size=(n, f), dtype=np.uint8)
    lid = rng.integers(0, 16, size=n).astype(np.int32)
    w3 = rng.normal(size=(n, 3)).astype(np.float32)
    cid = np.arange(k, dtype=np.int32)
    cid[5] = -1
    got = wave_histogram_pallas_t(jnp.asarray(X.T), jnp.asarray(lid),
                                  jnp.asarray(w3), jnp.asarray(cid), b,
                                  interpret=True)
    want = np.array(wave_histogram_reference(
        jnp.asarray(X), jnp.asarray(lid), jnp.asarray(w3),
        jnp.asarray(cid), b))
    want[cid < 0] = 0
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-4,
                               atol=5e-4)


def _compact_from_tbl(tbl, w):
    """(cols (W,10), psrc (W,)) compact operands from a dense (L,10)
    table — active rows scatter into slots, the rest get psrc=-3."""
    act = [l for l in range(len(tbl)) if tbl[l, 0] > 0.5]
    cols = np.zeros((w, 10), np.float32)
    psrc = np.full(w, -3, np.int32)
    for j, l in enumerate(act):
        cols[j] = tbl[l]
        psrc[j] = l
    return cols, psrc


@pytest.mark.parametrize("packed", [False, True])
def test_fused_compact_kernel_matches_oracle(packed):
    from lightgbm_tpu.ops.pallas_wave import wave_partition_hist_pallas_ct

    X, leaf_id, w3, cid, b = _data(n=2500, f=9 if packed else 7,
                                   b=15 if packed else 14, k=5, seed=9)
    L = 16
    rng = np.random.default_rng(10)
    leaf_id = rng.integers(0, 8, size=len(X)).astype(np.int32)
    tbl = np.zeros((L, 10), np.float32)
    for leaf in (1, 3, 5):
        tbl[leaf] = [1, rng.integers(0, X.shape[1]), rng.integers(0, b),
                     0, 0, rng.integers(0, 2), 8 + leaf, 0, 0, 0]
    cols, psrc = _compact_from_tbl(tbl, w=5)

    want_lid = _route_numpy(X, leaf_id, tbl)
    want_hist = np.array(wave_histogram_reference(
        jnp.asarray(X), jnp.asarray(want_lid), jnp.asarray(w3),
        jnp.asarray(cid), b))
    want_hist[np.asarray(cid) < 0] = 0.0

    if packed:
        Xdev = pack4_host(X).T
        lc = X.shape[1]
    else:
        Xdev, lc = X.T, 0
    got_lid, got_hist = wave_partition_hist_pallas_ct(
        jnp.asarray(Xdev), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc), b,
        interpret=True, logical_cols=lc)
    np.testing.assert_array_equal(np.asarray(got_lid), want_lid)
    np.testing.assert_allclose(np.asarray(got_hist), want_hist,
                               rtol=5e-4, atol=5e-4)


def test_fused_compact_kernel_bundled_remap():
    """The ct kernel's bundled branch (group offset / bin adjust / span
    remap) routes identically to the numpy oracle — nonzero goff/adj/span
    rows exercised, including out-of-range -> default-bin redirect."""
    from lightgbm_tpu.ops.pallas_wave import wave_partition_hist_pallas_ct

    X, leaf_id, w3, cid, b = _data(n=2200, f=7, b=14, k=5, seed=15)
    rng = np.random.default_rng(16)
    leaf_id = rng.integers(0, 8, size=len(X)).astype(np.int32)
    tbl = np.zeros((16, 10), np.float32)
    # leaf 2: group column 3, bins [4, 4+6) remap to adj 1, default bin 2
    tbl[2] = [1, 3, 5, 0, 2, 1, 10, 4, 1, 6]
    # leaf 5: group column 1, bins [0, 5), adj 0, default-right
    tbl[5] = [1, 1, 2, 0, 7, 0, 13, 0, 0, 5]
    cols, psrc = _compact_from_tbl(tbl, w=5)
    want_lid = _route_numpy(X, leaf_id, tbl, bundled=True)
    got_lid, _ = wave_partition_hist_pallas_ct(
        jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc), b,
        bundled=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_lid), want_lid)


# Expo's ten EFB groups (benchmark/configs/expo_700.json, `assumed`), and
# a seed whose two rare-level groups read 62 and 56: one layout for both
EXPO_BINS = (13, 32, 8, 23, 256, 59, 256, 59, 63, 63)
EXPO_PADS = (32, 32, 32, 32, 256, 64, 256, 64, 64, 64)


def _ragged_store(bins, n, seed):
    rng = np.random.default_rng(seed)
    X = np.stack([rng.integers(0, b, size=n) for b in bins],
                 axis=1).astype(np.uint8)
    leaf_id = rng.integers(0, 8, size=n).astype(np.int32)
    w3 = rng.normal(size=(n, 3)).astype(np.float32)
    return X, leaf_id, w3


@pytest.mark.parametrize("bins", [
    EXPO_BINS, (13, 32, 8, 23, 256, 62, 256, 56, 63, 63)],
    ids=["expo", "rare-62-56"])
@pytest.mark.parametrize("hilo", [False, True], ids=["bf16", "hilo"])
def test_ragged_kernel_is_the_uniform_kernel_on_each_groups_own_bins(bins,
                                                                     hilo):
    """A bundled store's groups hold 8 to 256 bins.  With the columns'
    own widths (ops/wave.py col_bin_pads) the fused kernel multiplies
    832 bins against 896 one-hot rows where the uniform pad has 2,560:
    the same leaf ids, the same histograms (the row tiles are the
    uniform kernel's), and zeros in the bins a group does not have."""
    from lightgbm_tpu.ops.pallas_wave import (_ragged_blocks,
                                              wave_partition_hist_pallas_ct)
    from lightgbm_tpu.ops.wave import col_bin_pads

    pads = col_bin_pads(bins, 256)
    assert pads == EXPO_PADS and sum(pads) == 896
    blocks = _ragged_blocks(pads)
    assert [len(b) * 32 for b in blocks] == [448, 448]
    assert sorted(s for b in blocks for s in b) == [
        (j, b0) for j, p in enumerate(pads) for b0 in range(0, p, 32)]

    X, leaf_id, w3 = _ragged_store(bins, n=2300, seed=36)
    cid = np.array([1, 9, -1, 5, 12], np.int32)
    tbl = np.zeros((16, 10), np.float32)
    tbl[2] = [1, 4, 100, 0, 0, 1, 9, 0, 1, 256]    # a wide group's bin
    tbl[6] = [1, 0, 5, 0, 0, 0, 12, 1, 1, 12]      # a narrow group's
    cols, psrc = _compact_from_tbl(tbl, w=5)
    args = (jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc), 256)
    want_lid, want = wave_partition_hist_pallas_ct(
        *args, bundled=True, interpret=True, hilo=hilo)
    got_lid, got = wave_partition_hist_pallas_ct(
        *args, bundled=True, interpret=True, hilo=hilo, col_pads=pads)
    np.testing.assert_array_equal(np.asarray(got_lid), np.asarray(want_lid))
    assert got.shape == want.shape == (5, 10, 256, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)
    for j, b in enumerate(bins):
        assert not np.asarray(got)[:, j, b:].any()
    assert np.asarray(got)[0].any()


def test_uniform_store_takes_the_uniform_kernel():
    """Where every column's width is the uniform pad (28 x 63 bins: the
    narrow cell; any store of up to 64 bins whose columns pass 32) the
    layout is () and the call is the program it was: the same jaxpr,
    and so the same Mosaic kernel and compile-cache key."""
    import jax
    from lightgbm_tpu.ops.pallas_wave import wave_partition_hist_pallas_ct
    from lightgbm_tpu.ops.wave import col_bin_pads

    assert col_bin_pads([63] * 28, 63) == ()
    assert col_bin_pads([40, 63, 33], 63) == ()
    assert col_bin_pads([255] * 4, 255) == ()
    assert col_bin_pads([2, 63], 63) == (32, 64)
    X, leaf_id, w3, cid, b = _data(n=1500, f=28, b=63, k=5, seed=3)
    cols, psrc = np.zeros((5, 10), np.float32), np.full(5, -3, np.int32)
    args = (jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc))
    before = jax.make_jaxpr(
        lambda *a: wave_partition_hist_pallas_ct(*a, b))(*args)
    after = jax.make_jaxpr(lambda *a: wave_partition_hist_pallas_ct(
        *a, b, col_pads=col_bin_pads([63] * 28, 63)))(*args)
    assert str(before) == str(after)
    assert "f32[1792,15]" in str(after)      # the (28 x 64, 3K) block


@pytest.mark.parametrize("hilo", [False, True], ids=["bf16", "hilo"])
@pytest.mark.parametrize("chunk", [16384, 512], ids=["one-chunk", "chunks"])
def test_ragged_root_pass_matches_scatter(hilo, chunk):
    """The root's one-hot pass by width class (ops/histogram.py
    `_onehot_accumulate`, col_pads): each class's columns taken from the
    chunk, contracted against the class's own width and written back in
    column order, against the segment-sum histogram."""
    from lightgbm_tpu.ops.histogram import (leaf_histogram_onehot,
                                            leaf_histogram_scatter)
    X, leaf_id, w3 = _ragged_store(EXPO_BINS, n=1800, seed=37)
    args = (jnp.asarray(X), jnp.asarray(w3[:, 0]),
            jnp.abs(jnp.asarray(w3[:, 1])), jnp.asarray(leaf_id % 2), 0,
            jnp.ones(len(X), jnp.float32))
    want = leaf_histogram_scatter(*args, num_bins=256)
    got = leaf_histogram_onehot(*args, num_bins=256, chunk=chunk, hilo=hilo,
                                col_pads=EXPO_PADS)
    assert got.shape == (10, 256, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-4)
    uniform = leaf_histogram_onehot(*args, num_bins=256, chunk=chunk,
                                    hilo=hilo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(uniform),
                               rtol=1e-6, atol=1e-5)


def test_kernel_hist_w_invariant_per_child():
    """Per-child histogram sums are independent of the wave width K:
    child c's (F, B, 3) block is bitwise identical whether the kernel
    runs with K=1 or c embedded in a K=5 slot set — each child owns its
    own output columns and tiles accumulate in the same order.  This is
    the structural property behind exact-order waves keeping the W
    ladder on TPU (tpu_wave_order=exact + pallas kernels)."""
    from lightgbm_tpu.ops.pallas_wave import (wave_histogram_pallas_t,
                                              wave_partition_hist_pallas_ct)
    X, leaf_id, w3, cid, b = _data(n=3100, f=7, b=14, k=5, seed=21)
    wide = np.asarray(wave_histogram_pallas_t(
        jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), b, interpret=True))
    for j, c in enumerate(cid):
        if c < 0:
            continue
        solo = np.asarray(wave_histogram_pallas_t(
            jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(np.array([c], np.int32)), b, interpret=True))
        np.testing.assert_array_equal(solo[0], wide[j])

    # same property for the fused kernel (empty split table: routing is
    # the identity, so the hist half sees the same leaf ids)
    cols = np.zeros((5, 10), np.float32)
    psrc = np.full(5, -3, np.int32)
    _, wide_ct = wave_partition_hist_pallas_ct(
        jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
        jnp.asarray(cid), jnp.asarray(cols), jnp.asarray(psrc), b,
        interpret=True)
    wide_ct = np.asarray(wide_ct)
    for j, c in enumerate(cid):
        if c < 0:
            continue
        _, solo_ct = wave_partition_hist_pallas_ct(
            jnp.asarray(X.T), jnp.asarray(leaf_id), jnp.asarray(w3),
            jnp.asarray(np.array([c], np.int32)),
            jnp.asarray(cols[:1]), jnp.asarray(psrc[:1]), b,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(solo_ct)[0], wide_ct[j])
