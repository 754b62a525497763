"""The cold compile does not follow the row count (PERF.md section 6,
PR 33), and a row count that is no multiple of any tile grows the trees
it always grew.

Before PR 33 the chunk loops of the root's pass and of the partition scan
ran over a (chunks, 16384, F) reshape of the bin matrix.  For a narrow
table the rows are the minor dimension of the matrix's device layout, the
reshape is a real transposition, and the TPU compiler's code for it grew
with the number of chunks unless that number was a multiple of 8: 20 us a
row, 255 s at Higgs' 10,500,000 rows against 4.6 s at 10,485,760.  The
loops now take windows of the matrix itself.  Compiled here for a
described v5e, no chip: a time of the compiler, not of the device.
"""
import time
import types

import numpy as np
import pytest

import lightgbm_tpu as lgb

HIGGS = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
         "learning_rate": 0.1, "min_data_in_leaf": 1,
         "min_sum_hessian_in_leaf": 100, "verbose": -1}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture
def as_tpu(monkeypatch):
    """`auto` resolves as on the chip, and the traces of another backend
    are dropped on the way in and out."""
    import jax
    from lightgbm_tpu.ops import wave

    def clear():
        wave.make_wave_core.cache_clear()
        wave.make_wave_jit.cache_clear()

    clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    clear()


def _compile_seconds(jitted, shapes):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jitted.lower(*shapes)
        t0 = time.time()
        compiled = lowered.compile()
        return time.time() - t0, compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _fused_step_seconds(bst, rows, one):
    """Compile seconds and optimised HLO of `bst`'s own fused step (its
    grow program, objective and score engine) at `rows` rows."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.fused_iter import FusedIteration

    gbdt = bst._gbdt
    lrn = gbdt.learner
    cols = int(lrn.X.shape[1])
    pad = (-rows) % 1024                      # as ops/learner.py pads
    stub = types.SimpleNamespace(_row_pad=pad, dtype=lrn.dtype,
                                 _grow=lrn._grow)
    fused = FusedIteration(stub, gbdt.objective, rows, gbdt._score_engine)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    n = rows + pad
    args = (shape((n, cols), jnp.uint8),
            shape((cols, n), jnp.uint8) if lrn._Xt is not None else None,
            [shape((rows,) + a.shape[1:], a.dtype)
             for a in fused._obj_arrays],
            shape((rows,), jnp.float32), shape((n,), jnp.float32),
            shape((cols,), jnp.bool_), shape((), jnp.float32))
    return _compile_seconds(fused._step, args)


def test_fused_pallas_ct_step_compiles_as_fast_at_any_row_count(
        topo, as_tpu):
    """The cell's own step (28 columns, 255 leaves, pallas_ct, W=32, the
    Pallas score update) at 1,000,000 rows (1,000,448 once the upload
    has padded them), whose 62 chunks of 16,384 do not come in eights,
    against 1,048,576: the parent took about 20 s against 4.5 s here."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    rng = np.random.default_rng(0)
    X = rng.integers(0, 63, size=(2048, 28)).astype(np.float32)
    y = (rng.random(2048) > 0.5).astype(np.float32)
    bst = lgb.Booster(dict(HIGGS), lgb.Dataset(X, label=y,
                                               params=dict(HIGGS)))
    plan = bst._gbdt.learner.plan
    assert (plan.hist_mode, plan.wave_width, plan.slab) == \
        ("pallas_ct", 32, False)
    odd, text = _fused_step_seconds(bst, 1_000_000, one)
    even, _ = _fused_step_seconds(bst, 1_048_576, one)
    assert odd < 1.5 * even and odd < 30 and even < 30, (odd, even)
    # both kernels inside the one step, each under its scope
    from lightgbm_tpu.obs import timers
    _, table = timers.scope_table(text)
    scopes = {name.rsplit(".", 1)[0]: scope for name, scope in table.items()
              if name.startswith(("wave_partition_hist", "score_update"))}
    assert scopes == {"wave_partition_hist_pallas_ct": "wave_histogram",
                      "score_update_pallas": "score_update"}
    assert {"gradients", "root_histogram", "split_search",
            "tree_commit"} <= set(table.values())
    # no transposed copy of the bin matrix by chunks is left
    assert "u8[62,16384,28]" not in text


@pytest.mark.parametrize("bins,acc_rows", [
    ((13, 32, 8, 23, 256, 59, 256, 59, 63, 63), 896),   # two blocks of 448
    ((256, 256, 256, 256, 40, 3), 1120),    # blocks of 384, 384 and 352
], ids=["expo", "a-shorter-last-block"])
def test_the_ragged_fused_kernel_and_root_compile_for_a_v5e(topo, bins,
                                                            acc_rows):
    """The bundled cell's two one-hot passes at its ten groups' own
    widths (PR 36): Mosaic takes the ragged walk's sublane broadcasts,
    its row slices of the accumulator and the (896, 3K) block, and XLA
    the root's width classes, for a described v5e at 8,388,608 rows
    (interpret mode shows neither: a walk that sliced one iota for its
    shorter last block passed it and crashed the compiler)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops.histogram import leaf_histogram_onehot
    from lightgbm_tpu.ops.pallas_wave import wave_partition_hist_pallas_ct
    from lightgbm_tpu.ops.wave import col_bin_pads

    one = SingleDeviceSharding(topo.devices[0])
    pads = col_bin_pads(bins, 256)
    n, w, f = 8_388_608, 32, len(bins)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    def launch(xt, lid, g, h, cid, cols, psrc):
        w3 = jnp.stack([g, h, jnp.ones_like(g)], axis=-1)
        return wave_partition_hist_pallas_ct(
            xt, lid, w3, cid, cols, psrc, 256, bundled=True, hilo=False,
            col_pads=pads)

    _, text = _compile_seconds(jax.jit(launch), (
        shape((f, n), jnp.uint8), shape((n,), jnp.int32),
        shape((n,), jnp.float32), shape((n,), jnp.float32),
        shape((w,), jnp.int32), shape((w, 10), jnp.float32),
        shape((w,), jnp.int32)))
    assert "wave_partition_hist_pallas_ct" in text
    assert "f32[%d,96]" % acc_rows in text
    assert "f32[%d,96]" % (f * 256) not in text

    def root(x, lid, g, h):
        return leaf_histogram_onehot(x, g, h, lid, 0, None, num_bins=256,
                                     col_pads=pads)

    _, text = _compile_seconds(jax.jit(root), (
        shape((n, f), jnp.uint8), shape((n,), jnp.int32),
        shape((n,), jnp.float32), shape((n,), jnp.float32)))
    assert "f32[%d,256,3]" % f in text


@pytest.mark.parametrize("cols", [28, 64])
def test_pallas_t_grow_program_compiles_as_fast_at_any_row_count(
        topo, as_tpu, cols):
    """The other kernel's grow program (partition scan, `pallas_t` over
    every row, the root's pass): narrow, where the parent's partition
    scan had the same transposed copy (about 12 s against 3 s here), and
    at a small wide shape, where the rows are the major dimension and
    there never was one.  Without the row slab: its five-operand sort
    alone takes the TPU compiler 40 s at any size, which would drown what
    this test looks for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops.split_finder import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave import make_wave_core

    one = SingleDeviceSharding(topo.devices[0])
    core = make_wave_core(31, 63, SplitParams(0.0, 0.0, 0.0, 1.0, 100.0,
                                              True), -1, 8, jnp.float32,
                          None, False, 0, True, "pallas_t", 16384, 0, 0,
                          False, "compact", False, False, False)

    def grow(X, Xt, g, h, rm, mask, num_bin, default_bin, is_cat):
        return core(X, g, h, rm, mask,
                    FeatureMeta(num_bin, default_bin, is_cat), None, Xt=Xt)

    def seconds(n):
        def shape(s, d):
            return jax.ShapeDtypeStruct(s, d, sharding=one)

        return _compile_seconds(jax.jit(grow), (
            shape((n, cols), jnp.uint8), shape((cols, n), jnp.uint8),
            shape((n,), jnp.float32), shape((n,), jnp.float32),
            shape((n,), jnp.float32), shape((cols,), jnp.bool_),
            shape((cols,), jnp.int32), shape((cols,), jnp.int32),
            shape((cols,), jnp.bool_)))

    # 489 x 1024 rows: 31 chunks, the last cut; against 32 whole ones
    odd, text = seconds(500_736 if cols == 28 else 100_352)
    even, _ = seconds(524_288 if cols == 28 else 131_072)
    # (the kernel alone takes Mosaic 7 s at 64 columns: a looser ceiling)
    assert odd < 1.5 * even and odd < 60 and even < 60, (odd, even)
    assert "%wave_histogram_pallas_t" in text


# ------------------------------------------------ the same trees, to the bit

GOLDEN = {
    # crc32 of the first three trees' split columns, thresholds, leaf
    # counts and float32 leaf values, grown by the parent (commit a9fd9e3)
    # on the CPU from the data of `_golden_data`; see `_digest`
    "onehot": 0x3240E612,
    "pallas_t": 0xE4568E23,
    "pallas_ct": 0xF4DC1679,
}


def _golden_data():
    rng = np.random.default_rng(33)
    n, f = 20_011, 12               # 20,011 rows: no tile divides them
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return X, y


def _digest(bst):
    import zlib

    bst._gbdt._materialize()
    crc = 0
    for t in bst._gbdt.models[:3]:
        ni = t.num_leaves - 1
        for a in (t.split_feature_inner[:ni], t.threshold_in_bin[:ni],
                  t.leaf_count[:t.num_leaves]):
            crc = zlib.crc32(np.ascontiguousarray(a, np.int64), crc)
        crc = zlib.crc32(np.ascontiguousarray(
            t.leaf_value[:t.num_leaves], np.float32), crc)
    return crc


@pytest.mark.parametrize("mode", sorted(GOLDEN))
def test_a_row_count_no_tile_divides_grows_the_parents_trees(mode):
    """20,011 rows: 20,480 after the upload's pad, two chunks of 16,384
    in the chunk loops (the second cut), three tiles of 8,192 in the
    kernels.  The chunks' contents and their order are the parent's, so
    the sums are its sums to the bit."""
    X, y = _golden_data()
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 5, "verbose": -1, "tpu_growth": "wave",
              "tpu_wave_width": 8, "tpu_histogram_mode": mode,
              "tpu_pallas_interpret": mode != "onehot"}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=3)
    assert _digest(bst) == GOLDEN[mode]


# ------------------------------------------- leaf counts, from histograms

@pytest.mark.parametrize("extra", [
    {}, {"bagging_fraction": 0.6, "bagging_freq": 1},
    {"tree_learner": "data"}, {"tpu_wave_order": "exact"},
    {"tpu_histogram_mode": "pallas_ct", "tpu_pallas_interpret": True}],
    ids=["serial", "bagging", "data-parallel", "exact-order", "pallas_ct"])
def test_leaves_are_counted_as_the_rows_that_walk_to_them(extra):
    """The wave program counts a tree's leaves from the count channel of
    their cached histograms, bin by bin in int32 (ops/wave.py): a float32
    count of a node of 2^24 rows or more rounds to even, and the odd row
    rides `rest = total - accumulated` down to one leaf (on the chip at
    41,943,040 rows: 2-6 rows a run).  At any size a leaf's count is the
    rows (under bagging: the rows in the bag) that walk to it."""
    X, y = _golden_data()
    params = dict({"objective": "binary", "num_leaves": 31, "max_bin": 63,
                   "min_data_in_leaf": 5, "verbose": -1,
                   "tpu_growth": "wave", "tpu_wave_width": 8}, **extra)
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=3)
    gbdt = bst._gbdt
    in_bag = (np.ones(len(X)) if gbdt.row_mult is None
              else np.asarray(gbdt.row_mult, np.float64)[:len(X)])
    gbdt._materialize()
    tree = gbdt.models[-1]
    walked = np.bincount(bst.predict(X, pred_leaf=True)[:, -1],
                         weights=in_bag, minlength=tree.num_leaves)
    assert tree.num_leaves > 8 and in_bag.sum() > 0
    assert tree.leaf_count[:tree.num_leaves].tolist() == walked.tolist()


# ------------------------------------- the score's host mirror, by pieces

@pytest.mark.parametrize("n", [5, 1024, 1025, 3000, 4096])
def test_a_score_matrix_comes_to_the_host_by_pieces(n, monkeypatch):
    """`models/gbdt.py _host_float64`: a score matrix of more columns than
    one piece holds is fetched piece by piece (a 168 MB copy in one left
    every later dispatch of the process at 6 ms where it took 1.6, on the
    chip, PR 33); the last piece starts early enough to be whole, so all
    pieces are one program.  Whatever the count, the values are the
    matrix's."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt

    piece = 1024
    monkeypatch.setattr(gbdt, "_HOST_PIECE", piece)
    monkeypatch.setattr(gbdt, "_columns_from", jax.jit(
        lambda x, start: jax.lax.dynamic_slice_in_dim(x, start, piece,
                                                      axis=1)))
    x = jnp.arange(2 * n, dtype=jnp.float32).reshape(2, n) / 7
    got = gbdt._host_float64(x)
    assert got.dtype == np.float64 and got.shape == (2, n)
    assert np.array_equal(got, np.asarray(x, np.float64))
    if n > piece:
        assert gbdt._columns_from._cache_size() == 1


def test_the_multiclass_class_step_compiles_for_a_v5e(topo, as_tpu):
    """The multiclass cell's step: Covertype's 10 numerical
    columns and two one-hot blocks, which the program's EFB packs into 12
    group columns, seven classes, one grow body scanned over them, at the
    cell's 48,234,496 rows for a described v5e: both kernels inside the
    one program, each under its scope, and the softmax under
    `gradients`."""
    import copy
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.obs import timers
    from lightgbm_tpu.ops.fused_iter import FusedIteration

    one = SingleDeviceSharding(topo.devices[0])
    rng = np.random.default_rng(40)
    n0 = 4096
    levels = [rng.integers(0, k, n0) for k in (4, 40)]
    X = np.concatenate([rng.integers(0, 63, (n0, 10)).astype(np.float32)]
                       + [np.eye(k, dtype=np.float32)[v]
                          for k, v in zip((4, 40), levels)], axis=1)
    y = rng.integers(0, 7, n0).astype(np.float32)
    params = dict(HIGGS, objective="multiclass", num_class=7)
    bst = lgb.Booster(dict(params), lgb.Dataset(X, label=y,
                                                params=dict(params)))
    gbdt = bst._gbdt
    lrn = gbdt.learner
    assert (lrn.plan.hist_mode, lrn.plan.wave_width, lrn.plan.fused_wanted,
            int(lrn.X.shape[1])) == ("pallas_ct", 32, True, 12)
    rows = 48_234_496
    objective = copy.copy(gbdt.objective)
    objective.num_data = rows
    stub = types.SimpleNamespace(_row_pad=0, dtype=lrn.dtype,
                                 _grow=lrn._grow)
    fused = FusedIteration(stub, objective, rows, gbdt._score_engine,
                           range(7))

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    args = (shape((rows, 12), jnp.uint8), shape((12, rows), jnp.uint8),
            [shape((rows,), a.dtype) for a in fused._obj_arrays],
            shape((7, rows), jnp.float32), shape((rows,), jnp.float32),
            shape((7, 54), jnp.bool_), shape((), jnp.float32))
    seconds, text = _compile_seconds(fused._step, args)
    assert seconds < 60, seconds
    _, table = timers.scope_table(text)
    scopes = {name.rsplit(".", 1)[0]: scope for name, scope in table.items()
              if name.startswith(("wave_partition_hist", "score_update"))}
    assert scopes == {"wave_partition_hist_pallas_ct": "wave_histogram",
                      "score_update_pallas": "score_update"}
    assert {"gradients", "root_histogram", "split_search",
            "bundle_view"} <= set(table.values())
