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


# ---------------------------------------------------------------------------
# From a binned directory, over four of the devices: the deployment of the
# benchmark's epsilon_2000_dp4 cell, tiny.  The directory's files end at
# rows 1700, 3400, 5100 and 6800; a device's rows end at 2048, 4096 and 6144.

DP4_ROWS, DP4_COLS, DP4_SHARD_ROWS = 7003, 12, 1700
DP4 = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
       "min_data_in_leaf": 5, "learning_rate": 0.1, "verbose": -1,
       "tpu_growth": "wave", "tpu_wave_width": 4}


@pytest.fixture(scope="module")
def dp4_dir(tmp_path_factory):
    from lightgbm_tpu.io import binned_format
    rng = np.random.default_rng(7)
    X = rng.normal(size=(DP4_ROWS, DP4_COLS))
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2]
         + 0.1 * rng.normal(size=DP4_ROWS) > 0).astype(np.float64)
    path = str(tmp_path_factory.mktemp("dp4") / "d")
    ds = lgb.Dataset(X, label=y, params=dict(DP4)).construct()
    binned_format.save_training_data(ds._handle, path,
                                     shard_rows=DP4_SHARD_ROWS)
    return path


@pytest.fixture
def four_devices(monkeypatch):
    """`tree_learner=data` through the public path takes every device;
    hand it the first four."""
    from lightgbm_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "make_data_mesh",
                        lambda devices=None: make_data_mesh(
                            jax.devices()[:4]))


def _booster(path, **extra):
    params = dict(DP4, **extra)
    ds = lgb.Dataset.from_binned(path, params=dict(params))
    ds.construct()
    return lgb.Booster(dict(params), ds), ds


def _tree_records():
    from lightgbm_tpu.obs import timers
    return [r["fields"] for r in timers.snapshot()
            if r["kind"] == "count" and r["name"] == "tree"]


def _lowered_by_each_update(bst, updates=3):
    """The functions each of `updates` steps of `bst` lowered, by name."""
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **kw: lowered.append(kw.get("fun_name"))
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration"
        else None)
    per_update = []
    for _ in range(updates):
        before = len(lowered)
        bst.update()
        per_update.append(lowered[before:])
    return per_update


def test_sharded_upload_gives_each_device_its_rows_and_no_others(
        dp4_dir, four_devices):
    from lightgbm_tpu.obs import timers
    timers.clear()
    bst, ds = _booster(dp4_dir, tree_learner="data")
    lrn = bst._gbdt.learner
    assert type(lrn) is DataParallelTreeLearner
    assert lrn.mesh.devices.size == 4
    # the host never built the matrix
    assert ds._handle._binned is None
    local = 2048                    # 7003 rows to 4 x 1024 a device
    assert lrn.X.shape == (4 * local, DP4_COLS) and lrn._pad == 8192 - 7003
    reader = ds._handle._binned_reader
    whole = np.asarray(reader.matrix())
    shards = sorted(lrn.X.addressable_shards, key=lambda s: s.index[0].start)
    assert [s.device for s in shards] == list(lrn.mesh.devices.flat)
    for k, s in enumerate(shards):
        have = np.asarray(s.data)
        real = whole[k * local:(k + 1) * local]
        np.testing.assert_array_equal(have[:len(real)], real)
        assert not have[len(real):].any()       # the tail's pad rows
    # one upload_shard span a page, each naming its device
    pages = [r["ids"] for r in timers.snapshot()
             if r["kind"] == "span" and r["name"] == "upload_shard"]
    assert sorted(p["device"] for p in pages) == [0, 0, 1, 1, 2, 2, 3, 3]
    assert bst._gbdt._score_dev.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(lrn.mesh, jax.sharding.PartitionSpec()),
        2)                          # 7003 rows do not divide by four


def test_shards_root_histograms_add_up_to_the_whole_tables(
        dp4_dir, four_devices):
    """The share tied to the whole: the four local root histograms sum to
    the whole table's, every real row counted once and no pad row."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import leaf_histogram_onehot
    bst, ds = _booster(dp4_dir, tree_learner="data")
    lrn = bst._gbdt.learner
    rng = np.random.default_rng(3)
    g = rng.normal(size=lrn.X.shape[0]).astype(np.float32)
    h = rng.uniform(0.1, 0.3, size=lrn.X.shape[0]).astype(np.float32)
    ones = np.asarray(lrn._ones)

    def hist(x, lo, hi):
        return np.asarray(leaf_histogram_onehot(
            jnp.asarray(x), jnp.asarray(g[lo:hi]), jnp.asarray(h[lo:hi]),
            jnp.zeros(hi - lo, jnp.int32), 0, jnp.asarray(ones[lo:hi]),
            num_bins=lrn.num_bins), np.float64)

    parts = [hist(np.asarray(s.data), s.index[0].start, s.index[0].stop)
             for s in lrn.X.addressable_shards]
    whole = hist(np.asarray(ds._handle._binned_reader.matrix()), 0, DP4_ROWS)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-4)
    assert sum(p[0, :, 2].sum() for p in parts) == DP4_ROWS


def test_mesh_booster_grows_the_serial_trees_once_lowered_and_counted(
        dp4_dir, four_devices):
    from lightgbm_tpu.obs import timers
    timers.clear()
    timers._scopes.clear()
    dp, _ = _booster(dp4_dir, tree_learner="data")
    per_update = _lowered_by_each_update(dp)
    lrn = dp._gbdt.learner
    # one lowering of the grow program a booster, and nothing at all is
    # lowered after the first update
    assert per_update[0].count("jit(grow)") == 1 and len(lrn._compiled) == 1
    assert per_update[1] == [] == per_update[2]
    table = timers.device_scopes()["jit_grow"]
    assert {"hist_allreduce", "wave_histogram", "split_search",
            "wave_partition", "root_histogram"} <= set(table.values())
    # a `dispatch` span closes inside every iteration
    spans = [r for r in timers.snapshot() if r["kind"] == "span"]
    its = {r["seq"] for r in spans if r["name"] == "iteration"}
    assert len(its) == 3
    assert [r["cause"] in its for r in spans
            if r["name"] == "dispatch"] == [True] * 3
    dp._gbdt._materialize()
    mesh_records = _tree_records()
    # one `mesh_memory` record a batch of trees, a number a device (the
    # CPU's allocator keeps none: zeros)
    memory = [r["fields"]["peak_bytes_in_use"] for r in timers.snapshot()
              if r["kind"] == "count" and r["name"] == "mesh_memory"]
    assert memory == [[0, 0, 0, 0]]

    timers.clear()
    serial, _ = _booster(dp4_dir, tree_learner="serial")
    for _ in range(3):
        serial.update()
    serial._gbdt._materialize()
    assert not [r for r in timers.snapshot() if r["name"] == "mesh_memory"]
    for td, ts in zip(dp._gbdt.models, serial._gbdt.models):
        nl = ts.num_leaves
        assert td.num_leaves == nl == 8
        for field in ("split_feature_inner", "threshold_in_bin",
                      "left_child", "right_child", "internal_count"):
            np.testing.assert_array_equal(getattr(td, field)[:nl - 1],
                                          getattr(ts, field)[:nl - 1])
        np.testing.assert_array_equal(td.leaf_count[:nl], ts.leaf_count[:nl])
        np.testing.assert_allclose(td.leaf_value[:nl], ts.leaf_value[:nl],
                                   rtol=1e-4)
    # 8 leaves at width 4 are three waves: 1, 2 and 4 splits.  One shard
    # hands over the root's three sums and (F, B, 3) histogram, then a
    # (W, F, B, 3) block a wave, in float32
    fb3 = DP4_COLS * lrn.num_bins * 3
    for rec, one in zip(mesh_records, _tree_records()):
        assert rec["waves"] == 3 == one["waves"]
        assert rec["shards"] == 4 and one["shards"] == 1
        assert rec["allreduce_bytes"] == 4 * (3 + fb3 + 3 * 4 * fb3)
        assert one["allreduce_bytes"] == 0
        # the record is the mesh's: every shard's rows, pad included
        assert rec["rows"] == 8192 and one["rows"] == 7 * 1024
        assert rec["rows_visited"] == 4 * 8192
        for key in ("committed", "slots", "hist_rows"):
            assert rec[key] == one[key]


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _primitives(jaxpr):
    """Names of every primitive in `jaxpr`, nested bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in _sub_jaxprs(eqn):
            yield from _primitives(sub)


def _loops_in_loops(jaxpr, depth=0):
    """The bodies of every `while` that sits inside another `while`."""
    for eqn in jaxpr.eqns:
        inner = depth + (eqn.primitive.name == "while")
        if inner == 2:
            yield eqn.params["body_jaxpr"].jaxpr
        for sub in _sub_jaxprs(eqn):
            yield from _loops_in_loops(sub, min(inner, 1))


def test_slab_loop_under_shard_map_holds_no_collective_and_lowers_once(
        dp4_dir, four_devices, monkeypatch):
    """The row slab on every shard of the mesh (the interpreted pallas_t
    kernel).  A shard's slab loop runs as often as ITS rows of the wave's
    children need, so shards differ in trips: legal only while the loop
    holds no collective.  In the traced program the loop that launches
    the kernel holds none; in the compiled one every all-reduce sits
    under `hist_allreduce`, outside any inner loop; and the program is
    still lowered once a booster."""
    from lightgbm_tpu.obs import timers
    timers.clear()
    calls = []
    ahead = timers.scoped_executable
    monkeypatch.setattr(timers, "scoped_executable", lambda fn, cache, args: (
        calls.append((fn, args)), ahead(fn, cache, args))[1])
    dp, _ = _booster(dp4_dir, tree_learner="data",
                     tpu_histogram_mode="pallas_t",
                     tpu_pallas_interpret=True)
    lrn = dp._gbdt.learner
    assert type(lrn) is DataParallelTreeLearner and lrn.wave_compact
    per_update = _lowered_by_each_update(dp)
    assert per_update[0].count("jit(grow)") == 1 and len(lrn._compiled) == 1
    assert per_update[1] == [] == per_update[2]
    dp._gbdt._materialize()
    for rec in _tree_records():
        assert rec["compacted"] == rec["waves"] == 3
        assert rec["rows_visited"] == rec["rows"] + rec["kernel_rows"]
        assert rec["kernel_rows"] < 3 * rec["rows"]

    grow, args = calls[0]
    slab_loops = [body for body in _loops_in_loops(grow.trace(*args).jaxpr)
                  if "pallas_call" in set(_primitives(body))]
    assert len(slab_loops) == 1
    inside = set(_primitives(slab_loops[0]))
    assert "gather" in inside                 # the slab's rows, then the kernel
    assert not [p for p in inside if "psum" in p or p.startswith("all_")
                or p in ("pmax", "pmin", "ppermute")], sorted(inside)

    text, = [c.as_text() for c in lrn._compiled.values()]
    reduces = [line for line in text.splitlines()
               if " all-reduce(" in line or " all-reduce-start(" in line]
    assert reduces
    for line in reduces:
        op_name = line.split('op_name="')[1].split('"')[0]
        assert "hist_allreduce" in op_name, op_name
        assert "while/body/while" not in op_name, op_name
    # the slab's own loop is there, as a loop, inside the loop of waves
    assert "while/body/while/body/wave_compact" in text
