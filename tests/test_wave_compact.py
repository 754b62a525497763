"""The wave's row slab (ops/wave.py slab_hist) against the full-N pass.

A wave keeps the histograms of its smaller children only.  On the split
pipeline (partition scan, then the pallas_t kernel) the rows of those
children are known when the kernel starts: they are gathered, in row
order, into one slab of N/2 rows, and the kernel stops at the slab's
last full tile (ops/pallas_wave.py, `n_active`).  Claims under test: the
same SPLIT STRUCTURE and the same ROW PARTITION as the full pass (a row
outside the slab matches no child, and the partition is untouched), with
bit-equal trees while the slab is one tile; across tiles the float
fields may drift by f32 ulps (the slab moves rows across tile
boundaries), pinned tiny.  When a smaller child by WEIGHTED count holds
over half the rows a second slab follows the first.

Runs the real engine end-to-end on the CPU through interpret-mode
kernels (make_wave_core's pallas_interpret static); `compact=False` is
the plumbing that grows the same tree without the slab.

Under a data mesh (`shards=4`: the program under shard_map over four of
the host's devices, as parallel/mesh.py builds it) the children are the
smaller ones by the SUMMED histograms and every shard gathers its own
rows of them: a shard may hold most of its rows there, or none.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.obs.timers import COUNTERS
from lightgbm_tpu.ops.grow import TreeArrays
from lightgbm_tpu.ops.learner import SerialTreeLearner, build_split_params
from lightgbm_tpu.ops.pallas_wave import (slab_chunk, slab_plan,
                                          wave_histogram_pallas_t)
from lightgbm_tpu.ops.split_finder import FeatureMeta
from lightgbm_tpu.ops.wave import make_wave_core, make_wave_grow_fn
from lightgbm_tpu.utils.config import Config

N, F = 6000, 8
NM, SHARDS = 8192, 4        # 2,048 rows a shard: its slab is 1,024, one tile
STRUCTURE = ("num_leaves", "split_feature", "threshold_bin",
             "default_bin_for_zero", "default_bin", "is_cat", "left_child",
             "right_child", "leaf_parent", "leaf_count", "leaf_depth",
             "internal_count")
FLOATS = ("split_gain", "internal_value", "leaf_value")


def _setup(num_leaves, n=N, max_bin=63):
    rng = np.random.default_rng(11)
    X = rng.normal(size=(n, F))
    y = (X[:, 1] + np.cos(X[:, 4] * 2) + 0.4 * rng.normal(size=n) > 0.5)
    cfg = Config({"num_leaves": num_leaves, "min_data_in_leaf": 3,
                  "max_bin": max_bin, "verbose": -1})
    td = TrainingData.from_matrix(X, label=y.astype(np.float64),
                                  config=cfg)
    meta = FeatureMeta(num_bin=jnp.asarray(td.num_bin_arr),
                       default_bin=jnp.asarray(td.default_bin_arr),
                       is_categorical=jnp.asarray(td.is_categorical_arr))
    grad = jnp.asarray((0.5 - y).astype(np.float32))
    hess = jnp.full(n, 0.25, jnp.float32)
    return cfg, td, meta, grad, hess


def _run(compact, num_leaves, wave_width, row_mult=None, exact_order=False,
         n=N, hist_mode="pallas_t", packed=False, grad=None, shards=0,
         order=None):
    """`shards`: under shard_map over that many host devices, rows split
    evenly in their order; `order` permutes the rows first."""
    cfg, td, meta, grad0, hess = _setup(num_leaves, n=n,
                                        max_bin=15 if packed else 63)
    grad = grad0 if grad is None else jnp.asarray(grad)
    params = build_split_params(cfg)
    nb = int(td.num_bin_arr.max())
    X = td.binned
    if packed:
        from lightgbm_tpu.ops.pack import pack4_host
        X = pack4_host(np.asarray(X))
    X = jnp.asarray(X)
    grow = make_wave_grow_fn(num_leaves, nb, meta, params, -1,
                             wave_width=wave_width, hist_mode=hist_mode,
                             with_xt=True, exact_order=exact_order,
                             packed_cols=td.binned.shape[1] if packed else 0,
                             compact=compact, pallas_interpret=True,
                             psum_axis="data" if shards else None)
    rm = (jnp.ones(n, jnp.float32) if row_mult is None
          else jnp.asarray(row_mult))
    fm = jnp.ones(td.num_features, dtype=bool)
    if order is not None:
        X, grad, hess, rm = (a[jnp.asarray(order)]
                             for a in (X, grad, hess, rm))
    if shards:
        # the interpreter fails the varying-axes check on a slab launch
        # (parallel/mesh.py): off here as there
        grow = jax.shard_map(
            grow, mesh=Mesh(np.array(jax.devices()[:shards]), ("data",)),
            in_specs=(P("data", None), P("data"), P("data"), P("data"),
                      P(), P(None, "data")),
            out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))),
                       P("data")), check_vma=False)
    tree, leaf_id = jax.jit(grow)(X, grad, hess, rm, fm, jnp.transpose(X))
    return tree, leaf_id


def _counters(tree):
    return dict(zip(COUNTERS, (int(v) for v in np.asarray(tree.counters))))


def _same(a, b, fields, close=False):
    for field in fields:
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        if close:
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6,
                                       err_msg=field)
        else:
            np.testing.assert_array_equal(x, y, err_msg=field)


@pytest.mark.parametrize("exact_order", [False, True])
@pytest.mark.parametrize("wave_width", [1, 4, 32])
def test_slab_matches_full_pass_at_one_tile(wave_width, exact_order):
    """6000 rows: the slab (3000 rows) is one kernel tile, so every sum
    adds the same rows in the same order and the trees are bit-equal,
    floats included; the exact-order rollback remaps leaf ids AFTER the
    wave pass and must compose with the slab."""
    assert slab_plan(N, F, 63, wave_width) == (3000, 3000)
    t_full, l_full = _run(False, 63, wave_width, exact_order=exact_order)
    t_slab, l_slab = _run(True, 63, wave_width, exact_order=exact_order)
    assert int(t_full.num_leaves) == 63
    _same(t_full, t_slab, STRUCTURE + FLOATS)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_slab))
    c = _counters(t_slab)
    assert c["compacted"] == c["waves"] > 0
    assert _counters(t_full)["compacted"] == 0


def test_slab_across_tiles_structure_equal_floats_close():
    """20,000 rows: the slab is two tiles of 8,192 where the full pass
    has three, so rows cross tile boundaries and per-tile partial sums
    pair differently: structure and partition stay exact, the float
    fields agree to 1e-5."""
    assert slab_plan(20_000, F, 63, 4) == (16_384, 8_192)
    t_full, l_full = _run(False, 63, 4, n=20_000)
    t_slab, l_slab = _run(True, 63, 4, n=20_000)
    _same(t_full, t_slab, STRUCTURE)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_slab))
    _same(t_full, t_slab, FLOATS, close=True)
    c = _counters(t_slab)
    assert c["compacted"] == c["waves"]
    # every launch stopped at one or two tiles
    assert c["waves"] * 8_192 <= c["kernel_rows"] <= c["waves"] * 16_384
    assert c["kernel_rows"] < c["waves"] * 16_384


def test_early_stop_changes_no_sum():
    """`n_active` given against not given on the same slab: the tiles
    past the last full one hold fill rows only (leaf -2, weight 0), so
    skipping them is bit-equal; with nothing active the output is the
    zeroed block."""
    rng = np.random.default_rng(5)
    n, na, nb = 3000, 1300, 63
    X = rng.integers(0, nb, size=(n, F)).astype(np.uint8)
    lid = rng.integers(0, 6, size=n).astype(np.int32)
    w3 = rng.normal(size=(n, 3)).astype(np.float32)
    lid[na:], w3[na:] = -2, 0.0
    cid = jnp.asarray([1, 3, -1, 5], jnp.int32)
    args = (jnp.asarray(X.T), jnp.asarray(lid), jnp.asarray(w3), cid, nb)
    kw = dict(interpret=True, row_tile=512)          # 6 tiles of 512
    full = wave_histogram_pallas_t(*args, **kw)
    stop = wave_histogram_pallas_t(*args, n_active=jnp.asarray(na), **kw)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(stop))
    assert float(jnp.abs(full).max()) > 0
    # a promise broken on purpose shows that the tiles really are skipped
    short = wave_histogram_pallas_t(*args, n_active=jnp.asarray(512), **kw)
    assert not np.array_equal(np.asarray(full), np.asarray(short))
    none = wave_histogram_pallas_t(*args, n_active=jnp.asarray(0), **kw)
    assert float(jnp.abs(none).max()) == 0.0


def test_a_second_slab_follows_when_the_children_hold_most_rows():
    """Bagging weights: the smaller child is chosen by WEIGHTED count,
    a slab holds ROWS.  70% of the rows weigh 0.01 and the label splits
    them from the others, so the root's smaller child by weight holds
    4,200 of 6,000 rows, more than one slab's 3,000: that wave's launch
    runs over two slabs (nothing is truncated, light rows keep their
    leaf ids for the score update), and the tree equals the one grown
    without the slab."""
    col = np.asarray(_setup(63)[1].binned)[:, 1]
    light = col <= np.quantile(col, 0.7)
    rng = np.random.default_rng(7)
    y = ~light ^ (rng.random(N) < 0.1)
    rm = np.where(light, 0.01, 1.0).astype(np.float32)
    kw = dict(row_mult=rm, grad=(0.5 - y).astype(np.float32))
    t_full, l_full = _run(False, 63, 4, **kw)
    t_slab, l_slab = _run(True, 63, 4, **kw)
    _same(t_full, t_slab, STRUCTURE)
    _same(t_full, t_slab, FLOATS, close=True)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_slab))
    c = _counters(t_slab)
    assert c["compacted"] == c["waves"]
    # one tile a slab here: some wave visited two of them
    assert c["kernel_rows"] > c["waves"] * 3000
    assert c["kernel_rows"] % 3000 == 0


def test_slab_with_packed_bins():
    """4-bit packing: the slab gathers rows of the packed (N, ceil(F/2))
    matrix and the kernel unpacks them in VMEM as it does the full
    pass's; the packed tree equals the unpacked one."""
    t_u, l_u = _run(True, 63, 4, packed=False, n=N)
    t_p, l_p = _run(True, 63, 4, packed=True, n=N)
    t_pf, l_pf = _run(False, 63, 4, packed=True, n=N)
    _same(t_p, t_pf, STRUCTURE + FLOATS)
    np.testing.assert_array_equal(np.asarray(l_p), np.asarray(l_pf))
    assert _counters(t_p)["compacted"] == _counters(t_p)["waves"] > 0
    assert int(t_u.num_leaves) == 63


def test_counters_against_a_hand_count_on_a_three_wave_tree():
    """8 leaves at W=4: the root's split, then two, then four.  Every
    wave's launch visits one tile of the slab (cap = c = 3000 rows), and
    the slab holds exactly the smaller children's rows."""
    tree, leaf_id = _run(True, 8, 4)
    c = _counters(tree)
    assert c["waves"] == 3 and c["committed"] == 7
    assert c["compacted"] == 3
    assert c["kernel_rows"] == 3 * 3000
    assert c["rows"] == N
    left, right = np.asarray(tree.left_child), np.asarray(tree.right_child)
    count = lambda ch: int(tree.leaf_count[~ch] if ch < 0   # noqa: E731
                           else tree.internal_count[ch])
    assert c["hist_rows"] == sum(min(count(left[i]), count(right[i]))
                                 for i in range(7))
    assert c["hist_rows"] <= c["kernel_rows"]
    off = _counters(_run(False, 8, 4)[0])
    assert off["kernel_rows"] == 0 == off["compacted"] and off["waves"] == 3


def _learner(monkeypatch=None, **keys):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 4))
    X[rng.random(X.shape) < 0.7] = 0.0
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config(dict({"num_leaves": 15, "min_data_in_leaf": 3,
                       "max_bin": 63, "verbose": -1}, **keys))
    td = TrainingData.from_matrix(X, label=y, config=cfg)
    return SerialTreeLearner(cfg, td)


@pytest.mark.parametrize("keys, on", [
    ({"tpu_histogram_mode": "pallas_t", "tpu_pallas_interpret": True}, True),
    ({"tpu_histogram_mode": "pallas_ct", "tpu_pallas_interpret": True},
     False),
    ({"tpu_histogram_mode": "pallas_t"}, False),     # the XLA engine runs
    ({"tpu_sparse": True}, False),
    ({"tpu_sparse_kernel": True}, False),
    ({}, False),
])
def test_learner_resolves_the_slab_from_what_it_observes(keys, on):
    """No key turns the slab on or off: the serial learner reports it on
    where the pallas_t kernel really runs (here: its interpreter) on a
    dense store under wave growth, and off under pallas_ct, the sparse
    stores and wherever the XLA engine runs in the kernel's place."""
    learner = _learner(**dict({"tpu_growth": "wave"}, **keys))
    assert learner.wave_compact is on
    assert learner.obs_info()["wave_compact"] is on
    assert not hasattr(learner.config, "tpu_wave_compact")   # no such key


def test_slab_under_a_mesh_axis_as_on_one_device_and_only_with_pallas_t(
        monkeypatch):
    """`slab_active` does not ask for `psum_axis`: a mesh shard runs the
    slab wherever one device would.  On a pretend TPU pallas_t turns it
    on with no interpret flag, pallas_ct and f64 do not."""
    from lightgbm_tpu.ops.wave import slab_active
    assert slab_active(True, "pallas_t", jnp.float32, None, True)
    assert slab_active(True, "pallas_t", jnp.float32, "data", True)
    assert not slab_active(False, "pallas_t", jnp.float32, "data", True)
    assert not slab_active(True, "pallas_t", jnp.float32, "data", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    make_wave_core.cache_clear()
    assert slab_active(True, "pallas_t", jnp.float32, None)
    assert not slab_active(True, "pallas_ct", jnp.float32, "data")
    assert not slab_active(True, "pallas_t", jnp.float64, "data")
    assert slab_active(True, "pallas_t", jnp.float32, "data")


# ------------------------------------------------- under a data mesh

@functools.lru_cache(maxsize=None)
def _mesh_run(compact, wave_width):
    """63 leaves over the four shards, rows in their generated order."""
    return _run(compact, 63, wave_width, n=NM, shards=SHARDS)


def _slab_rows(active, cap=NM // SHARDS // 2):
    """Rows the slab launches of one wave visit on a shard that holds
    `active` rows of the wave's children: one tile (= cap here) a slab,
    ceil(active / cap) slabs."""
    return -(-active // cap) * cap


@pytest.mark.parametrize("wave_width", [1, 4, 32])
def test_mesh_slab_matches_the_mesh_full_pass(wave_width):
    """tree_learner=data's program with the slab against the same mesh
    without it: every shard adds its own rows of the children in their
    order, the psum adds the shards' blocks in the same order either
    way: same structure, same partition, floats to 1e-5."""
    assert slab_plan(NM // SHARDS, F, 63, wave_width) == (1024, 1024)
    t_full, l_full = _mesh_run(False, wave_width)
    t_slab, l_slab = _mesh_run(True, wave_width)
    assert int(t_full.num_leaves) == 63
    _same(t_full, t_slab, STRUCTURE)
    _same(t_full, t_slab, FLOATS, close=True)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_slab))
    c, off = _counters(t_slab), _counters(t_full)
    assert c["compacted"] == c["waves"] > 0 == off["compacted"]
    assert c["rows"] == NM == off["rows"]
    # a shard's slab launch stops at its one tile: under a full pass's
    assert 0 < c["kernel_rows"] < c["waves"] * NM and not off["kernel_rows"]
    # two words more a wave through the all-reduce: the rows the launches
    # visited and the rows the moves gathered
    assert c["allreduce_words"] == off["allreduce_words"] + 2 * c["waves"]


def test_mesh_slab_grows_the_serial_slabs_tree():
    """Four shards' slabs against one device's on the same rows: the
    sums pair differently (four partial blocks and a psum), so structure
    and partition are equal and the floats close."""
    t_one, l_one = _run(True, 63, 4, n=NM)
    t_dp, l_dp = _mesh_run(True, 4)
    _same(t_one, t_dp, STRUCTURE)
    _same(t_one, t_dp, FLOATS, close=True)
    np.testing.assert_array_equal(np.asarray(l_one), np.asarray(l_dp))
    one, dp = _counters(t_one), _counters(t_dp)
    assert dp["hist_rows"] == one["hist_rows"] and dp["waves"] == one["waves"]
    assert dp["compacted"] == dp["waves"]        # 1 a wave, not 1 a shard


def test_sorted_rows_put_the_smaller_child_on_one_shard():
    """"At most half" is global: rows sorted by the root's split column
    leave one shard with all of its rows in the root's smaller child (a
    second slab follows the first there) and another with none (its loop
    runs zero times and hands zeros to the psum).  Nothing is truncated:
    the tree is the unsorted rows' tree, leaf for leaf."""
    stump, _ = _run(True, 2, 4, n=NM, shards=SHARDS)
    col = np.asarray(_setup(2, n=NM)[1].binned)[:, int(stump.split_feature[0])]
    order = np.argsort(col, kind="stable")
    t2, l2 = _run(True, 2, 4, n=NM, shards=SHARDS, order=order)
    _same(stump, t2, STRUCTURE)
    small = int(np.argmin(np.bincount(np.asarray(l2), minlength=2)))
    active = (np.asarray(l2) == small).reshape(SHARDS, -1).sum(axis=1)
    assert active.max() == NM // SHARDS and active.min() == 0
    assert sorted(_slab_rows(int(a)) for a in active)[::3] == [0, 2048]
    c = _counters(t2)
    assert c["waves"] == 1 == c["compacted"]
    assert c["kernel_rows"] == sum(_slab_rows(int(a)) for a in active)
    # the whole tree from sorted rows: the generated order's tree, and
    # every row in the leaf it had there
    t_sorted, l_sorted = _run(True, 63, 4, n=NM, shards=SHARDS, order=order)
    t_slab, l_slab = _mesh_run(True, 4)
    _same(t_slab, t_sorted, STRUCTURE)
    _same(t_slab, t_sorted, FLOATS, close=True)
    np.testing.assert_array_equal(np.asarray(l_slab)[order],
                                  np.asarray(l_sorted))


def test_bagging_weights_under_the_mesh():
    """The weighted-count case on every shard: 70% of the rows weigh
    0.01, the smaller child by weight holds most rows of each shard, so
    the shards' loops run two slabs; same trees as without the slab."""
    col = np.asarray(_setup(63, n=NM)[1].binned)[:, 1]
    light = col <= np.quantile(col, 0.7)
    y = ~light ^ (np.random.default_rng(7).random(NM) < 0.1)
    kw = dict(row_mult=np.where(light, 0.01, 1.0).astype(np.float32),
              grad=(0.5 - y).astype(np.float32), n=NM, shards=SHARDS)
    t_full, l_full = _run(False, 63, 4, **kw)
    t_slab, l_slab = _run(True, 63, 4, **kw)
    _same(t_full, t_slab, STRUCTURE)
    _same(t_full, t_slab, FLOATS, close=True)
    np.testing.assert_array_equal(np.asarray(l_full), np.asarray(l_slab))
    c = _counters(t_slab)
    assert c["compacted"] == c["waves"]
    # some wave visited two slabs on some shard
    assert c["kernel_rows"] > c["waves"] * NM // 2
    assert c["kernel_rows"] % 1024 == 0


def test_mesh_counters_against_a_hand_count_on_a_three_wave_tree(
        monkeypatch):
    """`lgb.train(tree_learner=data)` with the interpreted kernel, 8
    leaves at W=4: the root's split, then two, then four.  The record is
    the mesh's: `kernel_rows` the sum over shards of what their slab
    launches visited (from each shard's own rows of each wave's smaller
    children), `compacted` 1 a wave, `rows_visited = rows +
    kernel_rows`, and two words a wave beside the histogram block in
    `allreduce_bytes` (the rows visited and the rows moved)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import timers
    from lightgbm_tpu.parallel import mesh as mesh_mod
    make_mesh = mesh_mod.make_data_mesh
    monkeypatch.setattr(mesh_mod, "make_data_mesh",
                        lambda devices=None: make_mesh(
                            jax.devices()[:SHARDS]))
    rng = np.random.default_rng(11)
    X = rng.normal(size=(NM, F))
    y = (X[:, 1] + np.cos(X[:, 4] * 2) + 0.4 * rng.normal(size=NM) > 0.5)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "min_data_in_leaf": 3, "verbose": -1, "tree_learner": "data",
              "tpu_growth": "wave", "tpu_wave_width": 4,
              "tpu_histogram_mode": "pallas_t",
              "tpu_pallas_interpret": True}
    timers.clear()
    bst = lgb.train(params, lgb.Dataset(X, label=y.astype(np.float64),
                                        params=params), num_boost_round=1)
    lrn = bst._gbdt.learner
    assert type(lrn).__name__ == "DataParallelTreeLearner"
    assert lrn.wave_compact and lrn.obs_info()["wave_compact"]
    bst._gbdt._materialize()
    rec, = [r["fields"] for r in timers.snapshot()
            if r["kind"] == "count" and r["name"] == "tree"]
    tree = bst._gbdt.models[0]
    assert rec["waves"] == 3 == rec["compacted"] and rec["committed"] == 7
    assert rec["shards"] == SHARDS and rec["rows"] == NM
    # every row's leaf, and every node's leaves, from the grown tree
    leaf = bst.predict(X, pred_leaf=True).reshape(-1).astype(int)

    def leaves(ch):
        return ([~ch] if ch < 0 else leaves(tree.left_child[ch])
                + leaves(tree.right_child[ch]))

    def count(ch):
        return int(tree.leaf_count[~ch] if ch < 0
                   else tree.internal_count[ch])

    kernel_rows = 0
    for wave in ([0], [1, 2], [3, 4, 5, 6]):     # nodes in commit order
        kids = []
        for node in wave:
            lc, rc = tree.left_child[node], tree.right_child[node]
            kids += leaves(lc if count(lc) < count(rc) else rc)
        active = np.isin(leaf, kids).reshape(SHARDS, -1).sum(axis=1)
        kernel_rows += sum(_slab_rows(int(a)) for a in active)
    assert rec["kernel_rows"] == kernel_rows
    assert rec["rows_visited"] == rec["rows"] + kernel_rows
    assert rec["hist_rows"] <= kernel_rows < 3 * NM
    fb3 = F * lrn.num_bins * 3
    assert rec["allreduce_bytes"] == 4 * (3 + fb3 + 3 * (4 * fb3 + 2))


# --------------------------------------- the move by chunks (move_rows)

CAP, TILE, G = 4096, 512, 1024      # a slab of 8 kernel tiles, 4 chunks


def _single_take(X, keys, live, g, buf):
    """The move as it was before the chunks: one take and one transpose
    over the slab's whole capacity, whatever `live` is."""
    del live, g, buf
    xt = jnp.transpose(jnp.take(X, keys, axis=0, mode="clip"))
    return xt, jnp.asarray(keys.shape[0], jnp.int32)


def _slab_operands(live, n=10_000, seed=5):
    """One slab as slab_hist hands it to the kernel: `live` rows of the
    children in row order, then fill rows (keys past the table, leaf -2,
    weight 0)."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 63, size=(n, F)).astype(np.uint8)
    active = min(live, CAP)
    keys = np.concatenate([
        np.sort(rng.choice(n, size=active, replace=False)),
        n + np.arange(CAP - active)]).astype(np.int32)
    lid = np.where(np.arange(CAP) < active,
                   rng.integers(0, 6, size=CAP), -2).astype(np.int32)
    w3 = np.where(np.arange(CAP)[:, None] < active,
                  rng.normal(size=(CAP, 3)), 0.0).astype(np.float32)
    return (jnp.asarray(X), jnp.asarray(keys), jnp.asarray(lid),
            jnp.asarray(w3), jnp.asarray([1, 3, -1, 5], jnp.int32))


def _slab_launch(xt, lid, w3, cid, live):
    return wave_histogram_pallas_t(xt, lid, w3, cid, 63, interpret=True,
                                   row_tile=TILE, n_active=jnp.asarray(live))


def _trees_with(monkeypatch, move, **kw):
    from lightgbm_tpu.ops import wave
    monkeypatch.setattr(wave, "move_rows", move)
    make_wave_core.cache_clear()
    try:
        return _run(True, 63, 4, n=70_000, **kw)
    finally:
        make_wave_core.cache_clear()


@pytest.mark.parametrize("case", [0, 1, G, G + 1, CAP, CAP + 700,
                                  "tree", "second_slab"])
def test_chunked_move_equals_the_single_take(case, monkeypatch):
    """The slab moved by chunks up to the last live row against the one
    take over its whole capacity.  A number: that many live rows in a
    slab of four chunks (none, one, a whole chunk, one row into the next,
    a full slab, more than a slab holds): the chunks written hold the
    single take's rows, the rest of the buffer is untouched, and the
    launch's sums are equal to the bit.  "tree": 63 leaves on 70,000 rows
    (a slab of 40,960 rows, five chunks of one tile) grown either way;
    "second_slab": the same under bagging weights that put most rows in
    the smaller child, so that a second slab follows with trips of its
    own.  Equal trees, to the bit."""
    from lightgbm_tpu.ops.wave import move_rows
    if isinstance(case, str):
        kw = {}
        if case == "second_slab":
            col = np.asarray(_setup(63, n=70_000)[1].binned)[:, 1]
            light = col <= np.quantile(col, 0.7)
            y = ~light ^ (np.random.default_rng(7).random(70_000) < 0.1)
            kw = dict(row_mult=np.where(light, 0.01, 1.0).astype(np.float32),
                      grad=(0.5 - y).astype(np.float32))
        cap, tile = slab_plan(70_000, F, 63, 4)
        assert cap == 5 * tile == 5 * slab_chunk(cap, tile)
        t_one, l_one = _trees_with(monkeypatch, _single_take, **kw)
        t_chunks, l_chunks = _trees_with(monkeypatch, move_rows, **kw)
        assert int(t_one.num_leaves) == 63
        _same(t_one, t_chunks, STRUCTURE + FLOATS)
        np.testing.assert_array_equal(np.asarray(l_one), np.asarray(l_chunks))
        one, chunks = _counters(t_one), _counters(t_chunks)
        assert chunks["kernel_rows"] == one["kernel_rows"]
        assert one["slab_rows_moved"] > chunks["slab_rows_moved"] \
            == chunks["kernel_rows"]                  # a chunk is a tile
        if case == "second_slab":
            assert one["slab_rows_moved"] > one["waves"] * cap
        return
    X, keys, lid, w3, cid = _slab_operands(case)
    whole, _ = _single_take(X, keys, case, G, None)
    marked = jnp.full((F, CAP), 200, jnp.uint8)
    buf, moved = jax.jit(move_rows, static_argnums=3)(X, keys, case, G,
                                                      marked)
    written = -(-min(case, CAP) // G) * G
    assert int(moved) == written
    np.testing.assert_array_equal(np.asarray(buf)[:, :written],
                                  np.asarray(whole)[:, :written])
    assert (np.asarray(buf)[:, written:] == 200).all()
    np.testing.assert_array_equal(
        np.asarray(_slab_launch(whole, lid, w3, cid, case)),
        np.asarray(_slab_launch(buf, lid, w3, cid, case)))


@pytest.mark.parametrize("bin_", [255, 5])
def test_what_the_buffer_holds_past_the_last_chunk_changes_no_sum(bin_):
    """1,100 live rows in a slab of 4,096: two chunks of 1,024 are moved
    and three kernel tiles of 512 read.  Past the chunks the buffer is
    filled on purpose, with a bin no column has and with one every
    column has: the launch stops before it, and inside the last tile the
    rows from 1,100 on carry leaf -2 and weight 0, so no sum changes."""
    from lightgbm_tpu.ops.wave import move_rows
    live = 1100
    X, keys, lid, w3, cid = _slab_operands(live)
    hists = []
    for fill in (0, bin_):
        buf, moved = move_rows(X, keys, live, G,
                               jnp.full((F, CAP), fill, jnp.uint8))
        assert int(moved) == 2 * G
        assert (np.asarray(buf)[:, 2 * G:] == fill).all()
        hists.append(np.asarray(_slab_launch(buf, lid, w3, cid, live)))
    np.testing.assert_array_equal(*hists)
    assert np.abs(hists[0]).max() > 0


def test_last_chunk_of_a_slab_that_is_no_whole_number_of_chunks():
    """Epsilon's slab is 293 tiles and a chunk 9: the 33rd chunk starts
    at cap - g and moves some rows twice, to the same place.  Here 7
    tiles by chunks of 2: a full slab comes out as the single take's."""
    from lightgbm_tpu.ops.wave import move_rows
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.integers(0, 63, size=(9000, F)).astype(np.uint8))
    cap = 7 * TILE
    keys = jnp.asarray(np.sort(rng.choice(9000, size=cap, replace=False))
                       .astype(np.int32))
    for live, chunks in ((cap, 4), (3 * G, 3), (3 * G + 1, 4)):
        buf, moved = move_rows(X, keys, live, G,
                               jnp.full((F, cap), 200, jnp.uint8))
        assert int(moved) == chunks * G
        upto = min(chunks * G, cap)
        np.testing.assert_array_equal(
            np.asarray(buf)[:, :upto], np.asarray(X)[np.asarray(keys)].T[
                :, :upto])


def test_slab_rows_moved_against_a_hand_count_on_a_three_wave_tree():
    """The tree of test_counters_against_a_hand_count_on_a_three_wave_tree:
    cap = tile = chunk = 3,000 rows, so each of the three waves moves one
    chunk, where the single take moved the slab's whole capacity whatever
    it held; without the slab nothing is moved."""
    c = _counters(_run(True, 8, 4)[0])
    assert slab_chunk(*slab_plan(N, F, 63, 4)) == 3000
    assert c["waves"] == 3 == c["compacted"]
    assert c["slab_rows_moved"] == 3 * 3000 == c["kernel_rows"]
    assert c["hist_rows"] <= c["kernel_rows"] <= c["slab_rows_moved"] \
        <= c["kernel_rows"] + c["compacted"] * 3000
    assert _counters(_run(False, 8, 4)[0])["slab_rows_moved"] == 0
    # 63 leaves on 20,000 rows: two tiles a slab, a chunk a tile
    c = _counters(_run(True, 63, 4, n=20_000)[0])
    assert slab_chunk(*slab_plan(20_000, F, 63, 4)) == 8192
    assert c["kernel_rows"] == c["slab_rows_moved"] < c["waves"] * 16_384


def test_mesh_slab_rows_moved_sums_the_shards_own_trips():
    """Rows sorted by the root's split column (the stump of
    test_sorted_rows_put_the_smaller_child_on_one_shard): a shard moves
    two slabs of one chunk each, another runs no trip at all, and the
    record holds the sum over the shards, handed over in the same
    all-reduce operand as `kernel_rows`."""
    stump, _ = _run(True, 2, 4, n=NM, shards=SHARDS)
    col = np.asarray(_setup(2, n=NM)[1].binned)[:, int(stump.split_feature[0])]
    t2, l2 = _run(True, 2, 4, n=NM, shards=SHARDS,
                  order=np.argsort(col, kind="stable"))
    small = int(np.argmin(np.bincount(np.asarray(l2), minlength=2)))
    active = (np.asarray(l2) == small).reshape(SHARDS, -1).sum(axis=1)
    assert active.max() == NM // SHARDS and active.min() == 0
    trips = sorted(-(-int(a) // 1024) for a in active)
    assert trips[0] == 0 and trips[-1] == 2       # each shard its own count
    c, off = _counters(t2), _counters(_run(False, 2, 4, n=NM,
                                           shards=SHARDS)[0])
    assert slab_chunk(*slab_plan(NM // SHARDS, F, 63, 4)) == 1024
    assert c["slab_rows_moved"] == sum(_slab_rows(int(a)) for a in active)
    assert c["slab_rows_moved"] == c["kernel_rows"]
    assert c["allreduce_words"] == off["allreduce_words"] + 2 * c["waves"]
    assert off["slab_rows_moved"] == 0


@pytest.mark.parametrize("cap, tile, chunks", [
    (3000, 3000, 1), (1024, 1024, 1), (16_384, 8192, 2), (40_960, 8192, 5),
    (600_064, 2048, 33), (1_250_944, 3712, 31)])
def test_chunk_rule(cap, tile, chunks):
    """A chunk is a whole number of the slab launch's tiles, about a
    thirty-second of the slab: one chunk where the slab is one tile, a
    chunk a tile up to 47 tiles, 28-36 chunks at the two wide cells'
    shapes (Epsilon's 1,200,128 x 2,000 and Bosch's 2,500,608 x 968 as
    the upload pads them)."""
    g = slab_chunk(cap, tile)
    assert g % tile == 0 and 0 < g <= cap
    assert -(-cap // g) == chunks
    if cap > 48 * tile:
        assert 28 <= chunks <= 36
