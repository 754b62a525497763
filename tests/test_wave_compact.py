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
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.obs.timers import COUNTERS
from lightgbm_tpu.ops.learner import SerialTreeLearner, build_split_params
from lightgbm_tpu.ops.pallas_wave import slab_plan, wave_histogram_pallas_t
from lightgbm_tpu.ops.split_finder import FeatureMeta
from lightgbm_tpu.ops.wave import make_wave_core, make_wave_grow_fn
from lightgbm_tpu.utils.config import Config

N, F = 6000, 8
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
         n=N, hist_mode="pallas_t", packed=False, grad=None):
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
                             compact=compact, pallas_interpret=True)
    rm = (jnp.ones(n, jnp.float32) if row_mult is None
          else jnp.asarray(row_mult))
    fm = jnp.ones(td.num_features, dtype=bool)
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


def test_no_slab_under_a_mesh_axis_or_on_the_tpu_without_pallas_t(
        monkeypatch):
    """`slab_active` under `psum_axis` (a mesh shard's slab has no
    measurement yet) and on a pretend TPU: pallas_t turns it on there
    with no interpret flag, pallas_ct and f64 do not."""
    from lightgbm_tpu.ops.wave import slab_active
    assert slab_active(True, "pallas_t", jnp.float32, None, True)
    assert not slab_active(True, "pallas_t", jnp.float32, "data", True)
    assert not slab_active(False, "pallas_t", jnp.float32, None, True)
    assert not slab_active(True, "pallas_t", jnp.float32, None, False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    make_wave_core.cache_clear()
    assert slab_active(True, "pallas_t", jnp.float32, None)
    assert not slab_active(True, "pallas_ct", jnp.float32, None)
    assert not slab_active(True, "pallas_t", jnp.float64, None)
    assert not slab_active(True, "pallas_t", jnp.float32, "data")
