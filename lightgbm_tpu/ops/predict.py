"""Device-side tree application: traversal on binned data + score updates.

Replaces Tree::AddPredictionToScore (src/io/tree.cpp) and the train-side
ScoreUpdater::AddScore-via-partition (score_updater.hpp:91-99) with jitted
XLA programs so boosting iterations never synchronize with the host.
Decision semantics match dense_bin.hpp:190-222 (default-bin redirect,
numerical <=, categorical ==).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.common import kMaxTreeOutput
from .partition import score_update_impl


class TraversalArrays(NamedTuple):
    """Minimal device arrays needed to traverse a tree on binned data."""
    num_leaves: jnp.ndarray        # scalar i32
    split_feature: jnp.ndarray     # (L-1,) i32 (inner index)
    threshold_bin: jnp.ndarray     # (L-1,) i32
    default_bin_for_zero: jnp.ndarray  # (L-1,) i32
    default_bin: jnp.ndarray       # (L-1,) i32
    is_cat: jnp.ndarray            # (L-1,) i32
    left_child: jnp.ndarray        # (L-1,) i32
    right_child: jnp.ndarray       # (L-1,) i32
    leaf_value: jnp.ndarray        # (L,) f


def traversal_from_grow(tree_arrays) -> TraversalArrays:
    """View ops.grow.TreeArrays as TraversalArrays (shared buffers)."""
    return TraversalArrays(
        num_leaves=tree_arrays.num_leaves,
        split_feature=tree_arrays.split_feature,
        threshold_bin=tree_arrays.threshold_bin,
        default_bin_for_zero=tree_arrays.default_bin_for_zero,
        default_bin=tree_arrays.default_bin,
        is_cat=tree_arrays.is_cat,
        left_child=tree_arrays.left_child,
        right_child=tree_arrays.right_child,
        leaf_value=tree_arrays.leaf_value,
    )


def traversal_from_host_tree(tree, dtype=jnp.float32) -> TraversalArrays:
    """Upload a models.Tree (with bin thresholds) for device traversal."""
    ni = max(tree.num_leaves - 1, 1)
    nl = max(tree.num_leaves, 2)
    return TraversalArrays(
        num_leaves=jnp.asarray(tree.num_leaves, jnp.int32),
        split_feature=jnp.asarray(tree.split_feature_inner[:ni], jnp.int32),
        threshold_bin=jnp.asarray(tree.threshold_in_bin[:ni], jnp.int32),
        default_bin_for_zero=jnp.asarray(tree.default_bin_for_zero[:ni], jnp.int32),
        default_bin=jnp.asarray(tree.zero_bin[:ni], jnp.int32),
        is_cat=jnp.asarray(tree.decision_type[:ni], jnp.int32),
        left_child=jnp.asarray(tree.left_child[:ni], jnp.int32),
        right_child=jnp.asarray(tree.right_child[:ni], jnp.int32),
        leaf_value=jnp.asarray(tree.leaf_value[:nl], dtype),
    )


@functools.partial(jax.jit, static_argnames=("packed",))
def leaf_index_binned(tree: TraversalArrays, X, layout=None,
                      packed: bool = False):
    """Per-row leaf index by iterative descent (Tree::GetLeaf semantics on
    bins); returns zeros for single-leaf trees.

    layout: optional ops.grow.BundleArrays when X holds EFB group columns —
    bins are reconstructed per node feature (feature_group.h semantics).
    packed: X is 4-bit packed in the ops/pack.py split-half layout (logical
    column j < Fh lives in the low nibble of stored column j, j >= Fh in
    the high nibble of column j - Fh).
    """
    n = X.shape[0]
    rows = jnp.arange(n)
    fh = X.shape[1]                      # stored width (packed: ceil(F/2))

    def col_bins(f, nd):
        """Bin of each row at (possibly packed) device column f."""
        if not packed:
            return X[rows, f].astype(jnp.int32)
        p = jnp.where(f < fh, f, f - fh)
        raw = X[rows, p].astype(jnp.int32)
        return jnp.where(f < fh, raw & 15, raw >> 4)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        nd = jnp.maximum(node, 0)
        f = tree.split_feature[nd]
        if layout is None:
            b = col_bins(f, nd)
        else:
            v = col_bins(layout.group_of[f], nd)
            off = layout.bin_off[f]
            in_range = (v >= off) & (v < off + layout.bin_span[f])
            b = jnp.where(in_range, v - off + layout.bin_adj[f],
                          tree.default_bin[nd])
        thr = tree.threshold_bin[nd]
        cat = tree.is_cat[nd] > 0
        dbz = tree.default_bin_for_zero[nd]
        dflt = tree.default_bin[nd]
        go_left = jnp.where(cat, b == thr, b <= thr)
        def_left = jnp.where(cat, dbz == thr, dbz <= thr)
        go_left = jnp.where(b == dflt, def_left, go_left)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(node >= 0, nxt, node)

    init = jnp.where(tree.num_leaves > 1,
                     jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32))
    node = lax.while_loop(cond, body, init)
    return jnp.where(tree.num_leaves > 1, ~node, 0)


@functools.partial(jax.jit, static_argnames=("packed",))
def add_tree_to_score(score, X, tree: TraversalArrays, scale, layout=None,
                      packed: bool = False):
    """score += scale * clip(leaf_value)[leaf(X)] — Tree::AddPredictionToScore
    with the Shrinkage clamp (tree.h:110-118) applied at read time."""
    leaf = leaf_index_binned(tree, X, layout, packed=packed)
    vals = jnp.clip(tree.leaf_value * scale, -kMaxTreeOutput, kMaxTreeOutput)
    add = jnp.where(tree.num_leaves > 1, vals[leaf], 0.0)
    return score + add.astype(score.dtype)


@jax.jit
def _update_score_gather(score, leaf_id, leaf_value, scale):
    # single-source arithmetic shared with the fused iteration program
    # (ops/fused_iter.py) — bit-identity depends on both paths tracing
    # the same impl
    with jax.named_scope("score_update"):
        return score_update_impl(score, leaf_id, leaf_value, scale)


def _score_update_kernel(tbl_ref, lid_ref, score_ref, out_ref, *, L):
    """score += tbl[lid] as an unrolled compare-select over the L-entry
    SMEM table — EXACT (the same f32 values are selected, added once)."""
    lid = lid_ref[:]                                   # (8, c) int32
    add = jnp.zeros(lid.shape, jnp.float32)
    for j in range(L):
        add = jnp.where(lid == j, tbl_ref[0, j], add)
    out_ref[:] = score_ref[:] + add.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("score_update")
def _update_score_pallas(score, leaf_id, vals, interpret=False):
    """Pallas form of the partition score update.

    The XLA gather of a (L,) table over N rows measured ~8 cycles/row
    at the 10.5M flagship (86 ms/iter = 11% of training, 13:17 trace);
    the compare-select sweep runs at VPU rate instead.  Exactness: each
    row selects the SAME clipped f32 leaf value the gather would read
    and adds it to the same score element — no reduction-order or
    precision change anywhere.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .grow import vma_struct
    n = score.shape[0]
    L = int(vals.shape[0])
    c = 4096
    npad = (-n) % (8 * c)
    # same out-of-range semantics as the gather form (clamp to [0, L-1])
    # so the two engines are bit-equal on EVERY input; pad rows clamp to
    # 0 but their scores are sliced away below
    leaf_id = jnp.clip(leaf_id, 0, L - 1)
    s2 = (jnp.pad(score, (0, npad)) if npad else score).reshape(8, -1)
    l2 = (jnp.pad(leaf_id, (0, npad)) if npad else leaf_id).reshape(8, -1)
    m = s2.shape[1]
    kernel = functools.partial(_score_update_kernel, L=L)
    operands = (vals[None, :].astype(jnp.float32), l2, s2)
    out = pl.pallas_call(
        kernel,
        name="score_update_pallas",
        grid=(m // c,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # (1, L) table
            pl.BlockSpec((8, c), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, c), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, c), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=vma_struct(s2.shape, score.dtype, *operands),
        interpret=interpret,
    )(*operands)
    return out.reshape(-1)[:n]


def pallas_score_update_runs(engine: str, num_leaves: int, score_dtype,
                             backend=None) -> bool:
    """True where the compare-select kernel above takes the train-side
    score update: asked for, a TPU, at most 512 leaf slots, an f32 score.
    The kernel's work is O(L) per row (one unrolled select per leaf
    slot), so large-leaf configs fall back to the gather, whose cost is
    L-independent — 512 keeps the kernel comfortably ahead of the
    measured ~8-cycle/row gather while bounding trace/compile size.
    f32-only: with tpu_use_dp=true the score/leaf values are f64 and the
    kernel's f32 table cast would break the bit-equality claim (and f64
    VMEM blocks don't lower on TPU) — those configs use the gather."""
    return (engine == "pallas"
            and (backend or jax.default_backend()) == "tpu"
            and num_leaves <= 512 and score_dtype == jnp.float32)


def score_update_traced(score, leaf_id, leaf_value, scale,
                        engine: str = "gather"):
    """score += clip(scale * leaf_value)[leaf_id] on ONE device, free of
    dispatch on concrete arrays, so the fused iteration
    (ops/fused_iter.py) inlines the same update the staged chain
    dispatches: the kernel where `pallas_score_update_runs`, else the
    gather form (ops/partition.py), bit-equal to each other."""
    if pallas_score_update_runs(engine, leaf_value.shape[0], score.dtype):
        vals = jnp.clip(leaf_value * scale, -kMaxTreeOutput,
                        kMaxTreeOutput)
        return _update_score_pallas(score, leaf_id, vals)
    return score_update_impl(score, leaf_id, leaf_value, scale)


def update_score_from_partition(score, leaf_id, leaf_value, scale,
                                engine: str = "gather"):
    """Train-side score update via the learner's final partition
    (score_updater.hpp:91-99): score += clip(scale * leaf_value)[leaf_id].

    engine='pallas' (TPU): the compare-select kernel above — bit-equal
    results, measured faster at large N; anything else: the XLA gather
    (`pallas_score_update_runs` says which).
    One device only: outside shard_map a Mosaic kernel cannot be
    partitioned automatically ("Mosaic kernels cannot be automatically
    partitioned" at lowering), so the mesh learners' row-sharded
    leaf_id / score take the gather, which XLA partitions by itself.
    """
    if (pallas_score_update_runs(engine, leaf_value.shape[0], score.dtype)
            and len(score.devices() | leaf_id.devices()) == 1):
        return score_update_traced(score, leaf_id, leaf_value, scale,
                                   engine)
    return _update_score_gather(score, leaf_id, leaf_value, scale)


@jax.jit
def add_constant_to_score(score, value):
    return score + value


# --------------------------------------------------------------------------
# Bulk prediction on RAW feature values, device-side (Predictor analog for
# large batches).  The reference predicts row-wise on the host with f64
# threshold compares (predictor.hpp:33-96, tree.h:250-276); a TPU bulk
# path must keep those f64 decisions exact without paying f64 compute.
# Trick: RANK ENCODING — per feature, collect every numerical threshold
# any tree uses, sort-unique them ON THE HOST IN F64, and replace each
# feature value by its insertion rank (count of thresholds < value).
# Then `value <= threshold` == `rank(value) <= index(threshold)`: an
# int32 compare on device, bit-faithful to the host decision.  NaN ranks
# past every threshold (numpy sorts it last) -> goes right, matching the
# C++ `operator<=` semantics.  Categorical nodes compare the int-cast
# value directly; the zero-range default redirect becomes a per-node
# "default goes left" bit (the node's default_value is a constant, so
# its decision is host-computable).  Routing is therefore BIT-EQUAL
# to the host predictor; leaf values accumulate in f32 with Kahan
# compensation in fixed tree order (JAX's default x64-off mode cannot
# hold f64 scores device-side), so outputs match the host's f64 sums to
# f32 rounding (~1e-7 relative) with exact leaf assignment.
# --------------------------------------------------------------------------

_CAT_SENTINEL = -(2 ** 31) + 1


class RankedTrees(NamedTuple):
    """Stacked device arrays for the ranked traversal (a jit pytree)."""
    feat: jnp.ndarray          # (T, M) i32 node split feature (outer idx)
    thr: jnp.ndarray           # (T, M) i32 rank (num) or int value (cat)
    is_cat: jnp.ndarray        # (T, M) i32
    default_left: jnp.ndarray  # (T, M) i32 decision of the zero default
    left: jnp.ndarray          # (T, M) i32
    right: jnp.ndarray         # (T, M) i32
    leaf_value: jnp.ndarray    # (T, L) f32 (shrinkage already baked in)
    num_leaves: jnp.ndarray    # (T,) i32
    tree_class: jnp.ndarray    # (T,) i32 class column per tree


class RankedPredictor:
    """Host-prepared state for device bulk prediction: the device tree
    stack plus the HOST-ONLY rank tables (f64) and cat-feature set —
    kept out of the jit pytree."""

    def __init__(self, dev: "RankedTrees", thresholds: tuple,
                 cat_features: frozenset, max_feature: int):
        self.dev = dev
        self.thresholds = thresholds
        self.cat_features = cat_features
        self.max_feature = max_feature     # host int: no sync per predict


def build_ranked_predictor(models, num_class: int,
                           num_features: int) -> "RankedPredictor":
    """Pack host Trees into stacked device arrays + per-feature rank
    tables.  Raises ValueError when a feature is used both numerically
    and categorically (callers fall back to the host path).

    All per-node work is vectorized over Tree.node_arrays views — the
    build is O(nodes) numpy, not O(nodes) interpreted Python, which is
    what makes cold-start of the serving tier (serve/executable.py) a
    few ms for 100-tree/255-leaf models instead of seconds."""
    import numpy as np

    T = len(models)
    M = max([max(t.num_leaves - 1, 1) for t in models] + [1])
    L = max([max(t.num_leaves, 2) for t in models] + [2])
    feat = np.zeros((T, M), np.int32)
    thr_raw = np.zeros((T, M), np.float64)
    is_cat = np.zeros((T, M), np.int32)
    dleft = np.zeros((T, M), np.int32)
    left = np.full((T, M), -1, np.int32)
    right = np.full((T, M), -1, np.int32)
    leaf_value = np.zeros((T, L), np.float64)
    num_leaves = np.zeros(T, np.int32)
    valid = np.zeros((T, M), bool)           # realized internal nodes
    for t, tree in enumerate(models):
        nl = tree.num_leaves
        ni = max(nl - 1, 0)
        num_leaves[t] = nl
        leaf_value[t, :nl] = tree.leaf_value[:nl]
        if ni == 0:
            continue
        na = tree.node_arrays()
        valid[t, :ni] = True
        feat[t, :ni] = na.split_feature
        thr_raw[t, :ni] = na.threshold
        cat = na.decision_type == 1
        is_cat[t, :ni] = cat
        left[t, :ni] = na.left_child
        right[t, :ni] = na.right_child
        # the zero-range default decision per node, host-computable once:
        # numerical `dv <= th`; categorical `int64(dv) == int64(th)` —
        # cast only the cat nodes (a numeric default can be 1e300, whose
        # int cast is undefined)
        with np.errstate(invalid="ignore"):
            dl = na.default_value <= na.threshold
        if cat.any():
            th_i = na.threshold[cat].astype(np.int64)
            if np.abs(th_i).max() > 2 ** 31 - 2:
                # the device compares int32; an out-of-domain cat
                # threshold cannot be encoded without breaking the
                # bit-equal routing contract -> host path
                raise ValueError(
                    "categorical threshold %r exceeds int32"
                    % float(na.threshold[cat][
                        int(np.abs(th_i).argmax())]))
            dl = dl.copy()
            dl[cat] = na.default_value[cat].astype(np.int64) == th_i
        dleft[t, :ni] = dl
    cat_features = frozenset(np.unique(feat[valid & (is_cat > 0)]).tolist())
    num_mask = valid & (is_cat == 0)
    num_features_used = frozenset(np.unique(feat[num_mask]).tolist())
    mixed = cat_features & num_features_used
    if mixed:
        raise ValueError("features used both ways: %s" % sorted(mixed))

    # per-feature sorted-unique numerical thresholds, then every numeric
    # node's rank in its feature's table — grouped searchsorted per used
    # feature instead of a Python loop over nodes
    thresholds = [np.empty(0, np.float64)] * max(num_features, 0)
    thr_rank = np.zeros((T, M), np.int32)
    for f in sorted(num_features_used):
        nodes_f = num_mask & (feat == f)
        arr = np.unique(thr_raw[nodes_f])
        if 0 <= f < num_features:
            thresholds[f] = arr
        thr_rank[nodes_f] = np.searchsorted(
            arr, thr_raw[nodes_f], side="left").astype(np.int32)
    cat_mask = valid & (is_cat > 0)
    if cat_mask.any():
        thr_rank[cat_mask] = thr_raw[cat_mask].astype(np.int64).astype(
            np.int32)

    tree_class = (jnp.arange(T, dtype=jnp.int32) % max(num_class, 1))
    dev = RankedTrees(
        feat=jnp.asarray(feat), thr=jnp.asarray(thr_rank),
        is_cat=jnp.asarray(is_cat), default_left=jnp.asarray(dleft),
        left=jnp.asarray(left), right=jnp.asarray(right),
        leaf_value=jnp.asarray(leaf_value, jnp.float32),
        num_leaves=jnp.asarray(num_leaves), tree_class=tree_class)
    max_feature = int(feat.max()) if T else 0
    return RankedPredictor(dev, tuple(thresholds),
                           frozenset(cat_features), max_feature)


def rank_encode(rp: "RankedPredictor", features) -> tuple:
    """Host: (N, F) raw f64 values -> int32 rank/cat matrix + zero-range
    mask.  All f64 decisions happen HERE (numpy), once per value."""
    import numpy as np
    from ..utils.common import kMissingValueRange

    X = np.asarray(features, np.float64)
    n, F = X.shape
    V = np.zeros((n, F), np.int32)
    for f in range(F):
        col = X[:, f]
        if f in rp.cat_features:
            # kept domain |v| <= 2^31-2; anything outside maps to the
            # sentinel, which can never equal an (in-domain, enforced at
            # build) threshold — so out-of-range values route right
            # exactly as the host int64 compare does
            with np.errstate(invalid="ignore"):
                iv = np.where(np.isfinite(col), col, 0.0).astype(np.int64)
            V[:, f] = np.where(
                np.isfinite(col) & (np.abs(iv) <= 2 ** 31 - 2),
                iv, _CAT_SENTINEL).astype(np.int32)
        else:
            V[:, f] = np.searchsorted(rp.thresholds[f], col,
                                      side="left").astype(np.int32)
    D = (X > -kMissingValueRange) & (X <= kMissingValueRange)
    return V, D


def _ranked_leaf(slot, V, D, rows, vary_axis=None):
    """Leaf index per row for one stacked tree slot (0 for stumps)."""
    (feat, thr, cat, dl, lc, rc, lv, nl, cls) = slot
    n = V.shape[0]

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        nd = jnp.maximum(node, 0)
        f = feat[nd]
        v = V[rows, f]
        gl = jnp.where(cat[nd] > 0, v == thr[nd], v <= thr[nd])
        gl = jnp.where(D[rows, f], dl[nd] > 0, gl)
        nxt = jnp.where(gl, lc[nd], rc[nd])
        return jnp.where(node >= 0, nxt, node)

    init = jnp.where(nl > 1, jnp.zeros(n, jnp.int32),
                     jnp.full(n, -1, jnp.int32))
    if vary_axis is not None:
        # under shard_map the carry must be shard-varying like the body
        # output (which reads the row-sharded V/D); init alone is built
        # from replicated tree arrays, so cast it explicitly
        from .grow import pvary_for
        init = pvary_for(init, vary_axis)
    node = lax.while_loop(cond, body, init)
    return jnp.where(nl > 1, ~node, 0)


def _ranked_predict_impl(dev: "RankedTrees", V, D, num_class: int,
                         vary_axis=None):
    """Traceable body of ranked prediction (shared by the single-device
    jit and the per-shard program in ``ranked_predict_sharded``)."""
    n = V.shape[0]
    rows = jnp.arange(n)

    def one_tree(carry, slot):
        score, comp = carry
        lv, nl, cls = slot[6], slot[7], slot[8]
        leaf = _ranked_leaf(slot, V, D, rows, vary_axis)
        add = jnp.where(nl > 1, lv[leaf], jnp.zeros((), lv.dtype))
        col_hit = (jnp.arange(num_class) == cls).astype(add.dtype)
        y = add[:, None] * col_hit[None, :] - comp
        t = score + y
        comp = (t - score) - y
        return (t, comp), None

    init = (jnp.zeros((n, num_class), dev.leaf_value.dtype),
            jnp.zeros((n, num_class), dev.leaf_value.dtype))
    if vary_axis is not None:
        from .grow import pvary_for
        init = tuple(pvary_for(a, vary_axis) for a in init)
    (score, _), _ = lax.scan(one_tree, init, tuple(dev))
    return score


@functools.partial(jax.jit, static_argnames=("num_class",))
def ranked_predict_device(dev: "RankedTrees", V, D, num_class: int):
    """(N, num_class) f32 raw scores.  Leaf ROUTING is bit-equal to the
    host f64 predictor (the ranks encode every f64 compare); values
    accumulate with Kahan compensation in fixed tree order."""
    return _ranked_predict_impl(dev, V, D, num_class)


@jax.jit
def ranked_leaf_indices_device(dev: "RankedTrees", V, D):
    """(N, T) leaf index per tree — the routing-exactness probe."""
    rows = jnp.arange(V.shape[0])

    def one(_, slot):
        return None, _ranked_leaf(slot, V, D, rows)

    _, leaves = lax.scan(one, None, tuple(dev))
    return jnp.transpose(leaves)


def _sharded_predict_ctx(rp: "RankedPredictor", num_class: int, devices):
    """Build (once per device set) the mesh, the replicated tree stack,
    and the jitted shard_map program for row-sharded prediction; cached
    on the RankedPredictor so the chunk loop pays one model broadcast
    per predict call, not one per chunk."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS, make_data_mesh

    key = (tuple(devices), num_class)
    cached = getattr(rp, "_shard_ctx", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    mesh = make_data_mesh(devices)
    repl = NamedSharding(mesh, P())
    rows_sh = NamedSharding(mesh, P(DATA_AXIS, None))
    dev_repl = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, repl), rp.dev)

    # per-shard program: each device runs the traversal on ITS rows only,
    # so the while_loop's `any(node >= 0)` cond reduces locally — no
    # per-step cross-device all-reduce, zero collectives end to end
    def _local(dev_, V_, D_):
        return _ranked_predict_impl(dev_, V_, D_, num_class,
                                    vary_axis=DATA_AXIS)

    fn = jax.jit(jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None)))
    ctx = (rows_sh, dev_repl, fn)
    rp._shard_ctx = (key, ctx)
    return ctx


def ranked_predict_sharded(rp: "RankedPredictor", V, D, num_class: int,
                           devices=None):
    """Row-sharded bulk prediction over a 1-D LOCAL device mesh.

    Prediction is embarrassingly parallel in rows, so the multi-chip
    design is pure data parallelism: the tree stack is replicated to
    every local device, host V/D rows are placed directly with a
    row-sharded NamedSharding (each shard streams host→owning-device;
    nothing stages on device 0), and the traversal runs under shard_map
    so every device's while_loop terminates on its own rows.  Per-row
    arithmetic (the tree scan with Kahan compensation) is unchanged, so
    the result is bit-identical to the single-device path.

    Multi-process: each process predicts ITS OWN rows over its local
    devices only — matching the reference's per-rank prediction
    (src/application/application.cpp Predict runs per-rank on local
    rows); no global mesh, so nothing is placed on non-addressable
    devices.

    V/D may be numpy arrays; returns (scores, n) where rows n: are pad.
    """
    import numpy as np

    if devices is None:
        devices = jax.local_devices()
    ndev = len(devices)
    n = V.shape[0]
    if ndev <= 1:
        return ranked_predict_device(
            rp.dev, jnp.asarray(V), jnp.asarray(D), num_class), n
    rows_sh, dev_repl, fn = _sharded_predict_ctx(rp, num_class, devices)
    from ..parallel.mesh import pad_rows
    pad = pad_rows(n, ndev)
    if pad:
        # padded rows traverse with rank 0 / in-range flags; sliced off
        # by the caller, so their values are irrelevant
        V = np.concatenate([np.asarray(V),
                            np.zeros((pad, V.shape[1]), V.dtype)])
        D = np.concatenate([np.asarray(D),
                            np.zeros((pad, D.shape[1]), D.dtype)])
    V = jax.device_put(np.ascontiguousarray(V), rows_sh)
    D = jax.device_put(np.ascontiguousarray(D), rows_sh)
    return fn(dev_repl, V, D), n
