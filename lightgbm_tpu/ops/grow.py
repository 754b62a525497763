"""Device-resident leaf-wise tree growth — ONE dispatch per tree.

The reference drives the leaf loop from the host (SerialTreeLearner::Train,
serial_tree_learner.cpp:168-223), which is fine at C++ latencies but fatal
when the accelerator sits behind a link with ~100ms round-trips.  Here the
entire grow loop is a `lax.while_loop` inside one jitted program:

  carry: (step, done, leaf_id, leaf-ordered row permutation + segment
          table (order/lstart/lcount, used by the ordered schedule), per-leaf
          histogram cache (absent when histogram_pool_size disables it),
          per-leaf packed best splits, per-leaf sums, flat tree arrays)
  body:  pick best leaf (argmax over packed gains) -> apply split to the
         row->leaf map (masked full-N update, or an in-segment partition of
         the permutation once the ordered schedule engages) -> smaller child
         histogram by masked scan or segment gather, larger by
         parent-subtraction (feature_histogram.hpp:63-69) when the cache is
         on, else rescanned -> best-split scan for both children.

Tree arrays come back as a device pytree; the host materializes a
models.Tree from them once per tree (real-valued thresholds resolved on host
in float64 from the BinMappers).  Under a data-parallel mesh the same
program shard_maps with a psum around the histogram — the reference's
ReduceScatter path (data_parallel_tree_learner.cpp:148-222).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.timers import COUNTERS
from .histogram import (compact_rows, compact_rows_topk, gathered_histogram,
                        leaf_histogram_onehot, leaf_histogram_scatter)
from .split_finder import (DEFAULT_BIN_FOR_ZERO, FEATURE, GAIN, IS_CAT,
                           LEFT_COUNT, LEFT_OUTPUT, LEFT_SUM_G, LEFT_SUM_H,
                           RIGHT_COUNT, RIGHT_OUTPUT, RIGHT_SUM_G, RIGHT_SUM_H,
                           SECOND_FEATURE, SECOND_GAIN, SPLIT_VEC_SIZE,
                           THRESHOLD, FeatureMeta, SplitParams,
                           depth_gated_best, find_best_split_impl,
                           per_feature_candidates)


class BundleArrays(NamedTuple):
    """Device-side EFB layout (io/bundle.py BundleLayout uploaded).

    The learner's histograms are built over GROUP columns (G, Bg, 3); the
    split scan runs on per-FEATURE views gathered via `gather_idx` with the
    default bin reconstructed by subtraction — the FixHistogram trick
    (dataset.cpp:764-783) vectorized over all features at once.
    """
    group_of: jnp.ndarray        # (F,) i32 feature -> group column
    bin_off: jnp.ndarray         # (F,) i32
    bin_adj: jnp.ndarray         # (F,) i32
    bin_span: jnp.ndarray        # (F,) i32
    gather_idx: jnp.ndarray      # (F, B) i32 into flattened (G*Bg)
    valid_mask: jnp.ndarray      # (F, B) bool — non-default, in-range bins


class TreeArrays(NamedTuple):
    """Flat SoA tree mirroring tree.h:195-229, device-resident."""
    num_leaves: jnp.ndarray          # scalar i32
    split_feature: jnp.ndarray       # (L-1,) i32 inner feature index
    threshold_bin: jnp.ndarray       # (L-1,) i32
    default_bin_for_zero: jnp.ndarray  # (L-1,) i32
    default_bin: jnp.ndarray         # (L-1,) i32 (feature's zero bin)
    is_cat: jnp.ndarray              # (L-1,) i32
    left_child: jnp.ndarray          # (L-1,) i32 (~leaf for leaves)
    right_child: jnp.ndarray         # (L-1,) i32
    split_gain: jnp.ndarray          # (L-1,) f
    internal_value: jnp.ndarray      # (L-1,) f
    internal_count: jnp.ndarray      # (L-1,) i32
    leaf_parent: jnp.ndarray         # (L,) i32
    leaf_value: jnp.ndarray          # (L,) f  (unshrunk outputs)
    leaf_count: jnp.ndarray          # (L,) i32
    leaf_depth: jnp.ndarray          # (L,) i32
    # split-audit trail: the runner-up feature each split beat and its
    # gain (-1 / 0 when the winner was the only valid candidate)
    second_feature: jnp.ndarray      # (L-1,) i32
    second_gain: jnp.ndarray         # (L-1,) f
    # what the grow loop did (obs/timers.py COUNTERS, i32): filled by the
    # wave grower, zeros from this module's leaf-wise grower
    counters: jnp.ndarray            # (len(COUNTERS),) i32


def feature_hist_view(ghist, sums, meta, bundle, has_bundle: bool,
                      fix_default: bool = False):
    """Group histograms -> per-feature (F, B, 3) views with the default
    bin rebuilt by subtraction (FixHistogram, dataset.cpp:764-783).
    Shared by the exact (grow) and wave growth engines.

    fix_default: reconstruct the default-bin slot even without a bundle —
    the sparse store (ops/sparse_store.py) never materializes fill-bin
    entries, so their slots arrive zero and carry the remainder."""
    if not has_bundle:
        if fix_default:
            fidx = jnp.arange(ghist.shape[0])
            return ghist.at[fidx, meta.default_bin].set(
                sums[None, :] - ghist.sum(axis=1))
        return ghist
    # a scope of its own inside the caller's `split_search`
    # (obs/timers.py INNER_SCOPES): what the bundle costs a search
    with jax.named_scope("bundle_view"):
        flat = ghist.reshape(-1, 3)
        v = flat[bundle.gather_idx] * bundle.valid_mask[..., None].astype(
            ghist.dtype)
        fidx = jnp.arange(v.shape[0])
        return v.at[fidx, meta.default_bin].set(
            sums[None, :] - v.sum(axis=1))


def pvary_for(x, axis: str):
    """Mark x shard-varying over `axis` under shard_map (VMA rules)."""
    return lax.pcast(x, (axis,), to="varying")


def _vma_of(*operands):
    """The mesh axes any operand varies over under shard_map (empty
    outside it)."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def vma_struct(shape, dtype, *operands):
    """``out_shape`` entry for a pallas_call computed from `operands`.

    Under shard_map's varying-axes check a pallas_call must declare how
    each output varies over the mesh: it varies over every axis any of
    its operands varies over."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=_vma_of(*operands))


def vary_like(init, *operands):
    """`init` marked varying over every axis any operand varies over.

    A scan carry must enter the loop with the type it leaves with: a
    zeros accumulator that the body adds shard-local sums into has to
    start out shard-varying too (the chunk scans only loop when a shard
    holds more rows than one chunk, i.e. at real sizes)."""
    vma = _vma_of(*operands)
    return lax.pcast(init, tuple(vma), to="varying") if vma else init


def default_row_capacities(n: int, min_capacity: int = 2048,
                           max_tiers: int = 10):
    """Descending static row-gather capacities n, n/2, n/4, ... — the tier
    ladder for compacted leaf histograms.  The top tier is full-N (under a
    data mesh a shard can hold ALL its local rows of the globally-smaller
    child), lower tiers bound wasted work to <2x the leaf's true row count
    until the ladder bottoms out."""
    caps = []
    c = int(n)
    while len(caps) < max_tiers:
        caps.append(c)
        if c <= min_capacity or c <= 1:
            break
        c = (c + 1) // 2
    return tuple(caps)


def make_grow_fn(num_leaves: int, num_bins: int, meta: FeatureMeta,
                 params: SplitParams, max_depth: int,
                 hist_mode: str = "scatter", hist_dtype=jnp.float32,
                 psum_axis: str = None, feature_axis: str = None,
                 voting_k: int = 0, num_voting_machines: int = 1,
                 bundle: BundleArrays = None, group_bins: int = 0,
                 row_capacities: tuple = (), cache_hists: bool = True,
                 seg_after: int = 15, packed_cols: int = 0,
                 sparse_col_cap: int = 0):
    """Bind `meta`/`bundle` onto the shared memoized grow program.

    The heavy lifting lives in `make_grow_core`, which is cached on the
    STATIC configuration only — two boosters (e.g. cv() folds) with the
    same shapes share one compiled XLA program instead of paying a fresh
    ~30s trace+compile each (meta/bundle arrays are call-time arguments
    of the cached function, not closure constants).
    """
    core = make_grow_core(num_leaves, num_bins, params, max_depth,
                          hist_mode, hist_dtype, psum_axis, feature_axis,
                          voting_k, num_voting_machines,
                          bundle is not None, group_bins,
                          row_capacities, cache_hists, seg_after,
                          packed_cols, sparse_col_cap)

    def grow(X, grad, hess, row_mult, feature_mask):
        return core(X, grad, hess, row_mult, feature_mask, meta, bundle)

    grow.core = core
    return grow


@functools.lru_cache(maxsize=64)
def make_grow_jit(*static_args):
    """jit(make_grow_core(...)) cached on the same static key, so repeated
    boosters/folds reuse one compiled executable, not just one traceable."""
    return jax.jit(make_grow_core(*static_args))


@functools.lru_cache(maxsize=64)
def make_grow_core(num_leaves: int, num_bins: int,
                   params: SplitParams, max_depth: int,
                   hist_mode: str = "scatter", hist_dtype=jnp.float32,
                   psum_axis: str = None, feature_axis: str = None,
                   voting_k: int = 0, num_voting_machines: int = 1,
                   has_bundle: bool = False, group_bins: int = 0,
                   row_capacities: tuple = (), cache_hists: bool = True,
                   seg_after: int = 15, packed_cols: int = 0,
                   sparse_col_cap: int = 0):
    """Build the jitted grow(X, grad, hess, row_mult, feature_mask) program.

    psum_axis: when set, histograms and scalar sums are psum'd over that
    mesh axis (data-parallel training under shard_map).

    feature_axis: when set, X arrives feature-sharded ((N, F_local) per
    shard, rows replicated) and only the packed best-split vector crosses
    devices — an all_gather + strict-> fold reproducing the reference's
    SplitInfo MaxReduce with its smaller-feature tie-break
    (feature_parallel_tree_learner.cpp:52-76, split_info.hpp:102-107).
    `meta`/`feature_mask` stay full-width; each shard slices its block.

    voting_k > 0 (with psum_axis): voting-parallel — per leaf, each shard
    proposes its local top-k features by leaf-size-weighted gain, the global
    top-k of the pmax'd weighted gains are selected, and ONLY those k
    histograms are psum'd (voting_parallel_tree_learner.cpp:164-300).
    Cross-device traffic per leaf drops from F*B*3 to k*B*3.
    num_voting_machines divides the local min_data/min_hessian constraints
    as the reference does (voting_parallel_tree_learner.cpp:54-56).
    """
    L = num_leaves
    voting = voting_k > 0 and psum_axis is not None
    if has_bundle and feature_axis is not None:
        raise ValueError("EFB bundling is not supported with the "
                         "feature-parallel learner (set enable_bundle=false)")
    sparse_mode = hist_mode == "sparse"
    if sparse_mode and (feature_axis is not None or voting_k > 0
                        or packed_cols):
        raise ValueError("tpu_sparse supports the serial/data-parallel "
                         "exact engine only (no feature-parallel, voting, "
                         "or 4-bit packing)")
    hist_bins = group_bins if has_bundle else num_bins
    # Pallas kernels take the full-N mask form; gathering only applies to
    # the onehot/scatter kernels.  The sparse store has no row-gatherable
    # dense matrix at all.
    use_gather = (len(row_capacities) > 0
                  and hist_mode not in ("pallas", "sparse"))
    # Ordered-partition mode: the carry holds a leaf-grouped row permutation
    # (DataPartition's indices_/leaf_begin_/leaf_count_, data_partition.hpp:
    # 94-147).  Each split touches ONLY the parent's segment — partition is
    # O(rows_in_parent) and the smaller-child histogram O(rows_in_child * F)
    # like the reference's ordered iteration (serial_tree_learner.cpp:424-450,
    # dense_bin.hpp:66-98) — instead of O(N) per split.  Static shapes via
    # the capacity-tier ladder.
    #
    # TPU economics force a two-phase schedule: random scatter/gather runs
    # ~125M elem/s on v5e while the masked one-hot pass streams all N rows
    # in ~2.4ms/1M — so for the first SEG_AFTER splits (big leaves) the
    # masked full-N path is cheaper, and ONE stable sort of leaf_id at the
    # transition builds the permutation that every later (small) split
    # partitions in-segment.  The sort amortizes over the L-1-SEG_AFTER
    # deep splits that dominate a 255-leaf tree.
    #
    # Disabled under the feature-parallel learner (its go-left bitmask psum
    # would sit inside a tier switch, which collectives cannot: branches
    # must agree across shards); FP keeps the compact-per-split gather.
    SEG_AFTER = seg_after
    # measured on v5e (1M x 28 x 63 bins): segment splits cost ~1.5-1.8ms in
    # gather/scatter versus ~2.3ms for a full masked pass, so the ordered
    # schedule only wins when deep cheap splits dominate (large trees);
    # below the crossover the pure masked streaming path is faster
    ordered = (use_gather and feature_axis is None
               and num_leaves - 1 > 128)
    # TPU: sort-based compaction (scatter ~8ms + cumsum ~2.4ms vs top_k
    # ~3.4ms at 1M rows, measured); CPU: cumsum+scatter is cheaper.
    compact_mode = "topk" if jax.default_backend() == "tpu" else "scatter"

    def seg_tier(count):
        """Index of the smallest capacity tier holding `count` rows."""
        capv = jnp.asarray(row_capacities, jnp.int32)      # descending
        return jnp.clip(jnp.sum((capv >= count).astype(jnp.int32)) - 1, 0,
                        len(row_capacities) - 1)

    def seg_block(order, start, count, cap: int):
        """A (cap,) window of `order` covering segment [start, start+count).

        The slice start is clamped so the window stays in bounds without
        padding; `valid` marks the segment's positions inside the window.
        off + count <= cap always holds because start + count <= n.
        """
        n = order.shape[0]
        s = jnp.clip(start, 0, max(n - cap, 0))
        off = start - s
        blk = lax.dynamic_slice(order, (s,), (cap,))
        pos = jnp.arange(cap, dtype=jnp.int32)
        valid = (pos >= off) & (pos < off + count)
        return s, off, blk, valid

    def seg_hist(X, g, h, row_mult, order, start, count):
        """(F, B, 3) histogram of the rows in segment [start, start+count)
        of `order` — this shard's part, no collectives (tier switches may
        diverge across shards; callers psum outside)."""
        def branch(cap):
            def run(_):
                _, _, blk, valid = seg_block(order, start, count, cap)
                return gathered_histogram(X, g, h, row_mult, blk, valid,
                                          hist_bins, hist_mode,
                                          logical_cols=packed_cols)
            return run
        return lax.switch(seg_tier(count),
                          [branch(c) for c in row_capacities], None)

    if packed_cols and hist_mode == "pallas":
        raise ValueError("4-bit packing is not supported by the pallas "
                         "exact-growth kernel (use onehot/scatter)")
    if sparse_mode:
        from .sparse_store import leaf_histogram_sparse

        def hist_fn(X, g, h, leaf_id, leaf, row_mult):
            return leaf_histogram_sparse(X, g, h, leaf_id, leaf, row_mult,
                                         hist_bins, X.fill.shape[0])
    elif hist_mode == "onehot":
        hist_fn = functools.partial(leaf_histogram_onehot,
                                    num_bins=hist_bins,
                                    logical_cols=packed_cols)
    elif hist_mode == "pallas":
        from .pallas_hist import leaf_histogram_pallas
        hist_fn = functools.partial(leaf_histogram_pallas, num_bins=hist_bins)
    elif hist_mode == "scatter":
        hist_fn = functools.partial(leaf_histogram_scatter,
                                    num_bins=hist_bins,
                                    logical_cols=packed_cols)
    else:
        from ..utils.log import Log
        Log.fatal("Unknown tpu_histogram_mode %s "
                  "(expected auto/scatter/onehot/pallas)", hist_mode)

    def to_feature_hist(ghist, sums, meta, bundle):
        return feature_hist_view(ghist, sums, meta, bundle, has_bundle,
                                 fix_default=sparse_mode)

    def maybe_psum(x):
        if psum_axis is not None:
            return lax.psum(x, psum_axis)
        return x

    # compact-per-split gathers only pay where the masked pass is repeated
    # per shard over replicated rows (the feature-parallel learner, which
    # cannot run ordered mode); serial/data-parallel non-ordered growth
    # keeps the cheaper masked streaming pass (measured: top_k compaction
    # ~3.4ms vs masked one-hot ~2.4ms at 1M x 28 x 63 on v5e)
    compact_gather = use_gather and not ordered and feature_axis is not None

    def local_hist(X, g, h, leaf_id, leaf, row_mult):
        """This shard's histogram of `leaf` — compact-gathered under the
        feature-parallel learner (O(rows_in_leaf) like dense_bin.hpp:66-98),
        else the full-N masked scan.  Ordered mode handles small leaves via
        segments, so its remaining callers (root + big-leaf phase) always
        take the masked streaming pass."""
        if not compact_gather:
            return hist_fn(X, g, h, leaf_id, leaf, row_mult)
        mask = leaf_id == leaf
        count = jnp.sum(mask.astype(jnp.int32))
        if compact_mode == "scatter":
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        tier = seg_tier(count)

        def tier_branch(c):
            def run(_):
                if compact_mode == "scatter":
                    idx = compact_rows(mask, pos, c)
                else:
                    idx = compact_rows_topk(mask, c)
                valid = jnp.arange(c, dtype=jnp.int32) < count
                return gathered_histogram(X, g, h, row_mult, idx, valid,
                                          hist_bins, hist_mode,
                                          logical_cols=packed_cols)
            return run

        return lax.switch(tier, [tier_branch(c) for c in row_capacities],
                          None)

    def hist_of_leaf(X, g, h, leaf_id, leaf, row_mult):
        h_local = local_hist(X, g, h, leaf_id, leaf, row_mult)
        if voting:
            return h_local          # voting: keep local, psum only top-k
        return maybe_psum(h_local)

    if voting:
        local_params = params._replace(
            min_data_in_leaf=params.min_data_in_leaf / num_voting_machines,
            min_sum_hessian_in_leaf=(params.min_sum_hessian_in_leaf
                                     / num_voting_machines))

    def depth_gate(b, depth):
        if max_depth > 0:
            b = b.at[GAIN].set(jnp.where(depth < max_depth, b[GAIN], -jnp.inf))
        return b

    def best_of_serial(hist, sums, feature_mask, depth, meta, bundle):
        return depth_gated_best(to_feature_hist(hist, sums, meta, bundle),
                                sums, meta, feature_mask, params, max_depth,
                                depth)

    def best_of_feature_parallel(hist, sums, feature_mask, depth,
                                 local_meta, offset):
        F_local = hist.shape[0]
        local_mask = lax.dynamic_slice_in_dim(feature_mask, offset, F_local)
        b = find_best_split_impl(hist, sums[0], sums[1], sums[2], local_meta,
                                 local_mask, params)
        b = b.at[FEATURE].add(offset.astype(b.dtype))
        sf = b[SECOND_FEATURE]
        b = b.at[SECOND_FEATURE].set(
            jnp.where(sf >= 0, sf + offset.astype(b.dtype), sf))
        gathered = lax.all_gather(b, feature_axis)      # (n_shards, V)
        # strict-> fold keeps the earlier shard on ties; shards hold
        # contiguous feature blocks, so this IS the smaller-global-feature
        # tie-break of SplitInfo::MaxReducer (split_info.hpp:60-76,102-107)
        best = gathered[0]
        for i in range(1, gathered.shape[0]):
            take = gathered[i][GAIN] > best[GAIN]
            win = jnp.where(take, gathered[i], best)
            lose = jnp.where(take, best, gathered[i])
            # merged runner-up: the loser's winning candidate competes
            # with the winner's own runner-up (both are valid non-winners)
            loser_valid = jnp.isfinite(lose[GAIN]) & (lose[GAIN] > 0.0)
            use_loser = loser_valid & (lose[GAIN] > win[SECOND_GAIN])
            win = win.at[SECOND_GAIN].set(
                jnp.where(use_loser, lose[GAIN], win[SECOND_GAIN]))
            win = win.at[SECOND_FEATURE].set(
                jnp.where(use_loser, lose[FEATURE], win[SECOND_FEATURE]))
            best = win
        return depth_gate(best, depth)

    def best_of_voting(ghist_local, sums, feature_mask, depth, meta,
                       bundle):
        # local candidates against LOCAL leaf sums with constraints divided
        # by num_machines (voting_parallel_tree_learner.cpp:54-56)
        local_sums = jnp.sum(ghist_local[0], axis=0)    # (3,) of this shard
        hist_local = to_feature_hist(ghist_local, local_sums, meta, bundle)
        F = hist_local.shape[0]
        k = min(voting_k, F)
        cand, _, _, _, local_shift = per_feature_candidates(
            hist_local, local_sums[0], local_sums[1], local_sums[2], meta,
            local_params)
        # vote on the improvement (gain minus this shard's gain_shift), the
        # quantity the reference's SplitInfo.gain carries into GlobalVoting —
        # raw gains would bias the vote toward shards with skewed parent sums
        gains = jnp.where(feature_mask, cand.gain - local_shift, -jnp.inf)
        # weight by local leaf size vs global mean (GlobalVoting,
        # voting_parallel_tree_learner.cpp:164-193)
        mean_cnt = jnp.maximum(sums[2] / num_voting_machines, 1.0)
        weighted = gains * (local_sums[2] / mean_cnt)
        weighted = jnp.where(jnp.isfinite(gains), weighted, -jnp.inf)
        # keep only this shard's top-k proposals
        kth = lax.top_k(weighted, k)[0][-1]
        proposal = jnp.where(weighted >= kth, weighted, -jnp.inf)
        global_gain = lax.pmax(proposal, psum_axis)     # (F,)
        sel = lax.top_k(global_gain, k)[1]              # global top-k features
        # ONLY the selected histograms cross the wire
        hist_sel = lax.psum(jnp.take(hist_local, sel, axis=0), psum_axis)
        sub_meta = FeatureMeta(num_bin=meta.num_bin[sel],
                               default_bin=meta.default_bin[sel],
                               is_categorical=meta.is_categorical[sel])
        b = find_best_split_impl(hist_sel, sums[0], sums[1], sums[2],
                                 sub_meta, feature_mask[sel], params)
        f_local = b[FEATURE].astype(jnp.int32)
        b = b.at[FEATURE].set(sel[f_local].astype(b.dtype))
        sf_local = b[SECOND_FEATURE].astype(jnp.int32)
        b = b.at[SECOND_FEATURE].set(
            jnp.where(sf_local >= 0,
                      sel[jnp.clip(sf_local, 0, k - 1)].astype(b.dtype),
                      b[SECOND_FEATURE]))
        return depth_gate(b, depth)

    def grow(X, grad, hess, row_mult, feature_mask, meta, bundle):
        n = grad.shape[0]       # X may be a SparseDeviceStore pytree
        grad = grad.astype(hist_dtype)
        hess = hess.astype(hist_dtype)
        row_mult = row_mult.astype(hist_dtype)
        leaf_id = jnp.zeros(n, dtype=jnp.int32)
        # ordered mode: leaf-grouped row permutation + per-leaf segment
        # table (DataPartition's indices_/leaf_begin_/leaf_count_);
        # size-0 placeholders otherwise so non-ordered growers don't carry
        # dead O(N) loop state
        if ordered:
            order = jnp.arange(n, dtype=jnp.int32)
            lstart = jnp.zeros(L, dtype=jnp.int32)
            lcount = jnp.zeros(L, dtype=jnp.int32).at[0].set(n)
        else:
            order = jnp.zeros(0, jnp.int32)
            lstart = jnp.zeros(0, jnp.int32)
            lcount = jnp.zeros(0, jnp.int32)
        if psum_axis is not None:
            # under shard_map the row->leaf map is shard-varying from the
            # first split on; mark the initial carry accordingly (VMA rules)
            leaf_id = pvary_for(leaf_id, psum_axis)
            order = pvary_for(order, psum_axis)
            lstart = pvary_for(lstart, psum_axis)
            lcount = pvary_for(lcount, psum_axis)

        if feature_axis is not None:
            F_local = X.shape[1]
            offset = lax.axis_index(feature_axis) * F_local
            local_meta = FeatureMeta(
                num_bin=lax.dynamic_slice_in_dim(
                    meta.num_bin, offset, F_local),
                default_bin=lax.dynamic_slice_in_dim(
                    meta.default_bin, offset, F_local),
                is_categorical=lax.dynamic_slice_in_dim(
                    meta.is_categorical, offset, F_local))

            def best_of(h, s, m, d):
                return best_of_feature_parallel(h, s, m, d, local_meta, offset)
        elif voting:
            def best_of(h, s, m, d):
                return best_of_voting(h, s, m, d, meta, bundle)
        else:
            def best_of(h, s, m, d):
                return best_of_serial(h, s, m, d, meta, bundle)

        root_sums = maybe_psum(jnp.stack([
            jnp.sum(grad * row_mult), jnp.sum(hess * row_mult),
            jnp.sum(row_mult)]))
        hist0 = hist_of_leaf(X, grad, hess, leaf_id, 0, row_mult)

        F = hist0.shape[0]
        B = hist0.shape[1]
        if cache_hists:
            hists = jnp.zeros((L, F, B, 3), dtype=hist_dtype).at[0].set(hist0)
        else:
            # HistogramPool disabled (histogram_pool_size budget exceeded):
            # no per-leaf cache, larger children are re-scanned instead of
            # obtained by parent subtraction — memory O(F*B*3) instead of
            # O(L*F*B*3), the recompute arm of feature_histogram.hpp:398-565.
            hists = jnp.zeros((0,), dtype=hist_dtype)
        bests = jnp.full((L, SPLIT_VEC_SIZE), -jnp.inf, dtype=hist_dtype)
        bests = bests.at[0].set(best_of(hist0, root_sums, feature_mask, 0))
        sums = jnp.zeros((L, 3), dtype=hist_dtype).at[0].set(root_sums)

        tree = TreeArrays(
            num_leaves=jnp.asarray(1, jnp.int32),
            split_feature=jnp.zeros(L - 1, jnp.int32),
            threshold_bin=jnp.zeros(L - 1, jnp.int32),
            default_bin_for_zero=jnp.zeros(L - 1, jnp.int32),
            default_bin=jnp.zeros(L - 1, jnp.int32),
            is_cat=jnp.zeros(L - 1, jnp.int32),
            left_child=jnp.zeros(L - 1, jnp.int32),
            right_child=jnp.zeros(L - 1, jnp.int32),
            split_gain=jnp.zeros(L - 1, hist_dtype),
            internal_value=jnp.zeros(L - 1, hist_dtype),
            internal_count=jnp.zeros(L - 1, jnp.int32),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            leaf_value=jnp.zeros(L, hist_dtype),
            leaf_count=jnp.zeros(L, jnp.int32).at[0].set(
                root_sums[2].astype(jnp.int32)),
            leaf_depth=jnp.zeros(L, jnp.int32),
            second_feature=jnp.full(L - 1, -1, jnp.int32),
            second_gain=jnp.zeros(L - 1, hist_dtype),
            counters=jnp.zeros(len(COUNTERS), jnp.int32),
        )

        def cond(carry):
            step, done = carry[0], carry[1]
            return (step < L - 1) & ~done

        def body(carry):
            (step, done, leaf_id, order, lstart, lcount, hists, bests, sums,
             tree) = carry
            gains = bests[:, GAIN]
            best_leaf = jnp.argmax(gains).astype(jnp.int32)
            info = bests[best_leaf]
            ok = info[GAIN] > 0.0     # SerialTreeLearner::Train:203-207

            node = step                       # new internal node index
            new_leaf = step + 1               # right child leaf index
            f = info[FEATURE].astype(jnp.int32)
            thr = info[THRESHOLD].astype(jnp.int32)
            dbz = info[DEFAULT_BIN_FOR_ZERO].astype(jnp.int32)
            cat = info[IS_CAT] > 0.5
            fdefault = meta.default_bin[f]
            default_left = jnp.where(cat, dbz == thr, dbz <= thr)

            def bundle_remap(gcol):
                # group column -> feature-local bins (feature_group.h
                # PushData inverted); out-of-range rows sit at the default
                goff = bundle.bin_off[f]
                in_range = (gcol >= goff) & (gcol < goff + bundle.bin_span[f])
                return jnp.where(in_range, gcol - goff + bundle.bin_adj[f],
                                 fdefault)

            def fetch_col_of(Xs, j):
                """Device column j of Xs as int32 bins — nibble-extracted
                when the store is 4-bit packed (ops/pack.py split-half:
                logical j < Fh lives in col j's low nibble, j >= Fh in
                col j-Fh's high nibble)."""
                if not packed_cols:
                    return jnp.take(Xs, j, axis=-1).astype(jnp.int32)
                fh = Xs.shape[-1]
                pj = jnp.where(j < fh, j, j - fh)
                raw = jnp.take(Xs, pj, axis=-1).astype(jnp.int32)
                return jnp.where(j < fh, raw & 15, raw >> 4)

            def split_column_full():
                """Winning feature's bin values for ALL rows (this shard)."""
                j = bundle.group_of[f] if has_bundle else f
                if sparse_mode:
                    from .sparse_store import sparse_split_column
                    col = sparse_split_column(X, j, n, sparse_col_cap)
                else:
                    col = fetch_col_of(X, j)
                return bundle_remap(col) if has_bundle else col

            def go_left_of(col):
                """dense_bin.hpp:190-222: threshold compare with the default
                bin routed by default_left."""
                gl = jnp.where(cat, col == thr, col <= thr)
                return jnp.where(col == fdefault, default_left, gl)

            # ---- partition (dense_bin.hpp:190-222 semantics)
            if ordered:
                # transition: ONE stable sort of leaf_id builds the
                # leaf-grouped permutation + segment table that all later
                # (small) splits partition in-segment
                def do_sort(_):
                    o = jnp.argsort(leaf_id).astype(jnp.int32)
                    slid = jnp.take(leaf_id, o)
                    lid_iota = jnp.arange(L, dtype=jnp.int32)
                    ls = jnp.searchsorted(slid, lid_iota,
                                          side="left").astype(jnp.int32)
                    le = jnp.searchsorted(slid, lid_iota,
                                          side="right").astype(jnp.int32)
                    return o, ls, le - ls

                order, lstart, lcount = lax.cond(
                    step == SEG_AFTER, do_sort,
                    lambda _: (order, lstart, lcount), None)

                def phase_masked(_):
                    # big-leaf phase: full-N masked update (VPU streaming
                    # beats scatter at these row counts)
                    in_leaf = leaf_id == best_leaf
                    go_left = go_left_of(split_column_full())
                    new_lid = jnp.where(in_leaf & ~go_left, new_leaf,
                                        leaf_id)
                    return (jnp.where(ok, new_lid, leaf_id), order, lstart,
                            lcount)

                def phase_seg(_):
                    # small-leaf phase: split ONLY the parent's segment
                    # (DataPartition::Split, data_partition.hpp:118-147) —
                    # stable in-segment partition + leaf_id scatter for the
                    # rows that moved right
                    s_p = lstart[best_leaf]
                    c_p = lcount[best_leaf]

                    def part_branch(cap):
                        def run(_):
                            s, off, blk, valid = seg_block(order, s_p, c_p,
                                                           cap)
                            j = bundle.group_of[f] if has_bundle else f
                            # two gather orders, chosen statically per tier:
                            # rows-then-column touches cap*F bytes, column-
                            # then-rows touches n
                            if cap * X.shape[1] <= n:
                                colb = fetch_col_of(
                                    jnp.take(X, blk, axis=0), j)
                            else:
                                colb = jnp.take(fetch_col_of(X, j),
                                                blk).astype(jnp.int32)
                            if has_bundle:
                                colb = bundle_remap(colb)
                            gl = go_left_of(colb) & valid
                            nleft = jnp.sum(gl.astype(jnp.int32))
                            posl = jnp.cumsum(gl.astype(jnp.int32)) - 1
                            posr = (nleft - 1
                                    + jnp.cumsum(
                                        (valid & ~gl).astype(jnp.int32)))
                            tgt = jnp.where(gl, posl, posr) + off
                            tgt = jnp.where(valid & ok, tgt, cap)  # ~ok: noop
                            new_blk = blk.at[tgt].set(blk, mode="drop")
                            new_order = lax.dynamic_update_slice(
                                order, new_blk, (s,))
                            ridx = jnp.where(valid & ~gl & ok, blk, n)
                            new_lid = leaf_id.at[ridx].set(new_leaf,
                                                           mode="drop")
                            return new_order, new_lid, nleft
                        return run

                    new_order, new_lid, nleft = lax.switch(
                        seg_tier(c_p),
                        [part_branch(c) for c in row_capacities], None)
                    ls = lstart.at[new_leaf].set(
                        jnp.where(ok, s_p + nleft, lstart[new_leaf]))
                    lc = lcount.at[new_leaf].set(
                        jnp.where(ok, c_p - nleft, lcount[new_leaf]))
                    lc = lc.at[best_leaf].set(
                        jnp.where(ok, nleft, lc[best_leaf]))
                    return new_lid, new_order, ls, lc

                leaf_id, order, lstart, lcount = lax.cond(
                    step < SEG_AFTER, phase_masked, phase_seg, None)
            else:
                if feature_axis is not None:
                    # the winning column lives on exactly one feature shard;
                    # compute its go-left mask there and psum it to everyone —
                    # the "every rank re-executes the split" step of the
                    # reference collapses to one bitmask broadcast
                    own = (f >= offset) & (f < offset + F_local)
                    fl = jnp.clip(f - offset, 0, F_local - 1)
                    col = jnp.take(X, fl, axis=1).astype(jnp.int32)
                    go_left = lax.psum(
                        (go_left_of(col) & own).astype(jnp.int32),
                        feature_axis) > 0
                else:
                    go_left = go_left_of(split_column_full())
                in_leaf = leaf_id == best_leaf
                new_leaf_id = jnp.where(in_leaf & ~go_left, new_leaf, leaf_id)
                leaf_id = jnp.where(ok, new_leaf_id, leaf_id)

            # ---- tree bookkeeping (tree.cpp:55-110)
            parent = tree.leaf_parent[best_leaf]
            # fix the grandparent's child pointer
            lc = tree.left_child
            rc = tree.right_child
            was_left = lc[jnp.maximum(parent, 0)] == ~best_leaf
            lc = lc.at[jnp.maximum(parent, 0)].set(
                jnp.where(ok & (parent >= 0) & was_left, node,
                          lc[jnp.maximum(parent, 0)]))
            rc = rc.at[jnp.maximum(parent, 0)].set(
                jnp.where(ok & (parent >= 0) & ~was_left, node,
                          rc[jnp.maximum(parent, 0)]))
            lc = lc.at[node].set(jnp.where(ok, ~best_leaf, lc[node]))
            rc = rc.at[node].set(jnp.where(ok, ~new_leaf, rc[node]))

            depth = tree.leaf_depth[best_leaf] + 1
            upd = lambda arr, idx, val: arr.at[idx].set(
                jnp.where(ok, val, arr[idx]))
            tree = tree._replace(
                num_leaves=tree.num_leaves + ok.astype(jnp.int32),
                split_feature=upd(tree.split_feature, node, f),
                threshold_bin=upd(tree.threshold_bin, node, thr),
                default_bin_for_zero=upd(tree.default_bin_for_zero, node, dbz),
                default_bin=upd(tree.default_bin, node, fdefault),
                is_cat=upd(tree.is_cat, node, cat.astype(jnp.int32)),
                left_child=lc,
                right_child=rc,
                split_gain=upd(tree.split_gain, node, info[GAIN]),
                internal_value=upd(tree.internal_value, node,
                                   tree.leaf_value[best_leaf]),
                internal_count=upd(tree.internal_count, node,
                                   (info[LEFT_COUNT] + info[RIGHT_COUNT])
                                   .astype(jnp.int32)),
                leaf_parent=upd(upd(tree.leaf_parent, best_leaf, node),
                                new_leaf, jnp.where(ok, node, -1)),
                leaf_value=upd(upd(tree.leaf_value, best_leaf,
                                   info[LEFT_OUTPUT]),
                               new_leaf, info[RIGHT_OUTPUT]),
                leaf_count=upd(upd(tree.leaf_count, best_leaf,
                                   info[LEFT_COUNT].astype(jnp.int32)),
                               new_leaf, info[RIGHT_COUNT].astype(jnp.int32)),
                leaf_depth=upd(upd(tree.leaf_depth, best_leaf, depth),
                               new_leaf, depth),
                second_feature=upd(tree.second_feature, node,
                                   info[SECOND_FEATURE].astype(jnp.int32)),
                second_gain=upd(tree.second_gain, node,
                                jnp.where(jnp.isfinite(info[SECOND_GAIN]),
                                          info[SECOND_GAIN], 0.0)),
            )

            # ---- children: smaller scanned, larger by subtraction
            left_sums = jnp.stack([info[LEFT_SUM_G], info[LEFT_SUM_H],
                                   info[LEFT_COUNT]])
            right_sums = jnp.stack([info[RIGHT_SUM_G], info[RIGHT_SUM_H],
                                    info[RIGHT_COUNT]])
            left_smaller = info[LEFT_COUNT] < info[RIGHT_COUNT]
            small = jnp.where(left_smaller, best_leaf, new_leaf)
            large = jnp.where(left_smaller, new_leaf, best_leaf)
            small_sums = jnp.where(left_smaller, left_sums, right_sums)
            large_sums = jnp.where(left_smaller, right_sums, left_sums)

            if ordered:
                def hist_of_seg(leaf):
                    # phase-matched local histogram; the psum sits OUTSIDE
                    # both the phase cond and the tier switch (tier choice
                    # is shard-varying under the data mesh)
                    hl = lax.cond(
                        step < SEG_AFTER,
                        lambda lf: hist_fn(X, grad, hess, leaf_id, lf,
                                           row_mult),
                        lambda lf: seg_hist(X, grad, hess, row_mult, order,
                                            lstart[lf], lcount[lf]),
                        leaf)
                    if voting:
                        return hl
                    return maybe_psum(hl)
                hist_small = hist_of_seg(small)
            else:
                hist_small = hist_of_leaf(X, grad, hess, leaf_id, small,
                                          row_mult)
            if cache_hists:
                # larger child by parent subtraction (feature_histogram.hpp:63)
                hist_large = hists[best_leaf] - hist_small
                hists = hists.at[small].set(
                    jnp.where(ok, hist_small, hists[small]))
                hists = hists.at[large].set(
                    jnp.where(ok, hist_large, hists[large]))
            elif ordered:
                hist_large = hist_of_seg(large)
            else:
                hist_large = hist_of_leaf(X, grad, hess, leaf_id, large,
                                          row_mult)
            sums = sums.at[small].set(jnp.where(ok, small_sums, sums[small]))
            sums = sums.at[large].set(jnp.where(ok, large_sums, sums[large]))

            best_small = best_of(hist_small, small_sums, feature_mask, depth)
            best_large = best_of(hist_large, large_sums, feature_mask, depth)
            neg = jnp.full((SPLIT_VEC_SIZE,), -jnp.inf, bests.dtype)
            bests = bests.at[best_leaf].set(neg)   # consumed
            bests = bests.at[small].set(jnp.where(ok, best_small, bests[small]))
            bests = bests.at[large].set(jnp.where(ok, best_large, bests[large]))

            return (step + ok.astype(jnp.int32), ~ok, leaf_id, order, lstart,
                    lcount, hists, bests, sums, tree)

        carry = (jnp.asarray(0, jnp.int32), jnp.asarray(False), leaf_id,
                 order, lstart, lcount, hists, bests, sums, tree)
        carry = lax.while_loop(cond, body, carry)
        leaf_id, tree = carry[2], carry[-1]
        return tree, leaf_id

    return grow
