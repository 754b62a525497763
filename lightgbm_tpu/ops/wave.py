"""Wave growth — best-first tree construction batched for the MXU.

The reference grows leaf-wise, one split at a time, histogramming only the
smaller child's rows (serial_tree_learner.cpp:168-223).  That economics
relies on cheap random access; on TPU, random gather/scatter runs orders of
magnitude below the streaming/matmul roofline (measured ~μs/row via XLA
gather on v5e), so per-leaf row gathers lose to full passes.

The TPU-native schedule instead splits the top-W pending leaves per WAVE:

* ONE streaming partition pass moves every affected row (each row looks up
  its leaf's chosen split in an (L,K) table via a one-hot contraction — no
  gathers);
* ONE batched histogram pass computes ALL W smaller-child histograms:
  per row chunk, the bin one-hot (C, F*B) is contracted against per-child
  masked weights (C, 3W) on the MXU.  The one-hot construction (the VPU
  cost) is paid once per wave instead of once per split, and the
  contraction rides the MXU at ~25-50x the VPU rate — this is where a
  255-leaf tree's 254 histogram scans collapse.
* larger children come from parent subtraction (feature_histogram.hpp:63)
  against the same per-leaf cache the leaf-wise grower uses, and the packed
  best-split search (split_finder.py) vmaps over all 2W children.

wave_width=1 reproduces the reference's leaf-wise order EXACTLY (top-1 ==
argmax, identical node numbering to ops/grow.py).  Larger waves split the
top-W by gain simultaneously — the same greedy frontier, batched; tree
quality matches leaf-wise to benchmark noise (see tests/test_wave.py) while
training time per tree drops from O(num_leaves) full passes to
O(num_leaves / W) passes plus MXU time.

Under a data mesh the two passes are shard-local and the wave's histogram
block is psum'd ONCE per wave — W× less collective latency than per-split
reductions (data_parallel_tree_learner.cpp:148-222 analog).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..obs.timers import COUNTERS
from .grow import TreeArrays, feature_hist_view, pvary_for, vary_like
from .histogram import (chunk_rows, leaf_histogram_onehot,
                        leaf_histogram_scatter)
from .split_finder import (DEFAULT_BIN_FOR_ZERO, FEATURE, GAIN, IS_CAT,
                           LEFT_COUNT, LEFT_OUTPUT, LEFT_SUM_G, LEFT_SUM_H,
                           RIGHT_COUNT, RIGHT_OUTPUT, RIGHT_SUM_G,
                           RIGHT_SUM_H, SECOND_FEATURE, SECOND_GAIN,
                           SPLIT_VEC_SIZE, THRESHOLD, FeatureMeta,
                           SplitParams, best_splits_vmapped)

# modes implemented only as wave-schedule Pallas kernels; every
# engine/learner gate imports THIS tuple so adding a kernel variant is a
# one-line change.  Lives here (not pallas_wave.py) so CPU-only installs
# never import jax.experimental.pallas just to validate a config.
WAVE_ONLY_MODES = ("pallas_t", "pallas_ct")

# float32 holds every whole number below this and, above it, only the
# even ones: the histograms' count channel, the split search's sums over
# the bins and a split's (LEFT_COUNT, RIGHT_COUNT) are all float32
F32_WHOLE = 1 << 24

# the grow program's phases, by the names obs/timers.py SCOPES declares:
# the step program's HLO instructions are read back by them
scope = jax.named_scope


def _bin_pad(num_bins: int) -> int:
    """Padded per-feature bin width so F*Bp stays lane-friendly (shared
    policy of the Pallas wave kernels and the auto-mode VMEM gate)."""
    if num_bins <= 64:
        return 64
    return ((num_bins + 127) // 128) * 128


# a column's one-hot width is its own bins rounded up to this: bins that
# differ by a few between seeds (a bundle's rare levels) share a program
BIN_GRANULE = 32


def col_bin_pads(bins_per_col, num_bins: int) -> tuple:
    """The one-hot width of each column of a store whose widest column
    has `num_bins` bins: the column's own bins rounded up to
    `BIN_GRANULE`, so a kernel multiplies against the bins a column has
    and not against the widest column's.  () where every width is
    `_bin_pad(num_bins)`: the store is uniform and the uniform kernels
    serve it as they are."""
    pads = tuple(-(-max(int(b), 1) // BIN_GRANULE) * BIN_GRANULE
                 for b in bins_per_col)
    return () if all(p == _bin_pad(num_bins) for p in pads) else pads


def hist_block_bytes(ncols: int, bin_pad: int, width: int) -> int:
    """Bytes of the (ncols*bin_pad, 3W) f32 accumulator block the wave
    kernels keep resident in VMEM — the single geometry fact behind the
    auto-mode VMEM gate (ops/plan.py WAVE_VMEM_GATE), the
    accumulator-aware tile planner (ops/pallas_wave.py _tile_plan) and
    the static VMEM sweep (analysis/vmem.py)."""
    return ncols * bin_pad * 12 * width


def _slot_hist(ohf, match, wc, W, hist_dtype, exact_order):
    """One wave chunk's histogram contraction: (C, q) one-hot x per-child
    masked weights -> (q, 3W).  Under exact order the contraction runs
    per candidate slot in tpu_wave_width=1's operand shapes — one wide
    GEMM's reduction order varies with the (C, 3W) width and would drift
    from the pinned leaf-wise baseline by ulps.  ONE copy shared by
    wave_pass and rehist so the bit-equality-critical layout cannot
    diverge."""
    c = ohf.shape[0]
    if exact_order:
        parts = [jnp.einsum("cq,cw->qw", ohf, match[:, w:w + 1] * wc,
                            preferred_element_type=hist_dtype)
                 for w in range(W)]
        return jnp.concatenate(parts, axis=1)
    wmat = (match[:, :, None] * wc[:, None, :]).reshape(c, 3 * W)
    return jnp.einsum("cq,cw->qw", ohf, wmat,
                      preferred_element_type=hist_dtype)


def move_rows(X, keys, live, g, buf):
    """The row slab's move -> (`buf` with its first chunks written, the
    rows they hold).

    Chunk i is rows `keys[i*g : (i+1)*g]` of the row-major X, gathered
    and transposed into columns i*g.. of the (Fdev, cap) `buf`.  Only the
    chunks that hold one of the first `live` keys run (a traced trip
    count), so the move follows the rows the kernel will read, not the
    slab's capacity; what `buf` held past them stays, and is never read:
    the launch stops at the last tile with a live row, and g is a whole
    number of its tiles (ops/pallas_wave.py slab_chunk).  Where g does
    not divide cap the last chunk starts at cap - g and moves some rows
    twice, to the same place.  Keys past the table (fill rows, weight 0)
    take its last row."""
    cap = keys.shape[0]

    def chunk(i, buf):
        at = jnp.minimum(i * g, cap - g)
        rows = jnp.take(X, lax.dynamic_slice(keys, (at,), (g,)), axis=0,
                        mode="clip")
        return lax.dynamic_update_slice(buf, jnp.transpose(rows), (0, at))

    trips = (jnp.clip(live, 0, cap) + (g - 1)) // g
    return lax.fori_loop(0, trips, chunk, buf), trips * g


def pallas_wave_active(hist_mode, hist_dtype=jnp.float32, backend=None):
    """True when a Pallas wave kernel will ACTUALLY run: TPU backend, f32
    accumulation (the kernels are single-dtype), and a pallas mode.  The
    single copy of this predicate: the engine gate below asks it of the
    running backend, ops/plan.py of the `backend` it was handed."""
    return ((backend or jax.default_backend()) == "tpu"
            and hist_dtype == jnp.float32
            and hist_mode in ("pallas",) + WAVE_ONLY_MODES)


def transposed_wave_active(hist_mode, hist_dtype=jnp.float32, backend=None):
    """True when the running kernel is one of the TRANSPOSED layouts —
    i.e. a per-booster (F, N) Xt is worth materializing."""
    return (hist_mode in ("pallas_t", "pallas_ct")
            and pallas_wave_active(hist_mode, hist_dtype, backend))


def slab_active(compact: bool, hist_mode: str, hist_dtype, psum_axis,
                pallas_interpret: bool = False, backend=None) -> bool:
    """True when a wave's histogram launch reads the row slab of its
    smaller children in place of all N rows: the pallas_t kernel really
    runs (or its interpreter), on one device or on every shard of a data
    mesh (`psum_axis` decides nothing: a shard's slab holds its own rows
    of the children).  Decided from what the program can observe; the
    plan reports it (ops/plan.py Plan.slab, the learner's obs_info)."""
    runs = pallas_wave_active(hist_mode, hist_dtype, backend) or (
        pallas_interpret and hist_dtype == jnp.float32)
    return bool(compact and hist_mode == "pallas_t" and runs)


def make_wave_grow_fn(num_leaves: int, num_bins: int, meta: FeatureMeta,
                      params: SplitParams, max_depth: int,
                      wave_width: int = 16, hist_dtype=jnp.float32,
                      psum_axis: str = None, bundle=None,
                      group_bins: int = 0, cache_hists: bool = True,
                      hist_mode: str = "onehot", chunk: int = 16384,
                      packed_cols: int = 0, sparse_col_cap: int = 0,
                      with_xt: bool = False, exact_order: bool = False,
                      lookup: str = "onehot", hist_hilo: bool = True,
                      compact: bool = True,
                      pallas_interpret: bool = False,
                      col_pads: tuple = ()):
    """Bind meta/bundle onto the cached wave-grow program (same contract as
    ops/grow.make_grow_fn: grow(X, grad, hess, row_mult, feature_mask) ->
    (TreeArrays, leaf_id)).

    with_xt=True: the returned grow takes a SIXTH positional arg — the
    precomputed transposed bin matrix for the transposed Pallas kernels —
    so shard_map callers can pass a per-booster Xt instead of paying one
    (F, N) materialization per tree dispatch (the serial learner's
    keyword path, learner.py)."""
    core = make_wave_core(num_leaves, num_bins, params, max_depth,
                          wave_width, hist_dtype, psum_axis,
                          bundle is not None, group_bins, cache_hists,
                          hist_mode, chunk, packed_cols, sparse_col_cap,
                          exact_order, lookup, hist_hilo, compact,
                          pallas_interpret, col_pads)

    if with_xt:
        def grow(X, grad, hess, row_mult, feature_mask, Xt):
            return core(X, grad, hess, row_mult, feature_mask, meta,
                        bundle, Xt=Xt)
    else:
        def grow(X, grad, hess, row_mult, feature_mask):
            return core(X, grad, hess, row_mult, feature_mask, meta, bundle)

    grow.core = core
    return grow


@functools.lru_cache(maxsize=64)
def make_wave_jit(*static_args):
    """jit(make_wave_core(...)) cached on the static key so repeated
    boosters / cv folds reuse one compiled executable (the wave analog of
    grow.make_grow_jit)."""
    return jax.jit(make_wave_core(*static_args))


@functools.lru_cache(maxsize=64)
def make_wave_core(num_leaves: int, num_bins: int, params: SplitParams,
                   max_depth: int, wave_width: int, hist_dtype,
                   psum_axis: str, has_bundle: bool, group_bins: int,
                   cache_hists: bool, hist_mode: str, chunk: int,
                   packed_cols: int = 0, sparse_col_cap: int = 0,
                   exact_order: bool = False, lookup: str = "onehot",
                   hist_hilo: bool = True, compact: bool = True,
                   pallas_interpret: bool = False, col_pads: tuple = ()):
    """col_pads: the columns' own one-hot widths where the store is ragged
    and the fused kernel takes them (`col_bin_pads`, asked by ops/plan.py
    store_col_pads); () = the uniform pad for every column.

    packed_cols > 0: X is 4-bit packed (ops/pack.py, two columns per
    byte) and packed_cols is the LOGICAL column count; every chunk is
    unpacked in-scan so the full-width matrix never hits HBM (the
    dense_nbits_bin.hpp:37 bandwidth halving, TPU form).

    hist_mode == 'sparse': X is a SparseDeviceStore (ops/sparse_store.py)
    and sparse_col_cap its per-column entry bound.  The wave then pays
    O(nnz) per W splits instead of per split: the partition reads only
    the W chosen split columns (materialized from the store), and ALL W
    smaller-child histograms come from ONE segment_sum over the nonzero
    entries with segment id ``slot*(F*B) + col*B + bin``."""
    L = num_leaves
    W = max(1, min(wave_width, L - 1))
    chunk = max(int(chunk), 256)      # guard tpu_wave_chunk<=0 etc.
    hist_bins = group_bins if has_bundle else num_bins
    sparse_mode = hist_mode in ("sparse", "sparse_mxu")
    # 'sparse_mxu': X is a ChunkedSparseStore (ops/sparse_mxu.py) and
    # sparse_col_cap its per-column CHUNK bound; child histograms come
    # from the entry-chunk MXU kernel on TPU (segment_sum oracle form
    # elsewhere) instead of a segment_sum over the coordinate store
    mxu_sparse = hist_mode == "sparse_mxu"
    if sparse_mode and packed_cols:
        raise ValueError("tpu_sparse and 4-bit packing are exclusive")
    # the bin one-hot holds only 0/1 — exact in bf16 — and is the dominant
    # HBM traffic of the wave pass; on TPU the MXU also multiplies bf16
    # natively.  Weights and the accumulator stay in hist_dtype.
    oh_dtype = (jnp.bfloat16
                if jax.default_backend() == "tpu"
                and hist_dtype == jnp.float32 else hist_dtype)
    # fused Pallas kernels (ops/pallas_wave.py): generate the one-hot in
    # VMEM instead of materializing (chunk, F*B) blocks through HBM.
    # Opt-in (hist_mode='pallas' row-major / 'pallas_t' transposed) while
    # their end-to-end win is validated; precision is handled by the bf16
    # hi/lo weight split (manual rounding — Mosaic's cast truncates).
    # pallas_interpret=True (tests only) runs the Pallas kernels in
    # interpret mode on any backend, so the kernel engine paths — the
    # row slab included — are CPU-testable end-to-end
    use_pallas_hist = pallas_wave_active(hist_mode, hist_dtype) or (
        pallas_interpret and hist_dtype == jnp.float32
        and hist_mode in ("pallas",) + WAVE_ONLY_MODES)
    # 'pallas_ct' (v5) is fused (partition + histogram in one kernel,
    # ONE read of Xt per wave) and transposed; the earlier fused
    # variants pallas_f/pallas_ft were deleted after losing every
    # on-chip A/B to pallas_t (tools/AB_RESULTS.md, BENCH_NOTES.md r4)
    pallas_transposed = hist_mode in ("pallas_t", "pallas_ct")
    pallas_fused = hist_mode == "pallas_ct"
    # the row slab (slab_hist below) serves the split pipeline, partition
    # scan then pallas_t, on one device and on every shard of a data
    # mesh; the fused ct kernel routes and histograms in one read and
    # has none.  `compact` is no knob (no config key reaches it): False
    # lets a test grow the same tree without the slab
    compact = slab_active(compact, hist_mode, hist_dtype, psum_axis,
                          pallas_interpret)

    def maybe_psum(x, sent):
        """`x` summed over the mesh's shards; its element count joins
        `sent`, the operands this trace hands to the all-reduce."""
        if psum_axis is not None:
            sent.append(x.size)
            with scope("hist_allreduce"):
                return lax.psum(x, psum_axis)
        return x

    def to_feature_hist(ghist, sums, meta, bundle):
        return feature_hist_view(ghist, sums, meta, bundle, has_bundle,
                                 fix_default=sparse_mode)

    # scatter-add serializes on TPU (~226ms vs onehot's 7.2ms at 1Mx28,
    # B=63) — only the explicit 'scatter' mode should pay it; the pallas
    # modes keep the fast one-hot root (once per tree, before the kernel
    # takes over the per-wave work)
    root_hist_fn = (leaf_histogram_scatter if hist_mode == "scatter"
                    else leaf_histogram_onehot)

    def grow(X, grad, hess, row_mult, feature_mask, meta, bundle, Xt=None):
        n = grad.shape[0]       # X may be a SparseDeviceStore pytree
        if sparse_mode:
            Fc = X.fill.shape[0]
        else:
            Fc = packed_cols or X.shape[1]    # LOGICAL group columns
        if packed_cols:
            from .pack import unpack4
            unpack = lambda xc: unpack4(xc, Fc)  # noqa: E731
        else:
            unpack = lambda xc: xc               # noqa: E731
        with scope("gradients"):
            grad = grad.astype(hist_dtype)
            hess = hess.astype(hist_dtype)
            row_mult = row_mult.astype(hist_dtype)
            w3 = jnp.stack([grad * row_mult, hess * row_mult, row_mult],
                           axis=-1)       # (N, 3) per-row weight channels
        leaf_id = jnp.zeros(n, dtype=jnp.int32)
        if psum_axis is not None:
            leaf_id = pvary_for(leaf_id, psum_axis)

        c = min(chunk, max(n, 1))
        pad = (-n) % c
        nch = (n + pad) // c
        if not sparse_mode:
            with scope("wave_partition"):
                # the chunk loops below take windows of Xp itself
                # (histogram.chunk_rows), never a (nch, c, F) reshape
                Xp = jnp.pad(X, ((0, pad), (0, 0))) if pad else X
        # ---- the row slab: a wave keeps the histograms of its smaller
        # children only, and by count those hold at most half the rows
        # (0.28 N on average over a 255-leaf tree).  The partition has
        # already run when the kernel starts, so the rows it needs are
        # known: gather them, in row order, into a slab of N/2 rows and
        # let the kernel stop at the slab's last full tile — the
        # reference's leaf-ordered economics (touch only the rows of the
        # leaves being split, ordered_sparse_bin.hpp:26-209) with ONE
        # static shape.  cap is the slab launch's own tile multiple, so
        # that launch pads nothing.
        slab_cap = slab_tile = slab_g = 0
        if compact and not sparse_mode:
            from .pallas_wave import slab_chunk, slab_plan
            slab_cap, slab_tile = slab_plan(n, Fc, hist_bins, W,
                                            packed=bool(packed_cols))
            slab_g = slab_chunk(slab_cap, slab_tile)
            if slab_cap >= n:          # a single row: nothing to skip
                slab_cap = 0
        # transposed matrix for the v2 kernel (MXU-native dot orientation):
        # callers that hold X for many trees pass a precomputed Xt (the
        # learner materializes it once per booster); otherwise fall back to
        # one (F, N) materialization per tree dispatch.  The slab is
        # gathered from the row-major X: with it only the no-cache
        # `rehist` reads Xt
        if (use_pallas_hist and pallas_transposed and Xt is None
                and not (slab_cap and cache_hists)):
            with scope("wave_histogram"):
                Xt = jnp.transpose(X)

        # ---- sparse (coordinate-store) variants: partition reads ONLY
        # the W chosen split columns; all W child histograms are ONE
        # segment_sum over the nonzeros
        # the three O(nnz) weight-channel gathers are tree-constant —
        # hoisted to ONE gather per tree (gather_entry_weights); only
        # the leaf-id gather stays per-wave
        if mxu_sparse and (jax.default_backend() == "tpu"
                           and hist_dtype == jnp.float32):
            from .sparse_mxu import gather_entry_weights
            with scope("wave_histogram"):
                mxu_entry_w = gather_entry_weights(X, w3)
        else:
            mxu_entry_w = None

        @scope("wave_histogram")
        def sparse_child_hists(lid, ids, valid):
            if mxu_sparse:
                from .sparse_mxu import (chunked_child_hists_ref,
                                         sparse_wave_histogram_mxu)
                cid = jnp.where(valid, ids, -1)
                if (jax.default_backend() == "tpu"
                        and hist_dtype == jnp.float32):
                    return sparse_wave_histogram_mxu(
                        X, lid, w3, cid, hist_bins, Fc, hilo=hist_hilo,
                        entry_weights=mxu_entry_w, num_leaves=L)
                return chunked_child_hists_ref(
                    X, lid, w3, cid, hist_bins, Fc, L)
            slot_tbl = jnp.full(L, -1, jnp.int32).at[
                jnp.where(valid, ids, L)].set(
                    jnp.arange(W, dtype=jnp.int32), mode="drop")
            leaf_nz = jnp.take(lid, X.nz_row)
            slot = jnp.take(slot_tbl, leaf_nz)             # (nnz,)
            wnz = jnp.take(w3, X.nz_row, axis=0)           # (nnz, 3)
            # the sharded store pads sections with nz_seg == Fc*B (one
            # past the histogram); the slot offset must not relocate
            # those pads into the NEXT slot's valid range
            real = (slot >= 0) & (X.nz_seg < Fc * hist_bins)
            seg = jnp.where(real,
                            slot * (Fc * hist_bins) + X.nz_seg,
                            W * Fc * hist_bins)            # drop
            flat = jax.ops.segment_sum(
                wnz, seg, num_segments=W * Fc * hist_bins)
            return flat.reshape(W, Fc, hist_bins, 3)

        def route_rows(r, colv, lc):
            """Split routing shared by the dense chunk scan and the
            sparse pass: bundle remap, threshold compare, default-bin
            redirect, right-child move (dense_bin.hpp:190-222)."""
            if has_bundle:
                goff = r[:, 7].astype(jnp.int32)
                in_range = ((colv >= goff)
                            & (colv < goff + r[:, 9].astype(jnp.int32)))
                colv = jnp.where(in_range,
                                 colv - goff + r[:, 8].astype(jnp.int32),
                                 r[:, 4].astype(jnp.int32))
            thr_r = r[:, 2].astype(jnp.int32)
            gl = jnp.where(r[:, 3] > 0.5, colv == thr_r, colv <= thr_r)
            gl = jnp.where(colv == r[:, 4].astype(jnp.int32),
                           r[:, 5] > 0.5, gl)
            active = r[:, 0] > 0.5
            return jnp.where(active & ~gl, r[:, 6].astype(jnp.int32), lc)

        def sparse_wave_pass(lid, tbl, small_id, valid, col_ids):
            if mxu_sparse:
                from .sparse_mxu import chunked_split_column as _colfn
            else:
                from .sparse_store import sparse_split_column as _colfn
            with scope("wave_partition"):
                r = jnp.take(tbl, lid, axis=0)             # (N, 10)
                cj = r[:, 1].astype(jnp.int32)
                colv = jnp.zeros(n, jnp.int32)
                for w in range(W):                         # static W
                    vals = _colfn(X, col_ids[w], n, sparse_col_cap)
                    colv = jnp.where(cj == col_ids[w], vals, colv)
                new_lid = route_rows(r, colv, lid)
            return new_lid, sparse_child_hists(new_lid, small_id, valid)

        # (rows the slab launches visited, rows the move gathered): a
        # wave that ran no slab
        no_rows = jnp.zeros(2, jnp.int32)

        @scope("wave_histogram")
        def pallas_hist(lid, cid):
            """Dispatch to the fused kernel in the configured layout —
            the single call site for both wave_pass and rehist."""
            if pallas_transposed:
                from .pallas_wave import wave_histogram_pallas_t
                return wave_histogram_pallas_t(Xt, lid, w3, cid, hist_bins,
                                               logical_cols=packed_cols,
                                               hilo=hist_hilo,
                                               interpret=pallas_interpret)
            from .pallas_wave import wave_histogram_pallas
            return wave_histogram_pallas(X, lid, w3, cid, hist_bins,
                                         logical_cols=packed_cols,
                                         hilo=hist_hilo,
                                         interpret=pallas_interpret)

        def wave_pass(leaf_id, tbl, cols, psrc, small_id, valid):
            """Partition + child histograms, fused into ONE chunked sweep.

            Per chunk: rows look up their leaf's split row in the split
            table (`lookup` strategy below), route left/right (the
            partition), then the chunk's bin one-hot (C, Fc*B) is contracted
            against per-child masked weights (C, 3W) on the MXU.  Nothing
            N x L or N x W is ever materialized.  Shard-local; callers psum
            the histogram block.

            Lookup strategies for the per-row split row `r` (C, 10):
            - 'onehot': (C, L) leaf one-hot @ (L, 10) table on the MXU —
              exact f32, but the one-hot costs L*4 bytes/row of traffic.
            - 'compact': each row matches at most ONE of the W wave
              parents (splits are disjoint), so r is a masked sum over
              the (W, 10) rows — W/L of the one-hot footprint and the
              sum has <=1 nonzero term (exact in any order).
            - 'gather': r = tbl[leaf_id] — the form the sparse pass
              already uses; XLA's TPU gather economics decide.

            On TPU the histogram half runs as the Pallas kernel (one-hot
            generated in VMEM, ops/pallas_wave.py) and the scan below
            only partitions; 'pallas_ct' fuses BOTH halves into one
            kernel — a single read of Xt per wave.

            Returns (new leaf ids, (W, Fc, B, 3) histograms, the rows the
            slabs' launches visited and their moves gathered: this shard's
            own, like the sums).
            """
            if use_pallas_hist and pallas_fused:
                from .pallas_wave import wave_partition_hist_pallas_ct
                # one kernel routes and histograms: it reads as histogram
                with scope("wave_histogram"):
                    return wave_partition_hist_pallas_ct(
                        Xt, leaf_id, w3,
                        jnp.where(valid, small_id, -1), cols, psrc,
                        hist_bins, bundled=has_bundle,
                        logical_cols=packed_cols, hilo=hist_hilo,
                        interpret=pallas_interpret,
                        col_pads=col_pads) + (no_rows,)
            with scope("wave_partition"):
                lb = jnp.pad(leaf_id, (0, pad)) if pad else leaf_id
                wpad = jnp.pad(w3, ((0, pad), (0, 0))) if pad else w3
                l_iota = jnp.arange(L, dtype=jnp.int32)
                f_iota = jnp.arange(Fc, dtype=jnp.int32)

            def step(i, carry):
                acc, lids = carry       # chunk i's leaf ids move in place
                with scope("wave_partition"):   # (C,F) (C,) (C,3)
                    xc, lc, wc = chunk_rows((Xp, lids, wpad), i, c)
                    xc = unpack(xc)                 # (C, Fc) logical bins
                    if lookup == "compact":
                        # <=1 match per row, so the sum is exact and XLA
                        # can fuse the (C, W, 10) broadcast into the
                        # reduction — no (C, L) one-hot ever exists
                        pm = lc[:, None] == psrc[None, :]      # (C, W)
                        r = jnp.sum(
                            jnp.where(pm[:, :, None], cols[None, :, :],
                                      0.0), axis=1)            # (C, 10)
                    elif lookup == "gather":
                        r = jnp.take(tbl, jnp.clip(lc, 0, L - 1), axis=0)
                    else:
                        leaf_oh = (lc[:, None] == l_iota[None, :]).astype(
                            jnp.float32)            # (C, L)
                        # HIGHEST: TPU's default matmul precision is bf16,
                        # which rounds integer table entries above 256
                        # (column ids, thresholds, leaf ids) — the lookup
                        # must be exact f32
                        r = jnp.matmul(leaf_oh, tbl,
                                       precision=lax.Precision.HIGHEST)
                    cj = r[:, 1].astype(jnp.int32)
                    colv = jnp.sum(
                        jnp.where(cj[:, None] == f_iota[None, :], xc, 0)
                        .astype(jnp.int32), axis=1)  # (C,) split-column bin
                    lc2 = route_rows(r, colv, lc)
                if not use_pallas_hist:
                    with scope("wave_histogram"):
                        # child-masked weights: (C, W) match x (C, 3)
                        match = ((lc2[:, None] == small_id[None, :])
                                 & valid[None, :]).astype(hist_dtype)
                        oh = jax.nn.one_hot(xc.astype(jnp.int32), hist_bins,
                                            dtype=oh_dtype)  # (C, Fc, B)
                        acc = acc + _slot_hist(
                            oh.reshape(c, Fc * hist_bins), match, wc, W,
                            hist_dtype, exact_order)
                with scope("wave_partition"):
                    lids = lax.dynamic_update_slice_in_dim(lids, lc2, i * c,
                                                           axis=0)
                return acc, lids

            acc_shape = ((Fc * hist_bins, 3 * W) if not use_pallas_hist
                         else (1, 1))
            init = jnp.zeros(acc_shape, dtype=hist_dtype)
            if nch == 1:
                flat, lid2 = step(0, (init, lb))
            else:
                if not use_pallas_hist:
                    init = vary_like(init, Xp, lb, wpad)
                flat, lid2 = lax.fori_loop(0, nch, step, (init, lb))
            new_leaf_id = lid2[:n] if pad else lid2
            visited = no_rows
            if use_pallas_hist:
                cid = jnp.where(valid, small_id, -1)
                if slab_cap:
                    hist, visited = slab_hist(new_leaf_id, cid)
                else:
                    hist = pallas_hist(new_leaf_id, cid)
            else:
                # (Fc*B, W*3) -> (W, Fc, B, 3)
                with scope("wave_histogram"):
                    hist = flat.reshape(Fc, hist_bins, W, 3).transpose(
                        2, 0, 1, 3)
            return new_leaf_id, hist, visited

        def slab_hist(leaf_id, cid):
            """Histograms of the children `cid` from slabs of their rows
            -> (hist, (rows the launches visited, rows the moves gathered)).

            One sort puts the children's rows first, in row order; a
            slab is the next `slab_cap` of them, gathered from X by
            chunks up to the last live one (move_rows).  With
            all weights 1, on one device, one slab holds them all (a
            smaller child by count has at most half its parent's rows).
            The count is WEIGHTED and the slab holds ROWS, so under
            bagging, GOSS or row_mult 0 rows they can be more than half:
            then a second slab follows, and nothing is ever truncated.

            Under a data mesh "at most half" is a GLOBAL argument, not a
            local one: the children are the smaller ones by the SUMMED
            histograms, and a shard gathers ITS OWN rows of them, which
            may be most of the shard (rows sorted by a split column, a
            skewed file split) or none.  So `n_active` and the loop's
            trip count are per-shard values: the second slab takes what
            the first cannot hold, and a shard that holds none runs the
            loop zero times and hands zeros to the wave's psum.  Shards
            may differ in trips only because the loop holds no
            collective: the psum follows it (the wave's body).

            Exactness: a row outside the slabs matches no child, so it
            adds 0.0 to every sum; the rows keep their order, so each
            slot's rows enter its sums in the order the full pass adds
            them and only tile (and slab) boundaries move: bit-equal at
            one tile, f32 ulps across tiles (tests/test_wave_compact.py).
            Fill rows carry leaf -2 and weight 0, as the kernel's own
            padding does."""
            from .pallas_wave import wave_histogram_pallas_t
            with scope("wave_compact"):
                mask = jnp.any(leaf_id[:, None] == cid[None, :], axis=1)
                n_active = jnp.sum(mask.astype(jnp.int32))
                # ONE sort moves the per-row operands: its keys are the
                # active rows' numbers, in row order, then the others'
                # (>= n).  On the v5e (PERF.md, PR 27) it takes 5.8 /
                # 12.6 ms a wave at 1.2M / 2.5M rows, where a
                # one-operand sort and three element gathers take 10.7
                # / 33.8 ms and jnp.nonzero alone 11.5 / 23.6 ms.
                # Padded to two slabs, so a slab's window never runs off
                rows = jnp.arange(n, dtype=jnp.int32)
                order = [jnp.pad(x, (0, 2 * slab_cap - n)) for x in lax.sort(
                    (jnp.where(mask, rows, n + rows), leaf_id,
                     w3[:, 0], w3[:, 1], w3[:, 2]), num_keys=1)]

            def slab(j, acc):
                hist, rows_seen = acc
                with scope("wave_compact"):
                    key, lid_c, *w_c = (
                        lax.dynamic_slice(x, (j * slab_cap,), (slab_cap,))
                        for x in order)
                    left = n_active - j * slab_cap     # active from here on
                    live = jnp.arange(slab_cap, dtype=jnp.int32) < left
                    lid_c = jnp.where(live, lid_c, -2)
                    w3_c = jnp.where(live[:, None], jnp.stack(w_c, -1), 0.0)
                    # the buffer starts out as the allocator left it:
                    # fill rows' bins may be anything (their weight is
                    # 0, and past the last live tile nothing is read),
                    # and zeros cost 2.3 ms a wave at 2,000 columns.  Its
                    # layout is pinned to the kernel's: left free, XLA
                    # lays it out column-major, which spares the chunks
                    # their transposes and transposes all slab_cap rows
                    # after the loop instead
                    buf = with_layout_constraint(
                        lax.empty((X.shape[1], slab_cap), X.dtype),
                        Layout(major_to_minor=(0, 1)))
                    xt_c, moved = move_rows(X, key, left, slab_g,
                                            vary_like(buf, leaf_id))
                with scope("wave_histogram"):
                    hist = hist + wave_histogram_pallas_t(
                        xt_c, lid_c, w3_c, cid, hist_bins,
                        logical_cols=packed_cols, hilo=hist_hilo,
                        interpret=pallas_interpret, n_active=left)
                tiles = (jnp.minimum(left, slab_cap)
                         + (slab_tile - 1)) // slab_tile
                return hist, rows_seen + jnp.stack([tiles * slab_tile,
                                                    moved])

            with scope("wave_histogram"):
                zero = jnp.zeros((W, Fc, hist_bins, 3), hist_dtype)
            # the carry enters the loop as it leaves: shard-local sums
            return lax.fori_loop(
                0, (n_active + (slab_cap - 1)) // slab_cap, slab,
                vary_like((zero, no_rows), leaf_id))

        @scope("wave_histogram")
        def rehist(leaf_id, ids, valid):
            """Histograms of `ids` children only (no partition) — the
            no-cache larger-child pass."""
            if use_pallas_hist:
                return pallas_hist(leaf_id, jnp.where(valid, ids, -1))
            lb = jnp.pad(leaf_id, (0, pad)) if pad else leaf_id
            wpad = jnp.pad(w3, ((0, pad), (0, 0))) if pad else w3

            def step(i, acc):
                xc, lc, wc = chunk_rows((Xp, lb, wpad), i, c)
                xc = unpack(xc)
                match = ((lc[:, None] == ids[None, :])
                         & valid[None, :]).astype(hist_dtype)
                oh = jax.nn.one_hot(xc.astype(jnp.int32), hist_bins,
                                    dtype=oh_dtype)
                return acc + _slot_hist(
                    oh.reshape(c, Fc * hist_bins), match, wc, W,
                    hist_dtype, exact_order)

            init = jnp.zeros((Fc * hist_bins, 3 * W), dtype=hist_dtype)
            if nch == 1:
                flat = step(0, init)
            else:
                flat = lax.fori_loop(0, nch, step,
                                     vary_like(init, Xp, lb, wpad))
            return flat.reshape(Fc, hist_bins, W, 3).transpose(2, 0, 1, 3)

        @scope("split_search")
        def best_of_many(hists_k, sums_k, depths_k, feature_mask, meta,
                         bundle):
            """vmapped packed best-split search over K children — the
            shared split_finder helper with the EFB/default-bin view
            applied inside the vmap."""
            return best_splits_vmapped(
                hists_k, sums_k, depths_k, meta, feature_mask, params,
                max_depth,
                hist_view=lambda h, s: to_feature_hist(h, s, meta, bundle))

        # ---- root
        with scope("root_histogram"):
            root_sums = jnp.sum(w3, axis=0)
            if mxu_sparse:
                # root histogram through the same kernel call shape as the
                # wave passes (one compiled executable): slot 0 targets the
                # root, the other W-1 slots are inactive
                hist0 = sparse_child_hists(
                    leaf_id, jnp.zeros(W, jnp.int32),
                    jnp.arange(W) == 0)[0]
            elif sparse_mode:
                from .sparse_store import leaf_histogram_sparse
                hist0 = leaf_histogram_sparse(
                    X, grad, hess, leaf_id, 0, row_mult, hist_bins, Fc)
            else:
                # where the wave kernels make two bf16 products a weight
                # the root does too: the larger child is its parent less
                # the smaller, and down that chain a leaf would be left
                # with all of a once-rounded root's error
                root_kw = ({"chunk": chunk,
                            "hilo": bool(use_pallas_hist and hist_hilo),
                            "col_pads": col_pads}
                           if root_hist_fn is leaf_histogram_onehot else {})
                hist0 = root_hist_fn(
                    X, grad, hess, leaf_id, 0, row_mult, num_bins=hist_bins,
                    logical_cols=packed_cols, **root_kw)
        root_sent = []
        root_sums = maybe_psum(root_sums, root_sent)
        hist0 = maybe_psum(hist0, root_sent)
        Fh, B = hist0.shape[0], hist0.shape[1]
        with scope("root_histogram"):
            if cache_hists:
                hists = jnp.zeros((L, Fh, B, 3), hist_dtype).at[0].set(hist0)
            else:
                hists = jnp.zeros((0,), hist_dtype)
        bests = jnp.full((L, SPLIT_VEC_SIZE), -jnp.inf, dtype=hist_dtype)
        bests = bests.at[0].set(best_of_many(
            hist0[None], root_sums[None], jnp.zeros(1, jnp.int32),
            feature_mask, meta, bundle)[0])
        sums = jnp.zeros((L, 3), hist_dtype).at[0].set(root_sums)
        # what the loop counts (obs/timers.py COUNTERS): each wave adds
        # [1, W, k, kc, rows of the committed smaller children, 0, rows
        # its slab launches visited, rows their moves gathered, 1 if it
        # ran any, elements it handed to the all-reduce]; `rows` is the
        # rows every full pass visits.
        # Under a mesh the record is the mesh's: every shard's n rows
        # (the child counts already are global, they come from the summed
        # histograms), the rows ALL shards' slab launches visited and
        # moved (two words more through the wave's all-reduce), and the
        # elements ONE shard hands over, the root's to start with
        shards = 1 if psum_axis is None else lax.psum(1, psum_axis)
        counters = jnp.zeros(len(COUNTERS), jnp.int32).at[
            COUNTERS.index("rows")].set(n * shards).at[
            COUNTERS.index("allreduce_words")].set(sum(root_sent))
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
            counters=counters,
        )

        def cond(carry):
            nn, done = carry[0], carry[1]
            return (nn < L - 1) & ~done

        def body(carry):
            nn, done, leaf_id, hists, bests, sums, tree = carry
            with scope("split_search"):     # the frontier: top W by gain
                gains = bests[:, GAIN]
                budget = (L - 1) - nn
                gw, lw = lax.top_k(gains, W)
                rank = jnp.arange(W, dtype=jnp.int32)
                valid = (gw > 0.0) & (rank < budget)
                k = jnp.sum(valid.astype(jnp.int32))
                parent = lw.astype(jnp.int32)          # distinct leaf ids
                info = bests[parent]                   # (W, V)
                node = nn + rank                       # internal node ids
                newleaf = node + 1                     # right-child leaf ids

            with scope("wave_partition"):   # the wave's split tables
                f_w = info[:, FEATURE].astype(jnp.int32)
                thr_w = info[:, THRESHOLD].astype(jnp.int32)
                dbz_w = info[:, DEFAULT_BIN_FOR_ZERO].astype(jnp.int32)
                cat_w = info[:, IS_CAT] > 0.5
                fdef_w = meta.default_bin[f_w]
                dleft_w = jnp.where(cat_w, dbz_w == thr_w, dbz_w <= thr_w)

                # ---- per-leaf split tables, fused into one (L, K) f32
                # matrix (all entries < 2^24, exact in f32) looked up per
                # row by a one-hot contraction — no row gathers anywhere
                src = jnp.where(valid, parent, L)      # L -> dropped
                if has_bundle:
                    col_w = bundle.group_of[f_w]
                    goff_w = bundle.bin_off[f_w]
                    adj_w = bundle.bin_adj[f_w]
                    span_w = bundle.bin_span[f_w]
                else:
                    col_w = f_w
                    goff_w = jnp.zeros(W, jnp.int32)
                    adj_w = jnp.zeros(W, jnp.int32)
                    span_w = jnp.full(W, num_bins, jnp.int32)
                cols = jnp.stack([
                    jnp.ones(W, jnp.float32),              # 0: active
                    col_w.astype(jnp.float32),             # 1: device column
                    thr_w.astype(jnp.float32),             # 2: threshold bin
                    cat_w.astype(jnp.float32),             # 3: categorical
                    fdef_w.astype(jnp.float32),            # 4: default bin
                    dleft_w.astype(jnp.float32),           # 5: default left
                    newleaf.astype(jnp.float32),           # 6: right leaf id
                    goff_w.astype(jnp.float32),            # 7: group offset
                    adj_w.astype(jnp.float32),             # 8: bin adjust
                    span_w.astype(jnp.float32),            # 9: bin span
                ], axis=-1)                                # (W, 10)
                tbl = jnp.zeros((L, 10), jnp.float32).at[src].set(
                    cols, mode="drop")
                # compact-lookup operands: the W parent ids (invalid slots
                # get -3, which no real/padded leaf id ever equals) and the
                # raw (W, 10) rows — invalid rows can hold garbage, they
                # never match
                psrc = jnp.where(valid, parent, -3)

                # ---- fused partition + children histograms (one sweep)
                left_small = info[:, LEFT_COUNT] < info[:, RIGHT_COUNT]
                small_id = jnp.where(left_small, parent, newleaf)
                large_id = jnp.where(left_small, newleaf, parent)
            if sparse_mode:
                visited = no_rows
                leaf_id, hist_small = sparse_wave_pass(
                    leaf_id, tbl, small_id, valid, col_w)
            else:
                leaf_id, hist_small, visited = wave_pass(
                    leaf_id, tbl, cols, psrc, small_id, valid)
            sent = []
            hist_small = maybe_psum(hist_small, sent)       # (W, F, B, 3)
            if slab_cap:
                visited = maybe_psum(visited, sent)
            if cache_hists:
                with scope("wave_histogram"):
                    hist_large = hists[parent] - hist_small
            else:
                hist_large = maybe_psum(
                    sparse_child_hists(leaf_id, large_id, valid)
                    if sparse_mode else rehist(leaf_id, large_id, valid),
                    sent)

            # ---- vectorized split search for all 2W children
            with scope("split_search"):
                left_sums = jnp.stack([info[:, LEFT_SUM_G],
                                       info[:, LEFT_SUM_H],
                                       info[:, LEFT_COUNT]], axis=-1)
                right_sums = jnp.stack([info[:, RIGHT_SUM_G],
                                        info[:, RIGHT_SUM_H],
                                        info[:, RIGHT_COUNT]], axis=-1)
                small_sums = jnp.where(left_small[:, None], left_sums,
                                       right_sums)
                large_sums = jnp.where(left_small[:, None], right_sums,
                                       left_sums)
                depth = tree.leaf_depth[parent] + 1             # (W,)
                hists_k = jnp.concatenate([hist_small, hist_large])
                sums_k = jnp.concatenate([small_sums, large_sums])
                depths_k = jnp.concatenate([depth, depth])
                bests_k = best_of_many(hists_k, sums_k, depths_k,
                                       feature_mask, meta, bundle)  # (2W, V)

            with scope("tree_commit"):
                if exact_order:
                    # ---- EXACT leaf-wise order: the candidates were
                    # ranked by pre-wave gain, so leaf-wise would commit
                    # them in rank order UNTIL a child created earlier in
                    # the wave outranks the next candidate (the reference
                    # would split that child next,
                    # serial_tree_learner.cpp:203).  Commit exactly that
                    # prefix; roll the rest back below.  Histograms are
                    # reduction-order-identical across wave widths, so
                    # trees match tpu_wave_width=1 (the pinned leaf-wise
                    # order) bit-for-bit (the per-candidate contractions
                    # below keep reductions W=1-shaped) —
                    # tests/test_wave_exact_order.py.
                    sg, lg = bests_k[:W, GAIN], bests_k[W:, GAIN]
                    cg = jnp.maximum(sg, lg)
                    cg = jnp.where(valid, cg, -jnp.inf)
                    # leaf id attaining each candidate's child max (ties ->
                    # smaller id, matching top_k's first-occurrence pick)
                    cid = jnp.where(
                        (sg > lg) | ((sg == lg) & (small_id <= large_id)),
                        small_id, large_id)
                    # running (max gain, smallest id attaining it) over the
                    # committed prefix — W=1's top_k breaks exact gain ties
                    # by LOWEST LEAF ID, so the stop rule must too
                    def pairmax(a, b):
                        ga, ia = a
                        gb, ib = b
                        take_a = (ga > gb) | ((ga == gb) & (ia <= ib))
                        return (jnp.where(take_a, ga, gb),
                                jnp.where(take_a, ia, ib))
                    run, rid = lax.associative_scan(pairmax, (cg, cid))
                    mx = jnp.concatenate([jnp.full((1,), -jnp.inf, cg.dtype),
                                          run[:-1]])              # before t
                    mid = jnp.concatenate([jnp.zeros((1,), cid.dtype),
                                           rid[:-1]])
                    stop = (mx > gw) | ((mx == gw) & (mid < parent))  # (W,)
                    t_idx = jnp.where(jnp.any(stop),
                                      jnp.argmax(stop).astype(jnp.int32),
                                      jnp.asarray(W, jnp.int32))
                    kc = jnp.minimum(t_idx, k)
                    commit = rank < kc
                    # rollback: rows provisionally routed to an
                    # uncommitted right child return to the parent — ONE
                    # (L,)-table gather over leaf ids, no pass over X
                    undo = valid & ~commit
                    remap = jnp.arange(L, dtype=jnp.int32).at[
                        jnp.where(undo, newleaf, L)].set(parent, mode="drop")
                    leaf_id = jnp.take(remap, leaf_id)
                else:
                    commit, kc = valid, k

                if cache_hists:
                    hsrc = jnp.where(commit, small_id, L)
                    hists = hists.at[hsrc].set(hist_small, mode="drop")
                    lsrc = jnp.where(commit, large_id, L)
                    hists = hists.at[lsrc].set(hist_large, mode="drop")
                ssrc = jnp.where(commit, small_id, L)
                lsrc2 = jnp.where(commit, large_id, L)
                bests = bests.at[ssrc].set(bests_k[:W], mode="drop")
                bests = bests.at[lsrc2].set(bests_k[W:], mode="drop")
                sums = sums.at[ssrc].set(small_sums, mode="drop")
                sums = sums.at[lsrc2].set(large_sums, mode="drop")

                # ---- tree bookkeeping, vectorized over the wave
                nsrc = jnp.where(commit, node, L - 1 + 64)      # drop sentinel
                tparent = tree.leaf_parent[parent]              # (W,)
                # grandparent child-pointer fix: each split's (parent node,
                # side) slot is unique, so the W scatters cannot collide
                gp = jnp.maximum(tparent, 0)
                was_left = tree.left_child[gp] == ~parent
                fix = commit & (tparent >= 0)
                lc = tree.left_child.at[jnp.where(fix & was_left, gp, L + 63)
                                        ].set(node, mode="drop")
                rc = tree.right_child.at[jnp.where(fix & ~was_left, gp, L + 63)
                                         ].set(node, mode="drop")
                lc = lc.at[nsrc].set(~parent, mode="drop")
                rc = rc.at[nsrc].set(~newleaf, mode="drop")
                lsrc3 = jnp.where(commit, parent, L)
                rsrc3 = jnp.where(commit, newleaf, L)
                tree = tree._replace(
                    num_leaves=tree.num_leaves + kc,
                    split_feature=tree.split_feature.at[nsrc].set(
                        f_w, mode="drop"),
                    threshold_bin=tree.threshold_bin.at[nsrc].set(
                        thr_w, mode="drop"),
                    default_bin_for_zero=tree.default_bin_for_zero.at[
                        nsrc].set(dbz_w, mode="drop"),
                    default_bin=tree.default_bin.at[nsrc].set(
                        fdef_w, mode="drop"),
                    is_cat=tree.is_cat.at[nsrc].set(
                        cat_w.astype(jnp.int32), mode="drop"),
                    left_child=lc,
                    right_child=rc,
                    split_gain=tree.split_gain.at[nsrc].set(
                        info[:, GAIN], mode="drop"),
                    internal_value=tree.internal_value.at[nsrc].set(
                        tree.leaf_value[parent], mode="drop"),
                    internal_count=tree.internal_count.at[nsrc].set(
                        (info[:, LEFT_COUNT]
                         + info[:, RIGHT_COUNT]).astype(jnp.int32),
                        mode="drop"),
                    leaf_parent=tree.leaf_parent.at[lsrc3].set(
                        node, mode="drop").at[rsrc3].set(node, mode="drop"),
                    leaf_value=tree.leaf_value.at[lsrc3].set(
                        info[:, LEFT_OUTPUT], mode="drop").at[rsrc3].set(
                            info[:, RIGHT_OUTPUT], mode="drop"),
                    leaf_count=tree.leaf_count.at[lsrc3].set(
                        info[:, LEFT_COUNT].astype(jnp.int32),
                        mode="drop").at[rsrc3].set(
                            info[:, RIGHT_COUNT].astype(jnp.int32),
                            mode="drop"),
                    leaf_depth=tree.leaf_depth.at[lsrc3].set(
                        depth, mode="drop").at[rsrc3].set(depth, mode="drop"),
                    second_feature=tree.second_feature.at[nsrc].set(
                        info[:, SECOND_FEATURE].astype(jnp.int32),
                        mode="drop"),
                    second_gain=tree.second_gain.at[nsrc].set(
                        jnp.where(jnp.isfinite(info[:, SECOND_GAIN]),
                                  info[:, SECOND_GAIN], 0.0), mode="drop"),
                    counters=tree.counters + jnp.stack([
                        jnp.asarray(1, jnp.int32), jnp.asarray(W, jnp.int32),
                        k, kc,
                        jnp.sum(jnp.where(commit, jnp.minimum(
                            info[:, LEFT_COUNT],
                            info[:, RIGHT_COUNT]).astype(jnp.int32), 0)),
                        jnp.asarray(0, jnp.int32), visited[0], visited[1],
                        jnp.asarray(int(bool(slab_cap)), jnp.int32),
                        jnp.asarray(sum(sent), jnp.int32)]),
                )
            return (nn + kc, kc == 0, leaf_id, hists, bests, sums, tree)

        carry = (jnp.asarray(0, jnp.int32), jnp.asarray(False), leaf_id,
                 hists, bests, sums, tree)
        carry = lax.while_loop(cond, body, carry)
        tree, leaf_id = carry[-1], carry[2]
        if cache_hists and not sparse_mode:
            # a split's (LEFT_COUNT, RIGHT_COUNT) are float32 sums: a node
            # of 2^24 rows or more has a rounded count, and `rest = total
            # - accumulated` (ops/split_finder.py) hands the row or two it
            # is off down the chain of larger children to one leaf (read
            # on the chip at 41,943,040 rows, PR 33: 2-6 rows a run over
            # three trees).  A leaf's cached histogram holds its count
            # bin by bin, each exact while the root's fullest bin of that
            # column stays under 2^24: a kernel's sum of ones, or a
            # parent's less a smaller child's.  So the leaves are counted
            # from it, in int32, over the column whose fullest bin is
            # emptiest; below 2^24 rows the float counts are the same
            with scope("tree_commit"):
                fullest = jnp.max(hist0[:, :, 2], axis=1)         # (Fh,)
                col = jnp.argmin(fullest)
                counts = jnp.sum(
                    jnp.take(carry[3][..., 2], col, axis=1).astype(jnp.int32),
                    axis=1)                                       # (L,)
                tree = tree._replace(leaf_count=jnp.where(
                    (fullest[col] < F32_WHOLE)
                    & (jnp.arange(L) < tree.num_leaves),
                    counts, tree.leaf_count))
        return tree, leaf_id

    return grow
