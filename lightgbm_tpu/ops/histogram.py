"""Leaf histogram construction — the hottest op, in XLA.

Parity target: the reference's scatter-add kernels (dense_bin.hpp:66-98 on
CPU, src/treelearner/ocl/histogram*.cl on GPU).  TPU-first design instead of
a translation:

* ``scatter`` mode: one `segment_sum` per feature (vmapped), which XLA lowers
  to parallel scatter-adds.  Works on every backend; preferred on CPU.
* ``onehot`` mode: rows are processed in chunks; each chunk builds a
  (C, B) one-hot in bf16/f32 per feature block and contracts it against the
  (C, 3) weight matrix on the MXU — the `max_bin=63` lesson from
  docs/GPU-Performance.md:58-64 maps to "small B lives on the MXU".

Rows outside the target leaf contribute zero via the mask multiplier, which
also carries bagging/GOSS per-row weights (gbdt.cpp:265-324, goss.hpp:79-129
fold into the same mechanism).

Both kernels also come in a *gathered* form operating on a compacted
(capacity,) row-index buffer instead of a full-N mask: the grow loop
compacts the target leaf's rows first (compact_rows) and histograms only
those — restoring the reference's O(rows_in_leaf) cost
(serial_tree_learner.cpp:424-450, dense_bin.hpp:66-98) under XLA's static
shapes via capacity tiers (ops/grow.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _weights(grad, hess, leaf_id, leaf, row_mult):
    """(N, 3) [g, h, 1] masked to the target leaf and row multipliers."""
    mask = (leaf_id == leaf).astype(grad.dtype)
    if row_mult is not None:
        mask = mask * row_mult
    return jnp.stack([grad * mask, hess * mask, mask], axis=-1)


def compact_rows(mask, pos, capacity: int):
    """Indices of rows with mask=True, compacted to a (capacity,) buffer.

    pos = cumsum(mask) - 1 (each masked row's rank, precomputed once by the
    caller so the O(N) cumsum is shared across capacity tiers).  Rows beyond
    `capacity` are dropped — callers select a tier with capacity >= count.
    This is DataPartition's leaf-grouped index array (data_partition.hpp:
    94-147) rebuilt per leaf as one O(N) scatter.
    """
    n = mask.shape[0]
    target = jnp.where(mask, pos, capacity)      # out-of-bounds -> dropped
    return jnp.zeros(capacity, jnp.int32).at[target].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")


def compact_rows_topk(mask, capacity: int):
    """compact_rows via top_k instead of cumsum+scatter.

    On TPU a 1M-row scatter costs ~8ms and the cumsum another ~2.4ms, while
    top_k of the same keys is ~3.4ms total (measured on v5e) — so the
    sort-based compaction wins there.  Keys are n-i for masked rows, so the
    descending top_k yields the leaf's rows in ascending (stable) row
    order; slots past the true count surface arbitrary rows and must be
    masked by the caller's valid vector.
    """
    n = mask.shape[0]
    key = jnp.where(mask, n - jnp.arange(n, dtype=jnp.int32), -1)
    _, idx = lax.top_k(key, capacity)
    return idx.astype(jnp.int32)


def _gathered_weights(grad, hess, row_mult, idx, valid):
    m = valid.astype(grad.dtype)
    if row_mult is not None:
        m = m * jnp.take(row_mult, idx)
    return jnp.stack([jnp.take(grad, idx) * m, jnp.take(hess, idx) * m, m],
                     axis=-1)                     # (C, 3)


def chunk_rows(arrays, i, chunk: int):
    """Rows [i * chunk, (i + 1) * chunk) of each of `arrays`: the chunk
    loops' one way to a chunk (here and in ops/wave.py)."""
    return tuple(lax.dynamic_slice_in_dim(a, i * chunk, chunk, axis=0)
                 for a in arrays)


def _scatter_accumulate(binned, w, num_bins: int, logical_cols: int = 0):
    """(F, B, 3) from (C, F) bins and (C, 3) weights via segment_sum.

    logical_cols > 0: binned is 4-bit packed (ops/pack.py split-half
    layout); nibbles are extracted per column INSIDE the vmap so the
    full-width matrix never materializes."""
    def per_feature(col):
        return jax.ops.segment_sum(w, col.astype(jnp.int32),
                                   num_segments=num_bins)
    if not logical_cols:
        return jax.vmap(per_feature, in_axes=1)(binned)
    lo = jax.vmap(lambda c: per_feature(c.astype(jnp.int32) & 15),
                  in_axes=1)(binned)
    hi = jax.vmap(lambda c: per_feature(c.astype(jnp.int32) >> 4),
                  in_axes=1)(binned)
    return jnp.concatenate([lo, hi], axis=0)[:logical_cols]


def _width_classes(col_pads, num_bins: int):
    """A ragged store's columns by one-hot width -> ((width, columns),
    ...), widest class last; a width never passes `num_bins`."""
    classes = {}
    for j, p in enumerate(col_pads):
        classes.setdefault(min(p, num_bins), []).append(j)
    return tuple(sorted(classes.items()))


# the widest one-hot one contraction of the one-hot pass takes: on the v5e
# a 256-wide class cost 2.86 ps an element, 64-wide ones 0.67-0.86 and the
# 32-wide one 1.08, and 32-bin segments read slower than 64 (PERF.md section 6)
SEGMENT_BINS = 64


def _onehot_widths(col_pads, num_bins: int, f: int):
    """Each of the `f` columns' one-hot width."""
    return ([min(p, num_bins) for p in col_pads] if col_pads
            else [num_bins] * f)


def _segments(col_pads, num_bins: int, f: int):
    """Where some column's one-hot passes SEGMENT_BINS: the pass's width
    classes of pieces, ((width, ((column, first bin), ...)), ...), widest
    last.  A column that wide becomes SEGMENT_BINS-wide segments at first
    bins 0, 64, ... (the last one's bins past the column's width stay
    empty); a narrower column is one piece of its own width.  A class's
    pieces come by first bin, then column, so that they are runs of the
    chunk's columns.  () where no column passes."""
    widths = _onehot_widths(col_pads, num_bins, f)
    if max(widths) <= SEGMENT_BINS:
        return ()
    classes = {}
    for b0, j in sorted((b0, j) for j, w in enumerate(widths)
                        for b0 in range(0, w, SEGMENT_BINS)):
        classes.setdefault(min(widths[j], SEGMENT_BINS), []).append((j, b0))
    return tuple(sorted(classes.items()))


def onehot_pass_shape(col_pads, num_bins: int, f: int):
    """(the widest one-hot the one-hot pass contracts, the segments it
    cuts from wider columns) for a store of `f` columns."""
    widths = _onehot_widths(col_pads, num_bins, f)
    return (min(max(widths), SEGMENT_BINS),
            sum(-(-w // SEGMENT_BINS) for w in widths if w > SEGMENT_BINS))


def _columns(xc, cols):
    """Columns `cols` (ascending) of a chunk, as slices of their runs."""
    runs = [[cols[0], cols[0] + 1]]
    for j in cols[1:]:
        if j == runs[-1][1]:
            runs[-1][1] = j + 1
        else:
            runs.append([j, j + 1])
    return jnp.concatenate([xc[:, a:b] for a, b in runs], axis=1)


def _pieces(xc, pieces):
    """Pieces ((column, first bin), ...) of a chunk: each its column's bin
    less its first bin, so that a bin outside the segment is a value
    outside [0, width), a zero one-hot row."""
    first = [b0 for _, b0 in pieces]
    x = _columns(xc, [j for j, _ in pieces])
    return x - jnp.asarray(first, jnp.int32) if any(first) else x


def _onehot_accumulate(binned, w, num_bins: int, chunk: int,
                       logical_cols: int = 0, hilo: bool = False,
                       col_pads: tuple = ()):
    """(F, B, 3) via chunked one-hot contraction on the MXU.

    col_pads: the columns' one-hot widths where the store is ragged
    (ops/wave.py col_bin_pads; () = `num_bins` for every column): one
    contraction a width class, over the class's columns of the chunk,
    never of the whole matrix; the bins past a column's width come back
    zero, as they are.  No contraction's one-hot is wider than
    SEGMENT_BINS: a wider column, ragged or uniform, goes in as segments
    of that width in its class (`_segments`).

    logical_cols > 0: binned is 4-bit packed (ops/pack.py); chunks unpack
    in-scan so the full-width matrix never materializes in HBM.

    hilo: on the TPU the contraction rounds `w` to bf16 (one product a
    weight).  True contracts the weight cut to bf16's mantissa and what
    the cut left, as six channels of the one pass, and adds the halves:
    the split of ops/pallas_wave.py `_hi_lo`, for a root whose waves take
    the exact kernels.  The cut is a mask on the bits: XLA folds a
    float32 -> bf16 -> float32 round trip to nothing (excess precision is
    allowed), and the second product would be of zeros (read on the v5e,
    PR 28: the round trip's sums were the one product's to the last bit)."""
    if hilo:
        hi = lax.bitcast_convert_type(
            lax.bitcast_convert_type(w, jnp.uint32) & jnp.uint32(0xFFFF0000),
            w.dtype)
        w = jnp.concatenate([hi, w - hi], axis=-1)          # (N, 6)
    n, fdev = binned.shape
    k = w.shape[1]
    f = logical_cols or fdev
    chunk = min(chunk, max(n, 1))
    pad = (-n) % chunk
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    nchunks = (n + pad) // chunk

    def step(i, acc):
        # a chunk is a window of the (N, F) matrix itself, never a row of
        # a (nchunks, chunk, F) reshape: where the rows are the minor
        # dimension of the device layout (a narrow table) that reshape is
        # a real transposition, a copy of the matrix a tree, and the
        # TPU compiler's code for it grows with nchunks unless nchunks
        # is a multiple of 8 (PERF.md section 6, PR 33)
        xc, wc = chunk_rows((binned, w), i, chunk)
        if logical_cols:
            from .pack import unpack4
            xc = unpack4(xc, f)
        xc = xc.astype(jnp.int32)
        return tuple(
            a + jnp.einsum(
                "cfb,cw->fbw",
                jax.nn.one_hot(_pieces(xc, cols) if segments
                               else _columns(xc, cols) if col_pads else xc,
                               width, dtype=wc.dtype),   # (C, F, B)
                wc, preferred_element_type=wc.dtype)
            for a, (width, cols) in zip(acc, classes))

    # a column wider than SEGMENT_BINS goes in as segments of the
    # SEGMENT_BINS class; a store with no such column is what it was
    segments = _segments(col_pads, num_bins, f)
    classes = segments or (_width_classes(col_pads, num_bins) if col_pads
                           else ((num_bins, range(f)),))
    init = tuple(jnp.zeros((len(cols), width, k), dtype=w.dtype)
                 for width, cols in classes)
    if nchunks == 1:
        hists = step(0, init)
    else:
        from .grow import vary_like
        hists = lax.fori_loop(0, nchunks, step, vary_like(init, binned, w))
    if segments:
        # each column's segments side by side (a zero block where a
        # narrower column has fewer), cut or padded to `num_bins`
        row = {p: i for i, p in enumerate(
            p for _, pieces in classes for p in pieces)}
        nseg = max(b0 for _, b0 in row) // SEGMENT_BINS + 1
        hist = jnp.concatenate(
            [jnp.pad(h, ((0, 0), (0, SEGMENT_BINS - h.shape[1]), (0, 0)))
             for h in hists] + [jnp.zeros((1, SEGMENT_BINS, k), w.dtype)])[
            jnp.asarray([[row.get((j, s * SEGMENT_BINS), len(row))
                          for s in range(nseg)] for j in range(f)])]
        hist = hist.reshape(f, nseg * SEGMENT_BINS, k)
        hist = jnp.pad(hist, ((0, 0), (0, max(num_bins - hist.shape[1], 0)),
                              (0, 0)))[:, :num_bins]
    elif col_pads:
        # the classes' blocks side by side, then back in column order
        order = [j for _, cols in classes for j in cols]
        hist = jnp.concatenate(
            [jnp.pad(h, ((0, 0), (0, num_bins - h.shape[1]), (0, 0)))
             for h in hists])[jnp.asarray(sorted(range(f),
                                                 key=order.__getitem__))]
    else:
        hist, = hists
    return hist[..., :3] + hist[..., 3:] if hilo else hist


def gathered_histogram(X, grad, hess, row_mult, idx, valid, num_bins: int,
                       mode: str, chunk: int = 16384,
                       logical_cols: int = 0):
    """(F, B, 3) histogram of the rows in `idx` (valid-masked).

    The gathered analog of leaf_histogram: X/grad/hess/row_mult are full-N;
    idx is a compacted (capacity,) row-index buffer from compact_rows.
    logical_cols > 0: X is 4-bit packed (ops/pack.py); the gathered rows
    stay packed and the accumulators unpack in-scan.
    """
    Xs = jnp.take(X, idx, axis=0)                 # (C, F) or (C, Fh) packed
    w = _gathered_weights(grad, hess, row_mult, idx, valid)
    if mode == "onehot":
        return _onehot_accumulate(Xs, w, num_bins, chunk, logical_cols)
    return _scatter_accumulate(Xs, w, num_bins, logical_cols)


@functools.partial(jax.jit, static_argnames=("num_bins", "logical_cols"))
def leaf_histogram_scatter(binned, grad, hess, leaf_id, leaf, row_mult,
                           num_bins: int, logical_cols: int = 0):
    """(F, B, 3) histogram of the target leaf via per-feature segment_sum.

    binned: (N, F) uint8/uint16 bin ids; grad/hess: (N,) float;
    leaf_id: (N,) int32; leaf: scalar int; row_mult: (N,) float or None.
    """
    w = _weights(grad, hess, leaf_id, leaf, row_mult)  # (N, 3)
    return _scatter_accumulate(binned, w, num_bins, logical_cols)


@functools.partial(jax.jit, static_argnames=("num_bins", "chunk",
                                             "logical_cols", "hilo",
                                             "col_pads"))
def leaf_histogram_onehot(binned, grad, hess, leaf_id, leaf, row_mult,
                          num_bins: int, chunk: int = 16384,
                          logical_cols: int = 0, hilo: bool = False,
                          col_pads: tuple = ()):
    """(F, B, 3) histogram via chunked one-hot matmul on the MXU.

    For each row chunk: one_hot(bins) (C, F, B) contracted with weights
    (C, 3) -> (F, B, 3), accumulated over chunks with lax.scan so the
    one-hot tensor never exceeds chunk x F x B.
    """
    w = _weights(grad, hess, leaf_id, leaf, row_mult)  # (N, 3)
    # a uniform store's call is the one it was: the benchmark's tests and
    # faults wrap `_onehot_accumulate` by these six arguments
    ragged = {"col_pads": col_pads} if col_pads else {}
    return _onehot_accumulate(binned, w, num_bins, chunk, logical_cols,
                              hilo, **ragged)


def leaf_histogram(binned, grad, hess, leaf_id, leaf, row_mult,
                   num_bins: int, mode: str = "auto"):
    """Dispatch by mode; 'auto' picks onehot on TPU (the fused one-hot
    reduce is at the VPU roofline at every bin count — measured 7.2ms vs
    scatter's 226ms at B=63, 1M x 28 on v5e) and scatter on CPU.  Must stay
    in sync with the same policy in ops/learner.py."""
    if mode == "auto":
        mode = "onehot" if jax.default_backend() == "tpu" else "scatter"
    if mode == "onehot":
        return leaf_histogram_onehot(binned, grad, hess, leaf_id, leaf,
                                     row_mult, num_bins=num_bins)
    if mode == "pallas":
        from .pallas_hist import leaf_histogram_pallas
        return leaf_histogram_pallas(binned, grad, hess, leaf_id, leaf,
                                     row_mult, num_bins=num_bins)
    return leaf_histogram_scatter(binned, grad, hess, leaf_id, leaf,
                                  row_mult, num_bins=num_bins)


@functools.partial(jax.jit, static_argnames=())
def leaf_sums(grad, hess, leaf_id, leaf, row_mult):
    """Leaf total (sum_g, sum_h, count) — LeafSplits::Init (leaf_splits.hpp)."""
    w = _weights(grad, hess, leaf_id, leaf, row_mult)
    return jnp.sum(w, axis=0)
