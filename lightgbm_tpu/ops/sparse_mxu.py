"""MXU sparse histograms — entry-chunk store + Pallas contraction kernel.

Reference analog: OrderedSparseBin's per-leaf nonzero iteration
(src/io/ordered_sparse_bin.hpp:26-209) — histogram work proportional to
nnz, not N*F.  The first TPU form of that idea (ops/sparse_store.py)
reduces the coordinate list with one `segment_sum`, which is O(nnz) in
WORK but lowers to a serialized scatter-add on TPU (measured 58 s/iter
at the 1M x 968 @1% Bosch shape — 145x the reference CPU,
BENCH_NOTES.md "Device-side sparse store").  This module keeps the
O(nnz) economics but feeds the MXU instead:

* the nonzero (non-fill) entries are packed column-major into fixed
  ENTRY CHUNKS of E entries, each chunk owned by exactly ONE device
  column (columns are padded to whole chunks; pad entries carry bin -1,
  which matches no one-hot row, and row id N, which every gather/scatter
  drops) — so per-column skew costs at most E-1 pad entries per column,
  never a dense blow-up;
* per chunk the kernel builds the (Bp, E) bin one-hot and the (3K, E)
  per-child masked weights in VMEM (same exact-bf16 one-hot + hi/lo
  weight split as the dense wave kernels, ops/pallas_wave.py) and runs
  ONE (Bp, E) x (E, 3K) MXU contraction, accumulated into the chunk's
  column rows of the (F*Bp, 3K) output — no segment_sum, no scatter,
  no atomics (the TPU grid is sequential);
* per-entry leaf ids / gradient channels are row-gathers done XLA-side
  once per wave / per iteration — O(nnz) reads against the (N,) vectors.

HBM per histogram pass: 5 i32/f32 entry arrays = 20 bytes * nnz (at the
Bosch shape ~194 MB vs the dense wave's 968 MB bin-matrix read), and the
MXU work is B * 3K * nnz MACs — 1% of the dense wave's B * 3K * N * F.

Fill-bin slots stay ZERO exactly like the segment_sum store: the
histogram view reconstructs them from the leaf sums (FixHistogram,
src/treelearner/feature_histogram.hpp:904-941), so the store never
materializes fill entries at all.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .grow import vma_struct
from .wave import _bin_pad

ENTRY_CHUNK = 512     # entries per chunk (kernel lanes dim)
CHUNK_BLOCK = 8       # chunks per kernel grid step (block sublanes dim)


class ChunkedSparseStore(NamedTuple):
    """Column-major nonzero entries in whole-chunk-per-column layout.

    Pads: ent_row holds N (one past the last row — gathers clip it,
    partition scatters drop it), ent_bin holds -1 (matches no bin).
    """
    ent_row: jnp.ndarray    # (NC, E) i32 row ids
    ent_bin: jnp.ndarray    # (NC, E) i32 bin ids
    chunk_col: jnp.ndarray  # (NC, 1) i32 owning device column per chunk
    col_cptr: jnp.ndarray   # (F+1,) i32 chunk ranges per column
    fill: jnp.ndarray       # (F,) i32 per-column fill bin


def build_chunked_store(binned: np.ndarray, fill: np.ndarray,
                        num_bins: int, entry_chunk: int = ENTRY_CHUNK,
                        chunk_block: int = CHUNK_BLOCK,
                        auto_uniform: bool = False):
    """Host-side build from the (N, F) binned matrix.

    ``fill`` is the per-column bin the downstream view reconstructs (or
    never reads) — see sparse_store.column_fill_bins.  Returns
    (store, cap_chunks, device_bytes); cap_chunks bounds any single
    column's chunk count (the partition window size).

    auto_uniform (r5): when per-column skew is low, the entry chunk is
    widened so EVERY column is exactly one chunk (E = max column nnz
    rounded up to the base chunk).  Same structure, but the kernel then
    runs one (Bp, E) x (E, 3K) dot per COLUMN instead of ~cap tiny
    K=512 dots per column — at the Bosch shape that is ~19k
    M=64/N=96/K=512 dots collapsing into 968 K~10k dots (near-full MXU
    utilization, ~20x fewer dispatch+accumulate rounds).  Taken only
    when the pad overhead stays under 50% (skewed columns would blow
    the uniform layout up; they keep the narrow chunks).
    """
    n, f = binned.shape
    e = int(entry_chunk)
    mask_t = (binned != fill[None, :]).T            # (F, N) column-major
    cols, rows = np.nonzero(mask_t)
    bins = binned.T[mask_t].astype(np.int64)
    counts = np.bincount(cols, minlength=f).astype(np.int64)
    cchunks = -(-counts // e)                       # chunks per column
    if auto_uniform and f and len(rows):
        e_uni = max(e, -(-int(counts.max()) // e) * e)
        # all-fill columns cost zero chunks in EITHER layout — charge
        # the uniform layout only for its nonzero columns; and bound E
        # absolutely so a dense-ish low-skew store cannot widen past
        # what the kernel's VMEM blocks hold (5 x (8, E) i32/f32 input
        # blocks + the (3K, E) hi/lo weights + the (Bp, E) one-hot is
        # ~2 KB per entry at K=64 — 16384 keeps a grid step well under
        # the 100 MB budget)
        nzc = int(np.count_nonzero(counts))
        if (e_uni <= 16384
                and nzc * e_uni <= 1.5 * max(int(cchunks.sum()), 1) * e):
            e = e_uni
            cchunks = -(-counts // e)               # now <= 1 per column
    col_cptr = np.zeros(f + 1, np.int64)
    np.cumsum(cchunks, out=col_cptr[1:])
    nc = int(col_cptr[-1])
    nc_pad = -(-max(nc, 1) // chunk_block) * chunk_block
    ent_row = np.full((nc_pad, e), n, np.int32)
    ent_bin = np.full((nc_pad, e), -1, np.int32)
    chunk_col = np.zeros(nc_pad, np.int32)
    if nc:
        eptr = np.zeros(f + 1, np.int64)
        np.cumsum(counts, out=eptr[1:])
        within = np.arange(len(rows), dtype=np.int64) - eptr[cols]
        pos = col_cptr[cols] * e + within           # padded flat position
        ent_row.reshape(-1)[pos] = rows
        ent_bin.reshape(-1)[pos] = bins
        chunk_col[:nc] = np.repeat(np.arange(f, dtype=np.int32), cchunks)
    cap_chunks = int(cchunks.max()) if f and nc else 0
    store = ChunkedSparseStore(
        ent_row=jnp.asarray(ent_row), ent_bin=jnp.asarray(ent_bin),
        chunk_col=jnp.asarray(chunk_col[:, None]),
        col_cptr=jnp.asarray(col_cptr.astype(np.int32)),
        fill=jnp.asarray(np.asarray(fill, np.int64).astype(np.int32)))
    device_bytes = 4 * (2 * nc_pad * e + nc_pad + 2 * f + 1)
    return store, cap_chunks, device_bytes


def _chunk_hist_kernel(bin_ref, lid_ref, g_ref, h_ref, m_ref, cid_ref,
                       colv_ref, out_ref, *, bp, gc, hilo=True):
    """One grid step: gc chunks, each one (Bp, E) x (E, 3K) contraction
    accumulated into its column's row block of the (F*Bp, 3K) output."""
    from jax.experimental import pallas as pl

    from .pallas_wave import _hi_lo
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    for g in range(gc):
        # bin ids are < 2^24 — exact in f32; pad bins (-1) match no row
        binrow = bin_ref[g:g + 1, :].astype(jnp.float32)       # (1, E)
        match = (cid_ref[:] == lid_ref[g:g + 1, :]).astype(
            jnp.float32)                                       # (K, E)
        wmat = jnp.concatenate(
            [match * g_ref[g:g + 1, :], match * h_ref[g:g + 1, :],
             match * m_ref[g:g + 1, :]], axis=0)               # (3K, E)
        wh, wl = _hi_lo(wmat, hilo)
        e = binrow.shape[1]
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (bp, e), 0).astype(jnp.float32)
        oh = jnp.where(binrow == iota, jnp.float32(1.0),
                       jnp.float32(0.0)).astype(jnp.bfloat16)  # (Bp, E)
        acc = jax.lax.dot_general(                             # A @ B^T
            oh, wh, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (Bp, 3K)
        if wl is not None:
            acc = acc + jnp.float32(1.0 / 256.0) * jax.lax.dot_general(
                oh, wl, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        col = colv_ref[g, 0]
        rows = pl.dslice(col * bp, bp)
        out_ref[rows, :] = out_ref[rows, :] + acc


def gather_entry_weights(store: ChunkedSparseStore, w3):
    """Per-entry weight channels (g_e, h_e, m_e), each (NC, E) f32 —
    the three O(nnz) gathers that are CONSTANT across one tree's waves.

    Measured r4 (Bosch 1M x 968 @1%, 2.72 s/iter): the per-wave cost of
    the MXU sparse path was dominated by four O(nnz) XLA gathers at the
    same ~8-cycle/row economics as the score-update gather — ~46 ms
    each, ~185 ms/wave against a ~3 ms kernel.  w3 never changes inside
    a tree, so callers hoist these three OUT of the wave loop (one
    gather per TREE) and pass the result via `entry_weights`; only the
    leaf-id gather remains per-wave."""
    rows_flat = store.ent_row.reshape(-1)
    nc, e = store.ent_bin.shape
    w3f = w3.astype(jnp.float32)
    g_e = jnp.take(w3f[:, 0], rows_flat, mode="clip").reshape(nc, e)
    h_e = jnp.take(w3f[:, 1], rows_flat, mode="clip").reshape(nc, e)
    m_e = jnp.take(w3f[:, 2], rows_flat, mode="clip").reshape(nc, e)
    return g_e, h_e, m_e


@functools.partial(jax.jit, static_argnames=("num_bins", "num_cols",
                                             "interpret", "hilo",
                                             "num_leaves"))
def sparse_wave_histogram_mxu(store: ChunkedSparseStore, leaf_id, w3,
                              child_id, num_bins: int, num_cols: int,
                              interpret: bool = False, hilo: bool = True,
                              entry_weights=None, num_leaves: int = 0):
    """(K, F, B, 3) histograms of the rows whose leaf is child_id[k],
    from nonzero entries only (fill slots zero — view reconstructs).

    leaf_id: (N,) int32; w3: (N, 3) [g*mult, h*mult, mult] channels;
    child_id: (K,) int32 target leaves, -1 entries yield zero histograms.
    entry_weights: optional pre-gathered (g_e, h_e, m_e) from
    gather_entry_weights — pass it from any per-wave loop (see there).
    num_leaves > 0 narrows the leaf-id gather (the dominant per-wave
    term after the weight hoist) to the smallest dtype holding the ids
    — a 4x traffic cut at <=256 leaves IF the TPU gather is byte-bound
    (index-bound would make it a wash; the r05b A/B decides).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nc, e = store.ent_bin.shape
    k = int(child_id.shape[0])
    bp = _bin_pad(num_bins)
    # largest grid-step chunk count that divides the store's (static)
    # chunk dimension — CHUNK_BLOCK for default-built stores, smaller
    # when the store was built with a different chunk_block pad
    gc = math.gcd(nc, CHUNK_BLOCK)

    # per-entry row gathers, XLA-side: O(nnz) reads of the (N,) vectors.
    # Pad rows (id N) clip to N-1; their bin -1 zeroes the contribution.
    rows_flat = store.ent_row.reshape(-1)
    lid_src = leaf_id
    if 0 < num_leaves <= 256:
        lid_src = leaf_id.astype(jnp.uint8)
    elif 0 < num_leaves <= 65536:
        lid_src = leaf_id.astype(jnp.uint16)
    lid_e = jnp.take(lid_src, rows_flat,
                     mode="clip").reshape(nc, e).astype(jnp.int32)
    if entry_weights is None:
        entry_weights = gather_entry_weights(store, w3)
    g_e, h_e, m_e = entry_weights

    kernel = functools.partial(_chunk_hist_kernel, bp=bp, gc=gc, hilo=hilo)
    operands = (store.ent_bin, lid_e, g_e, h_e, m_e, child_id[:, None],
                store.chunk_col)
    flat = pl.pallas_call(
        kernel,
        grid=(nc // gc,),
        in_specs=[
            pl.BlockSpec((gc, e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # ent_bin
            pl.BlockSpec((gc, e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # lid_e
            pl.BlockSpec((gc, e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # g_e
            pl.BlockSpec((gc, e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # h_e
            pl.BlockSpec((gc, e), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # m_e
            pl.BlockSpec((k, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),   # child ids
            pl.BlockSpec((gc, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),   # chunk cols
        ],
        out_specs=pl.BlockSpec((num_cols * bp, 3 * k), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=vma_struct((num_cols * bp, 3 * k), jnp.float32,
                             *operands),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    h = flat.reshape(num_cols, bp, 3, k)[:, :num_bins]
    return jnp.transpose(h, (3, 0, 1, 2))


def chunked_child_hists_ref(store: ChunkedSparseStore, leaf_id, w3,
                            child_id, num_bins: int, num_cols: int,
                            num_leaves: int):
    """Pure-XLA oracle / non-TPU fallback — same contract as the kernel,
    via the segment_sum form (fine on CPU, serialized on TPU)."""
    nc, e = store.ent_bin.shape
    k = child_id.shape[0]
    rows = store.ent_row.reshape(-1)
    bins = store.ent_bin.reshape(-1)
    cols = jnp.repeat(store.chunk_col[:, 0], e)
    lid = jnp.take(leaf_id, rows, mode="clip")
    slot_tbl = jnp.full(num_leaves, k, jnp.int32).at[
        jnp.where(child_id >= 0, child_id, num_leaves)].set(
        jnp.arange(k, dtype=jnp.int32), mode="drop")
    slot = jnp.take(slot_tbl, jnp.clip(lid, 0, num_leaves - 1))
    valid = (bins >= 0) & (slot < k)
    seg = jnp.where(valid,
                    slot * (num_cols * num_bins) + cols * num_bins + bins,
                    k * num_cols * num_bins)       # dropped by segment_sum
    wnz = jnp.take(w3, rows, axis=0, mode="clip")
    flat = jax.ops.segment_sum(wnz, seg,
                               num_segments=k * num_cols * num_bins)
    return flat.reshape(k, num_cols, num_bins, 3)


def chunked_split_column(store: ChunkedSparseStore, j, n: int,
                         cap_chunks: int):
    """Full-N int32 bin column j: fill value + the column's entries,
    read through a static cap_chunks chunk window (the chunked analog of
    sparse_store.sparse_split_column)."""
    nc, e = store.ent_row.shape
    if cap_chunks == 0:                 # every value sits at the fill bin
        return jnp.full(n, store.fill[j], jnp.int32)
    cap = min(cap_chunks, nc)
    start = store.col_cptr[j]
    end = store.col_cptr[j + 1]
    cs = jnp.minimum(start, nc - cap)   # window start after edge clamp
    blk_r = lax.dynamic_slice(store.ent_row, (cs, 0), (cap, e))
    blk_b = lax.dynamic_slice(store.ent_bin, (cs, 0), (cap, e))
    cidx = cs + jnp.arange(cap, dtype=jnp.int32)[:, None]
    ok = (cidx >= start) & (cidx < end)            # chunks of column j
    rows = jnp.where(ok, blk_r, n).reshape(-1)
    bins = jnp.where(ok, blk_b, 0).reshape(-1)
    col = jnp.full(n, store.fill[j], jnp.int32)
    return col.at[rows].set(bins, mode="drop")
