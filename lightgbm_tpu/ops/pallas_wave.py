"""Fused wave-histogram Pallas kernel — the hot op of wave growth.

The XLA wave pass (ops/wave.py) materializes the (chunk, F*B) bin one-hot
to HBM between the VPU construction and the MXU contraction; at Higgs scale
that is ~74 GB of pure one-hot traffic per boosting iteration and sets the
whole training rate (measured: ~90ms/wave of a ~106ms wave at 10.5M rows).

This kernel generates the one-hot INSIDE VMEM, tile by tile, builds the
per-child masked weights in VMEM too, and feeds the MXU directly:

    for each row tile (Cg rows):
        oh    = (repeat(X_tile, Bp) == lane_bin_iota)        # VPU, in VMEM
        match = (leaf_tile == child_ids)                      # (Cg, K)
        w     = [match*g | match*h | match*mult]              # (Cg, 3K)
        acc  += ohᵀ @ bf16_hi(w) + ohᵀ @ bf16_lo(w)          # MXU

HBM traffic per wave drops to reading X (N*F bytes) + leaf_id + w3 —
~100x less than the materialized one-hot.  Precision: the one-hot is exact
in bf16 (it holds only 0/1); the weights are split into bf16 high + bf16
residual parts whose products accumulate in f32, giving ~2^-17 relative
error versus the reference's single-precision GPU histograms
(src/treelearner/ocl/histogram*.cl accumulate float).

Layout notes: `pltpu.repeat` TILES its operand ([x_0..x_F, x_0..x_F, ...]),
so the one-hot is bin-major — column j holds (feature j % F, bin j // F) —
and everything stays 2D (no Mosaic 3D reshapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grow import vma_struct
from .wave import (BIN_GRANULE, WAVE_ONLY_MODES,  # noqa: F401  (shared
                   _bin_pad)   # policy lives in wave.py, which stays
# importable without jax.experimental.pallas)


# -- VMEM scheduling thresholds (the 18-30 MB band post-mortem) ----------
# The former "pathology band" (the deleted HIST_BLOCK_BAND width
# rule) was a lossy proxy for a Mosaic scheduling edge the
# fused-iteration probe work finally isolated: the accumulator block's
# per-sub-block read-modify-write only overlaps the MXU contraction while
# the kernel's LIVE SET (resident accumulator + transient tiles) fits the
# ~52 MB overlap window; past it Mosaic serializes the accumulate-store
# against the next dot — UNLESS the accumulator alone is big enough
# (~44 MB) that the chunked-RMW schedule takes over, which overlaps
# regardless.  That is why the degeneracy looked like a band: small
# blocks fit, huge blocks went chunked, and only the middle serialized —
# and why the band misfired on yahoo's W=64 cell (34 MB resident + 33 MB
# transients: over the window, below the chunked threshold, 3.2x slower
# — the data point the (18,30) bounds could never encode).  All five
# measured r4/r5 cells (epsilon W16/W32, bosch W32/W64, yahoo W32/W64,
# BENCH_NOTES.md) fall on the right side of these two constants.
_OVERLAP_WINDOW = 52 << 20    # max live set Mosaic still overlaps
_CHUNKED_RMW_MIN = 44 << 20   # resident size where chunked RMW kicks in


def _plan_transient_bytes(fc, bsub, c, k, packed=False):
    """Per-grid-step transient VMEM of the wave kernels at row tile c:
    the repeated-bin f32 tile + bf16 one-hot (both (bsub*fc, c)), the
    double-buffered X tile, and the bf16 hi/lo weight rows + lid/w3."""
    xr = bsub * fc * c * 4
    oh = bsub * fc * c * 2
    xin = 2 * ((fc + 1) // 2 if packed else fc) * c
    w = 2 * (3 * k * c * 2) + 16 * c
    return xr + oh + xin + w


def _tile_plan(n, fc, bp, row_tile, k=0, packed=False):
    """Shared tile sizing for every wave kernel: bins per inner sub-block
    (~512 lanes per one-hot tile AND a divisor of bp so the loop covers
    every bin), and the row-tile size that keeps the (Cg, bsub*fc)
    f32/bf16 temporaries within the raised VMEM budget.  One copy so the
    policy cannot diverge across kernel layouts.

    k > 0 (the wave child count) turns on the accumulator-aware bound:
    when the resident (fc*bp, 3k) block is below the chunked-RMW
    threshold, the row tile shrinks until resident + transients fit the
    Mosaic overlap window — the fix for the former 18-30 MB band
    degeneracy (thresholds above; probe: `tile_plan_vmem_report`)."""
    bsub = 1
    while bsub * 2 * fc <= 512 and bp % (bsub * 2) == 0:
        bsub *= 2
    # c is the LANES dim of the transposed kernels' blocks, so it must be
    # a multiple of 128 (Pallas TPU block rule) unless it equals the
    # whole (padded) array dim — the c = n fallthrough below, where the
    # wrapper pads the array to exactly c
    c = max(512, min(row_tile // 128 * 128,
                     ((1 << 24) // (bsub * fc * 4)) // 128 * 128))
    resident = fc * bp * 12 * k
    if k and resident < _CHUNKED_RMW_MIN:
        per_row = _plan_transient_bytes(fc, bsub, 1, k, packed)
        cmax = ((_OVERLAP_WINDOW - resident) // per_row) // 128 * 128
        # the old 512 floor could force an oversubscribed live set; under
        # the accumulator-aware bound the floor relaxes to one (8, 128)
        # lane tile so tight shapes stay schedulable instead of fast-ish
        c = max(128, min(c, cmax))
    c = min(c, max(n, 1))
    return bsub, c


def tile_plan_vmem_report(n, fc, bp, k, row_tile=8192, packed=False):
    """Old-plan vs fixed-plan VMEM live-set accounting for one wave-kernel
    shape — the minimal reproduction of the former 18-30 MB band
    pathology and the regression probe that keeps it fixed
    (tests/test_fused_iter.py, docs/FusedIteration.md).

    Returns a dict with the legacy planner's row tile (`c_old`, fixed
    16 MB transient budget, resident block ignored), the current
    planner's (`c_new`), both live sets, and whether each plan lands in
    the serialized-RMW regime (`pathological_*`)."""
    bsub = 1
    while bsub * 2 * fc <= 512 and bp % (bsub * 2) == 0:
        bsub *= 2
    c_old = max(512, min(row_tile // 128 * 128,
                         ((1 << 24) // (bsub * fc * 4)) // 128 * 128))
    c_old = min(c_old, max(n, 1))
    _, c_new = _tile_plan(n, fc, bp, row_tile, k=k, packed=packed)
    resident = fc * bp * 12 * k
    chunked = resident >= _CHUNKED_RMW_MIN

    def live(c):
        return resident + _plan_transient_bytes(fc, bsub, c, k, packed)

    return {
        "bsub": bsub, "c_old": int(c_old), "c_new": int(c_new),
        "resident_bytes": int(resident),
        "live_old": int(live(c_old)), "live_new": int(live(c_new)),
        "overlap_window": int(_OVERLAP_WINDOW),
        "chunked_rmw": bool(chunked),
        "pathological_old": bool(not chunked
                                 and live(c_old) > _OVERLAP_WINDOW),
        "pathological_new": bool(not chunked
                                 and live(c_new) > _OVERLAP_WINDOW),
    }


def _round_bf16(wmat):
    """Round-to-nearest f32 -> bf16 in bit arithmetic (Mosaic's cast
    TRUNCATES — measured: biased sums ~100x above round-to-nearest
    theory — so the rounding must be done manually)."""
    return pltpu.bitcast(
        (pltpu.bitcast(wmat, jnp.uint32) + jnp.uint32(0x8000))
        & jnp.uint32(0xFFFF0000), jnp.float32).astype(jnp.bfloat16)


def _hi_lo(wmat, hilo=True):
    """bf16 weight split for the MXU: exact hi/lo pair (default), or a
    single round-to-nearest bf16 term (hilo=False — half the MXU work).

    Mantissa truncation for the hi part — a bf16 round-trip would be
    folded to identity under --xla_allow_excess_precision, silently
    zeroing the residual term (observed on v5e).  The residual is scaled
    by 2^8 (exact) into bf16 range and rounded manually (see
    _round_bf16).  The single-term mode is the reference GPU's
    single-precision-histogram trade
    (docs/GPU-Performance.md:127-130, gpu_use_dp=false default): ~2^-9
    relative product error instead of ~2^-17, f32 accumulation either
    way.
    """
    if not hilo:
        return _round_bf16(wmat), None
    wh_f32 = pltpu.bitcast(
        pltpu.bitcast(wmat, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    wh = wh_f32.astype(jnp.bfloat16)                 # exact: mantissa fits
    wl_f32 = (wmat - wh_f32) * jnp.float32(256.0)
    return wh, _round_bf16(wl_f32)


def _split_weights_t(lid_ref, w3_ref, cid_ref, hilo=True):
    """Per-child masked weights in the ROW-VECTOR orientation: (3K, Cg)
    bf16 hi/lo from lid (1, Cg), w3 (3, Cg), cid (K, 1).

    This orientation exists because any (N, small) operand pays TPU's
    (8, 128) lane tiling: an (N, 1) leaf-id column materializes at 128x
    its logical bytes (~5 GB at the 10.5M-row flagship shape — an
    instant HBM OOM), while (1, N)/(3, N) row layouts pad only the
    sublane dim (8x / 2.7x of their small logical size).  The
    broadcasts below produce (K, Cg)/(3K, Cg) tiles directly, no
    transposes anywhere."""
    match = (cid_ref[:] == lid_ref[:]).astype(jnp.float32)   # (K, Cg)
    wmat = jnp.concatenate(
        [match * w3_ref[ch:ch + 1, :] for ch in range(3)], axis=0)
    return _hi_lo(wmat, hilo)                                # (3K, Cg)


def _unpack4_t(xti, fc):
    """Split-half nibble unpack along SUBLANES for transposed (Fdev, Cg)
    tiles (ops/pack.py layout).  One copy shared by the transposed
    kernels so a pack-layout change cannot corrupt one of them."""
    return jnp.concatenate([xti & 15, xti >> 4], axis=0)[:fc]


def _accum_hist(out_ref, xr, base, wh, wl, *, bp, fc, bsub, dims):
    """Shared one-hot-generate + MXU-contract accumulation loop.

    xr/base: the repeated bin matrix and bin-iota, (Cg, bsub*Fc) row-major
    or (bsub*Fc, Cg) transposed;  wh/wl: bf16 hi/lo weights, (Cg, 3K) or
    (3K, Cg) — `dims` is the dot_general contraction pair matching the
    operand orientations, always contracting Cg.
    Accumulates (bsub*Fc, 3K) f32 blocks into out_ref rows per sub-block.
    """
    for s in range(bp // bsub):
        oh = jnp.where(xr == base + jnp.float32(s * bsub),
                       jnp.float32(1.0),
                       jnp.float32(0.0)).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            oh, wh, dimension_numbers=dims,
            preferred_element_type=jnp.float32)          # (bsub*Fc, 3K)
        if wl is not None:
            acc = acc + jnp.float32(1.0 / 256.0) * jax.lax.dot_general(
                oh, wl, dimension_numbers=dims,
                preferred_element_type=jnp.float32)
        rows = slice(s * bsub * fc, (s + 1) * bsub * fc)
        out_ref[rows, :] = out_ref[rows, :] + acc


# most one-hot rows of one contraction of the ragged walk, as the
# uniform loop's sub-blocks have (`_tile_plan`).  On the v5e a launch at
# Expo's widths takes the same 117.4 ms with blocks of 224, 320 and 448
# rows (PERF.md section 6, PR 36); far shorter blocks stream few rows
# past each 128 x 128 weight tile and starve the MXU
_RAGGED_BLOCK_ROWS = 512


def _ragged_blocks(col_pads):
    """The ragged walk of a store whose column j is `col_pads[j]` bins
    wide (ops/wave.py col_bin_pads): its (column, first bin) segments of
    `BIN_GRANULE` bins each, column by column, cut into blocks of equal
    length and at most `_RAGGED_BLOCK_ROWS` one-hot rows."""
    segs = [(j, b0) for j, p in enumerate(col_pads)
            for b0 in range(0, p, BIN_GRANULE)]
    nblocks = max(1, -(-len(segs) * BIN_GRANULE // _RAGGED_BLOCK_ROWS))
    per = max(1, -(-len(segs) // nblocks))
    return tuple(tuple(segs[i:i + per]) for i in range(0, len(segs), per))


def _accum_hist_ragged(out_ref, xt, wh, wl, *, blocks):
    """`_accum_hist` over the columns' own bins: xt (Fc, Cg) float32
    bins, wh/wl (3K, Cg).  A block's one-hot holds `BIN_GRANULE` rows a
    segment: the column's bins less the segment's first, against the bin
    within the segment.  out_ref's rows are the segments in order, so a
    column's bins lie together, at the offset the pads before it sum to.
    """
    cg = xt.shape[1]
    # the bin within its segment, built anew for a shorter last block:
    # Mosaic's compiler fails on a sublane slice of the longer one
    within = {}
    row0 = 0
    for block in blocks:
        rows = len(block) * BIN_GRANULE
        if rows not in within:
            within[rows] = (jax.lax.broadcasted_iota(
                jnp.int32, (rows, cg), 0) % BIN_GRANULE).astype(jnp.float32)
        rel = jnp.concatenate(
            [jnp.broadcast_to(xt[j:j + 1, :] - jnp.float32(b0),
                              (BIN_GRANULE, cg)) for j, b0 in block], axis=0)
        oh = jnp.where(rel == within[rows], jnp.float32(1.0),
                       jnp.float32(0.0)).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            oh, wh, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (rows, 3K)
        if wl is not None:
            acc = acc + jnp.float32(1.0 / 256.0) * jax.lax.dot_general(
                oh, wl, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        out_ref[row0:row0 + rows, :] = out_ref[row0:row0 + rows, :] + acc
        row0 += rows


def _wave_hist_kernel(x_ref, lid_ref, w3_ref, cid_ref, out_ref,
                      *, bp, fc, k, bsub, packed, hilo=True):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # bin ids are exact in f32 and the VPU compares f32 natively (bf16
    # compares are rejected by Mosaic on v5e); only the 0/1 one-hot result
    # is emitted in bf16 for the MXU
    xi = x_ref[:]
    if packed:
        from .pack import unpack4
        xi = unpack4(xi, fc)          # lane-contiguous split-half nibbles
    x = xi.astype(jnp.int32).astype(jnp.float32)         # (Cg, Fc)
    cg = x.shape[0]

    # child match + channel-major weights, built in VMEM — nothing
    # per-wave crosses HBM beyond X/leaf_id/w3 themselves
    wh, wl = _split_weights_t(lid_ref, w3_ref, cid_ref, hilo)  # (3K, Cg)

    # bins [s*bsub, (s+1)*bsub) x all features, bin-major columns.
    # f32 select then downcast: the i1 result carries f32 (8,128)
    # tiling and Mosaic cannot relayout it straight into a bf16 select
    xr = pltpu.repeat(x, bsub, axis=1)                   # (Cg, bsub*Fc)
    lane = jax.lax.broadcasted_iota(jnp.int32, (cg, bsub * fc), 1)
    base = (lane // fc).astype(jnp.float32)              # 0..bsub-1 pattern
    _accum_hist(out_ref, xr, base, wh, wl, bp=bp, fc=fc, bsub=bsub,
                dims=(((0,), (1,)), ((), ())))           # both contract Cg


@functools.partial(jax.jit, static_argnames=("num_bins", "row_tile",
                                             "interpret", "logical_cols",
                                             "hilo"))
def wave_histogram_pallas(X, leaf_id, w3, child_id, num_bins: int,
                          row_tile: int = 8192, interpret: bool = False,
                          logical_cols: int = 0, hilo: bool = True):
    """(K, F, B, 3) histograms of the rows whose leaf is child_id[k].

    X: (N, F) uint8/int bin ids;  leaf_id: (N,) int32 (already partitioned);
    w3: (N, 3) float32 [g, h, mult] per-row channels;
    child_id: (K,) int32 target leaves, -1 entries yield zero histograms.
    logical_cols > 0: X is 4-bit packed (ops/pack.py split-half layout) and
    logical_cols is the unpacked column count — the kernel unpacks in VMEM,
    so the packed matrix is all that crosses HBM.
    """
    n, fdev = X.shape
    fc = logical_cols or fdev
    k = child_id.shape[0]
    bp = _bin_pad(num_bins)
    bsub, c = _tile_plan(n, fc, bp, row_tile, k=k,
                         packed=bool(logical_cols))
    pad = (-n) % c
    # ROW-VECTOR layouts for the per-row operands: leaf ids as (1, N)
    # and weights as (3, N) keep TPU's (8, 128) tiling near-dense (8x /
    # 2.7x sublane pad) — the former (N, 1)/(N, 3) columns paid 128x /
    # 42.7x LANE padding (~5 GB each at 10.5M rows; the r03 flagship
    # OOM).  Blocks (1, c)/(3, c) are legal because the first dim equals
    # the whole array dim and c is 128-aligned (_tile_plan).
    lid2 = (jnp.pad(leaf_id, (0, pad), constant_values=-2) if pad
            else leaf_id)[None, :]                       # (1, N)
    w3t = jnp.transpose(w3.astype(jnp.float32))          # (3, N)
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        w3t = jnp.pad(w3t, ((0, 0), (0, pad)))
    nch = (n + pad) // c

    kernel = functools.partial(_wave_hist_kernel, bp=bp, fc=fc, k=k,
                               bsub=bsub, packed=bool(logical_cols),
                               hilo=hilo)
    operands = (X, lid2, w3t, child_id[:, None])
    flat = pl.pallas_call(
        kernel,
        name="wave_histogram_pallas",
        grid=(nch,),
        in_specs=[
            pl.BlockSpec((c, fdev), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((fc * bp, 3 * k), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=vma_struct((fc * bp, 3 * k), jnp.float32, *operands),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    # (Bp*Fc, 3K) bin-major rows, channel-major cols -> (K, Fc, B, 3)
    h = flat.reshape(bp, fc, 3, k)[:num_bins]
    return jnp.transpose(h, (3, 1, 0, 2))


def wave_histogram_reference(X, leaf_id, w3, child_id, num_bins: int):
    """Pure-XLA oracle for the kernel (same contract, any backend)."""
    match = (leaf_id[:, None] == child_id[None, :]).astype(jnp.float32)
    oh = jax.nn.one_hot(X.astype(jnp.int32), num_bins, dtype=jnp.float32)
    return jnp.einsum("nfb,nk,nc->kfbc", oh, match, w3)


# --------------------------------------------------------------------------
# v2: transposed operand layout.  The v1 kernel's dot contracts dim 0 of
# BOTH operands (oh (Cg, Q)^T @ w (Cg, 3K)) — the MXU's non-native
# orientation, which Mosaic may realize via an in-VMEM transpose of the
# 15MB one-hot tile.  Here the one-hot is GENERATED already transposed,
# (Q, Cg), from a transposed bin matrix X_t (F, N): the dot is then the
# native (M, K) @ (K, N) form with no transpose anywhere.  The partition
# scan keeps the row-major X; X_t is a one-time device-side copy.
# --------------------------------------------------------------------------

def _wave_hist_kernel_t(*refs, bp, fc, bsub, packed, hilo=True):
    """refs: [tiles,] xt, lid, w3, cid, out.  With the scalar-prefetched
    `tiles` (a row slab whose rows past the first `tiles[0]` tiles are
    fill: leaf -2, weight 0) those grid steps add nothing, so their body
    is skipped, and the wrapper's clamped index maps fetch no new block
    for them."""
    *tiles, xt_ref, lid_ref, w3_ref, cid_ref, out_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def tile():
        xi = xt_ref[:].astype(jnp.int32)                 # (Fdev, Cg)
        if packed:
            xi = _unpack4_t(xi, fc)
        xt = xi.astype(jnp.float32)                      # (Fc, Cg)
        cg = xt.shape[1]

        wh, wl = _split_weights_t(lid_ref, w3_ref, cid_ref, hilo)  # (3K, Cg)

        xr = pltpu.repeat(xt, bsub, axis=0)              # (bsub*Fc, Cg) tiled
        base = (jax.lax.broadcasted_iota(jnp.int32, (bsub * fc, cg), 0)
                // fc).astype(jnp.float32)               # bin-within-subblock
        _accum_hist(out_ref, xr, base, wh, wl, bp=bp, fc=fc, bsub=bsub,
                    dims=(((1,), (1,)), ((), ())))       # A @ B^T — both Cg

    if tiles:
        pl.when(i < tiles[0][0])(tile)
    else:
        tile()


def slab_plan(n, fc, num_bins, k, packed=False, row_tile=8192):
    """``(cap, c)`` of the wave's row slab (ops/wave.py): half of the n
    rows, rounded up to the row tile c that the slab's own launch plans,
    so that launch needs no pad."""
    half = -(-n // 2)
    _, c = _tile_plan(half, fc, _bin_pad(num_bins), row_tile, k=k,
                      packed=packed)
    return -(-half // c) * c, c


def slab_chunk(cap, c):
    """Rows a chunk of the slab's move gathers (ops/wave.py move_rows): a
    whole number of the launch's row tiles c, about cap / 32.  The move
    ends at the chunk that holds the last live row, so it moves up to one
    chunk more than the kernel reads.  A chunk costs by its bytes, not by
    being one (v5e, PERF.md section 6, PR 34: counts from 8 to 64 read
    alike), so the count only bounds that excess.  Up to 47 tiles: a
    chunk a tile."""
    return c * max(1, round(cap / c / 32))


@functools.partial(jax.jit, static_argnames=("num_bins", "row_tile",
                                             "interpret", "logical_cols",
                                             "hilo"))
def wave_histogram_pallas_t(X_t, leaf_id, w3, child_id, num_bins: int,
                            row_tile: int = 8192, interpret: bool = False,
                            logical_cols: int = 0, hilo: bool = True,
                            n_active=None):
    """Same contract as wave_histogram_pallas, but takes the TRANSPOSED bin
    matrix X_t (F, N) (packed: (ceil(F/2), N) with logical_cols set).

    n_active (a traced int32 scalar): the caller promises that every row
    from n_active on is fill (leaf id matching no child, or weight 0), as
    in the row slab of ops/wave.py.  The grid keeps its static length;
    the tiles past ceil(n_active / c) are neither fetched nor computed.
    That changes no sum, only the time."""
    fdev, n = X_t.shape
    fc = logical_cols or fdev
    k = child_id.shape[0]
    bp = _bin_pad(num_bins)
    bsub, c = _tile_plan(n, fc, bp, row_tile, k=k,
                         packed=bool(logical_cols))
    pad = (-n) % c
    # row-vector operand layouts — see wave_histogram_pallas
    lid2 = (jnp.pad(leaf_id, (0, pad), constant_values=-2) if pad
            else leaf_id)[None, :]                       # (1, N)
    w3t = jnp.transpose(w3.astype(jnp.float32))          # (3, N)
    if pad:
        X_t = jnp.pad(X_t, ((0, 0), (0, pad)))
        w3t = jnp.pad(w3t, ((0, 0), (0, pad)))
    nch = (n + pad) // c

    kernel = functools.partial(_wave_hist_kernel_t, bp=bp, fc=fc,
                               bsub=bsub, packed=bool(logical_cols),
                               hilo=hilo)
    operands = (X_t, lid2, w3t, child_id[:, None])
    if n_active is None:
        prefetch = ()

        def row_block(i):
            return 0, i
    else:
        tiles = (jnp.asarray(n_active, jnp.int32) + (c - 1)) // c
        prefetch = (jnp.clip(tiles, 0, nch).reshape(1),)

        def row_block(i, t):    # past the last full tile: stay on it
            return 0, jnp.minimum(i, jnp.maximum(t[0] - 1, 0))

    specs = dict(
        grid=(nch,),
        in_specs=[pl.BlockSpec(shape, row_block, memory_space=pltpu.VMEM)
                  for shape in ((fdev, c), (1, c), (3, c))]
        + [pl.BlockSpec((k, 1), lambda i, *t: (0, 0),
                        memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((fc * bp, 3 * k), lambda i, *t: (0, 0),
                               memory_space=pltpu.VMEM))
    if prefetch:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
    flat = pl.pallas_call(
        kernel,
        name="wave_histogram_pallas_t",
        out_shape=vma_struct((fc * bp, 3 * k), jnp.float32, *operands),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        **specs)(*prefetch, *operands)
    h = flat.reshape(bp, fc, 3, k)[:num_bins]
    return jnp.transpose(h, (3, 1, 0, 2))


# --------------------------------------------------------------------------
# v3: FUSED partition + histogram.  The wave engine's XLA path runs a
# chunked partition scan (leaf-split-table lookup + routing) and then the
# histogram kernel — two passes over X.  This kernel does both in one:
# per row tile, look up the (L, 10) split table by leaf id (one-hot
# contraction on the MXU), route rows to their child, emit the updated
# leaf ids, and accumulate the child histograms — ONE read of X per wave.
# Split-table column layout matches ops/wave.py (active, device column,
# threshold, is_cat, default bin, default-left, right-leaf id, bundle
# offset/adjust/span).
# --------------------------------------------------------------------------

# --------------------------------------------------------------------------
# v5 'pallas_ct': FUSED partition + histogram, COMPACT table, pure
# row-vector orientation.  (The v3/v4 fused kernels — 'pallas_f' and
# 'pallas_ft' — were deleted in round 4: both lost every on-chip A/B to
# the split pallas_t+scan pipeline and carried lane-padded (N, 1)/(N, 3)
# operands, an OOM liability at >2M rows; see tools/AB_RESULTS.md and
# BENCH_NOTES.md.)  Lessons from them and the r03 OOM applied together:
# every per-row operand is a row vector ((1, N) lid, (3, N) w3 — no
# lane-padded columns), the split lookup contracts the COMPACT (10, W)
# table against a (W, Cg) parent match (W/L of the (Cg, L) one-hot), the
# routing algebra runs entirely on (1, Cg) rows derived from the
# TRANSPOSED tile (colv comes from a masked sublane reduction of Xt —
# no row-major X operand at all), and the histogram is the v2 MXU-native
# A @ B^T.  ONE read of Xt per wave, no XLA partition scan, no
# transposes anywhere.
# --------------------------------------------------------------------------

def _wave_fused_kernel_ct(xt_ref, lid_ref, w3_ref, cid_ref, tblt_ref,
                          psrc_ref, lid_out_ref, out_ref,
                          *, bp, fc, k, bsub, packed, bundled,
                          hilo=True, blocks=()):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    xi = xt_ref[:].astype(jnp.int32)                 # (Fdev, Cg)
    if packed:
        xi = _unpack4_t(xi, fc)
    xint = xi                                        # (Fc, Cg) int32
    cg = xint.shape[1]

    # ---- compact split lookup: (W, Cg) parent match, (10, W) table
    lid_row = lid_ref[:]                             # (1, Cg)
    match_p = (psrc_ref[:] == lid_row).astype(jnp.float32)   # (W, Cg)
    r = jax.lax.dot_general(                         # (10, Cg)
        tblt_ref[:], match_p, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)         # int entries exact

    active = r[0:1, :] > 0.5                         # (1, Cg)
    cj = r[1:2, :].astype(jnp.int32)
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (fc, cg), 0)
    colv = jnp.sum(jnp.where(cj == f_iota, xint, 0), axis=0,
                   keepdims=True)                    # (1, Cg) split-col bin
    if bundled:
        goff = r[7:8, :].astype(jnp.int32)
        span = r[9:10, :].astype(jnp.int32)
        in_range = (colv >= goff) & (colv < goff + span)
        colv = jnp.where(in_range,
                         colv - goff + r[8:9, :].astype(jnp.int32),
                         r[4:5, :].astype(jnp.int32))
    thr = r[2:3, :].astype(jnp.int32)
    is_cat = r[3:4, :] > 0.5
    # f32 0/1 carry for the decision (the i8->i1 trunci Mosaic fix)
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    gl = jnp.where(is_cat,
                   jnp.where(colv == thr, one, zero),
                   jnp.where(colv <= thr, one, zero))
    gl = jnp.where(colv == r[4:5, :].astype(jnp.int32),
                   jnp.where(r[5:6, :] > 0.5, one, zero), gl)
    new_lid = jnp.where(active & (gl < 0.5),
                        r[6:7, :].astype(jnp.int32), lid_row)  # (1, Cg)
    lid_out_ref[:] = new_lid

    # ---- histograms from the UPDATED ids (v2 layout: (3K, Cg) weights;
    # the shared helper accepts any (1, Cg) row, not just a ref)
    wh, wl = _split_weights_t(new_lid, w3_ref, cid_ref, hilo)  # (3K, Cg)

    xt = xint.astype(jnp.float32)
    if blocks:                  # a ragged store: each column its own bins
        _accum_hist_ragged(out_ref, xt, wh, wl, blocks=blocks)
        return
    xr = pltpu.repeat(xt, bsub, axis=0)              # (bsub*Fc, Cg)
    base = (jax.lax.broadcasted_iota(jnp.int32, (bsub * fc, cg), 0)
            // fc).astype(jnp.float32)
    _accum_hist(out_ref, xr, base, wh, wl, bp=bp, fc=fc, bsub=bsub,
                dims=(((1,), (1,)), ((), ())))


@functools.partial(jax.jit, static_argnames=("num_bins", "bundled",
                                             "row_tile", "interpret",
                                             "logical_cols", "hilo",
                                             "col_pads"))
def wave_partition_hist_pallas_ct(X_t, leaf_id, w3, child_id, cols, psrc,
                                  num_bins: int, bundled: bool = False,
                                  row_tile: int = 8192,
                                  interpret: bool = False,
                                  logical_cols: int = 0,
                                  hilo: bool = True, col_pads: tuple = ()):
    """Fused wave step from the transposed matrix alone.

    X_t: (F, N) bins (packed: (ceil(F/2), N) with logical_cols);
    leaf_id: (N,) int32 pre-wave; w3: (N, 3) [g, h, mult];
    child_id: (K,) target smaller-child leaves (-1 = inactive);
    cols: (W, 10) compact split rows (ops/wave.py column layout);
    psrc: (W,) parent leaf id per wave slot (-3 = inactive).
    col_pads: the one-hot width of each column where the store is ragged
    (ops/wave.py col_bin_pads; () = every column `_bin_pad(num_bins)`):
    the kernel multiplies each column against its own bins, over the
    uniform kernel's row tiles, and the bins past a column's pad come
    back zero, as they are.
    Returns (new_leaf_id (N,), (K, F, B, 3) child histograms).
    """
    fdev, n = X_t.shape
    fc = logical_cols or fdev
    k = child_id.shape[0]
    bp = _bin_pad(num_bins)
    bsub, c = _tile_plan(n, fc, bp, row_tile, k=k,
                         packed=bool(logical_cols))
    acc_rows = sum(col_pads) or fc * bp
    pad = (-n) % c
    lid2 = (jnp.pad(leaf_id, (0, pad), constant_values=-2) if pad
            else leaf_id)[None, :]                   # (1, N)
    w3t = jnp.transpose(w3.astype(jnp.float32))      # (3, N)
    if pad:
        X_t = jnp.pad(X_t, ((0, 0), (0, pad)))
        w3t = jnp.pad(w3t, ((0, 0), (0, pad)))
    nch = (n + pad) // c
    tblt = jnp.transpose(cols.astype(jnp.float32))   # (10, W)

    kernel = functools.partial(_wave_fused_kernel_ct, bp=bp, fc=fc, k=k,
                               bsub=bsub, packed=bool(logical_cols),
                               bundled=bundled, hilo=hilo,
                               blocks=_ragged_blocks(col_pads))
    operands = (X_t, lid2, w3t, child_id[:, None], tblt, psrc[:, None])
    newlid, flat = pl.pallas_call(
        kernel,
        name="wave_partition_hist_pallas_ct",
        grid=(nch,),
        in_specs=[
            pl.BlockSpec((fdev, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((10, cols.shape[0]), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cols.shape[0], 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((acc_rows, 3 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            vma_struct((1, n + pad), jnp.int32, *operands),
            vma_struct((acc_rows, 3 * k), jnp.float32, *operands),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    if col_pads:
        # a column's rows lie together: back into num_bins bins a column
        starts = [sum(col_pads[:j]) for j in range(fc)]
        h = jnp.stack([
            jnp.pad(flat[s:s + min(p, num_bins)],
                    ((0, max(num_bins - p, 0)), (0, 0)))
            for s, p in zip(starts, col_pads)])      # (Fc, B, 3K)
        return newlid[0, :n], jnp.transpose(
            h.reshape(fc, num_bins, 3, k), (3, 0, 1, 2))
    h = flat.reshape(bp, fc, 3, k)[:num_bins]
    return newlid[0, :n], jnp.transpose(h, (3, 1, 0, 2))
