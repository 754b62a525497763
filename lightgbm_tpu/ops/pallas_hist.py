"""Pallas TPU kernel for leaf histogram construction — the hottest op.

Parity target: the reference's OpenCL histogram kernels
(src/treelearner/ocl/histogram256.cl etc.), which scatter-add into
workgroup-local memory with atomics.  TPUs have no fast scatter, so the
kernel re-expresses the histogram as a one-hot contraction on the MXU —
but unlike the XLA `onehot` path (ops/histogram.py), the one-hot tile is
built **inside VMEM** per (row-chunk, feature-block) grid cell and never
round-trips through HBM:

  grid = (F/F_BLK, N/ROW_CHUNK)          (row chunks iterate fastest)
  per cell: for f in feature block:
      oh  = (bins_iota == x[f, :])        (B, C) one-hot in VMEM
      acc = oh (B, C) @ w (3, C)^T        MXU contraction (A @ B^T)
      out[f] += acc                        revisiting accumulation over chunks

Layouts are chosen for the TPU tiling rules (last dim % 128, second-to-last
% 8): bins arrive transposed (F, N), weights as a (3, N) row-vector
[g*m, h*m, m] (an (N, 3) column operand would pay the 128-lane tile
padding — 42.7x its logical bytes; see pallas_wave.py), the
histogram leaves as (F, B, 3) — exactly the layout the split scanner wants,
no transposes anywhere.  The leaf mask and bagging/GOSS row multipliers are
folded into `w` by the caller, so rows outside the target leaf contribute
zero, as in the other histogram modes.

HBM traffic per leaf: read the bins + 12N bytes of weights, write F*B*12
bytes of histogram — the one-hot (N*F*B*4 bytes) stays on-chip.

Measured on v5e (1M x 28 rows, dedup-proof varying inputs): 25ms at B=63 /
45ms at B=255 versus XLA's fused one-hot reduce at 7.2ms / 25.6ms — the
XLA path is already at the VPU roofline, and the MXU contraction here
wastes 125/128 output lanes because a histogram has only 3 weight columns.
The kernel therefore is an optional mode (tpu_histogram_mode=pallas), kept
as the foundation for the regime where the MXU *does* win: batching many
weight columns (multiclass trees, multi-leaf level-wise growth) to fill
the N dimension.  Default TPU mode is `onehot` (ops/learner.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from .grow import vma_struct

# the one-hot tile + resident accumulator must fit here; the lanes
# (row-chunk) axis of the block can shrink no further than the TPU's
# 128-lane tile, so bin widths the floor cannot absorb are OUT of the
# kernel's capacity (supports_bins) rather than silently over budget
TILE_BUDGET = 6 * 2**20
_MIN_ROW_CHUNK = 128


def tile_shape(num_bins: int):
    """(F_BLK, ROW_CHUNK) sized so the (F_BLK*B, C) one-hot tile stays well
    under the ~16MB VMEM budget.  F_BLK stays at 8 (the TPU sublane
    minimum for f32 blocks); large-B kernels shrink the row chunk.

    The budget is accounted against the kernel's LIVE SET, not the
    one-hot tile alone — the wave-kernel band post-mortem
    (ops/pallas_wave.py::_tile_plan, docs/FusedIteration.md) showed that
    ignoring resident blocks is exactly how mid-size shapes silently
    oversubscribe VMEM.  Here the resident (F_BLK, B, 3) f32 accumulator
    is bounded (F_BLK is fixed at 8), so it is subtracted from the tile
    budget rather than driving a separate regime.

    The chunk floor is the 128-lane tile minimum, NOT a round perf
    number: the old 512 floor quietly handed B=1024 a 16MB one-hot
    (2.7x the budget) and B=4096 a 64MB one — the exact
    floor-masks-the-budget bug class of the wave band post-mortem,
    surfaced by the vmem lint pass (analysis/vmem.py) when it first ran.
    Widths even the 128 floor cannot absorb fail ``supports_bins`` and
    never reach the kernel (leaf_histogram_pallas falls back to onehot).

    Public: the vmem lint pass (analysis/vmem.py) evaluates it."""
    f_blk = 8
    row_chunk = 2048
    resident = f_blk * num_bins * 3 * 4          # the out block, VMEM-held
    budget = TILE_BUDGET - resident
    while f_blk * num_bins * row_chunk * 4 > budget \
            and row_chunk > _MIN_ROW_CHUNK:
        row_chunk //= 2
    return f_blk, row_chunk


def supports_bins(num_bins: int) -> bool:
    """True when some %128 row chunk keeps the kernel's live set
    (one-hot tile + resident accumulator) within TILE_BUDGET.  At f32
    with F_BLK=8 this tops out just under B=2048; beyond it the kernel
    would need bin-axis blocking it does not have."""
    f_blk = 8
    resident = f_blk * num_bins * 3 * 4
    return (f_blk * num_bins * _MIN_ROW_CHUNK * 4
            <= TILE_BUDGET - resident)


_tile_shape = tile_shape        # pre-v8 private name, kept importable


def _hist_kernel(x_ref, w_ref, out_ref, *, num_bins: int, f_blk: int):
    """One (feature-block, row-chunk) cell.

    x_ref: (F_BLK, C) f32 bin ids; w_ref: (3, C) f32 row-vector weights;
    out_ref: (F_BLK, B, 3) f32 accumulated over the row-chunk grid axis.

    The whole block's one-hot is built as ONE (F_BLK*B, C) tile: row r
    compares feature r//B against bin r%B.  The row replication x[r//B] is
    an MXU matmul with a constant 0/1 selection matrix, so the cell is two
    MXU contractions + one VPU compare — no per-feature loop.
    """
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    C = x_ref.shape[1]
    FB = f_blk * num_bins
    x = x_ref[:]                                       # (F_BLK, C) f32
    w = w_ref[:]                                       # (3, C) row-vector
    # S[r, j] = 1 iff j == r // B  (compile-time constant tile)
    r_over_b = lax.broadcasted_iota(jnp.int32, (FB, f_blk), 0) // num_bins
    feat = lax.broadcasted_iota(jnp.int32, (FB, f_blk), 1)
    sel = (r_over_b == feat).astype(jnp.float32)       # (FB, F_BLK)
    x_rep = jnp.dot(sel, x, preferred_element_type=jnp.float32)  # (FB, C)
    b_of_r = (lax.broadcasted_iota(jnp.int32, (FB, C), 0)
              % num_bins).astype(jnp.float32)
    oh = (x_rep == b_of_r).astype(jnp.float32)         # (FB, C)
    acc = lax.dot_general(                             # A @ B^T: both C
        oh, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (FB, 3)
    out_ref[:] = out_ref[:] + acc.reshape(f_blk, num_bins, 3)


@functools.partial(jax.jit, static_argnames=("num_bins", "interpret"))
def _hist_pallas(xt, w, num_bins: int, interpret: bool):
    f, n = xt.shape
    f_blk, row_chunk = tile_shape(num_bins)
    grid = (f // f_blk, n // row_chunk)
    kernel = functools.partial(_hist_kernel, num_bins=num_bins, f_blk=f_blk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((f_blk, row_chunk), lambda i, c: (i, c)),
            pl.BlockSpec((3, row_chunk), lambda i, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((f_blk, num_bins, 3), lambda i, c: (i, 0, 0)),
        out_shape=vma_struct((f, num_bins, 3), jnp.float32, xt, w),
        interpret=interpret,
    )(xt, w)


def leaf_histogram_pallas(binned, grad, hess, leaf_id, leaf, row_mult,
                          num_bins: int, interpret: bool = None):
    """(F, B, 3) histogram of the target leaf via the fused Pallas kernel.

    Same contract as leaf_histogram_scatter/onehot (ops/histogram.py).
    interpret defaults to True off-TPU so tests exercise the kernel on the
    CPU mesh (the reference's OpenCL-on-CPU trick, SURVEY.md §4).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not supports_bins(num_bins):
        # beyond the kernel's bin capacity even the minimum row chunk
        # oversubscribes VMEM — serve the request from the XLA one-hot
        # path instead of shipping an over-budget tile to the compiler
        from ..utils.log import Log
        from .histogram import leaf_histogram_onehot
        Log.warning("pallas histogram: num_bins=%d exceeds the kernel's "
                    "VMEM capacity (analysis/vmem.py vmem-hist-tile); "
                    "falling back to onehot", num_bins)
        return leaf_histogram_onehot(binned, grad, hess, leaf_id, leaf,
                                     row_mult, num_bins=num_bins)
    n, f = binned.shape
    from .histogram import _weights
    w = _weights(jnp.asarray(grad, jnp.float32),
                 jnp.asarray(hess, jnp.float32), leaf_id, leaf,
                 None if row_mult is None
                 else jnp.asarray(row_mult, jnp.float32))   # (N, 3)

    f_blk, row_chunk = tile_shape(num_bins)
    npad = (-n) % row_chunk
    fpad = (-f) % f_blk
    xt = binned.astype(jnp.float32).T                   # (F, N); bins < 2^24
                                                        # so f32 compare exact
    # weights as a (3, N) row-vector operand: an (N, 3) column layout
    # would pay TPU's 128-lane tile padding (42.7x its logical bytes —
    # the same class of HBM blowup fixed in pallas_wave.py)
    wt = jnp.transpose(w)                               # (3, N)
    if npad:
        xt = jnp.pad(xt, ((0, 0), (0, npad)))
        wt = jnp.pad(wt, ((0, 0), (0, npad)))           # zero weight rows
    if fpad:
        xt = jnp.pad(xt, ((0, fpad), (0, 0)))

    out = _hist_pallas(xt, wt, num_bins, interpret)
    return out[:f]                                      # (F, B, 3)
