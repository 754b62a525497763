"""The execution plan: which kernel, width, precision and program a
learner runs, resolved once from values.

``resolve_plan`` is the one place where ``auto`` and the ``tpu_*`` keys
turn into decisions.  Every input is a value (the backend is passed in;
this module never asks JAX for it), so the rows the chip runs are the
rows a CPU test pins (tests/test_plan.py).  Arrows point one way:

    config -> plan -> learner / mesh learner / booster -> wave -> kernels

The learner (ops/learner.py), the mesh learners (parallel/mesh.py) and
the booster (models/gbdt.py) read ``learner.plan``; ops/wave.py owns
"can this kernel run here" (`pallas_wave_active`,
`transposed_wave_active`, `slab_active`) and is asked once, from here.

What each rule rests on is said at the rule: a line of the driver's
ledger (``PERF_LEDGER.jsonl``), or "not measured by the driver".
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from ..utils.config import _FALSE_SET, _TRUE_SET, Config
from ..utils.log import Log
from .pack import can_pack4
from .wave import (WAVE_ONLY_MODES, _bin_pad, col_bin_pads,
                   hist_block_bytes, pallas_wave_active, slab_active,
                   transposed_wave_active)

# the VMEM budget the Pallas wave kernels compile under, shared with the
# auto hist-mode gate (64 MB of the kernels' 100 MB compiler limit so
# input tiles and temporaries fit too).  A correctness gate: a wider
# accumulator block would not compile.  Not measured by the driver (no
# cell's block comes near it: 49 MB at 2,000 x 64-pad x W=32).
WAVE_VMEM_GATE = 64 << 20

# pallas_ct (partition fused into the histogram kernel) up to this
# ncols * bin_pad on one device, pallas_t above.  The driver measures a
# cell on each side: under it `higgs_28_train` (28 x 64 = 1,792, PR 33)
# and ON it `expo_700_train` (10 EFB groups x a 256-bin pad = 2,560,
# PR 35: pallas_ct; an eleventh group would take pallas_t and the slab);
# far above it the wide cells (968 x 64 = 61,952: pallas_t).
# tests/test_plan.py pins the plan at 9, 10 and 11 groups.
CT_PROMOTION_BOUND = 2560

# auto histogram-cache budget when histogram_pool_size is unset (-1): the
# reference's default is unlimited, but an Epsilon-shaped cache
# (L=255,F=2000,B=255 ~ 1.5GB) per booster is an HBM hazard on shared
# chips, so above this we fall back to recompute instead of subtraction
_AUTO_HIST_CACHE_MB = 2048.0


class Plan(NamedTuple):
    """What ``auto`` and the ``tpu_*`` keys resolved to, once a learner."""
    hist_mode: str          # the tpu_histogram_mode values but auto,
    #                         and sparse / sparse_mxu for the two stores
    growth: str             # exact / wave
    wave_order: str         # batched / exact
    wave_width: int         # W (1 under exact growth)
    hist_hilo: bool         # two bf16 products a weight (False: one)
    wave_lookup: str        # onehot / compact / gather
    packed_cols: int        # logical columns of a 4-bit store, 0 = bytes
    sparse: str             # "" | "coo" | "mxu": the device store
    cache_hists: bool       # per-leaf histograms kept for subtraction
    wave_chunk: int         # rows a chunk of the XLA wave sweep
    pallas_interpret: bool  # the kernels through the interpreter (CPU)
    # the three predicates of ops/wave.py, asked once
    kernel_runs: bool       # a compiled Pallas wave kernel runs
    needs_xt: bool          # ... a transposed one: keep an (F, N) Xt
    slab: bool              # a wave's launch reads its row slab

    @property
    def fused_wanted(self) -> bool:
        """What ``tpu_fused_iter=auto`` wishes: one program an iteration
        where the compiled kernels run (there the staged chain's
        dispatches are what is left to save); ops/fused_iter.py
        ``fused_supported`` still has the last word.  Ledger, PR 25/27:
        both one-chip cells run fused; the four-chip cell runs staged
        because ``fused_supported`` refuses a mesh (PR 28/29)."""
        return self.kernel_runs


def hist_cache_enabled(config: Config, num_leaves: int, num_cols: int,
                       num_bins: int, dtype_bytes: int) -> bool:
    """HistogramPool policy (feature_histogram.hpp:398-565): cache per-leaf
    histograms (enabling larger-child-by-subtraction) only while the
    (L, F, B, 3) cache fits the histogram_pool_size budget; otherwise
    recompute both children and warn with the number."""
    need_mb = (num_leaves * max(num_cols, 1) * max(num_bins, 2) * 3
               * dtype_bytes) / 1e6
    budget = float(config.histogram_pool_size)
    if budget <= 0:
        budget = _AUTO_HIST_CACHE_MB
    if need_mb <= budget:
        return True
    Log.warning(
        "Histogram cache would need %.0f MB (num_leaves=%d x %d columns x "
        "%d bins x 3 x %dB) > histogram_pool_size budget %.0f MB; disabling "
        "the per-leaf histogram cache (children are recomputed instead of "
        "obtained by subtraction).", need_mb, num_leaves, num_cols,
        num_bins, dtype_bytes, budget)
    return False


def _order_sensitive(config: Config) -> bool:
    """Configs whose quality depends on the leaf-wise split ORDER
    (PARITY_TRAINING.md: lambdarank NDCG; DART/GOSS/InfiniteBoost
    compound the approximation through tree re-weighting / sampling)."""
    return (str(config.objective) in ("lambdarank", "rank")
            or str(config.boosting_type) in ("dart", "goss", "infinite",
                                             "infiniteboost"))


def resolve_wave_order(config: Config) -> str:
    """tpu_wave_order: auto -> 'exact' where order matters (those configs
    keep the wave's width WITH the reference's split sequence), 'batched'
    otherwise.  Ledger, PR 25-29: every cell runs 'batched'; 'exact' is
    not measured by the driver."""
    v = str(config.tpu_wave_order).strip().lower()
    if v not in ("auto", "batched", "exact"):
        Log.fatal("Unknown tpu_wave_order %s (expected auto/batched/"
                  "exact)", v)
    if v != "auto":
        return v
    return "exact" if _order_sensitive(config) else "batched"


def resolve_wave_width(config: Config, num_leaves: int,
                       wave_order: str = "batched") -> int:
    """tpu_wave_width=-1 -> auto: 8 up to 31 leaves, 16 up to 127, 32
    above; 1 for an order-sensitive config under an explicit
    tpu_wave_order=batched (tests/test_wave_exact_order.py holds 'exact'
    to the leaf-wise sequence at any W).  Explicit widths pass through.
    Ledger, PR 25/27: W=32 at 255 leaves in every cell; the ladder below
    255 leaves is not measured by the driver."""
    w = int(config.tpu_wave_width)
    if w > 0:
        return w
    if w != -1:
        Log.fatal("tpu_wave_width must be positive or -1 (auto), got %d", w)
    if _order_sensitive(config) and wave_order != "exact":
        # batched waves approximate the split order — these configs pay
        # W=1 unless the exact-order schedule carries them
        return 1
    if num_leaves <= 31:
        return 8
    if num_leaves <= 127:
        return 16
    return 32


def prior_hist_mode(config: Config, ncols: int, bin_pad: int,
                    num_leaves: int, psum_axis: Optional[str],
                    backend: str, dtype=jnp.float32) -> str:
    """tpu_histogram_mode=auto: a transposed Pallas wave kernel wherever
    the wave engine will run it (TPU, f32, the dense store, the serial or
    data learner, an accumulator block inside `WAVE_VMEM_GATE`): pallas_ct
    up to `CT_PROMOTION_BOUND` on one device, pallas_t otherwise; else
    onehot on a TPU and scatter off it.  Ledger, PR 25/27 (one chip) and
    PR 28/29 (mesh): pallas_t at 968 and 2,000 columns; the pallas_ct
    bound and the gate's onehot side are not measured by the driver."""
    on_tpu = backend == "tpu"
    wave_capable = (
        str(config.tpu_growth) in ("auto", "wave")
        and dtype == jnp.float32
        and not config.tpu_sparse
        and str(config.tree_learner) in ("serial", "data",
                                         "data_parallel"))
    # width only resolved (and validated) when the wave engine will
    # actually run — off-TPU growth resolves to exact here and a
    # garbage tpu_wave_width must keep training (ADVICE r2)
    vmem_hist_bytes = (hist_block_bytes(
        ncols, bin_pad,
        resolve_wave_width(config, num_leaves, resolve_wave_order(config)))
        if on_tpu and wave_capable else 0)
    if on_tpu and wave_capable and vmem_hist_bytes <= WAVE_VMEM_GATE:
        return ("pallas_ct"
                if ncols * bin_pad <= CT_PROMOTION_BOUND
                and psum_axis is None
                else "pallas_t")
    return "onehot" if on_tpu else "scatter"


def prior_hist_hilo(growth: str, psum_axis: Optional[str],
                    kernel_runs: bool) -> bool:
    """tpu_hist_precision=auto: one bf16 product a weight where a Pallas
    wave kernel runs under wave growth on one device, hi/lo (two
    products) everywhere else.  Ledger, PR 25/27: one product in both
    one-chip cells, `correct`; PR 28/29: hi/lo under the mesh, whose
    `loss_gap` limit one product would not meet (PERF.md section 7)."""
    return not (growth == "wave" and psum_axis is None and kernel_runs)


def store_bin_width(plan: "Plan", nbins: int) -> int:
    """The one-hot width a column of the device store is multiplied
    against under `plan`: the Pallas wave kernels (compiled or through
    the interpreter) pad the bins (`_bin_pad`), every other engine takes
    the `nbins` it is given."""
    return _bin_pad(nbins) if _wave_kernel(plan, "pallas") else nbins


def _wave_kernel(plan: "Plan", mode: str) -> bool:
    """A Pallas wave kernel whose mode starts with `mode` runs under
    `plan`, compiled or through the interpreter."""
    return plan.growth == "wave" and plan.hist_mode.startswith(mode) \
        and (plan.kernel_runs or plan.pallas_interpret)


def store_col_pads(plan: "Plan", bins_per_col, nbins: int) -> tuple:
    """Each column's own one-hot width, where `plan`'s kernel multiplies a
    column against its own bins and they differ from `store_bin_width`:
    the fused kernel (pallas_ct, compiled or through the interpreter) on
    a ragged store (ops/wave.py col_bin_pads: EFB groups of 13 to 256
    bins).  () everywhere else: every column is `store_bin_width` wide.
    What `auto` resolves to is judged on the uniform block, not on these
    (`prior_hist_mode`)."""
    return (col_bin_pads(bins_per_col, nbins)
            if _wave_kernel(plan, "pallas_ct") else ())


def resolve_plan(config: Config, *, ncols: int, nbins: int,
                 num_leaves: int, bins_per_col, backend: str, dtype,
                 psum_axis: Optional[str],
                 dense_device_data: bool) -> Plan:
    """The plan of one learner.  `ncols` x `nbins` is the device store's
    shape (EFB groups where the dataset is bundled), `bins_per_col` its
    bin counts, `backend` the caller's JAX default backend, `dtype` the
    accumulation type, `psum_axis` the mesh axis of a data or voting
    learner, `dense_device_data` whether the caller brings a dense device
    matrix of its own (the mesh learners do).  Checks the keys it reads:
    a bad value is fatal, an ineffective one warns."""
    on_tpu = backend == "tpu"
    hist_mode = config.tpu_histogram_mode
    if hist_mode not in (("auto", "onehot", "scatter", "pallas")
                         + WAVE_ONLY_MODES):
        Log.fatal("Unknown tpu_histogram_mode %s (expected auto/onehot/"
                  "scatter/pallas/pallas_t/pallas_ct)", hist_mode)
    if hist_mode == "auto":
        hist_mode = prior_hist_mode(config, ncols, _bin_pad(nbins),
                                    num_leaves, psum_axis, backend, dtype)
    cache_hists = hist_cache_enabled(config, num_leaves, ncols, nbins,
                                     jnp.dtype(dtype).itemsize)
    # growth schedule: 'wave' batches the top-W pending splits per
    # sweep so the histogram work rides the MXU (ops/wave.py); 'exact'
    # is the per-split leaf-wise order of the reference (ops/grow.py).
    # auto -> wave on TPU.  NOTE: W (tpu_wave_width, default -1 = auto
    # via resolve_wave_width) approximates the leaf-wise ORDER (same
    # greedy frontier, batched; quality parity in tests/test_wave.py)
    # — set tpu_wave_width=1 for the reference's exact split sequence.
    growth = config.tpu_growth
    if growth not in ("auto", "exact", "wave"):
        Log.fatal("Unknown tpu_growth %s (expected auto/exact/wave)",
                  growth)
    if growth == "auto":
        # 'pallas' is the exact engine's per-leaf kernel; the
        # WAVE_ONLY_MODES kernels exist only as wave kernels
        if hist_mode in WAVE_ONLY_MODES:
            growth = "wave"
        else:
            growth = ("wave" if on_tpu and hist_mode != "pallas"
                      else "exact")
    if growth == "exact" and hist_mode in WAVE_ONLY_MODES:
        Log.fatal("tpu_histogram_mode=%s requires tpu_growth=wave "
                  "(this kernel is wave-only)" % hist_mode)
    # ---- sparse device store (SparseBin/OrderedSparseBin analog,
    # ops/sparse_store.py): histograms from nonzero entries only, one
    # segment_sum over nnz per leaf instead of an O(N*F) dense pass.
    serial_learner = str(config.tree_learner) in ("serial",)
    # gate on the engine actually running, not the tree_learner
    # string: a 'data'/'voting' config falling back to the serial
    # engine on one device still gets the sparse store.  The
    # feature-parallel subclass is the exception — it calls the base
    # ctor with psum_axis=None but a pre-sharded dense device_data.
    true_serial = psum_axis is None and not dense_device_data
    # the data-parallel learner shards the coordinate store by row
    # blocks itself (parallel/mesh.py); feature/voting keep dense
    dp_learner = (psum_axis is not None
                  and str(config.tree_learner)
                  in ("data", "data_parallel"))
    sparse_on = bool(config.tpu_sparse)
    if sparse_on and not (true_serial or dp_learner):
        Log.warning("tpu_sparse=true ignored: the sparse device store "
                    "supports the serial and data-parallel learners "
                    "only")
        sparse_on = False
    sparse_kernel = bool(config.tpu_sparse_kernel)
    if sparse_kernel and not sparse_on:
        Log.warning("tpu_sparse_kernel=true has no effect without "
                    "tpu_sparse=true")
        sparse_kernel = False
    if sparse_on:
        if hist_mode.startswith("pallas"):
            Log.fatal("tpu_sparse=true is incompatible with "
                      "tpu_histogram_mode=%s (the pallas kernels are "
                      "dense-only)", hist_mode)
        if sparse_kernel and dp_learner:
            Log.warning("tpu_sparse_kernel=true ignored under the "
                        "data-parallel learner (the mesh sparse grow "
                        "shards the coordinate store)")
            sparse_kernel = False
        if sparse_kernel:
            # entry-chunk MXU store (ops/sparse_mxu.py) — wave-only:
            # the whole design amortizes one O(nnz) pass over W
            # splits and feeds the MXU per chunk
            if str(config.tpu_growth) == "exact":
                Log.fatal("tpu_sparse_kernel=true requires wave "
                          "growth (tpu_growth=exact scans per leaf)")
            growth = "wave"
            hist_mode = "sparse_mxu"
        else:
            # both engines take the coordinate store: exact scans
            # nonzeros per split, wave amortizes the O(nnz) pass
            # over W splits but pays W split-column
            # materializations.  Not measured by the driver (no
            # sparse cell), so auto growth stays exact; an explicit
            # tpu_growth=wave is honored
            if str(config.tpu_growth) == "auto":
                growth = "exact"
            hist_mode = "sparse"
    # wave width only matters (and is only validated) under wave
    # growth — an exact-growth config with a leftover garbage
    # tpu_wave_width must keep training (ADVICE r2).
    wave_order = (resolve_wave_order(config) if growth == "wave"
                  else "batched")
    wave_width = (resolve_wave_width(config, num_leaves, wave_order)
                  if growth == "wave" else 1)
    kernel_runs = pallas_wave_active(hist_mode, dtype, backend)
    hp = str(config.tpu_hist_precision).strip().lower()
    if hp not in ("auto", "hilo", "bf16"):
        Log.fatal("Unknown tpu_hist_precision %s (expected auto/"
                  "hilo/bf16)", config.tpu_hist_precision)
    if hp == "auto":
        hist_hilo = prior_hist_hilo(growth, psum_axis, kernel_runs)
    else:
        hist_hilo = hp != "bf16"
    lk = str(config.tpu_wave_lookup).strip().lower()
    # validate unconditionally (like tpu_histogram_mode): a typo'd
    # value must not be silently ignored just because growth resolved
    # to exact (ADVICE r3); it is APPLIED only under wave growth
    if lk not in ("auto", "onehot", "compact", "gather"):
        Log.fatal("Unknown tpu_wave_lookup %s (expected auto/"
                  "onehot/compact/gather)", config.tpu_wave_lookup)
    wave_lookup = "onehot"
    if growth == "wave":
        # auto -> compact on a TPU (rows matched against the W wave
        # parents only, W/L of the leaf one-hot's footprint), onehot
        # elsewhere (CPU layouts don't pay the lane padding).  Ledger,
        # PR 25-29: every cell runs compact; its other sides are not
        # measured by the driver
        if lk == "auto":
            wave_lookup = "compact" if on_tpu else "onehot"
        else:
            wave_lookup = lk
        # the "no effect" warning must only fire when the fused
        # kernel will ACTUALLY run — off-TPU those modes fall back
        # to the XLA partition scan where the lookup does apply
        # (ADVICE r3); the sparse pass owns its lookup everywhere
        fused_runs = hist_mode == "pallas_ct" and kernel_runs
        if lk != "auto" and (fused_runs or sparse_on):
            Log.warning("tpu_wave_lookup=%s has no effect under %s "
                        "(the fused kernels / sparse pass own their "
                        "own lookup)", lk,
                        "tpu_sparse" if sparse_on
                        else "tpu_histogram_mode=%s" % hist_mode)
    # 4-bit packing (dense_nbits_bin.hpp:37 analog, ops/pack.py): when
    # every device column fits a nibble, store TWO columns per byte in
    # HBM; the growth engines unpack per chunk/column in-scan, so the
    # bin matrix's HBM footprint and read traffic halve.  Supported by
    # the wave engine (the TPU default) and by exact growth under the
    # onehot/scatter kernels; the pallas kernels and mesh learners
    # keep byte bins.
    pack_cfg = str(config.tpu_bin_pack).strip().lower()
    if pack_cfg not in _TRUE_SET | _FALSE_SET | {"auto"}:
        Log.fatal("tpu_bin_pack: value %s cannot be parsed as "
                  "auto/bool", config.tpu_bin_pack)
    pack_forced = pack_cfg in _TRUE_SET
    pack_growth_ok = (growth == "wave"
                      or (growth == "exact"
                          and hist_mode in ("onehot", "scatter")))
    # mesh learners keep byte bins: data/voting arrive with psum_axis
    # set, but the feature-parallel subclass calls the base ctor with
    # psum_axis=None and a pre-sharded device matrix — gate on the
    # tree_learner config (serial_learner above), not just the axis
    packed_cols = 0
    if ((pack_forced or pack_cfg == "auto") and pack_growth_ok
            and not sparse_on
            and psum_axis is None and serial_learner
            and can_pack4(bins_per_col)):
        packed_cols = ncols
    elif pack_forced:
        reasons = []
        if sparse_on:
            reasons.append("the dense device store (tpu_sparse keeps "
                           "coordinates, there are no bin bytes to "
                           "pack)")
        elif not pack_growth_ok:
            reasons.append("wave growth or exact growth with the "
                           "onehot/scatter histogram kernels")
        if psum_axis is not None or not serial_learner:
            reasons.append("the serial (single-shard) learner")
        if not can_pack4(bins_per_col):
            reasons.append("at most 16 bins per column (max_bin<=15 "
                           "plus the reserved zero/missing bin)")
        Log.warning("tpu_bin_pack=true ignored: packing requires %s",
                    " and ".join(reasons))
    wave_chunk = int(config.tpu_wave_chunk)
    if wave_chunk <= 0:
        Log.fatal("tpu_wave_chunk must be positive, got %s",
                  config.tpu_wave_chunk)
    elif growth == "wave" and wave_chunk < 256:
        Log.warning("tpu_wave_chunk=%d is below the engine minimum; "
                    "the wave sweep uses 256-row chunks instead",
                    wave_chunk)
    # CPU-interpret Pallas execution (tests / CI parity runs): a
    # forced wave-kernel mode off-TPU normally falls back to the XLA
    # wave path; tpu_pallas_interpret=true runs the ACTUAL Pallas
    # kernels through the interpreter instead, so fused-vs-staged
    # bit-identity and the tile-plan regressions are CPU-testable
    # end-to-end (tests/test_fused_iter.py).  On TPU the flag is
    # meaningless — the compiled kernels run.
    pallas_interpret = bool(config.tpu_pallas_interpret)
    if pallas_interpret and on_tpu:
        Log.warning("tpu_pallas_interpret=true ignored on TPU (the "
                    "compiled Pallas kernels run)")
        pallas_interpret = False
    # whether a wave's histogram launch reads the row slab of its
    # smaller children (ops/wave.py slab_active): not a key, decided
    # from the kernel, the store and the execution that resolved.
    # Ledger, PR 27 (one chip) and PR 29 (every shard of the mesh)
    slab = (growth == "wave" and not sparse_on
            and slab_active(True, hist_mode, dtype, psum_axis,
                            pallas_interpret, backend))
    return Plan(
        hist_mode=hist_mode, growth=growth, wave_order=wave_order,
        wave_width=int(wave_width), hist_hilo=bool(hist_hilo),
        wave_lookup=wave_lookup, packed_cols=int(packed_cols),
        sparse=("" if not sparse_on else "mxu" if sparse_kernel
                else "coo"),
        cache_hists=cache_hists, wave_chunk=wave_chunk,
        pallas_interpret=pallas_interpret, kernel_runs=kernel_runs,
        needs_xt=transposed_wave_active(hist_mode, dtype, backend),
        slab=slab)
