"""Tree learner: host facade over the device-resident grower (ops/grow.py).

Replaces SerialTreeLearner (serial_tree_learner.cpp) with a single jitted
XLA program per tree; the host only samples feature_fraction masks, feeds
gradients, and materializes the finished tree.  `train_device` returns the
device pytree without any host sync — the GBDT loop uses it to keep the
whole boosting iteration on-device; `train` additionally materializes a
models.Tree (real-valued thresholds resolved in float64 via the BinMappers).

Bagging/GOSS enter via `row_mult`; data-parallel runs wrap the same grow
program in shard_map (parallel/mesh.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.dataset import TrainingData
from ..models.tree import Tree
from ..obs import NULL_OBSERVER, timers
from ..utils.config import Config
from ..utils.random import Random
from .grow import (BundleArrays, TreeArrays, default_row_capacities,
                   make_grow_fn)
from .split_finder import FeatureMeta, SplitParams
from ..utils.log import Log

# auto histogram-cache budget when histogram_pool_size is unset (-1): the
# reference's default is unlimited, but an Epsilon-shaped cache
# (L=255,F=2000,B=255 ~ 1.5GB) per booster is an HBM hazard on shared
# chips, so above this we fall back to recompute instead of subtraction
_AUTO_HIST_CACHE_MB = 2048.0


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


def build_bundle_arrays(train_data: TrainingData):
    """(BundleArrays, group_bins) for the device grower, or (None, 0) when
    the dataset has no EFB layout."""
    bund = train_data.bundle
    if bund is None:
        return None, 0
    num_bin = np.asarray(train_data.num_bin_arr, np.int64)
    default = np.asarray(train_data.default_bin_arr, np.int64)
    B = int(num_bin.max())
    Bg = int(bund.num_group_bins.max())
    b = np.arange(B)[None, :]
    gb = bund.bin_off[:, None] + b - bund.bin_adj[:, None]
    valid = (b < num_bin[:, None]) & (b != default[:, None])
    flat_idx = bund.group_of[:, None].astype(np.int64) * Bg + gb
    flat_idx = np.clip(flat_idx, 0, len(bund.num_group_bins) * Bg - 1)
    arrays = BundleArrays(
        group_of=jnp.asarray(bund.group_of, jnp.int32),
        bin_off=jnp.asarray(bund.bin_off, jnp.int32),
        bin_adj=jnp.asarray(bund.bin_adj, jnp.int32),
        bin_span=jnp.asarray(bund.bin_span, jnp.int32),
        gather_idx=jnp.asarray(flat_idx, jnp.int32),
        valid_mask=jnp.asarray(valid),
    )
    return arrays, Bg


# kernel-selection policy now lives in ops/autotune.py (the measured
# autotuner's PRIOR); re-exported here because tests and downstream
# code import the resolvers from the learner module.  (The former
# HIST_BLOCK_BAND / band_adjusted_width escape prior is gone: the
# 18-30 MB degeneracy was root-caused to the tile planner's live-set
# overshoot and fixed in ops/pallas_wave.py _tile_plan — post-mortem
# in docs/FusedIteration.md.)
from .autotune import (_order_sensitive, resolve_wave_order,
                       resolve_wave_width)


def build_split_params(config: Config) -> SplitParams:
    return SplitParams(
        lambda_l1=float(config.lambda_l1),
        lambda_l2=float(config.lambda_l2),
        min_gain_to_split=float(config.min_gain_to_split),
        min_data_in_leaf=float(config.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
        use_missing=bool(config.use_missing),
    )


def _page_rows(reader, lo: int, hi: int, row_pad: int, device=None):
    """Rows [lo, hi) of `reader` and `row_pad` zero rows after them as one
    array on `device` (None: the default device), one page (the part of
    one directory shard inside the range) at a time."""
    ids = {} if device is None else {"device": int(device.id)}
    parts = []
    for shard, (_, view) in enumerate(reader.iter_rows(lo, hi)):
        with timers.span("upload_shard", shard=shard, **ids):
            with timers.span("host_copy"):
                page = np.ascontiguousarray(view)
            with timers.span("h2d"):    # the call; the copy is async
                parts.append(jax.device_put(page, device))
    if row_pad:
        parts.append(jnp.zeros((int(row_pad), reader.num_columns),
                               reader.dtype, device=device))
    if len(parts) == 1:
        return parts[0]
    with timers.span("concat"):         # dispatch only
        return jnp.concatenate(parts, axis=0)


def paged_device_matrix(train_data, row_pad: int = 0, sharding=None):
    """Device bin matrix paged shard-by-shard from a binned-format mmap
    reader (io/binned_format.py): the host never materializes the full
    (N, G) matrix, so peak host RSS stays O(shard) for out-of-core
    datasets.  Returns None when the dataset is not reader-backed —
    callers fall back to the one-shot host upload.

    `sharding` (a row sharding over a mesh, parallel/mesh.py): the rows
    and their `row_pad` tail are cut into one equal range a device, in
    mesh order, and each of this process's devices is sent only the pages
    of its own range, a thread a device; the host holds a page a device
    and no device ever holds another's rows."""
    reader = getattr(train_data, "_binned_reader", None)
    if reader is None or reader.num_columns == 0 or reader.num_data == 0:
        return None
    # the reader's row_range scopes the paging — on a rank-sharded open
    # (io/dataset.py from_binned(comm=...)) this rank uploads only its
    # own rows and never maps a foreign shard
    lo, hi = reader.row_range
    with timers.span("upload"):
        if sharding is None:
            return _page_rows(reader, lo, hi, row_pad)
        devices = [d for d in sharding.mesh.devices.flat
                   if d.process_index == jax.process_index()]
        local = (hi - lo + int(row_pad)) // len(devices)

        def one(k):
            a = min(lo + k * local, hi)
            b = min(a + local, hi)
            return _page_rows(reader, a, b, local - (b - a), devices[k])

        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(len(devices)) as pool:
            shards = list(pool.map(one, range(len(devices))))
        return jax.make_array_from_single_device_arrays(
            (local * sharding.mesh.devices.size, reader.num_columns),
            sharding, shards)


class SerialTreeLearner:
    # run observer (lightgbm_tpu/obs); a class-level NULL default keeps
    # every constructor untouched and the disabled path allocation-free
    _obs = NULL_OBSERVER

    def __init__(self, config: Config, train_data: TrainingData,
                 psum_axis: Optional[str] = None, device_data=None,
                 device_row_pad: int = 0, device_packed_cols: int = 0,
                 device_sparse_col_cap: int = 0):
        """device_data: pre-uploaded (and possibly row-padded) bin matrix,
        or a SparseDeviceStore (with device_sparse_col_cap set);
        device_row_pad says how many trailing pad rows it carries so
        row_mult/_ones stay aligned (reset_config's no-reupload reuse);
        device_packed_cols: the logical column count when device_data is
        4-bit packed (0 = unpacked)."""
        self.config = config
        self.train_data = train_data
        # schema events produced during construction (band escapes,
        # autotune probes/decision) — the observer is attached AFTER
        # construction (gbdt.py _reset_observer), so they queue here
        # and set_observer flushes them right after the run header
        self._pending_events = []
        self.num_leaves = config.num_leaves
        self.dtype = jnp.float64 if config.tpu_use_dp else jnp.float32
        self.num_bins = int(train_data.num_bin_arr.max()) if train_data.num_features else 2
        if train_data.num_features == 0:
            # every feature was trivial ("no meaningful features" warned at
            # load): feed ONE constant dummy column so the engines still
            # produce the boost-from-average stump, as the reference does —
            # the mesh learners already synthesize exactly this column
            self.meta = FeatureMeta(
                num_bin=jnp.asarray([2], jnp.int32),
                default_bin=jnp.asarray([0], jnp.int32),
                is_categorical=jnp.asarray([False]),
            )
        else:
            self.meta = FeatureMeta(
                num_bin=jnp.asarray(train_data.num_bin_arr),
                default_bin=jnp.asarray(train_data.default_bin_arr),
                is_categorical=jnp.asarray(train_data.is_categorical_arr),
            )
        self.params = build_split_params(config)
        from .wave import WAVE_ONLY_MODES, _bin_pad
        hist_mode = config.tpu_histogram_mode
        if hist_mode not in (("auto", "onehot", "scatter", "pallas")
                             + WAVE_ONLY_MODES):
            Log.fatal("Unknown tpu_histogram_mode %s (expected auto/onehot/"
                      "scatter/pallas/pallas_t/pallas_ct)", hist_mode)
        self.bundle_arrays, self.group_bins = build_bundle_arrays(train_data)
        ncols = (len(train_data.bundle.num_group_bins)
                 if train_data.bundle is not None
                 else max(train_data.num_features, 1))
        nbins = self.group_bins if train_data.bundle is not None \
            else self.num_bins
        if hist_mode == "auto":
            # the measured-heuristic PRIOR (ops/autotune.py
            # prior_hist_mode, with the chip-session provenance in its
            # docstring): pallas_ct / pallas_t where the wave engine
            # will run with VMEM headroom, onehot on TPU otherwise,
            # scatter on CPU.  In measure/force autotune modes the
            # decide() block below may override this with a probed
            # winner for the shape bucket.
            from .autotune import prior_hist_mode
            hist_mode = prior_hist_mode(config, ncols, _bin_pad(nbins),
                                        self.num_leaves, psum_axis)
        self.hist_mode = hist_mode
        self.cache_hists = hist_cache_enabled(
            config, self.num_leaves, ncols, nbins,
            8 if config.tpu_use_dp else 4)
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
                growth = ("wave" if jax.default_backend() == "tpu"
                          and hist_mode != "pallas" else "exact")
        if growth == "exact" and hist_mode in WAVE_ONLY_MODES:
            Log.fatal("tpu_histogram_mode=%s requires tpu_growth=wave "
                      "(this kernel is wave-only)" % hist_mode)
        # ---- sparse device store (SparseBin/OrderedSparseBin analog,
        # ops/sparse_store.py): histograms from nonzero entries only, one
        # segment_sum over nnz per leaf instead of an O(N*F) dense pass.
        # Serial exact engine only; the wave engine keeps the dense store.
        from ..utils.config import _FALSE_SET, _TRUE_SET
        from .sparse_store import SparseDeviceStore as _SpStore
        serial_learner = str(config.tree_learner) in ("serial",)
        # gate on the engine actually running, not the tree_learner
        # string: a 'data'/'voting' config falling back to the serial
        # engine on one device still gets the sparse store.  The
        # feature-parallel subclass is the exception — it calls this
        # ctor with psum_axis=None but a pre-sharded dense device_data.
        from .sparse_mxu import ChunkedSparseStore as _ChStore
        true_serial = (psum_axis is None
                       and (device_data is None
                            or isinstance(device_data,
                                          (_SpStore, _ChStore))))
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
                # materializations — measured SLOWER on the CPU mesh
                # (BENCH_NOTES.md) and unproven on chip, so auto growth
                # stays exact; an explicit tpu_growth=wave is honored
                if str(config.tpu_growth) == "auto":
                    growth = "exact"
                hist_mode = "sparse"
            self.hist_mode = hist_mode
        self.sparse_on = sparse_on
        self.sparse_col_cap = 0
        self.growth = growth
        # wave width only matters (and is only validated) under wave
        # growth — an exact-growth config with a leftover garbage
        # tpu_wave_width must keep training (ADVICE r2).
        self.wave_order = (resolve_wave_order(config)
                           if growth == "wave" else "batched")
        self.wave_width = (resolve_wave_width(config, self.num_leaves,
                                              self.wave_order)
                           if growth == "wave" else 1)
        # NOTE (PR 11): auto widths are no longer bent away from the
        # 18-30 MB accumulator-block band.  The band was a lossy proxy
        # for the tile planner's live-set overshoot of Mosaic's overlap
        # window; ops/pallas_wave.py _tile_plan now budgets the row tile
        # against the resident accumulator directly, so in-band widths
        # are no longer pathological (tile_plan_vmem_report is the
        # probe; regression-pinned in tests/test_pallas_wave.py and
        # tests/test_fused_iter.py, post-mortem in
        # docs/FusedIteration.md).  Old timelines may still carry
        # wave_band_escape events; the schema keeps accepting them.
        hp = str(config.tpu_hist_precision).strip().lower()
        if hp not in ("auto", "hilo", "bf16"):
            Log.fatal("Unknown tpu_hist_precision %s (expected auto/"
                      "hilo/bf16)", config.tpu_hist_precision)
        if hp == "auto":
            # the round-5 bf16 promotion PRIOR (ops/autotune.py
            # prior_hist_hilo carries the measured provenance); scoped
            # to serial wave execution like the pallas_ct promotion
            from .autotune import prior_hist_hilo
            self.hist_hilo = prior_hist_hilo(growth, psum_axis,
                                             self.hist_mode, self.dtype)
        else:
            self.hist_hilo = hp != "bf16"
        lk = str(config.tpu_wave_lookup).strip().lower()
        # validate unconditionally (like tpu_histogram_mode): a typo'd
        # value must not be silently ignored just because growth resolved
        # to exact (ADVICE r3); it is APPLIED only under wave growth
        if lk not in ("auto", "onehot", "compact", "gather"):
            Log.fatal("Unknown tpu_wave_lookup %s (expected auto/"
                      "onehot/compact/gather)", config.tpu_wave_lookup)
        if growth == "wave":
            # auto -> compact on TPU (measured on v5e at 1Mx28/255
            # leaves/W=32: 7.12 it/s vs onehot-lookup's 6.34 on the XLA
            # engine — the (C, L) leaf one-hot was ~L/W of pure traffic);
            # onehot elsewhere (CPU layouts don't pay the lane padding)
            if lk == "auto":
                self.wave_lookup = ("compact"
                                    if jax.default_backend() == "tpu"
                                    else "onehot")
            else:
                self.wave_lookup = lk
            # the "no effect" warning must only fire when the fused
            # kernel will ACTUALLY run — off-TPU those modes fall back
            # to the XLA partition scan where the lookup does apply
            # (ADVICE r3); the sparse pass owns its lookup everywhere
            from .wave import pallas_wave_active
            fused_runs = (hist_mode == "pallas_ct"
                          and pallas_wave_active(hist_mode, self.dtype))
            if lk != "auto" and (fused_runs or sparse_on):
                Log.warning("tpu_wave_lookup=%s has no effect under %s "
                            "(the fused kernels / sparse pass own their "
                            "own lookup)", lk,
                            "tpu_sparse" if sparse_on
                            else "tpu_histogram_mode=%s" % hist_mode)
        else:
            self.wave_lookup = "onehot"
        # 4-bit packing (dense_nbits_bin.hpp:37 analog, ops/pack.py): when
        # every device column fits a nibble, store TWO columns per byte in
        # HBM; the growth engines unpack per chunk/column in-scan, so the
        # bin matrix's HBM footprint and read traffic halve.  Supported by
        # the wave engine (the TPU default) and by exact growth under the
        # onehot/scatter kernels; the pallas kernels and mesh learners
        # keep byte bins.
        from .pack import can_pack4
        bins_per_col = (train_data.bundle.num_group_bins
                        if train_data.bundle is not None
                        else train_data.num_bin_arr)
        pack_cfg = str(config.tpu_bin_pack).strip().lower()
        if pack_cfg not in _TRUE_SET | _FALSE_SET | {"auto"}:
            Log.fatal("tpu_bin_pack: value %s cannot be parsed as "
                      "auto/bool", config.tpu_bin_pack)
        pack_forced = pack_cfg in _TRUE_SET
        pack_growth_ok = (growth == "wave"
                          or (growth == "exact"
                              and hist_mode in ("onehot", "scatter")))
        # mesh learners keep byte bins: data/voting arrive with psum_axis
        # set, but the feature-parallel subclass calls this base ctor with
        # psum_axis=None and a pre-sharded device matrix — gate on the
        # tree_learner config (serial_learner above), not just the axis
        self.packed_cols = 0
        if ((pack_forced or pack_cfg == "auto") and pack_growth_ok
                and not sparse_on
                and psum_axis is None and serial_learner
                and can_pack4(bins_per_col)):
            self.packed_cols = ncols
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
        if int(config.tpu_wave_chunk) <= 0:
            Log.fatal("tpu_wave_chunk must be positive, got %s",
                      config.tpu_wave_chunk)
        elif growth == "wave" and int(config.tpu_wave_chunk) < 256:
            Log.warning("tpu_wave_chunk=%d is below the engine minimum; "
                        "the wave sweep uses 256-row chunks instead",
                        int(config.tpu_wave_chunk))
        # ---- device upload (row-padded to a quantum so nearby dataset
        # sizes land on the same compiled shape; pad rows carry zero
        # row_mult and change nothing)
        self._row_pad = device_row_pad
        if sparse_on and psum_axis is not None:
            # data-parallel: the mesh learner replaces X with its
            # row-block coordinate stores after this ctor; keep the
            # dense device_data meanwhile
            self.X = device_data
        elif sparse_on:
            from .sparse_mxu import ChunkedSparseStore, build_chunked_store
            from .sparse_store import (SparseDeviceStore,
                                       build_sparse_store,
                                       column_fill_bins)
            self._row_pad = 0
            want_store = (ChunkedSparseStore if sparse_kernel
                          else SparseDeviceStore)
            if (isinstance(device_data, want_store)
                    and device_sparse_col_cap > 0):
                # reset_config reuse: same train_data -> same store
                self.X = device_data
                self.sparse_col_cap = device_sparse_col_cap
                if sparse_kernel:
                    nc, e = (int(s) for s in device_data.ent_bin.shape)
                    self.sparse_device_bytes = 4 * (
                        2 * nc * e + nc
                        + 2 * int(device_data.fill.shape[0]) + 1)
                else:
                    self.sparse_device_bytes = 4 * (
                        3 * int(device_data.nz_row.shape[0])
                        + 2 * int(device_data.fill.shape[0]) + 1)
            else:
                nbins_dev = (self.group_bins
                             if train_data.bundle is not None
                             else self.num_bins)
                binned = train_data.binned
                if binned.shape[1] == 0:    # dummy column (see meta above)
                    binned = np.zeros((train_data.num_data, 1), np.uint8)
                    fill = np.zeros(1, np.int64)
                else:
                    fill = column_fill_bins(train_data.num_bin_arr,
                                            train_data.default_bin_arr,
                                            train_data.bundle)
                if sparse_kernel:
                    # auto_uniform: low-skew stores widen the entry
                    # chunk so each column is ONE MXU dot (sparse_mxu)
                    def build(b, fl, nb):
                        return build_chunked_store(b, fl, nb,
                                                   auto_uniform=True)
                else:
                    build = build_sparse_store
                self.X, self.sparse_col_cap, self.sparse_device_bytes = \
                    build(binned, fill, nbins_dev)
        elif (device_data is not None
                and device_packed_cols == self.packed_cols):
            self.X = device_data
        else:
            from .pack import pack4_host
            self._row_pad = (-train_data.num_data) % 1024
            X = None
            if not self.packed_cols:
                # out-of-core datasets page shard-by-shard to the device —
                # no padded full-size host copy is ever built
                X = paged_device_matrix(train_data, self._row_pad)
            if X is not None:
                self.X = X
            else:
                binned = train_data.binned
                if binned.shape[1] == 0:    # dummy column (see meta above)
                    binned = np.zeros((train_data.num_data, 1), np.uint8)
                n = binned.shape[0]
                self._row_pad = (-n) % 1024
                if self._row_pad:
                    binned = np.concatenate(
                        [binned, np.zeros((self._row_pad, binned.shape[1]),
                                          binned.dtype)])
                if self.packed_cols:
                    binned = pack4_host(binned)
                self.X = jnp.asarray(binned)
        if self._row_pad:
            self._ones = jnp.concatenate(
                [jnp.ones(train_data.num_data, self.dtype),
                 jnp.zeros(self._row_pad, self.dtype)])
        else:
            self._ones = jnp.ones(train_data.num_data, self.dtype)
        self._full_mask = jnp.ones(max(train_data.num_features, 1), dtype=bool)
        # CPU-interpret Pallas execution (tests / CI parity runs): a
        # forced wave-kernel mode off-TPU normally falls back to the XLA
        # wave path; tpu_pallas_interpret=true runs the ACTUAL Pallas
        # kernels through the interpreter instead, so fused-vs-staged
        # bit-identity and the tile-plan regressions are CPU-testable
        # end-to-end (tests/test_fused_iter.py, CI bench-smoke).  On TPU
        # the flag is meaningless — the compiled kernels run.
        self.pallas_interpret = bool(config.tpu_pallas_interpret)
        if self.pallas_interpret and jax.default_backend() == "tpu":
            Log.warning("tpu_pallas_interpret=true ignored on TPU (the "
                        "compiled Pallas kernels run)")
            self.pallas_interpret = False
        # ---- measured kernel autotune (ops/autotune.py).  Everything
        # resolved above — hist_mode, wave_width, hist_hilo — is the
        # heuristic PRIOR; under
        # tpu_autotune=measure/force on a real device, decide() probes
        # the 3-5 candidate cells for this shape bucket on the uploaded
        # bin matrix and the measured winner overrides the prior (the
        # winner is cached on disk, so one probe cost per shape bucket
        # per device kind).  Under off (the default) decide() only
        # records the prior decision on the timeline.
        from . import autotune as _at
        at_shape = _at.ShapeBucket(int(ncols), int(_bin_pad(nbins)),
                                   int(self.num_leaves),
                                   _at.row_bucket(train_data.num_data))
        at_prior = _at.Cell(self.hist_mode, int(self.wave_width),
                            bool(self.hist_hilo), fused=False)
        at_pins = _at.Pins(
            # pins = explicit user choices + quality gates, never tuned
            kernel=str(config.tpu_histogram_mode) != "auto",
            width=(int(config.tpu_wave_width) > 0
                   or (_order_sensitive(config)
                       and self.wave_order != "exact")),
            precision=hp != "auto",
            # an explicit tpu_fused_iter=on/off is a user decision the
            # tuner must not second-guess; auto leaves the staged/fused
            # flip a measured dimension (rev-2 cells)
            fused=str(config.tpu_fused_iter).strip().lower() != "auto")
        at_eligible = (growth == "wave" and psum_axis is None
                       and not sparse_on and self.dtype == jnp.float32
                       and self.hist_mode in WAVE_ONLY_MODES)
        at_probe = (self._make_autotune_probe(config)
                    if at_eligible else None)
        dec = _at.decide(config, at_shape, at_prior, at_pins,
                         at_eligible, probe=at_probe,
                         ct_allowed=psum_axis is None)
        self.autotune_mode, self.autotune_source = dec.mode, dec.source
        self._pending_events.extend(dec.events)
        # measured staged-vs-fused verdict for this shape bucket; the
        # booster's tpu_fused_iter=auto resolution consults it
        # (models/gbdt.py _resolve_fused_iter)
        self.fused_autotune = bool(dec.cell.fused)
        if dec.cell != at_prior:
            self.hist_mode = hist_mode = dec.cell.hist_mode
            self.wave_width = int(dec.cell.wave_width)
            self.hist_hilo = bool(dec.cell.hist_hilo)
        # whether a wave's histogram launch reads the row slab of its
        # smaller children (ops/wave.py slab_active): not a key, decided
        # from the kernel, the store and the execution that resolved
        from .wave import slab_active
        self.wave_compact = (growth == "wave" and not sparse_on
                             and slab_active(True, hist_mode, self.dtype,
                                             psum_axis,
                                             self.pallas_interpret))
        # Ordered-partition growth (grow.py): per-split cost is O(parent
        # segment) for the partition and O(child segment * F) for the
        # histogram — the reference's DataPartition + ordered-iteration
        # economics (data_partition.hpp:94-147, dense_bin.hpp:66-98) — so
        # the capacity-tier ladder pays at every shape.  Pallas histogram
        # kernels take the full-N mask form and keep the legacy path.
        self.row_capacities = (
            default_row_capacities(train_data.num_data + self._row_pad)
            if hist_mode not in ("pallas", "sparse",
                                 "sparse_mxu") + WAVE_ONLY_MODES
            else ())
        # per-booster transposed bin matrix for the transposed Pallas
        # kernels; passed to the grow program after feature_mask
        self._Xt = None
        # distributed learners (psum_axis set) own their grow construction
        # in parallel/mesh.py — including the wave-vs-voting choice
        if growth == "wave" and psum_axis is None:
            from .wave import make_wave_jit
            core = make_wave_jit(
                self.num_leaves, self.num_bins, self.params,
                config.max_depth, self.wave_width, self.dtype, None,
                self.bundle_arrays is not None, self.group_bins,
                self.cache_hists, hist_mode,
                int(config.tpu_wave_chunk), self.packed_cols,
                self.sparse_col_cap, self.wave_order == "exact",
                self.wave_lookup, self.hist_hilo, True,
                self.pallas_interpret)
            meta, bund = self.meta, self.bundle_arrays
            # the transposed kernel's (F, N) matrix: materialized ONCE per
            # booster (X never changes across trees), not per dispatch;
            # the shared predicate keeps this in lockstep with the engine
            # gate so no dead (F, N) copy is pinned when the kernel won't
            # run.  It is a call ARGUMENT of the grow program (as X is),
            # never a closed-over constant: a dataset-sized literal inside
            # the program would scale compile time, every compile-cache
            # entry and a second HBM copy with the dataset
            from .wave import transposed_wave_active
            if transposed_wave_active(hist_mode, self.dtype):
                with timers.span("transpose_xt"):   # dispatch only
                    self._Xt = jnp.transpose(self.X)

            def _grow(X, g, h, rm, m, Xt=None, _core=core, _meta=meta,
                      _bund=bund):
                return _core(X, g, h, rm, m, _meta, _bund, Xt=Xt)

            # AOT hook for obs compile attribution: the wrapper itself is
            # not jitted, so expose the core's lowering over the observed
            # call args (obs/compile.py analyze_compiled)
            _grow._aot_lower = (
                lambda X, g, h, rm, m, Xt=None, _core=core, _meta=meta,
                _bund=bund:
                _core.lower(X, g, h, rm, m, _meta, _bund, Xt=Xt))
            self._grow = _grow
        elif psum_axis is None:
            # cached jitted core: a second booster/fold with the same
            # static config reuses the compiled executable (meta/bundle
            # are call-time args, ops/grow.py make_grow_jit)
            from .grow import make_grow_jit
            core = make_grow_jit(self.num_leaves, self.num_bins,
                                 self.params, config.max_depth, hist_mode,
                                 self.dtype, None, None, 0, 1,
                                 self.bundle_arrays is not None,
                                 self.group_bins, self.row_capacities,
                                 self.cache_hists, 15, self.packed_cols,
                                 self.sparse_col_cap)
            meta, bund = self.meta, self.bundle_arrays

            def _grow(X, g, h, rm, m, _core=core, _meta=meta, _bund=bund):
                return _core(X, g, h, rm, m, _meta, _bund)

            _grow._aot_lower = (
                lambda X, g, h, rm, m, _core=core, _meta=meta, _bund=bund:
                _core.lower(X, g, h, rm, m, _meta, _bund))
            self._grow = _grow
        elif sparse_on:
            # the data-parallel mesh subclass owns the sparse grow (it
            # has the col_cap and the sharded store); a base fallback
            # with col_cap=0 would silently misroute every partition
            self._grow = None
        else:
            # the distributed base fallback is the exact engine; the
            # wave-only pallas_t kernel maps to onehot here — mesh
            # subclasses that run the wave schedule install their own
            # pallas_t-capable grow right after this constructor
            base_mode = ("onehot" if hist_mode in WAVE_ONLY_MODES
                         else hist_mode)
            self._grow = make_grow_fn(self.num_leaves, self.num_bins,
                                      self.meta, self.params,
                                      config.max_depth, hist_mode=base_mode,
                                      hist_dtype=self.dtype,
                                      psum_axis=psum_axis,
                                      bundle=self.bundle_arrays,
                                      group_bins=self.group_bins,
                                      row_capacities=self.row_capacities,
                                      cache_hists=self.cache_hists)
        # feature_fraction RNG persists across trees
        # (serial_tree_learner.cpp:40-96 Init + :257-275 BeforeTrain)
        self._feature_rng = Random(config.feature_fraction_seed)

    # --------------------------------------------------------- autotuning
    def _make_autotune_probe(self, config):
        """Probe factory for ops/autotune.py: builds a candidate cell's
        wave core STANDALONE — same statics as the production core
        below except the cell's tuned dimensions — against the real
        uploaded bin matrix with synthetic deterministic gradients, and
        returns a nullary run closure the tuner times.  make_wave_jit
        is lru-cached, so the winning cell's probe compile is reused by
        the production core.

        ``cell.fused`` flips the probe between the two iteration
        dataflows the booster can submit (models/gbdt.py): the staged
        chain times gradients / grow / score-update as separate
        dispatches (host glue between them included in what the timer
        sees), the fused chain times the whole step as ONE jitted entry
        — the exact shape ops/fused_iter.py compiles — so the
        staged-vs-fused flip is genuinely measured, not guessed."""
        from .wave import make_wave_jit, transposed_wave_active
        from .partition import score_update_impl
        from ..obs.timers import fence

        def probe(cell):
            core = make_wave_jit(
                self.num_leaves, self.num_bins, self.params,
                config.max_depth, int(cell.wave_width), self.dtype,
                None, self.bundle_arrays is not None, self.group_bins,
                self.cache_hists, cell.hist_mode,
                int(config.tpu_wave_chunk), self.packed_cols,
                self.sparse_col_cap, self.wave_order == "exact",
                self.wave_lookup, bool(cell.hist_hilo), True,
                self.pallas_interpret)
            xt = (jnp.transpose(self.X)
                  if transposed_wave_active(cell.hist_mode, self.dtype)
                  else None)
            n = int(self._ones.shape[0])
            rm, mask = self._ones, self._full_mask
            meta, bund = self.meta, self.bundle_arrays
            # deterministic, real-shaped iteration state: an L2-style
            # in-graph gradient from the running score against a
            # sign-varying target, so splits have gain and the wave
            # actually sweeps
            tgt = jnp.asarray(np.linspace(-1.0, 1.0, n), self.dtype)
            score0 = jnp.zeros((n,), self.dtype)
            scale = jnp.asarray(0.1, self.dtype)

            def _grad(score, tgt):
                return score - tgt, jnp.full((n,), 0.25, self.dtype)

            if cell.fused:
                # every dataset-sized array is a program argument, as in
                # ops/fused_iter.py
                def _step(X, Xt, rm, tgt, score):
                    g, h = _grad(score, tgt)
                    tree, leaf_id = core(X, g, h, rm, mask, meta,
                                         bund, Xt=Xt)
                    return score_update_impl(score, leaf_id,
                                             tree.leaf_value, scale)

                step = jax.jit(_step)

                def run():
                    # measurement-scoped sync: the tuner needs the wall
                    # time of the finished program.  Production
                    # iterations never block mid-tree (bench.py --dry
                    # asserts a zero fence-count delta); every probe
                    # sync goes through obs/timers.fence so that audit
                    # has a single counted choke point.
                    fence(step(self.X, xt, rm, tgt, score0))
            else:
                grad_fn = jax.jit(_grad)
                upd = jax.jit(score_update_impl)

                def run():
                    # the staged chain the booster submits: three
                    # separate dispatches with the host glue between
                    # them inside the timed window
                    g, h = grad_fn(score0, tgt)
                    tree, leaf_id = core(self.X, g, h, rm, mask, meta,
                                         bund, Xt=xt)
                    fence(upd(score0, leaf_id, tree.leaf_value, scale))

            return run

        return probe

    # -------------------------------------------------------- observability
    def set_observer(self, obs) -> None:
        self._obs = obs
        pend = getattr(self, "_pending_events", None)
        if pend and getattr(obs, "enabled", False):
            # construction-time events (band escapes, autotune
            # probes/decision) recorded now that the run header exists
            for ev, fields in pend:
                obs.event(ev, **fields)
            del pend[:]

    def obs_info(self) -> dict:
        """Static run-header context: which engines/knobs this learner
        resolved to (the 'auto' params post-resolution)."""
        return {
            "learner": type(self).__name__,
            "growth": getattr(self, "growth", ""),
            "hist_mode": getattr(self, "hist_mode", ""),
            "wave_width": int(getattr(self, "wave_width", 0) or 0),
            "wave_order": getattr(self, "wave_order", ""),
            "wave_lookup": getattr(self, "wave_lookup", ""),
            "hist_hilo": bool(getattr(self, "hist_hilo", True)),
            "wave_compact": bool(getattr(self, "wave_compact", False)),
            "autotune_mode": getattr(self, "autotune_mode", "off"),
            "autotune_source": getattr(self, "autotune_source", ""),
            "fused": bool(getattr(self, "fused_autotune", False)),
            "pallas_interpret": bool(getattr(self, "pallas_interpret",
                                             False)),
            "packed_cols": int(getattr(self, "packed_cols", 0) or 0),
            "num_leaves": int(self.num_leaves),
            "num_bins": int(self.num_bins),
            "dtype": jnp.dtype(self.dtype).name,
            "cache_hists": bool(getattr(self, "cache_hists", False)),
        }

    # ------------------------------------------------------------ internals
    def sample_feature_mask(self):
        f = self.train_data.num_features
        if self.config.feature_fraction >= 1.0 or f == 0:
            return self._full_mask
        used_cnt = int(f * self.config.feature_fraction)
        idx = self._feature_rng.sample(f, used_cnt)
        mask = np.zeros(f, dtype=bool)
        mask[idx] = True
        return jnp.asarray(mask)

    # ----------------------------------------------------------------- train
    def grow_args(self, grad, hess, row_mult, feature_mask) -> tuple:
        """Positional arguments of ``self._grow``: the device bin matrix,
        the per-row vectors, the feature mask and, where a transposed
        Pallas kernel runs, the per-booster Xt."""
        args = (self.X, grad, hess, row_mult, feature_mask)
        if self._Xt is not None:
            args += (self._Xt,)
        return args

    def train_device(self, grad, hess, row_mult=None,
                     feature_mask=None) -> Tuple[TreeArrays, jnp.ndarray]:
        """Grow one tree fully on device; no host synchronization."""
        if row_mult is None:
            row_mult = self._ones
        else:
            row_mult = jnp.asarray(row_mult, self.dtype)
            if self._row_pad:
                row_mult = jnp.concatenate(
                    [row_mult, jnp.zeros(self._row_pad, self.dtype)])
        if feature_mask is None:
            feature_mask = self.sample_feature_mask()
        grad = jnp.asarray(grad, self.dtype)
        hess = jnp.asarray(hess, self.dtype)
        if self._row_pad:
            grad = jnp.concatenate(
                [grad, jnp.zeros(self._row_pad, self.dtype)])
            hess = jnp.concatenate(
                [hess, jnp.zeros(self._row_pad, self.dtype)])
        obs = self._obs
        args = self.grow_args(grad, hess, row_mult, feature_mask)
        obs.entry_args("tree_grow", self._grow, args,
                       names=("X", "grad", "hess", "row_mult",
                              "feature_mask", "Xt")[:len(args)])
        t0 = obs.entry_start()
        with timers.span("dispatch"):
            tree, leaf_id = self._grow(*args)
        obs.entry_end("tree_grow", t0, (tree, leaf_id))
        if self._row_pad:
            leaf_id = leaf_id[:self.train_data.num_data]
        return tree, leaf_id

    def train(self, grad, hess, row_mult=None) -> Tuple[Tree, jnp.ndarray]:
        dev_tree, leaf_id = self.train_device(grad, hess, row_mult)
        tree = self.materialize(dev_tree)
        return tree, leaf_id

    def materialize(self, dev_tree: TreeArrays) -> Tree:
        from ..obs.timers import fenced_get
        return materialize_tree(fenced_get(dev_tree), self.train_data,
                                self.num_leaves)

    # ------------------------------------------------------------ DART refit
    def fit_by_existing_tree(self, tree: Tree, grad, hess) -> Tree:
        """Refit leaf outputs of an existing structure on new gradients
        (SerialTreeLearner::FitByExistingTree, serial_tree_learner.cpp:225-250).
        """
        leaves = self._leaf_index_binned(tree)
        grad = np.asarray(grad, dtype=np.float64)
        hess = np.asarray(hess, dtype=np.float64)
        l1, l2 = self.config.lambda_l1, self.config.lambda_l2
        for leaf in range(tree.num_leaves):
            m = leaves == leaf
            sum_g = grad[m].sum()
            sum_h = hess[m].sum()
            reg = max(abs(sum_g) - l1, 0.0)
            out = -np.sign(sum_g) * reg / (sum_h + l2 + 1e-15)
            tree.set_leaf_value(leaf, out)
        return tree

    def _leaf_index_binned(self, tree: Tree) -> np.ndarray:
        binned = self.train_data.binned
        n = binned.shape[0]
        if tree.num_leaves <= 1:
            return np.zeros(n, dtype=np.int32)
        bund = self.train_data.bundle
        node = np.zeros(n, dtype=np.int32)
        active = node >= 0
        while active.any():
            idx = np.nonzero(active)[0]
            nd = node[idx]
            f = tree.split_feature_inner[nd]
            if bund is None:
                b = binned[idx, f].astype(np.int64)
            else:
                v = binned[idx, bund.group_of[f]].astype(np.int64)
                off = bund.bin_off[f]
                in_range = (v >= off) & (v < off + bund.bin_span[f])
                b = np.where(in_range, v - off + bund.bin_adj[f],
                             self.train_data.default_bin_arr[f])
            th = tree.threshold_in_bin[nd]
            is_cat = tree.decision_type[nd] == 1
            go_left = np.where(is_cat, b == th, b <= th)
            is_def = b == tree.zero_bin[nd]
            dbz = tree.default_bin_for_zero[nd]
            def_left = np.where(is_cat, dbz == th, dbz <= th)
            go_left = np.where(is_def, def_left, go_left)
            node[idx] = np.where(go_left, tree.left_child[nd], tree.right_child[nd])
            active = node >= 0
        return (~node).astype(np.int32)


def materialize_tree(host_tree: TreeArrays, train_data: TrainingData,
                     max_leaves: int) -> Tree:
    """Device tree arrays -> models.Tree with real-valued thresholds.

    Real thresholds and default values are resolved host-side in float64
    (Dataset::RealThreshold, dataset.h:457-462) so the text model format
    keeps full precision.
    """
    nl = int(host_tree.num_leaves)
    tree = Tree(max(max_leaves, 2))
    tree.num_leaves = nl
    if nl <= 1:
        return tree
    ni = nl - 1
    tree.split_feature_inner[:ni] = host_tree.split_feature[:ni]
    tree.threshold_in_bin[:ni] = host_tree.threshold_bin[:ni]
    tree.default_bin_for_zero[:ni] = host_tree.default_bin_for_zero[:ni]
    tree.zero_bin[:ni] = host_tree.default_bin[:ni]
    tree.decision_type[:ni] = host_tree.is_cat[:ni].astype(np.int8)
    tree.has_categorical = bool(host_tree.is_cat[:ni].any())
    tree.left_child[:ni] = host_tree.left_child[:ni]
    tree.right_child[:ni] = host_tree.right_child[:ni]
    tree.split_gain[:ni] = host_tree.split_gain[:ni]
    tree.internal_value[:ni] = host_tree.internal_value[:ni]
    tree.internal_count[:ni] = host_tree.internal_count[:ni]
    tree.leaf_parent[:nl] = host_tree.leaf_parent[:nl]
    tree.leaf_value[:nl] = host_tree.leaf_value[:nl]
    tree.leaf_count[:nl] = host_tree.leaf_count[:nl]
    tree.leaf_depth[:nl] = host_tree.leaf_depth[:nl]
    tree.second_gain[:ni] = host_tree.second_gain[:ni]
    from ..utils.common import avoid_inf
    for i in range(ni):
        inner_f = int(host_tree.split_feature[i])
        mapper = train_data.feature_bin_mapper(inner_f)
        tree.split_feature[i] = train_data.real_feature_index(inner_f)
        # runner-up candidate resolved to the real feature index (the
        # split-audit margin surface; -1 = no competitor)
        sf_inner = int(host_tree.second_feature[i])
        tree.second_feature[i] = (train_data.real_feature_index(sf_inner)
                                  if sf_inner >= 0 else -1)
        tree.threshold[i] = avoid_inf(
            mapper.bin_to_value(int(host_tree.threshold_bin[i])))
        dbz = int(host_tree.default_bin_for_zero[i])
        if dbz != mapper.default_bin:
            # AvoidInf as in Tree::Split (tree.cpp:75)
            tree.default_value[i] = avoid_inf(mapper.bin_to_value(dbz))
        else:
            tree.default_value[i] = 0.0
    return tree
