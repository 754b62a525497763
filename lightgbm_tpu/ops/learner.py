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
from .plan import resolve_plan, store_bin_width, store_col_pads
from .sparse_mxu import ChunkedSparseStore, build_chunked_store
from .sparse_store import (SparseDeviceStore, build_sparse_store,
                           column_fill_bins)
from .split_finder import FeatureMeta, SplitParams
from .wave import WAVE_ONLY_MODES


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


def _plan_read(field):
    return property(lambda self: getattr(self.plan, field))


class SerialTreeLearner:
    # run observer (lightgbm_tpu/obs); a class-level NULL default keeps
    # every constructor untouched and the disabled path allocation-free
    _obs = NULL_OBSERVER

    # what `auto` and the tpu_* keys resolved to (ops/plan.py), under the
    # names the mesh learners, ops/fused_iter.py and the tests read
    hist_mode = _plan_read("hist_mode")
    growth = _plan_read("growth")
    wave_order = _plan_read("wave_order")
    wave_width = _plan_read("wave_width")
    hist_hilo = _plan_read("hist_hilo")
    wave_lookup = _plan_read("wave_lookup")
    packed_cols = _plan_read("packed_cols")
    cache_hists = _plan_read("cache_hists")
    pallas_interpret = _plan_read("pallas_interpret")
    wave_compact = _plan_read("slab")
    sparse_on = property(lambda self: bool(self.plan.sparse))

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
        self.bundle_arrays, self.group_bins = build_bundle_arrays(train_data)
        bundle = train_data.bundle
        bins_per_col = (bundle.num_group_bins if bundle is not None
                        else train_data.num_bin_arr)
        nbins = self.group_bins if bundle is not None else self.num_bins
        self.plan = resolve_plan(
            config,
            ncols=(len(bundle.num_group_bins) if bundle is not None
                   else max(train_data.num_features, 1)),
            nbins=nbins, num_leaves=self.num_leaves,
            bins_per_col=bins_per_col,
            backend=jax.default_backend(), dtype=self.dtype,
            psum_axis=psum_axis,
            dense_device_data=(device_data is not None and not isinstance(
                device_data, (SparseDeviceStore, ChunkedSparseStore))))
        self.col_pads = store_col_pads(self.plan, bins_per_col, nbins)
        if bundle is not None:
            # what EFB made of the features, once a learner (inside the
            # caller's `learner_build` span): the bins the groups hold
            # against the one-hot widths the plan's engine multiplies by
            timers.count(
                "bundle", bundle_groups=bundle.num_groups,
                bundled_features=sum(
                    len(g) for g in bundle.groups if len(g) > 1),
                group_bins_used=int(bundle.num_group_bins.sum()),
                group_bins_padded=sum(self.col_pads)
                or bundle.num_groups * store_bin_width(
                    self.plan, self.group_bins))
        self._upload(psum_axis, device_data, device_row_pad,
                     device_packed_cols, device_sparse_col_cap)
        self._build_grow(psum_axis)
        # feature_fraction RNG persists across trees
        # (serial_tree_learner.cpp:40-96 Init + :257-275 BeforeTrain)
        self._feature_rng = Random(config.feature_fraction_seed)

    def _upload(self, psum_axis, device_data, device_row_pad,
                device_packed_cols, device_sparse_col_cap):
        """The device store the plan asks for, and the per-row constants
        beside it (the constructor's arguments, as it documents them)."""
        train_data = self.train_data
        sparse_on, sparse_kernel = self.sparse_on, self.plan.sparse == "mxu"
        self.sparse_col_cap = 0
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

    def _build_grow(self, psum_axis):
        """`self._grow`: the plan's grow program over this learner's
        statics (and `self._Xt` where a transposed kernel reads one)."""
        config, train_data = self.config, self.train_data
        hist_mode, growth = self.hist_mode, self.growth
        sparse_on = self.sparse_on
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
                self.plan.wave_chunk, self.packed_cols,
                self.sparse_col_cap, self.wave_order == "exact",
                self.wave_lookup, self.hist_hilo, True,
                self.pallas_interpret, self.col_pads)
            meta, bund = self.meta, self.bundle_arrays
            # the transposed kernel's (F, N) matrix: materialized ONCE per
            # booster (X never changes across trees), not per dispatch;
            # the plan asks the engine's own predicate, so no dead (F, N)
            # copy is pinned when the kernel won't run.  It is a call
            # ARGUMENT of the grow program (as X is), never a closed-over
            # constant: a dataset-sized literal inside the program would
            # scale compile time, every compile-cache entry and a second
            # HBM copy with the dataset
            if self.plan.needs_xt:
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

    # -------------------------------------------------------- observability
    def set_observer(self, obs) -> None:
        self._obs = obs

    def obs_info(self) -> dict:
        """Static run-header context: which engines/knobs this learner
        resolved to (the 'auto' params post-resolution)."""
        plan = self.plan
        return {
            "learner": type(self).__name__,
            "growth": plan.growth,
            "hist_mode": plan.hist_mode,
            "wave_width": plan.wave_width,
            "wave_order": plan.wave_order,
            "wave_lookup": plan.wave_lookup,
            "hist_hilo": plan.hist_hilo,
            "wave_compact": plan.slab,
            "pallas_interpret": plan.pallas_interpret,
            "packed_cols": plan.packed_cols,
            "num_leaves": int(self.num_leaves),
            "num_bins": int(self.num_bins),
            "dtype": jnp.dtype(self.dtype).name,
            "cache_hists": plan.cache_hists,
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
