"""Distributed tree learning over a device mesh — the Network layer reborn.

The reference distributes with a socket/MPI Allreduce stack
(src/network/network.cpp:23-185, linkers_socket.cpp) and three learner
subclasses (feature/data/voting parallel, src/treelearner/
*_parallel_tree_learner.cpp).  TPU-native, the whole Network layer collapses
into XLA collectives over an ICI mesh:

* data-parallel  — rows sharded, histograms psum'd inside the grow program
  (`lax.psum` == ReduceScatter+Allgather of HistogramBinEntry sums,
  data_parallel_tree_learner.cpp:148-222);
* feature-parallel — all rows everywhere, features sharded; only the best
  SplitInfo crosses devices (an argmax-reduce of the packed split vector,
  feature_parallel_tree_learner.cpp:52-76) plus one row-bitmask psum for
  the partition;
* voting-parallel — data-parallel with top-k histogram exchange: local
  top-k proposals by leaf-size-weighted gain, pmax-vote, psum of only the
  k selected histograms (voting_parallel_tree_learner.cpp:164-300) —
  per-leaf traffic drops from F*B*3 to top_k*B*3, the PV-Tree compression
  for DCN-spanning meshes.

Multi-host: `jax.distributed.initialize` + the same mesh spanning all
processes replaces machine_list_file/port handshakes (linkers_socket.cpp).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io.dataset import TrainingData
from ..obs import timers
from ..ops.grow import make_grow_fn
from ..ops.learner import SerialTreeLearner, paged_device_matrix
from ..ops.wave import WAVE_ONLY_MODES
from ..ops.split_finder import FeatureMeta
from ..utils.config import Config
from ..utils.log import Log

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def make_data_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    # Device HANDLES (host metadata), not a device array — no transfer
    return Mesh(np.asarray(devices), (DATA_AXIS,))  # lint: ignore[sync-asarray]


def make_feature_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    # Device HANDLES (host metadata), not a device array — no transfer
    return Mesh(np.asarray(devices), (FEATURE_AXIS,))  # lint: ignore[sync-asarray]


def pad_rows(n: int, num_shards: int) -> int:
    """Rows padded so each shard holds the same count (XLA static shapes)."""
    return (-n) % num_shards


def make_row_sharded(mesh: Mesh, host_local: np.ndarray, extra_dims=0):
    """A row-sharded global jax.Array from host data.

    Single-process: a plain device_put.  Multi-process (jax.distributed
    initialized, the DCN path replacing linkers_socket.cpp): `host_local`
    is THIS process's row shard and the global array is assembled from the
    per-process shards — rows must already be padded so every process
    contributes the same count.
    """
    spec = P(DATA_AXIS, *([None] * extra_dims))
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(host_local, sharding)
    return jax.make_array_from_process_local_data(sharding, host_local)


def _per_tree_collective_bytes(learner) -> int:
    """Per-tree collective traffic from collective_info()'s per-reduce
    estimates x the number of reduces a tree issues (splits, or wave
    sweeps) — the increment train_device adds to the registry counter."""
    info = learner.collective_info()
    splits = max(int(learner.num_leaves) - 1, 1)
    total = 0
    for coll in ("psum", "allgather"):
        d = info.get(coll) or {}
        if "per_wave_bytes" in d:
            w = max(int(getattr(learner, "wave_width", 1) or 1), 1)
            total += d["per_wave_bytes"] * ((splits + w - 1) // w)
        elif "per_leaf_bytes" in d:
            total += d["per_leaf_bytes"] * splits
        elif "per_split_bytes" in d:
            total += d["per_split_bytes"] * splits
    return int(total)


def _init_collective_counter(learner, obs) -> None:
    """set_observer for distributed learners: the base contract
    (learner._obs = obs) plus the collective-bytes counter
    (obs/metrics.py), accumulated per grown tree — created only when the
    observer is on so the disabled hot path stays allocation-free."""
    learner._obs = obs
    learner._m_coll = None
    if getattr(obs, "enabled", False):
        from ..obs import REGISTRY
        learner._m_coll = REGISTRY.counter(
            "lgbm_collective_bytes_total",
            "estimated bytes moved by cross-device collectives "
            "(psum/all_gather) during tree growth")
        learner._coll_tree_bytes = _per_tree_collective_bytes(learner)


class DataParallelTreeLearner(SerialTreeLearner):
    """Row-sharded learner; one psum per histogram construction.

    The same grow program as the serial learner runs under shard_map with
    `psum_axis='data'`: per-leaf histograms and root sums are all-reduced so
    every shard sees identical split decisions and applies them to its local
    rows — the lock-step SPMD structure of the reference's data-parallel
    loop (SURVEY.md §3.5) with XLA supplying the ring reductions.
    """

    def __init__(self, config: Config, train_data: TrainingData,
                 mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_data_mesh()
        n_shards = self.mesh.devices.size
        self._nproc = jax.process_count()
        n = train_data.num_data        # multi-process: THIS process's rows
        if self._nproc > 1 and n_shards % self._nproc != 0:
            Log.fatal("Data mesh of %d devices cannot be split across %d "
                      "processes evenly", n_shards, self._nproc)
        if self._nproc > 1 and n % max(n_shards // self._nproc, 1) != 0:
            # global arrays must align with the caller's global score/grad
            # buffers; implicit tail padding would desync their lengths
            Log.fatal("Multi-process training needs local rows (%d) "
                      "pre-padded to a multiple of the per-process shard "
                      "count (%d)", n, max(n_shards // self._nproc, 1))
        # one process pads the table's tail, once, at upload, to the
        # serial learner's 1024-row quantum a shard: a shard then has the
        # shape the same rows have on one chip, and the kernel's launch
        # pads nothing wave after wave.  The pad rows carry row_mult 0 and
        # are in no sum.  Several processes pad nothing: a process's pad
        # would lie in the middle of the global row order, where the
        # caller's global score and gradient buffers have no hole for it,
        # which is why the check above refuses rows that would need any
        local_shards = max(n_shards // self._nproc, 1)
        pad = 0 if self._nproc > 1 else pad_rows(n, n_shards * 1024)
        self._pad = pad
        # the sparse store replaces X below — don't upload (and orphan)
        # the dense matrix when it will never be used.  Must mirror the
        # base ctor's gate exactly (voting subclasses stay dense).
        want_sparse = (bool(config.tpu_sparse)
                       and str(config.tree_learner)
                       in ("data", "data_parallel"))
        # a reader-backed dataset is paged to the devices, each its own
        # rows: the host never builds the matrix (train_data._binned
        # stays None)
        x_sharding = NamedSharding(self.mesh, P(DATA_AXIS, None))
        X_dev = (None if want_sparse
                 else paged_device_matrix(train_data, pad, x_sharding))
        binned = None
        if X_dev is None:
            binned = train_data.binned
            if pad:
                binned = np.concatenate(
                    [binned, np.zeros((pad, binned.shape[1]),
                                      binned.dtype)])
            if not want_sparse:
                X_dev = make_row_sharded(self.mesh, binned, extra_dims=1)
        super().__init__(config, train_data, psum_axis=DATA_AXIS,
                         device_data=X_dev)
        # GLOBAL row count: every process contributes n+pad rows
        self._global_rows = (n + pad) * self._nproc
        if self.sparse_on:
            # row-block coordinate stores, flat-concatenated so
            # P(DATA_AXIS) hands each device its local store with LOCAL
            # row ids (ops/sparse_store.py).  Multi-process: every rank
            # builds its OWN blocks and allgathers (nnz, col_cap) so all
            # sections pad identically — the sparse analog of the
            # distributed bin-mapper agreement (dataset_loader.cpp:768).
            from ..ops.sparse_store import (SparseDeviceStore,
                                            assemble_sharded_store,
                                            column_fill_bins,
                                            sharded_store_parts)
            nbins_dev = (self.group_bins
                         if train_data.bundle is not None
                         else self.num_bins)
            sp_binned = binned
            if sp_binned.shape[1] == 0:
                sp_binned = np.zeros((n + pad, 1), np.uint8)
                fill = np.zeros(1, np.int64)
            else:
                fill = column_fill_bins(train_data.num_bin_arr,
                                        train_data.default_bin_arr,
                                        train_data.bundle)
            parts, nnz_needed, col_cap = sharded_store_parts(
                sp_binned, fill, nbins_dev, local_shards)
            if self._nproc > 1:
                from .comm import JaxProcessComm
                agreed = JaxProcessComm().allgather_obj(
                    [int(nnz_needed), int(col_cap)])
                nnz_needed = max(a[0] for a in agreed)
                col_cap = max(a[1] for a in agreed)
            host_store, self.sparse_device_bytes = assemble_sharded_store(
                parts, sp_binned.shape[1], nbins_dev, nnz_needed)
            self.sparse_col_cap = col_cap
            self.X = SparseDeviceStore(*[
                make_row_sharded(self.mesh, np.asarray(leaf))
                for leaf in host_store])
        self._row_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        # the grow program's arguments keep one placement from the first
        # call on (rows over the mesh, the feature mask on every device),
        # so it is lowered and compiled once a booster: ahead of time, as
        # the fused step is, which registers its scope table
        # (`hist_allreduce` among its scopes)
        self._replicated = NamedSharding(self.mesh, P())
        self._full_mask = jax.device_put(self._full_mask, self._replicated)
        self._compiled = {}
        self._ones = make_row_sharded(
            self.mesh,
            np.concatenate([np.ones(n, np.float32),
                            np.zeros(pad, np.float32)]).astype(self.dtype))
        from ..ops.grow import default_row_capacities
        local_rows = (n + pad) // local_shards
        caps = (default_row_capacities(local_rows)
                if self.row_capacities else ())   # same gate, per-shard rows
        voting = bool(self._grow_kwargs(n_shards).get("voting_k", 0))
        check_vma = True
        if self.growth == "wave" and not voting:
            # wave schedule under the data mesh: the per-wave histogram
            # block is psum'd ONCE (W splits per collective instead of one)
            from ..ops.wave import make_wave_grow_fn
            # transposed Pallas kernels: materialize Xt ONCE per booster
            # (a row-shard of X and the matching column-shard of Xt live
            # on the same device, so this transpose is comm-free) instead
            # of once per tree dispatch inside the shard-mapped grow
            needs_xt = self.plan.needs_xt
            grow = make_wave_grow_fn(
                self.num_leaves, self.num_bins, self.meta, self.params,
                config.max_depth, wave_width=self.wave_width,
                hist_dtype=self.dtype, psum_axis=DATA_AXIS,
                bundle=self.bundle_arrays, group_bins=self.group_bins,
                cache_hists=self.cache_hists, hist_mode=self.hist_mode,
                chunk=self.plan.wave_chunk,
                sparse_col_cap=self.sparse_col_cap, with_xt=needs_xt,
                exact_order=self.wave_order == "exact",
                lookup=self.wave_lookup, hist_hilo=self.hist_hilo,
                pallas_interpret=self.pallas_interpret)
            # the varying-axes check stays on wherever Mosaic compiles the
            # kernels.  JAX's Pallas HLO interpreter (tests, off-TPU)
            # fails it on a row slab's launch: it evaluates the block
            # index maps with the scalar-prefetched tile count, a shard's
            # own, beside its invariant loop index (`dynamic_slice
            # requires varying manual axes to match`, pallas/core.py
            # compute_start_indices_interpret)
            check_vma = not (self.pallas_interpret and self.wave_compact)
            if needs_xt:
                self._Xt = jax.jit(
                    jnp.transpose,
                    out_shardings=NamedSharding(self.mesh,
                                                P(None, DATA_AXIS)))(self.X)
        else:
            if self.hist_mode in WAVE_ONLY_MODES:
                Log.fatal("tpu_histogram_mode=%s is wave-only; the "
                          "voting-parallel learner's exact engine does not "
                          "support it" % self.hist_mode)
            grow = make_grow_fn(self.num_leaves, self.num_bins, self.meta,
                                self.params, config.max_depth,
                                hist_mode=self.hist_mode,
                                hist_dtype=self.dtype,
                                psum_axis=DATA_AXIS,
                                bundle=self.bundle_arrays,
                                group_bins=self.group_bins,
                                row_capacities=caps,
                                cache_hists=self.cache_hists,
                                sparse_col_cap=self.sparse_col_cap,
                                **self._grow_kwargs(n_shards))
        if self.sparse_on:
            from ..ops.sparse_store import SparseDeviceStore
            x_spec = SparseDeviceStore(*([P(DATA_AXIS)] * 5))
        else:
            x_spec = P(DATA_AXIS, None)
        in_specs = (x_spec, P(DATA_AXIS), P(DATA_AXIS),
                    P(DATA_AXIS), P())
        if self._Xt is not None:
            in_specs += (P(None, DATA_AXIS),)
        sharded_grow = jax.shard_map(
            grow, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(jax.tree_util.tree_map(lambda _: P(),
                                              self._dummy_tree_spec()),
                       P(DATA_AXIS)),
            check_vma=check_vma)
        self._grow = jax.jit(sharded_grow)
        Log.info("%s over %d devices (%d padded rows)",
                 type(self).__name__, n_shards, pad)

    def _grow_kwargs(self, n_shards):
        return {}

    def collective_info(self):
        """Static topology + per-collective byte ESTIMATES for the run
        header.  The host cannot time XLA collectives (they live inside
        the one jitted grow program) — measured collective time needs an
        obs_trace_iters profiler window; these numbers size the traffic.
        Histograms are (grad, hess, count) triples per (feature, bin)."""
        dtype_bytes = jnp.dtype(self.dtype).itemsize
        f = max(self.train_data.num_features, 1)
        info = {"learner": type(self).__name__, "axis": DATA_AXIS,
                "n_devices": int(self.mesh.devices.size),
                "n_processes": int(self._nproc),
                "global_rows": int(self._global_rows),
                "estimates": True}
        if self.growth == "wave":
            w = int(self.wave_width)
            info["psum"] = {"what": "wave histogram block (W splits "
                                    "per collective)",
                            "per_wave_bytes":
                                f * self.num_bins * 3 * w * dtype_bytes}
        else:
            info["psum"] = {"what": "per-leaf histogram",
                            "per_leaf_bytes":
                                f * self.num_bins * 3 * dtype_bytes}
        return info

    def set_observer(self, obs) -> None:
        _init_collective_counter(self, obs)

    def _dummy_tree_spec(self):
        # a TreeArrays-shaped pytree of None leaves for out_specs mapping
        from ..ops.grow import TreeArrays
        return TreeArrays(*([0] * len(TreeArrays._fields)))

    def _pad_rows_dev(self, arr, fill=0.0):
        if isinstance(arr, jax.Array) and arr.ndim == 1 \
                and arr.shape[0] == self._global_rows \
                and arr.dtype == self.dtype \
                and arr.sharding.is_equivalent_to(self._row_sharding, 1):
            return arr          # already a (global) row-sharded device array
        if self._nproc == 1:
            # async on-device pad + placement (no host round-trip: the
            # boosting loop stays fully pipelined, gbdt.py:344-350)
            arr = jnp.asarray(arr, self.dtype)
            if self._pad:
                arr = jnp.concatenate(
                    [arr, jnp.full((self._pad,), fill, self.dtype)])
            return jax.device_put(arr, self._row_sharding)
        arr = np.asarray(arr, self.dtype)     # local shard -> global array
        if self._pad:
            arr = np.concatenate(
                [arr, np.full((self._pad,), fill, self.dtype)])
        return make_row_sharded(self.mesh, arr)

    def place_score(self, score):
        """The booster's (k, N) score where the staged chain's per-row
        programs leave it once they have run over this learner's leaf
        ids: rows over the mesh where they divide evenly, else on every
        device.  Starting there, no program of the chain is lowered a
        second time for a second placement.  (Multi-process: the score
        stays the rank's own rows.)"""
        if self._nproc > 1:
            return score
        even = score.shape[-1] % self.mesh.devices.size == 0
        return jax.device_put(score, NamedSharding(
            self.mesh, P(None, DATA_AXIS) if even else P()))

    def local_rows(self, global_arr):
        """This process's rows of a row-sharded global array, pad
        dropped — the bridge that lets the per-rank GBDT controller keep
        LOCAL score/gradient arrays while the grow program psums over
        the global mesh.  Pure addressable-shard reads: no cross-process
        transfer, no host round-trip."""
        shards = sorted(global_arr.addressable_shards,
                        key=lambda s: int(s.index[0].start or 0))
        parts = [s.data for s in shards]
        loc = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return loc[:self.train_data.num_data]

    def train_device(self, grad, hess, row_mult=None, feature_mask=None):
        """Grow one tree.  Multi-process callers pass LOCAL row shards
        (host or device); they come back as a GLOBAL row-sharded array
        from _pad_rows_dev and the returned row->leaf map is global too
        (tests/mp_worker.py drives this directly; the GBDT layer slices
        it back to local rows via ``local_rows``).  Single-process
        callers pass host arrays and get unpadded local maps."""
        grad = self._pad_rows_dev(grad)
        hess = self._pad_rows_dev(hess)
        if row_mult is None:
            row_mult = self._ones
        else:
            row_mult = self._pad_rows_dev(row_mult)
        if feature_mask is None:
            feature_mask = self.sample_feature_mask()
        feature_mask = jax.device_put(feature_mask, self._replicated)
        args = self.grow_args(grad, hess, row_mult, feature_mask)
        obs = self._obs
        obs.entry_args("tree_grow", self._grow, args,
                       names=("X", "grad", "hess", "row_mult",
                              "feature_mask", "Xt")[:len(args)])
        t0 = obs.entry_start()
        with timers.span("dispatch"):
            tree, leaf_id = timers.scoped_executable(
                self._grow, self._compiled, args)(*args)
        obs.entry_end("tree_grow", t0, (tree, leaf_id))
        if getattr(self, "_m_coll", None) is not None:
            self._m_coll.inc(self._coll_tree_bytes)
        if self._nproc > 1:
            return tree, leaf_id     # global, matches global score arrays
        return tree, leaf_id[:self.train_data.num_data] if self._pad else leaf_id


class VotingParallelTreeLearner(DataParallelTreeLearner):
    """Data-parallel with PV-Tree top-k histogram exchange.

    Identical row sharding; the grow program votes per leaf (local top_k
    proposals weighted by leaf size, pmax, global top_k) and psums only the
    selected feature histograms (voting_parallel_tree_learner.cpp:164-300).
    Exact when top_k >= num_features; an approximation that preserves tree
    quality in the PV-Tree regime otherwise.
    """

    def _grow_kwargs(self, n_shards):
        return {"voting_k": int(self.config.top_k),
                "num_voting_machines": int(n_shards)}

    def collective_info(self):
        info = super().collective_info()
        top_k = int(self.config.top_k)
        info["psum"] = {"what": "PV-Tree voted histograms (top_k "
                                "features per leaf)",
                        "per_leaf_bytes": top_k * self.num_bins * 3
                        * jnp.dtype(self.dtype).itemsize,
                        "top_k": top_k}
        return info


class FeatureParallelTreeLearner(SerialTreeLearner):
    """Feature-sharded learner: rows replicated, split search partitioned.

    Each device scans its contiguous feature block; one packed SplitInfo
    all_gather + strict-> fold picks the global best (the reference's
    Allreduce(MaxReducer), feature_parallel_tree_learner.cpp:52-76), and a
    single row-bitmask psum re-executes the split everywhere.  Histogram
    memory per device shrinks by n_shards — this is the wide-dataset
    (tensor-parallel-over-features) axis of the mesh.
    """

    def __init__(self, config: Config, train_data: TrainingData,
                 mesh: Optional[Mesh] = None):
        if train_data.bundle is not None:
            Log.fatal("The feature-parallel learner requires "
                      "enable_bundle=false (dataset was built with EFB)")
        self.mesh = mesh if mesh is not None else make_feature_mesh()
        if FEATURE_AXIS not in self.mesh.axis_names:
            self.mesh = make_feature_mesh(self.mesh.devices.reshape(-1))
        n_shards = self.mesh.devices.size
        f = max(train_data.num_features, 1)
        fpad = (-f) % n_shards
        self._fpad = fpad
        binned = train_data.binned
        if binned.size == 0:
            binned = np.zeros((train_data.num_data, f), np.uint8)
        if fpad:
            binned = np.concatenate(
                [binned, np.zeros((binned.shape[0], fpad), binned.dtype)],
                axis=1)
        x_sharding = NamedSharding(self.mesh, P(None, FEATURE_AXIS))
        X_dev = jax.device_put(binned, x_sharding)
        super().__init__(config, train_data, device_data=X_dev)
        # padded features: num_bin=1 -> no valid threshold -> gain stays -inf
        pad_i32 = lambda a, v: jnp.concatenate(
            [jnp.asarray(a, jnp.int32), jnp.full(fpad, v, jnp.int32)])
        self.meta = FeatureMeta(
            num_bin=pad_i32(train_data.num_bin_arr, 1),
            default_bin=pad_i32(train_data.default_bin_arr, 0),
            is_categorical=jnp.concatenate(
                [jnp.asarray(train_data.is_categorical_arr, bool),
                 jnp.zeros(fpad, bool)]))
        if self.hist_mode in WAVE_ONLY_MODES:
            Log.fatal("tpu_histogram_mode=%s is wave-only; the "
                      "feature-parallel learner's exact engine does not "
                      "support it" % self.hist_mode)
        grow = make_grow_fn(self.num_leaves, self.num_bins, self.meta,
                            self.params, config.max_depth,
                            hist_mode=self.hist_mode, hist_dtype=self.dtype,
                            feature_axis=FEATURE_AXIS,
                            row_capacities=self.row_capacities,
                            cache_hists=self.cache_hists)
        from ..ops.grow import TreeArrays
        tree_specs = jax.tree_util.tree_map(
            lambda _: P(), TreeArrays(*([0] * len(TreeArrays._fields))))
        # the all_gather'd SplitInfo fold is replicated by construction
        # but the varying-axes analysis cannot prove it
        sharded_grow = jax.shard_map(
            grow, mesh=self.mesh,
            in_specs=(P(None, FEATURE_AXIS), P(), P(), P(), P()),
            out_specs=(tree_specs, P()), check_vma=False)
        self._grow = jax.jit(sharded_grow)
        Log.info("Feature-parallel learner over %d devices "
                 "(%d padded features)", n_shards, fpad)

    def sample_feature_mask(self):
        mask = super().sample_feature_mask()
        if self._fpad:
            mask = jnp.concatenate([mask, jnp.zeros(self._fpad, bool)])
        return mask

    def set_observer(self, obs) -> None:
        _init_collective_counter(self, obs)

    def train_device(self, grad, hess, row_mult=None, feature_mask=None):
        out = super().train_device(grad, hess, row_mult, feature_mask)
        if getattr(self, "_m_coll", None) is not None:
            self._m_coll.inc(self._coll_tree_bytes)
        return out

    def collective_info(self):
        """Per-split traffic: one packed-SplitInfo all_gather (the
        Allreduce(MaxReducer) analog) + one row-bitmask psum.  Estimates
        only — see DataParallelTreeLearner.collective_info."""
        n_shards = int(self.mesh.devices.size)
        return {"learner": type(self).__name__, "axis": FEATURE_AXIS,
                "n_devices": n_shards, "n_processes": 1,
                "global_rows": int(self.train_data.num_data),
                "estimates": True,
                "allgather": {"what": "packed SplitInfo per split",
                              "per_split_bytes": 13 * 4 * n_shards},
                "psum": {"what": "row-bitmask split re-execution",
                         "per_split_bytes":
                             int(self.train_data.num_data) * 4}}


def create_tree_learner(config: Config, train_data: TrainingData,
                        mesh: Optional[Mesh] = None):
    """TreeLearner::CreateTreeLearner (tree_learner.h:19-82) — learner type
    x device dispatch: 'serial' on one device; 'data'/'feature'/'voting'
    parallel over the mesh."""
    ltype = config.tree_learner
    n_dev = len(jax.devices()) if mesh is None else mesh.devices.size
    if n_dev > 1:
        if ltype in ("data", "data_parallel"):
            return DataParallelTreeLearner(config, train_data, mesh)
        if ltype in ("voting", "voting_parallel"):
            return VotingParallelTreeLearner(config, train_data, mesh)
        if ltype in ("feature", "feature_parallel"):
            return FeatureParallelTreeLearner(config, train_data, mesh)
    if ltype not in ("serial", "data", "feature", "voting", "data_parallel",
                     "feature_parallel", "voting_parallel"):
        Log.fatal("Unknown tree learner type %s", ltype)
    return SerialTreeLearner(config, train_data)
