"""GBDT booster: the training loop, bagging, scores, eval, model I/O.

Parity target: src/boosting/gbdt.cpp / gbdt.h.  Mirrored behaviors:

* boost_from_average stub tree on the first iteration for single-class
  regression-style objectives (gbdt.cpp:339-362);
* degenerate-class skip with constant default output (gbdt.cpp:166-205);
* bagging re-drawn every ``bagging_freq`` iterations with exact
  ``bagging_fraction`` count (gbdt.cpp:242-324) — realized as a per-row
  0/1 multiplier folded into the histogram weights instead of index
  re-partitioning (TPU-friendly; same leaf statistics);
* early stopping bookkeeping per (valid set, metric) with
  factor_to_bigger_better and model pop-back (gbdt.cpp:527-585,479-500);
* rollback (gbdt.cpp:460-477);
* model text format round-trip (gbdt.cpp:817-971) — the compatibility
  surface shared with the reference line;
* split-count feature importance (gbdt.cpp:973-997).

TPU-first design: train/valid scores are DEVICE arrays; a fast-path
iteration (gradients -> grow tree -> partition score update -> valid
traversal updates) is a handful of async XLA dispatches with **zero host
round-trips** — essential because the accelerator may sit behind a
high-latency link.  Host numpy mirrors are pulled lazily (metric eval,
custom fobj) and trees are materialized lazily in one stacked transfer.
Scores layout is the reference's column-major flat array, shaped
(num_tree_per_iteration, num_data).
"""
from __future__ import annotations

import json
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.dataset import TrainingData
from ..metrics import Metric
from ..obs import NULL_OBSERVER, observer_from_config, timers
from ..obs.timers import fenced_get
from ..objectives import ObjectiveFunction, load_objective_from_string
from ..ops.fused_iter import ClassTree
from ..ops.learner import SerialTreeLearner, materialize_tree
from ..ops import predict as dev_predict
from ..utils.config import Config
from ..utils.common import parse_kv_lines
from ..utils.log import Log
from .tree import Tree

kEpsilon = 1e-15


# elements of one device-to-host copy of a score matrix (16 MB of
# float32).  A (1, 41,943,040) score fetched whole, 168 MB, leaves the TPU
# runtime taking 6 ms for every later dispatch of the process where it took
# 1.6 (read on the chip, PR 33: at once after `np.asarray(score,
# float64)` in three processes of three, never after twelve fetches by
# pieces of this size in two; the host's own allocations do not do it)
_HOST_PIECE = 1 << 22


@jax.jit
def _columns_from(x, start):
    return jax.lax.dynamic_slice_in_dim(x, start, _HOST_PIECE, axis=1)


def _host_float64(dev) -> np.ndarray:
    """The (k, n) device matrix `dev` on the host as float64, fetched in
    pieces of `_HOST_PIECE` columns where it has more (the last piece
    starts early enough to be whole, so every piece is one program)."""
    n = dev.shape[1] if dev.ndim == 2 else 0
    if n <= _HOST_PIECE:
        return np.asarray(dev, dtype=np.float64)
    out = np.empty(dev.shape, np.float64)
    for start in range(0, n, _HOST_PIECE):
        start = min(start, n - _HOST_PIECE)
        out[:, start:start + _HOST_PIECE] = np.asarray(
            _columns_from(dev, start))
    return out


class GBDT:
    """Gradient Boosting Decision Tree (boosting.h:21-261 interface)."""

    def __init__(self, config: Config,
                 train_data: Optional[TrainingData] = None,
                 objective: Optional[ObjectiveFunction] = None,
                 training_metrics: Sequence[Metric] = ()):
        self.config = config
        # models: host Trees; None entries are pending materialization from
        # the aligned _models_dev/_models_shrink slots
        self.models: List[Optional[Tree]] = []
        self._models_dev: List[Optional[object]] = []
        self._models_shrink: List[float] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.boost_from_average_used = False
        self.num_class = config.num_class if config else 1
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.objective = objective
        self.shrinkage_rate = config.learning_rate
        self.early_stopping_round = config.early_stopping_round
        self.train_data: Optional[TrainingData] = None
        self.learner: Optional[SerialTreeLearner] = None
        self.training_metrics: List[Metric] = list(training_metrics)
        self.valid_data: List[TrainingData] = []
        self.valid_metrics: List[List[Metric]] = []
        self._valid_X_dev: List[jnp.ndarray] = []
        self._valid_score_dev: List[jnp.ndarray] = []
        self._valid_score_host: List[Optional[np.ndarray]] = []
        self.best_score: List[List[float]] = []
        self.best_iter: List[List[int]] = []
        self.best_msg: List[List[str]] = []
        self._score_dev: Optional[jnp.ndarray] = None
        self._score_host: Optional[np.ndarray] = None
        self._obs = NULL_OBSERVER
        self._metrics = None
        # serving-time drift reference (obs/drift.py): lazily completed
        # from the dataset fingerprint + train scores + last eval, or
        # restored verbatim from the model text header
        self._drift_fingerprint: Optional[dict] = None
        self._last_eval_results: List[dict] = []
        # lazily-resolved fused iteration (ops/fused_iter.py): None =
        # unresolved; (obj_or_None,) = resolved.  Invalidated whenever
        # the learner / objective / observer it binds is rebuilt.
        self._fused_state = None
        self.num_tree_per_iteration = 1
        if objective is not None:
            self.num_tree_per_iteration = objective.num_tree_per_iteration()
            self.is_constant_hessian = objective.is_constant_hessian()
        else:
            self.num_tree_per_iteration = max(1, self.num_class)
            self.is_constant_hessian = False
        if train_data is not None:
            self.reset_training_data(config, train_data, objective,
                                     training_metrics)

    # ----------------------------------------------------------------- setup
    def _resolve_score_engine(self, config: Config) -> None:
        se = str(config.tpu_score_update).strip().lower()
        if se not in ("auto", "gather", "pallas"):
            Log.fatal("Unknown tpu_score_update %s (expected auto/"
                      "gather/pallas)", config.tpu_score_update)
        # auto -> the pallas compare-select kernel, bit-equal to the
        # gather, in the staged chain and, since PR 33, inside the fused
        # step (before it the fused step, which every one-chip cell runs,
        # took the gather whatever this said).  The dispatch itself
        # (ops/predict.py pallas_score_update_runs) still gates on TPU +
        # num_leaves<=512 + f32 score and falls back to the XLA gather
        # otherwise, so 'auto' is safe to resolve unconditionally here.
        self._score_engine = "pallas" if se == "auto" else se

    def _reset_observer(self, config: Config) -> None:
        """Build the run observer (lightgbm_tpu/obs) for this training
        dataset and emit the run header.  All-default obs params leave the
        shared NULL observer in place — the hot path then pays one
        attribute load and an empty call per hook, no fencing, no event
        objects."""
        prev = getattr(self, "_obs", NULL_OBSERVER)
        if prev.enabled:
            prev.close()
        self._obs = observer_from_config(
            config, comm=getattr(self.train_data, "_comm", None))
        self._metrics = None
        # model-observability cadence (obs/model.py): split audit + top-k
        # importance snapshots, both host-side on materialized trees
        self._obs_split_audit = bool(getattr(config, "obs_split_audit",
                                             False))
        self._obs_importance_every = int(
            getattr(config, "obs_importance_every", 0) or 0)
        self._obs_importance_topk = int(
            getattr(config, "obs_importance_topk", 20) or 20)
        if self._obs.enabled:
            devices = [{"id": int(d.id), "platform": str(d.platform),
                        "kind": str(getattr(d, "device_kind", ""))}
                       for d in jax.devices()]
            self._obs.run_header(
                backend=str(jax.default_backend()), devices=devices,
                params={k: str(v) for k, v in self.config.raw.items()},
                context=self.learner.obs_info())
            collective_info = getattr(self.learner, "collective_info", None)
            if collective_info is not None:
                self._obs.event("collectives", **collective_info())
            # arm the continuous host sampling profiler (obs/prof.py,
            # obs_prof_hz) for the run; finalize_telemetry -> obs.close()
            # disarms and flushes the final prof_profile window
            self._obs.prof_arm()
            # registry instruments are only touched when the observer is
            # on — the disabled hot path stays allocation-free (pinned by
            # the overhead guard in tests/test_obs.py)
            from ..obs import REGISTRY
            self._metrics = {
                "trees": REGISTRY.counter(
                    "lgbm_trees_built_total",
                    "trees grown on device by the training loop"),
                "leaves": REGISTRY.counter(
                    "lgbm_tree_leaves_built_total",
                    "leaves across materialized trained trees"),
            }
            nbins = getattr(self.train_data, "num_bin_arr", None)
            if nbins is not None:
                REGISTRY.counter(
                    "lgbm_dataset_bins_built_total",
                    "feature-discretization bins constructed for "
                    "training datasets").inc(int(np.sum(nbins)))
            # construction-phase accounting captured by io/dataset.py and
            # io/streaming.py: rows/chunks, sketch/bin/write phase
            # seconds, peak RSS, workers — the schema-v9 event
            # bench_compare gates (`construct_s`, --tol-construct)
            cstats = getattr(self.train_data, "_construct_stats", None)
            if cstats is not None:
                self._obs.event("dataset_construct", **cstats)
            # data-quality profile captured at Dataset construction
            # (io/dataset.py _profile_quality); may Log.fatal under
            # obs_health=fatal on a degenerate dataset — before any
            # iteration burns device time
            profile = getattr(self.train_data, "_data_profile", None)
            if (profile is not None
                    and bool(getattr(config, "obs_data_profile", True))):
                from ..obs import dataquality
                label_prof = dataquality.label_profile(
                    self.train_data.metadata.label)
                findings = dataquality.build_findings(
                    profile, label_prof,
                    getattr(self.train_data, "feature_names", None))
                dataquality.emit_data_profile(
                    self._obs, profile, label_prof, findings,
                    health_mode=str(getattr(config, "obs_health", "off")
                                    or "off").strip().lower())
        self.learner.set_observer(self._obs)

    def reset_config(self, config: Config) -> None:
        """GBDT::ResetConfig (gbdt.cpp:64-74): re-read training
        hyperparameters IN PLACE — training scores and the device-resident
        dataset are untouched, so a per-iteration reset_parameter callback
        costs one learner rebuild, not an O(num_trees) score replay plus a
        dataset re-upload (that full path is reset_training_data)."""
        # flush pending device trees first: _materialize stacks them, and
        # trees grown under the old num_leaves must not mix shapes with
        # trees grown under the new one
        self._materialize()
        self.config = config
        self.early_stopping_round = config.early_stopping_round
        self.shrinkage_rate = config.learning_rate
        self._resolve_score_engine(config)
        from ..ops.learner import SerialTreeLearner
        from ..parallel.mesh import create_tree_learner
        old = self.learner
        from ..ops.sparse_mxu import ChunkedSparseStore
        from ..ops.sparse_store import SparseDeviceStore
        old_sparse = isinstance(getattr(old, "X", None),
                                (SparseDeviceStore, ChunkedSparseStore))
        if (type(old) is SerialTreeLearner and old_sparse
                and bool(config.tpu_sparse)):
            # reuse the device sparse store — train_data is unchanged on a
            # hyperparameter reset, so the store is too
            self.learner = SerialTreeLearner(
                config, self.train_data, device_data=old.X,
                device_sparse_col_cap=old.sparse_col_cap)
        elif (type(old) is SerialTreeLearner and not old_sparse
                and not bool(config.tpu_sparse)   # sparse request rebuilds
                and old.X.shape[0]
                == self.train_data.num_data + old._row_pad):
            # reuse the uploaded (padded) bin matrix — no host->device
            # transfer on a hyperparameter reset
            self.learner = SerialTreeLearner(
                config, self.train_data, device_data=old.X,
                device_row_pad=old._row_pad,
                device_packed_cols=getattr(old, "packed_cols", 0))
        else:
            self.learner = create_tree_learner(config, self.train_data)
        # re-attach the run observer to the rebuilt learner so entry-point
        # timing survives a reset_parameter callback
        self.learner.set_observer(self._obs)
        # the fused iteration binds the OLD learner's grow closure
        self._fused_state = None
        # bagging state (gbdt.cpp ResetBaggingConfig, :134-160)
        self.bag_data_cnt = self.num_data
        self.row_mult = None
        if config.bagging_fraction < 1.0 and config.bagging_freq > 0:
            self.bag_data_cnt = int(config.bagging_fraction * self.num_data)

    def reset_training_data(self, config: Config, train_data: TrainingData,
                            objective: Optional[ObjectiveFunction],
                            training_metrics: Sequence[Metric]) -> None:
        """GBDT::ResetTrainingData (gbdt.cpp:76-208)."""
        self.config = config
        self.objective = objective
        self.early_stopping_round = config.early_stopping_round
        self.shrinkage_rate = config.learning_rate
        if objective is not None:
            self.num_tree_per_iteration = objective.num_tree_per_iteration()
            self.is_constant_hessian = objective.is_constant_hessian()
        self.train_data = train_data
        self.num_data = train_data.num_data
        from ..parallel.mesh import create_tree_learner
        with timers.span("learner_build"):
            self.learner = create_tree_learner(config, train_data)
        self.score_dtype = self.learner.dtype
        self._resolve_score_engine(config)
        self._reset_observer(config)
        # new learner + objective + observer: re-resolve the fused program
        self._fused_state = None
        self.training_metrics = list(training_metrics)
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos = train_data.feature_infos()

        k = self.num_tree_per_iteration
        init = train_data.metadata.init_score
        self.has_init_score = init is not None
        score0 = np.zeros((k, self.num_data), dtype=np.float64)
        if self.has_init_score:
            if len(init) % self.num_data != 0 or len(init) // self.num_data != k:
                Log.fatal("number of class for initial score error")
            score0[:] = np.asarray(init).reshape(k, self.num_data)
        self._score_dev = jnp.asarray(score0, self.score_dtype)
        # a mesh learner keeps the rows where its grow program takes and
        # leaves them, from the first iteration on
        place = getattr(self.learner, "place_score", None)
        if place is not None:
            self._score_dev = place(self._score_dev)
        self._score_host = None
        # re-apply every existing model (incl. loaded/continued ones) on the
        # (possibly new) training data
        self._materialize()
        for t, tree in enumerate(self.models):
            self._apply_tree_to_train(tree, t % k)

        # degenerate class handling (gbdt.cpp:166-205)
        self.class_need_train = [True] * k
        self.class_default_output = [0.0] * k
        if objective is not None and objective.skip_empty_class():
            label = np.asarray(train_data.metadata.label)
            if k > 1:
                for i in range(k):
                    cnt = int((label.astype(np.int32) == i).sum())
                    if cnt == self.num_data:
                        self.class_need_train[i] = False
                        self.class_default_output[i] = -np.log(kEpsilon)
                    elif cnt == 0:
                        self.class_need_train[i] = False
                        self.class_default_output[i] = -np.log(1.0 / kEpsilon - 1.0)
            else:
                cnt_pos = int((label > 0).sum())
                if cnt_pos == 0:
                    self.class_need_train[0] = False
                    self.class_default_output[0] = -np.log(1.0 / kEpsilon - 1.0)
                elif cnt_pos == self.num_data:
                    self.class_need_train[0] = False
                    self.class_default_output[0] = -np.log(kEpsilon)

        # bagging state (gbdt.cpp ResetBaggingConfig, :134-160)
        self.bag_data_cnt = self.num_data
        self.row_mult: Optional[jnp.ndarray] = None
        if config.bagging_fraction < 1.0 and config.bagging_freq > 0:
            self.bag_data_cnt = int(config.bagging_fraction * self.num_data)

    def add_valid_dataset(self, valid_data: TrainingData,
                          valid_metrics: Sequence[Metric]) -> None:
        """GBDT::AddValidDataset (gbdt.cpp:210-240)."""
        k = self.num_tree_per_iteration
        score = np.zeros((k, valid_data.num_data), dtype=np.float64)
        init = valid_data.metadata.init_score
        if init is not None:
            score[:] = np.asarray(init).reshape(k, valid_data.num_data)
        from ..ops.learner import paged_device_matrix
        # out-of-core valid sets upload shard-by-shard (no host matrix)
        Xv = paged_device_matrix(valid_data)
        if Xv is None:
            Xv = jnp.asarray(valid_data.binned)
        score_dev = jnp.asarray(score, self.score_dtype)
        self.valid_data.append(valid_data)
        self._valid_X_dev.append(Xv)
        self._valid_score_dev.append(score_dev)
        self._valid_score_host.append(None)
        vi = len(self.valid_data) - 1
        # apply existing models
        self._materialize()
        for t, tree in enumerate(self.models):
            self._apply_tree_to_valid(tree, vi, t % k)
        self.valid_metrics.append(list(valid_metrics))
        self.best_score.append([-np.inf] * len(valid_metrics))
        self.best_iter.append([0] * len(valid_metrics))
        self.best_msg.append([""] * len(valid_metrics))

    # ------------------------------------------------------ score management
    @property
    def train_score(self) -> np.ndarray:
        """Host mirror of the training scores (pull-on-demand)."""
        if self._score_host is None:
            self._score_host = _host_float64(self._score_dev)
        return self._score_host

    def valid_score_host(self, i: int) -> np.ndarray:
        if self._valid_score_host[i] is None:
            self._valid_score_host[i] = _host_float64(
                self._valid_score_dev[i])
        return self._valid_score_host[i]

    def _invalidate_train(self):
        self._score_host = None

    def _invalidate_valid(self, i: int):
        self._valid_score_host[i] = None

    def _apply_tree_to_train(self, tree: Tree, tid: int, scale: float = 1.0):
        """Add a host tree's prediction to the train score (device traversal
        when bin thresholds exist, raw-data fallback for loaded models)."""
        if tree.num_leaves <= 1:
            return
        from ..ops.sparse_mxu import ChunkedSparseStore
        from ..ops.sparse_store import SparseDeviceStore
        sparse_store = isinstance(self.learner.X,
                                  (SparseDeviceStore, ChunkedSparseStore))
        if tree.has_bin_thresholds and not sparse_store:
            ta = dev_predict.traversal_from_host_tree(tree, self.score_dtype)
            self._score_dev = self._score_dev.at[tid].set(
                dev_predict.add_tree_to_score(
                    self._score_dev[tid], self.learner.X[:self.num_data],
                    ta, jnp.asarray(scale, self.score_dtype),
                    self.learner.bundle_arrays,
                    packed=bool(getattr(self.learner, "packed_cols", 0))))
        elif self.train_data.raw_data is not None:
            s = self.train_score
            s[tid] += scale * tree.predict(self.train_data.raw_data)
            self._score_dev = self._score_dev.at[tid].set(
                jnp.asarray(s[tid], self.score_dtype))
        elif sparse_store:
            Log.fatal("tpu_sparse=true keeps no dense device matrix to "
                      "traverse; DART/rollback/continued training need the "
                      "raw data (keep_raw) under the sparse store")
        else:
            Log.fatal("Cannot apply a loaded model to binned-only data; "
                      "keep raw data when continuing training")
        self._invalidate_train()

    def _apply_tree_to_valid(self, tree: Tree, vi: int, tid: int,
                             scale: float = 1.0):
        if tree.num_leaves <= 1:
            return
        if tree.has_bin_thresholds:
            ta = dev_predict.traversal_from_host_tree(tree, self.score_dtype)
            self._valid_score_dev[vi] = self._valid_score_dev[vi].at[tid].set(
                dev_predict.add_tree_to_score(self._valid_score_dev[vi][tid],
                                              self._valid_X_dev[vi], ta,
                                              jnp.asarray(scale, self.score_dtype),
                                              self.learner.bundle_arrays))
        elif self.valid_data[vi].raw_data is not None:
            s = self.valid_score_host(vi)
            s[tid] += scale * tree.predict(self.valid_data[vi].raw_data)
            self._valid_score_dev[vi] = self._valid_score_dev[vi].at[tid].set(
                jnp.asarray(s[tid], self.score_dtype))
        else:
            Log.fatal("Validation data lacks both bin thresholds and raw data")
        self._invalidate_valid(vi)

    # ---------------------------------------------------- model realization
    def _materialize(self) -> None:
        """Materialize all pending device trees into host Trees (one stacked
        device->host transfer for the whole batch)."""
        pending = [i for i, m in enumerate(self.models) if m is None]
        if not pending:
            return
        with timers.span("materialize"):
            self._materialize_pending(pending)

    @staticmethod
    def _host_trees(devs) -> list:
        """Host TreeArrays of `devs`, in order, from one counted sync: the
        single trees stacked on the device first, each class step's batch
        fetched whole and split here (span ``class_unstack``)."""
        single = [d for d in devs if not isinstance(d, ClassTree)]
        batches = {id(d.batch): d.batch for d in devs
                   if isinstance(d, ClassTree)}
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *single) \
            if len(single) > 1 else (single[0] if single else None)
        host, host_batches = fenced_get((stacked, batches))
        singles = iter([jax.tree_util.tree_map(lambda x: x[j], host)
                        for j in range(len(single))]
                       if len(single) > 1 else [host])
        if not batches:
            return list(singles)
        with timers.span("class_unstack", trees=len(devs) - len(single)):
            return [jax.tree_util.tree_map(lambda x: x[d.index],
                                           host_batches[id(d.batch)])
                    if isinstance(d, ClassTree) else next(singles)
                    for d in devs]

    def _materialize_pending(self, pending) -> None:
        hosts = self._host_trees([self._models_dev[i] for i in pending])
        mesh = getattr(self.learner, "mesh", None)
        for i, ht in zip(pending, hosts):
            tree = materialize_tree(ht, self.train_data,
                                    self.config.num_leaves)
            tree.shrink(self._models_shrink[i])
            self.models[i] = tree
            self._models_dev[i] = None
            # the grow loop's counters came with the tree: one record a
            # tree, under the iteration that grew it
            c = dict(zip(timers.COUNTERS, (int(v) for v in ht.counters)))
            # a wave that ran no row slab visited every row
            c["kernel_rows"] += (c["waves"] - c["compacted"]) * c["rows"]
            it, tid = self._tree_iteration(i)
            timers.count("tree", it=it, tree=tid,
                         rows_visited=c["rows"] + c["kernel_rows"],
                         shards=1 if mesh is None else int(mesh.devices.size),
                         allreduce_bytes=c["allreduce_words"] * jnp.dtype(
                             self.learner.dtype).itemsize, **c)
        if mesh is not None:
            # what each of the mesh's devices has held at most, read where
            # the trees come to the host anyway: whether one device holds
            # more than its share of the rows
            from ..obs.memory import device_memory_stats
            ids = {int(d.id) for d in mesh.devices.flat}
            timers.count("mesh_memory", peak_bytes_in_use=[
                row.get("peak_bytes_in_use", 0)
                for row in device_memory_stats() if row["id"] in ids])
        if self._metrics is not None:
            # host num_leaves is free here — trees just landed on host
            self._metrics["leaves"].inc(
                sum(self.models[i].num_leaves for i in pending))
        # release device buffers
        self._models_shrink = [0.0 if m is not None else s
                               for m, s in zip(self.models, self._models_shrink)]

    def _tree_iteration(self, i: int) -> tuple:
        """(iteration of this run, tree within it) that grew
        ``self.models[i]``: loaded models and the boost-from-average stub
        come first."""
        k = max(self.num_tree_per_iteration, 1)
        first = self.num_init_iteration * k + int(self.boost_from_average_used)
        return divmod(i - first, k)

    def _append_host_tree(self, tree: Tree) -> None:
        self.models.append(tree)
        self._models_dev.append(None)
        self._models_shrink.append(1.0)

    # --------------------------------------------------------------- bagging
    def _bagging(self, it: int, gradients=None, hessians=None) -> None:
        """Re-draw the bag on schedule (gbdt.cpp:265-324).  The exact-count
        sample is drawn by ranking per-row random keys (same distribution as
        the reference's reservoir chunks; deterministic per seed+iter)."""
        cfg = self.config
        if self.bag_data_cnt < self.num_data and it % cfg.bagging_freq == 0:
            rng = np.random.default_rng(cfg.bagging_seed + it)
            keys = rng.random(self.num_data)
            idx = np.argpartition(keys, self.bag_data_cnt)[:self.bag_data_cnt]
            mult = np.zeros(self.num_data, dtype=np.float32)
            mult[idx] = 1.0
            self.row_mult = jnp.asarray(mult)
            Log.debug("Re-bagging, using %d data to train", self.bag_data_cnt)

    # ------------------------------------------------------------- iteration
    def _resolve_fused_iter(self):
        """Resolve ``tpu_fused_iter`` (auto/on/off) to a built
        FusedIteration, or None for the staged chain.  Resolved once and
        cached — the verdict depends only on booster/learner/objective
        shape, all of which invalidate ``_fused_state`` when rebuilt.

        auto: fuse when eligible AND the plan wishes it (ops/plan.py
        Plan.fused_wanted: the TPU Pallas wave path is live, so
        dispatch latency is what the fused program removes).  on: force
        when eligible; an explicit opt-in is never dropped silently, so
        ineligibility warns.  off: never."""
        if self._fused_state is not None:
            return self._fused_state[0]
        mode = str(getattr(self.config, "tpu_fused_iter", "auto")
                   or "auto").strip().lower()
        if mode not in ("auto", "on", "off"):
            Log.fatal("Unknown tpu_fused_iter %s (expected auto/on/off)",
                      self.config.tpu_fused_iter)
        fused = None
        if mode != "off":
            from ..ops import fused_iter as _fi
            ok, why = _fi.fused_supported(self)
            if not ok:
                if mode == "on":
                    Log.warning("tpu_fused_iter=on but the fused iteration "
                                "is unavailable (%s); using the staged "
                                "chain", why)
            else:
                if mode == "on" or self.learner.plan.fused_wanted:
                    k = self.num_tree_per_iteration
                    # k > 1: the class step, over the trained classes
                    classes = None if k == 1 else [
                        c for c in range(k) if self.class_need_train[c]]
                    fused = _fi.FusedIteration.build(
                        self.learner, self.objective,
                        self.num_data, self.score_dtype,
                        self._score_engine, classes)
        self._fused_state = (fused,)
        return fused

    def train_one_iter(self, gradients=None, hessians=None,
                       is_eval: bool = True) -> bool:
        """GBDT::TrainOneIter (gbdt.cpp:339-458); returns True to stop."""
        with timers.span("iteration", it=self.iter):
            return self._train_one_iter(gradients, hessians, is_eval)

    def _train_one_iter(self, gradients, hessians, is_eval: bool) -> bool:
        cfg = self.config
        k = self.num_tree_per_iteration
        obs = self._obs
        it0 = self.iter
        obs.iter_begin(it0)
        # iteration-context stamp: what the loop is doing right now, for
        # /statusz and incident evidence bundles (obs/incident.py) —
        # a host dict update, nothing on the device path
        obs.stamp_context(stage="boost", it=it0, trees=len(self.models))
        # split-audit needs to know which models this iteration appends
        # (includes the iteration-0 boost_from_average stub, which the
        # audit emitter skips — a stub has no realized split to record)
        start_models = len(self.models)
        # boost from average (gbdt.cpp:341-362)
        if (not self.models and cfg.boost_from_average
                and not self.has_init_score and self.num_class <= 1
                and self.objective is not None
                and self.objective.boost_from_average()):
            label = np.asarray(self.train_data.metadata.label, dtype=np.float64)
            init_score = float(label.sum() / self.num_data)
            stub = Tree(2)
            stub.split(0, 0, False, 0, 0, 0.0, init_score, init_score,
                       0, self.num_data, -1.0, 0, 0, 0.0)
            self._score_dev = self._score_dev + jnp.asarray(init_score,
                                                            self.score_dtype)
            self._invalidate_train()
            for vi in range(len(self.valid_data)):
                self._valid_score_dev[vi] = self._valid_score_dev[vi] + \
                    jnp.asarray(init_score, self.score_dtype)
                self._invalidate_valid(vi)
            self._append_host_tree(stub)
            self.boost_from_average_used = True

        custom = gradients is not None and hessians is not None
        # fused iteration (ops/fused_iter.py): gradients + grow + score
        # update submitted as ONE device entry per tree.  Per-call custom
        # gradients force the staged chain — they are host arrays the
        # fused program cannot see.
        fused = None if custom else self._resolve_fused_iter()
        g_dev = h_dev = None
        if fused is not None:
            # no host gradient section at all: the bag multiplier is the
            # only host-side training input the fused program takes
            # (eligibility excludes the GOSS rescale, so plain _bagging
            # is exactly what _bagging_with_grad would have done)
            self._bagging(self.iter)
            obs.lap("boost")
        elif not custom:
            if self.objective is None:
                Log.fatal("No object function provided")
            with timers.span("gradients"):
                g_dev, h_dev = self.objective.get_gradients(
                    self._score_for_objective())
                g_dev = jnp.reshape(g_dev, (k, self.num_data))
                h_dev = jnp.reshape(h_dev, (k, self.num_data))
            gradients = hessians = None
        else:
            gradients = np.array(gradients, dtype=np.float32).reshape(k, self.num_data)
            hessians = np.array(hessians, dtype=np.float32).reshape(k, self.num_data)
            g_dev = jnp.asarray(gradients)
            h_dev = jnp.asarray(hessians)

        if fused is None:
            # bagging / GOSS may need host gradients and may rescale them
            g_dev, h_dev = self._bagging_with_grad(self.iter, g_dev, h_dev)
            # "boost" = objective gradients + bagging (+ first-iter stub
            # tree)
            obs.lap("boost", (g_dev, h_dev))

        # health monitors (obs/health.py): dispatch the finiteness /
        # magnitude reductions async now, verdicts in one sync below
        health = obs.health
        health_leaves = None
        if health is not None and health.due(it0):
            health.stage_gradients(g_dev, h_dev)
            health_leaves = []

        num_leaves_this_iter = []
        last_leaf_id = None
        class_trees = None
        if fused is not None and fused.classes is not None:
            # one dispatch for every trained class: the softmax over the
            # (k, N) score, one grow body scanned over the classes and
            # their score updates (bit-identical to the staged chain's k
            # grows: tests/test_fused_multiclass.py)
            class_trees, last_leaf_id, self._score_dev = fused.run(
                self._score_dev, self.row_mult, None,
                jnp.asarray(self.shrinkage_rate, self.score_dtype))
            self._invalidate_train()
            obs.lap("step", self._score_dev)
            num_leaves_this_iter.append(class_trees.num_leaves)
        for tid in range(k):
            if self.class_need_train[tid]:
                if class_trees is not None:
                    dev_tree = ClassTree(
                        class_trees, fused.classes.index(tid))
                elif fused is not None:
                    # one dispatch: gradients, the grow while_loop and
                    # the partition score update never return to host
                    # (bit-identical to the staged chain below —
                    # tests/test_fused_iter.py)
                    dev_tree, leaf_id, new_score = fused.run(
                        self._score_dev[tid], self.row_mult, None,
                        jnp.asarray(self.shrinkage_rate, self.score_dtype))
                    self._score_dev = self._score_dev.at[tid].set(new_score)
                    self._invalidate_train()
                    # one dispatch, one lap: gradients, grow and the score
                    # update are one program and have no host boundary
                    obs.lap("step", self._score_dev)
                else:
                    # spans `grow_args` and `dispatch` inside
                    dev_tree, leaf_id = self.learner.train_device(
                        g_dev[tid], h_dev[tid], self.row_mult)
                    if getattr(self.learner, "_nproc", 1) > 1:
                        # multi-host pod: the grow program psums
                        # histograms over the global mesh and hands back
                        # a GLOBAL row->leaf map; scores here stay
                        # rank-LOCAL, so take this process's rows (an
                        # addressable-shard read, no collective)
                        leaf_id = self.learner.local_rows(leaf_id)
                    # "grow" = the histogram+split+partition XLA program
                    # (one jitted entry; finer decomposition needs a
                    # profiler window — see docs/Observability.md)
                    obs.lap("grow", leaf_id)
                    # device score updates (train via partition, valids
                    # via traversal) — all async
                    with timers.span("score_update"):
                        self._score_dev = self._score_dev.at[tid].set(
                            dev_predict.update_score_from_partition(
                                self._score_dev[tid], leaf_id,
                                dev_tree.leaf_value,
                                jnp.asarray(self.shrinkage_rate,
                                            self.score_dtype),
                                engine=self._score_engine))
                    self._invalidate_train()
                    obs.lap("partition", self._score_dev)
                if class_trees is None:
                    last_leaf_id = leaf_id
                    num_leaves_this_iter.append(dev_tree.num_leaves)
                if self.valid_data:
                    with timers.span("score_update"):
                        ta = dev_predict.traversal_from_grow(
                            dev_tree.on_device() if class_trees is not None
                            else dev_tree)
                        for vi in range(len(self.valid_data)):
                            self._valid_score_dev[vi] = \
                                self._valid_score_dev[vi].at[tid].set(
                                    dev_predict.add_tree_to_score(
                                        self._valid_score_dev[vi][tid],
                                        self._valid_X_dev[vi], ta,
                                        jnp.asarray(self.shrinkage_rate,
                                                    self.score_dtype),
                                        self.learner.bundle_arrays))
                            self._invalidate_valid(vi)
                    obs.lap("update", self._valid_score_dev[-1])
                self.models.append(None)
                self._models_dev.append(dev_tree)
                self._models_shrink.append(self.shrinkage_rate)
                if health_leaves is not None:
                    health_leaves.append(dev_tree.leaf_value)
                if self._metrics is not None:
                    self._metrics["trees"].inc()
            else:
                tree = Tree(2)
                if len(self.models) < k:
                    out = self.class_default_output[tid]
                    tree.split(0, 0, False, 0, 0, 0.0, out, out,
                               0, self.num_data, -1.0, 0, 0, 0.0)
                    self._score_dev = self._score_dev.at[tid].add(
                        jnp.asarray(out, self.score_dtype))
                    self._invalidate_train()
                    for vi in range(len(self.valid_data)):
                        self._valid_score_dev[vi] = \
                            self._valid_score_dev[vi].at[tid].add(
                                jnp.asarray(out, self.score_dtype))
                        self._invalidate_valid(vi)
                self._append_host_tree(tree)

        # snapshot BEFORE the opt-in sync work below (health verdicts,
        # eval, model obs): host_orchestration_s is the per-tree
        # submission glue, not the explicitly-priced sync features: the
        # `iteration` span's own host time so far, outside its call spans
        # (`gradients`, `grow_args`, `dispatch`, `score_update`) and the
        # observer's fences (obs/timers.py open_self_seconds)
        host_orch = timers.open_self_seconds() if obs.enabled else 0.0

        if health_leaves is not None:
            # one batched device_get over the staged scalars; may raise
            # LightGBMError under obs_health=fatal
            health.stage_leaf_values(health_leaves)
            health.run_checks(obs, it0)

        if last_leaf_id is not None:
            # straggler sampling (obs/straggler.py, obs_straggler_every):
            # the row->leaf map is the iteration's most row-sharded
            # artifact, so its per-shard arrival order exposes which
            # device the collectives waited on
            obs.straggler_sample(it0, last_leaf_id)

        # stop check: any trained tree must have >1 leaves.  Evaluating the
        # device scalars here costs one sync; skip it when nothing forces a
        # sync anyway (pure fast path) and rely on the periodic check.
        should_continue = True
        if num_leaves_this_iter:
            if is_eval or (self.iter % 16 == 0):
                should_continue = any(
                    bool((np.asarray(nl) > 1).any())
                    for nl in fenced_get(num_leaves_this_iter))
                comm = self._dist_comm()
                if comm is not None:
                    # pod-wide stop vote.  Trees are bit-identical across
                    # ranks (split search runs on psum'd histograms), so
                    # ranks normally agree — the vote pins the invariant:
                    # no rank may stop alone and leave the others hanging
                    # in the next wave's psum.  Cadence (is_eval or
                    # iter%16) is config-derived, hence collective-aligned.
                    from ..parallel.comm import vote_stop
                    should_continue = not vote_stop(comm,
                                                    not should_continue)
        else:
            should_continue = False
        if not should_continue:
            self._pop_degenerate_iterations()
            obs.iter_end(it0, value=self._score_dev, stopped=True,
                         host_orchestration_s=host_orch)
            return True
        self.iter += 1
        self._emit_model_obs(it0, start_models)
        if is_eval:
            stop = self.eval_and_check_early_stopping()
            obs.lap("eval")
            obs.iter_end(it0, value=self._score_dev,
                         host_orchestration_s=host_orch)
            return stop
        obs.iter_end(it0, value=self._score_dev,
                     host_orchestration_s=host_orch)
        return False

    def _emit_model_obs(self, it0: int, start_models: int) -> None:
        """Split-audit + importance events for this iteration (obs/model.py).

        Costs a _materialize (device sync) when due, so both are opt-in:
        ``obs_split_audit`` audits every iteration's new trees;
        ``obs_importance_every=N`` snapshots top-k importance every N
        iterations."""
        if not self._obs.enabled:
            return
        every = self._obs_importance_every
        imp_due = every > 0 and (it0 % every) == 0
        if not self._obs_split_audit and not imp_due:
            return
        from ..obs import model as obs_model
        self._materialize()
        if self._obs_split_audit:
            for t in range(start_models, len(self.models)):
                obs_model.emit_split_audit(self._obs, it0, t,
                                           self.models[t])
        if imp_due:
            obs_model.emit_importance(
                self._obs, it0, self.feature_importance("split"),
                self.feature_importance("gain"),
                self._obs_importance_topk)

    def _bagging_with_grad(self, it, g_dev, h_dev):
        """Hook: base bagging ignores gradients; GOSS overrides."""
        self._bagging(it)
        return g_dev, h_dev

    def _pop_degenerate_iterations(self) -> None:
        """No leaf met the split requirements: drop this iteration's trees
        and any identical degenerate tail (gbdt.cpp:440-448)."""
        Log.warning("Stopped training because there are no more leaves "
                    "that meet the split requirements.")
        k = self.num_tree_per_iteration
        for _ in range(k):
            self.models.pop()
            self._models_dev.pop()
            self._models_shrink.pop()

    def _score_for_objective(self):
        k = self.num_tree_per_iteration
        if k == 1:
            return self._score_dev[0]
        return jnp.reshape(self._score_dev, (-1,))

    def merge_from(self, other: "GBDT") -> None:
        """GBDT::MergeFrom (gbdt.h:47-62): the other model's trees come
        FIRST (as if this booster had been continued-trained from the other
        model), and the merged prefix becomes the init-iteration count.
        Scores are NOT replayed (matches the reference, which only merges
        the model arrays).  Trees are deep-copied so later in-place
        mutation (rollback's shrink, SetLeafValue) of one booster cannot
        corrupt the other."""
        import copy
        self._materialize()
        other._materialize()
        merged = [copy.deepcopy(t) for t in other.models]
        self.models = merged + self.models
        self._models_dev = [None] * len(merged) + self._models_dev
        self._models_shrink = [1.0] * len(merged) + self._models_shrink
        k = max(self.num_tree_per_iteration, 1)
        self.num_init_iteration = len(merged) // k
        self.num_iteration_for_pred = len(self.models) // k

    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:460-477)."""
        if self.iter <= 0:
            return
        self._materialize()
        k = self.num_tree_per_iteration
        cur_iter = self.iter + self.num_init_iteration - 1
        for tid in range(k):
            t = cur_iter * k + tid
            self.models[t].shrink(-1.0)
            self._apply_tree_to_train(self.models[t], tid)
            for vi in range(len(self.valid_data)):
                self._apply_tree_to_valid(self.models[t], vi, tid)
        for _ in range(k):
            self.models.pop()
            self._models_dev.pop()
            self._models_shrink.pop()
        self.iter -= 1

    # ------------------------------------------------------------------ eval
    def _dist_comm(self):
        """The training dataset's multi-process comm, or None.  Present
        only for rank-sharded datasets (io/dataset.py from_binned /
        from_matrix with a comm) — the signal that metric values are
        partial sums over local rows and stop decisions need a vote."""
        comm = (getattr(self.train_data, "_comm", None)
                if self.train_data is not None else None)
        if comm is not None and getattr(comm, "size", 1) > 1 \
                and not getattr(comm, "closed", False):
            return comm
        return None

    def _reduce_scores(self, scores, num_local_rows):
        """Row-weighted cross-rank mean of per-metric scores.  Metrics
        evaluate over the rank's LOCAL score shard; the weighted mean by
        local row count recovers the global row-average every rank then
        agrees on — which keeps the early-stopping bookkeeping (and its
        model pop-back) bit-identical across the pod.  Routes through
        the host comm (parallel/comm.py), so it lands in the
        host_collective observability stream with a seq number."""
        comm = self._dist_comm()
        if comm is None:
            return scores
        from ..parallel.comm import reduce_metrics
        red = reduce_metrics(
            comm, {str(i): float(s) for i, s in enumerate(scores)},
            weight=float(num_local_rows))
        return [red[str(i)] for i in range(len(scores))]

    def eval_and_check_early_stopping(self) -> bool:
        best_msg = self.output_metric(self.iter)
        met = bool(best_msg)
        comm = self._dist_comm()
        if comm is not None:
            # unanimous vote: with reduced metrics every rank already
            # computed the same answer, so this is a divergence guard —
            # a rank that disagrees (e.g. a stale shard) cannot keep
            # training against ranks that popped models back
            from ..parallel.comm import vote_stop
            met = vote_stop(comm, met)
        if met:
            Log.info("Early stopping at iteration %d, the best iteration round is %d",
                     self.iter, self.iter - self.early_stopping_round)
            Log.info("Output of best iteration round:\n%s", best_msg)
            for _ in range(self.early_stopping_round * self.num_tree_per_iteration):
                self.models.pop()
                self._models_dev.pop()
                self._models_shrink.pop()
        return met

    def output_metric(self, it: int) -> str:
        """GBDT::OutputMetric (gbdt.cpp:527-585)."""
        need_output = (it % self.config.output_freq) == 0
        ret = ""
        msg_lines: List[str] = []
        meet_pairs: List[Tuple[int, int]] = []
        # metric values double as timeline `eval` events (convergence /
        # overfit-gap surface for `obs explain` and bench_compare's
        # final_eval_metric gate) and as the drift fingerprint's eval
        # snapshot — always collected; only the event is observer-gated
        eval_results: List[dict] = []
        if need_output:
            for m in self.training_metrics:
                scores = self._reduce_scores(
                    m.eval(self.train_score, self.objective),
                    self.num_data)
                for name, s in zip(m.get_names(), scores):
                    line = "Iteration:%d, training %s : %g" % (it, name, s)
                    Log.info(line)
                    if self.early_stopping_round > 0:
                        msg_lines.append(line)
                    if eval_results is not None:
                        eval_results.append({"dataset": "training",
                                             "metric": name,
                                             "value": float(s)})
        if need_output or self.early_stopping_round > 0:
            for i in range(len(self.valid_metrics)):
                for j, m in enumerate(self.valid_metrics[i]):
                    test_scores = self._reduce_scores(
                        m.eval(self.valid_score_host(i), self.objective),
                        self.valid_data[i].num_data)
                    for name, s in zip(m.get_names(), test_scores):
                        line = "Iteration:%d, valid_%d %s : %g" % (it, i + 1, name, s)
                        if need_output:
                            Log.info(line)
                        if self.early_stopping_round > 0:
                            msg_lines.append(line)
                        if eval_results is not None:
                            eval_results.append(
                                {"dataset": "valid_%d" % (i + 1),
                                 "metric": name, "value": float(s)})
                    if not ret and self.early_stopping_round > 0:
                        cur = m.factor_to_bigger_better * test_scores[-1]
                        if cur > self.best_score[i][j]:
                            self.best_score[i][j] = cur
                            self.best_iter[i][j] = it
                            meet_pairs.append((i, j))
                        elif it - self.best_iter[i][j] >= self.early_stopping_round:
                            ret = self.best_msg[i][j]
        if eval_results:
            self._last_eval_results = eval_results
            self._drift_fingerprint = None   # eval snapshot went stale
            if self._obs.enabled:
                self._obs.event("eval", it=it, results=eval_results)
        msg = "\n".join(msg_lines)
        for i, j in meet_pairs:
            self.best_msg[i][j] = msg
        return ret

    def get_eval_at(self, data_idx: int) -> List[float]:
        """GBDT::GetEvalAt (gbdt.cpp:588-609)."""
        out: List[float] = []
        if data_idx == 0:
            for m in self.training_metrics:
                out.extend(m.eval(self.train_score, self.objective))
        else:
            i = data_idx - 1
            for m in self.valid_metrics[i]:
                out.extend(m.eval(self.valid_score_host(i), self.objective))
        return out

    def eval_names(self, data_idx: int) -> List[str]:
        ms = self.training_metrics if data_idx == 0 else self.valid_metrics[data_idx - 1]
        out: List[str] = []
        for m in ms:
            out.extend(m.get_names())
        return out

    # --------------------------------------------------------------- predict
    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def total_iterations(self) -> int:
        return len(self.models) // self.num_tree_per_iteration

    def _used_trees(self, num_iteration: int) -> int:
        num_used = len(self.models)
        if num_iteration > 0:
            ni = num_iteration + (1 if self.boost_from_average_used else 0)
            num_used = min(ni * self.num_tree_per_iteration, len(self.models))
        return num_used

    def predict_raw(self, features: np.ndarray,
                    num_iteration: int = -1,
                    allow_device: bool = True) -> np.ndarray:
        """Raw scores (N, num_tree_per_iteration) on real-valued features
        (gbdt_prediction.cpp PredictRaw).  allow_device=False pins the
        exact f64 host path — continued-training init scores need it
        (the device path's Kahan f32 accumulation is ~1e-7 relative)."""
        self._materialize()
        features = np.ascontiguousarray(np.asarray(features, dtype=np.float64))
        n = features.shape[0]
        k = self.num_tree_per_iteration
        num_used = self._used_trees(num_iteration)
        dev = (self._device_bulk_predict(features, num_used, k)
               if allow_device else None)
        if dev is not None:
            return dev
        from .. import native
        nat = native.predict_raw(
            [(self.models[t], t % k) for t in range(num_used)], k, features)
        if nat is not None:
            return nat
        out = np.zeros((n, k), dtype=np.float64)
        for t in range(num_used):
            out[:, t % k] += self.models[t].predict(features)
        return out

    # ------------------------------------------------- device bulk predict
    _DEVICE_PREDICT_MIN_ROWS = 100_000

    @staticmethod
    def _predict_chunk_rows(n_features: int, n_devices: int) -> int:
        """Rows per device-predict chunk.  Host V (i32) + D (bool) cost
        F*5 bytes/row; the one-deep pipeline keeps TWO chunks resident,
        so the per-chunk budget is 1.5 GB for a ~3 GB device peak
        (ADVICE r3: the old 3 GB/chunk budget meant a ~6 GB peak)."""
        bytes_per_row = max(n_features, 1) * 5
        return min(4_000_000 * max(n_devices, 1),
                   max(65_536, 1_500_000_000 // bytes_per_row))

    def _device_bulk_predict(self, features, num_used, k):
        """Rank-encoded TPU bulk prediction (ops/predict.py): f64-exact
        routing as int compares, Kahan f32 accumulation.  Returns None
        when the host paths should run instead (small batches, non-TPU
        backends under tpu_predict=auto, tpu_predict=false, or a model
        whose features mix categorical and numerical decisions)."""
        from ..utils.config import _FALSE_SET, _TRUE_SET
        cfg = str(getattr(self.config, "tpu_predict", "auto")).strip().lower()
        if cfg in _FALSE_SET:
            return None
        if cfg not in _TRUE_SET:       # auto
            if (jax.default_backend() != "tpu"
                    or features.shape[0] < self._DEVICE_PREDICT_MIN_ROWS):
                return None
        key = (num_used, k, len(self.models), self.iter,
               features.shape[1])
        if getattr(self, "_ranked_pred_key", None) != key:
            try:
                self._ranked_pred = dev_predict.build_ranked_predictor(
                    self.models[:num_used], k, features.shape[1])
            except ValueError as e:    # mixed cat/num feature use
                Log.warning("device bulk predict unavailable (%s); "
                            "using the host predictor", e)
                self._ranked_pred = None
            self._ranked_pred_key = key
        rp = self._ranked_pred
        if rp is None:
            return None
        if features.shape[1] < rp.max_feature + 1:
            return None                # fewer columns than the model uses
        devices = jax.local_devices()   # per-process rows -> local mesh
        out = np.empty((features.shape[0], k), np.float64)
        chunk = self._predict_chunk_rows(features.shape[1], len(devices))
        def dispatch(part):
            """Async: device call issued, nothing blocked on."""
            V, D = dev_predict.rank_encode(rp, part)
            n = len(part)
            # power-of-two row bucketing (floor 256, capped at the chunk
            # size): the jit cache keys on shape, so varying batch sizes
            # would otherwise each compile a fresh executable — padded
            # rows are sliced off in drain()
            bucket = min(1 << max(int(n - 1).bit_length(), 8), chunk)
            if bucket > n:
                V = np.concatenate(
                    [V, np.zeros((bucket - n, V.shape[1]), V.dtype)])
                D = np.concatenate(
                    [D, np.zeros((bucket - n, D.shape[1]), D.dtype)])
            if len(devices) > 1:
                # rows shard over the device mesh; trees replicate —
                # bit-identical to single-device (pure data parallel)
                score, _ = dev_predict.ranked_predict_sharded(
                    rp, V, D, k, devices=devices)
                return score, n
            return dev_predict.ranked_predict_device(
                rp.dev, jnp.asarray(V), jnp.asarray(D), k), n

        def drain(pending):
            plo, pscore, pnrows = pending
            out[plo:plo + pnrows] = np.asarray(
                fenced_get(pscore)[:pnrows], np.float64)

        # one-deep pipeline: encode chunk i+1 on the host while the
        # device computes chunk i (jax dispatch is async; device_get is
        # the only sync point)
        pending = None
        for lo in range(0, features.shape[0], chunk):
            score, nrows = dispatch(features[lo:lo + chunk])
            if pending is not None:
                drain(pending)
            pending = (lo, score, nrows)
        if pending is not None:
            drain(pending)
        return out

    def predict(self, features: np.ndarray,
                num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False) -> np.ndarray:
        if pred_leaf:
            return self.predict_leaf_index(features, num_iteration)
        raw = self.predict_raw(features, num_iteration)
        if raw_score or self.objective is None:
            return raw[:, 0] if raw.shape[1] == 1 else raw
        conv = np.asarray(self.objective.convert_output(
            raw if raw.shape[1] > 1 else raw[:, 0]))
        return conv

    def predict_leaf_index(self, features: np.ndarray,
                           num_iteration: int = -1) -> np.ndarray:
        self._materialize()
        features = np.ascontiguousarray(np.asarray(features, dtype=np.float64))
        num_used = self._used_trees(num_iteration)
        cols = [self.models[t].predict_leaf_index(features)
                for t in range(num_used)]
        return np.stack(cols, axis=1) if cols else np.zeros((features.shape[0], 0), np.int32)

    def pred_contrib(self, features: np.ndarray, num_iteration: int = -1,
                     per: str = "feature") -> np.ndarray:
        """Prediction attribution (debug path, host-only, f64 exact).

        per='tree': (N, num_used) matrix of each tree's contribution —
        column t sums into raw-score class t % num_tree_per_iteration, so
        summing the columns of a class reproduces predict_raw exactly.

        per='feature': gain-weighted path attribution per tree
        (Tree.predict_contrib), summed over trees.  Returns
        (N, num_features + 1) for single-output models — the last column
        is the bias (stub trees and zero-gain paths) — and
        (N, k, num_features + 1) for multi-class.  Rows sum to the raw
        score by construction.
        """
        if per not in ("feature", "tree"):
            raise KeyError("pred_contrib per must be 'feature' or 'tree'")
        self._materialize()
        features = np.ascontiguousarray(np.asarray(features,
                                                   dtype=np.float64))
        n = features.shape[0]
        k = self.num_tree_per_iteration
        num_used = self._used_trees(num_iteration)
        if per == "tree":
            out = np.zeros((n, num_used), dtype=np.float64)
            for t in range(num_used):
                out[:, t] = self.models[t].predict(features)
            return out
        nf = self.max_feature_idx + 1
        out = np.zeros((n, k, nf + 1), dtype=np.float64)
        for t in range(num_used):
            out[:, t % k, :] += self.models[t].predict_contrib(features, nf)
        return out[:, 0, :] if k == 1 else out

    # ------------------------------------------------------------- model I/O
    def sub_model_name(self) -> str:
        return "tree"

    def drift_fingerprint(self) -> Optional[dict]:
        """Serving-time drift reference (obs/drift.py): the dataset's
        per-feature binned histograms completed with the training-score
        distribution(s) and the final eval snapshot.  Cached — each
        eval pass invalidates it — and restored verbatim when the model
        was loaded from text, so a serving process never needs the
        training dataset."""
        if self._drift_fingerprint is not None:
            return self._drift_fingerprint
        td = getattr(self, "train_data", None)
        base = getattr(td, "_drift_fingerprint", None)
        if base is None:
            return None
        from ..obs import drift
        try:
            score = self.train_score
        except Exception:            # score engine not stood up yet
            score = None
        self._drift_fingerprint = drift.attach_scores(
            base, train_score=score, objective=self.objective,
            eval_results=self._last_eval_results)
        return self._drift_fingerprint

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        """GBDT::SaveModelToString (gbdt.cpp:817-861)."""
        self._materialize()
        lines = [self.sub_model_name()]
        lines.append("num_class=%d" % self.num_class)
        lines.append("num_tree_per_iteration=%d" % self.num_tree_per_iteration)
        lines.append("label_index=%d" % self.label_idx)
        lines.append("max_feature_idx=%d" % self.max_feature_idx)
        if self.objective is not None:
            lines.append("objective=%s" % self.objective.to_string())
        if self.boost_from_average_used:
            lines.append("boost_from_average")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self.feature_infos))
        fp = self.drift_fingerprint()
        if fp is not None:
            # one compact-JSON header line (no newlines, so it survives
            # parse_kv_lines round trips); any process loading the model
            # text gets the serving-time drift reference for free
            lines.append("drift_fingerprint=%s"
                         % json.dumps(fp, sort_keys=True,
                                      separators=(",", ":")))
        lines.append("")
        num_used = self._used_trees(num_iteration)
        for i in range(num_used):
            lines.append("Tree=%d" % i)
            lines.append(self.models[i].to_string())
        lines.append("")
        lines.append("feature importances:")
        for cnt, name in self.feature_importance_pairs():
            lines.append("%s=%d" % (name, cnt))
        return "\n".join(lines) + "\n"

    def save_model_to_file(self, filename: str, num_iteration: int = -1) -> None:
        with open(filename, "w") as f:
            f.write(self.save_model_to_string(num_iteration))

    def load_model_from_string(self, model_str: str) -> bool:
        """GBDT::LoadModelFromString (gbdt.cpp:875-971)."""
        self.models = []
        self._models_dev = []
        self._models_shrink = []
        lines = model_str.splitlines()
        header_lines = []
        for line in lines:
            if line.startswith("Tree="):
                break
            header_lines.append(line)
        kv = parse_kv_lines(header_lines)
        if "num_class" not in kv:
            Log.fatal("Model file doesn't specify the number of classes")
        self.num_class = int(kv["num_class"])
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration",
                                                 self.num_class))
        if "label_index" not in kv:
            Log.fatal("Model file doesn't specify the label index")
        self.label_idx = int(kv["label_index"])
        if "max_feature_idx" not in kv:
            Log.fatal("Model file doesn't specify max_feature_idx")
        self.max_feature_idx = int(kv["max_feature_idx"])
        self.boost_from_average_used = any(
            l.strip() == "boost_from_average" for l in header_lines)
        if "feature_names" in kv:
            self.feature_names = kv["feature_names"].split(" ")
            if len(self.feature_names) != self.max_feature_idx + 1:
                Log.fatal("Wrong size of feature_names")
        if "feature_infos" in kv:
            self.feature_infos = kv["feature_infos"].split(" ")
        if "objective" in kv:
            self.objective = load_objective_from_string(kv["objective"])
        if "drift_fingerprint" in kv:
            try:
                self._drift_fingerprint = json.loads(kv["drift_fingerprint"])
            except ValueError as e:
                Log.warning("ignoring malformed drift_fingerprint in "
                            "model text: %s", e)
        # tree blocks
        text = "\n".join(lines)
        parts = text.split("Tree=")
        for part in parts[1:]:
            block_lines = part.splitlines()
            body = []
            for bl in block_lines[1:]:
                if bl.startswith("feature importances"):
                    break
                body.append(bl)
            block = "\n".join(body).strip()
            if block:
                self._append_host_tree(Tree.from_string(block))
        self.num_iteration_for_pred = len(self.models) // max(self.num_tree_per_iteration, 1)
        self.num_init_iteration = self.num_iteration_for_pred
        self.iter = 0
        return True

    def dump_model(self, num_iteration: int = -1) -> str:
        """GBDT::DumpModel JSON (gbdt.cpp:665-699)."""
        self._materialize()
        out = ['{"name":"%s",' % self.sub_model_name(),
               '"num_class":%d,' % self.num_class,
               '"num_tree_per_iteration":%d,' % self.num_tree_per_iteration,
               '"label_index":%d,' % self.label_idx,
               '"max_feature_idx":%d,' % self.max_feature_idx]
        if self.objective is not None:
            out.append('"objective":"%s",' % self.objective.to_string())
        out.append('"feature_names":[%s],' % ",".join(
            '"%s"' % n for n in self.feature_names))
        out.append('"tree_info":[')
        num_used = self._used_trees(num_iteration)
        tree_strs = []
        for i in range(num_used):
            tree_strs.append('{"tree_index":%d,%s}' % (i, self.models[i].to_json()))
        out.append(",".join(tree_strs))
        out.append("]}")
        return "\n".join(out)

    # ------------------------------------------------------------ importance
    def feature_importance_pairs(self) -> List[Tuple[int, str]]:
        """Split-count importance, descending, stable (gbdt.cpp:973-997)."""
        counts = self.feature_importance()
        pairs = [(int(counts[i]), self.feature_names[i] if i < len(self.feature_names)
                  else "Column_%d" % i)
                 for i in range(len(counts)) if counts[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        return pairs

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """'split' = times a feature is used; 'gain' = total gain of the
        splits using it (python-package basic.py:1646-1680 semantics)."""
        if importance_type not in ("split", "gain"):
            raise KeyError("importance_type must be split or gain")
        self._materialize()
        if importance_type == "gain":
            gains = np.zeros(self.max_feature_idx + 1, dtype=np.float64)
            for tree in self.models:
                for i in range(tree.num_leaves - 1):
                    if tree.split_gain[i] > 0:
                        gains[tree.split_feature[i]] += tree.split_gain[i]
            return gains
        counts = np.zeros(self.max_feature_idx + 1, dtype=np.int64)
        for tree in self.models:
            for i in range(tree.num_leaves - 1):
                if tree.split_gain[i] > 0:
                    counts[tree.split_feature[i]] += 1
        return counts
