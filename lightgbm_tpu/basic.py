"""Python-facing Dataset / Booster — API parity with python-package/basic.py.

The reference wraps the C library through ctypes (basic.py:21,546,1171); here
the same public surface drives the in-process TPU engine directly, so there
is no language boundary to cross.  Semantics kept: lazy Dataset
construction, reference-alignment of validation sets, parameter dict
handling, custom objective ``fobj(preds, train_data) -> (grad, hess)`` via
``Booster.update``, prediction modes (raw/prob/leaf-index), model file
round-trip, continued training via ``init_model``.
"""
from __future__ import annotations

import time as _time
from typing import Dict, List, Optional

import numpy as np

from .io.dataset import TrainingData
from .metrics import create_metric
from .models.gbdt import GBDT
from .models.factory import create_boosting
from .objectives import create_objective
from .obs import timers
from .obs.metrics import observe_predict
from .utils.config import Config
from .utils.log import LightGBMError, Log

__all__ = ["Dataset", "Booster", "LightGBMError"]


def _data_from_any(data, label=None):
    """Accept numpy 2-D, pandas DataFrame, scipy sparse, list-of-lists, or
    file path.  Sparse inputs stay sparse (io/sparse.py) — they are binned
    column-by-column without densification."""
    if isinstance(data, str):
        return data, label
    from .io.sparse import SparseColumns, from_scipy, is_scipy_sparse
    if isinstance(data, SparseColumns):
        return data, label
    if is_scipy_sparse(data):
        return from_scipy(data), label
    try:
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            # kept as a frame until construct(): category columns must be
            # coded against the *reference* dataset's category lists, and
            # the reference may be attached after __init__ (set_reference)
            return data, label
        if label is not None and isinstance(label, (pd.Series, pd.DataFrame)):
            label = label.values
    except ImportError:
        pass
    return np.asarray(data, dtype=np.float64), label


_PANDAS_OK_KINDS = "biuf"   # bool / int / uint / float columns train directly


def _is_pandas_frame(data) -> bool:
    try:
        import pandas as pd
    except ImportError:
        return False
    return isinstance(data, pd.DataFrame)


def _data_from_pandas(data, feature_name, categorical_feature,
                      pandas_categorical):
    """Code category-dtype columns and resolve auto names — the semantics of
    the reference's pandas path (python-package/lightgbm/basic.py:224-291).

    Train call: ``pandas_categorical=None`` -> the per-column category lists
    are recorded from ``data`` and returned.  Valid/predict call: pass the
    train-time lists; each category column is re-coded against them so the
    integer codes agree across datasets even when the frames saw different
    category orders.  Returns ``(matrix, feature_name, categorical_feature,
    pandas_categorical)``.

    NaN/unseen categories code to -1, kept as-is: this vintage of the
    reference counts -1 as an ordinary category at train time
    (src/io/bin.cpp:242-255 has no negative filter) and maps values absent
    from the bin map to the last bin at predict (bin.h:435-439) — our
    binning does the same, so -1 handling is parity, not an accident.
    """
    cat_cols = [c for c in data.columns
                if str(data[c].dtype) == "category"]
    if pandas_categorical is None:          # train dataset records the maps
        pandas_categorical = [list(data[c].cat.categories) for c in cat_cols]
    else:                                   # valid/predict aligns to train
        if len(cat_cols) != len(pandas_categorical):
            raise LightGBMError(
                "train and valid dataset categorical_feature do not match.")
    if cat_cols:
        data = data.copy()      # never alter the caller's frame
        for c, train_cats in zip(cat_cols, pandas_categorical):
            if list(data[c].cat.categories) != list(train_cats):
                data[c] = data[c].cat.set_categories(train_cats)
            data[c] = data[c].cat.codes
    if categorical_feature is not None:
        if categorical_feature == "auto":
            categorical_feature = [str(c) for c in cat_cols]
        else:
            categorical_feature = (list(categorical_feature)
                                   + [str(c) for c in cat_cols])
    if feature_name == "auto":
        feature_name = [str(c) for c in data.columns]
    bad = [str(c) for c, dt in zip(data.columns, data.dtypes)
           if getattr(dt, "kind", "O") not in _PANDAS_OK_KINDS]
    if bad:
        raise LightGBMError(
            "DataFrame.dtypes for data must be int, float or bool; found "
            "unsupported dtypes in fields: " + ", ".join(bad))
    return (data.values.astype(np.float64), feature_name,
            categorical_feature, pandas_categorical)


def _json_default_numpy(obj):
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    raise TypeError("Cannot serialize %s in pandas_categorical"
                    % type(obj).__name__)


def _dump_pandas_categorical(pandas_categorical) -> str:
    import json
    return json.dumps(pandas_categorical, default=_json_default_numpy)


def _parse_pandas_categorical(model_str: str):
    """Read the trailing ``pandas_categorical:`` line a saved model carries
    (reference appends it after the model text, basic.py:283-291)."""
    import json
    idx = model_str.rfind("pandas_categorical:")
    if idx < 0:
        return None
    line = model_str[idx + len("pandas_categorical:"):].splitlines()[0]
    try:
        return json.loads(line)
    except ValueError:
        return None


class Dataset:
    """Lazily-constructed training dataset (python-package basic.py:546)."""

    def __init__(self, data, label=None, max_bin=None, reference=None,
                 weight=None, group=None, init_score=None, silent=False,
                 feature_name="auto", categorical_feature="auto", params=None,
                 free_raw_data=True):
        data, label = _data_from_any(data, label)
        self.data = data
        self.label = label
        self.max_bin = max_bin
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.silent = silent
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        if max_bin is not None:
            self.params.setdefault("max_bin", max_bin)
        self.free_raw_data = free_raw_data
        self.pandas_categorical = None
        self._handle: Optional[TrainingData] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None

    # ------------------------------------------------------------ construct
    def construct(self) -> "Dataset":
        """Bin the raw data and build the device-ready store (lazy; no-op when already constructed)."""
        if self._handle is not None:
            return self
        params = dict(self.params)
        cfg = Config(params)
        cat = []
        feature_names = None
        if isinstance(self.data, str):
            ref_td = self.reference._handle if self.reference is not None else None
            if TrainingData.can_load_binned(self.data):
                # pre-binned mmap directory: zero re-binning work
                with timers.span("dataset_open"):
                    self._handle = TrainingData.from_binned(self.data)
            elif TrainingData.can_load_binary(self.data):
                self._handle = TrainingData.load_binary(self.data)
            else:
                self._handle = TrainingData.from_file(self.data, cfg,
                                                      reference=ref_td)
        else:
            from .io.sparse import SparseColumns
            if self.reference is not None:
                self.reference.construct()
            data = self.data
            if _is_pandas_frame(data):
                ref_pc = (self.reference.pandas_categorical
                          if self.reference is not None else None)
                data, self.feature_name, self.categorical_feature, \
                    self.pandas_categorical = _data_from_pandas(
                        data, self.feature_name, self.categorical_feature,
                        ref_pc)
                self.data = data
            sparse = isinstance(data, SparseColumns)
            data = data if sparse else np.asarray(data, dtype=np.float64)
            if self.feature_name not in (None, "auto"):
                feature_names = list(self.feature_name)
            if self.categorical_feature not in (None, "auto"):
                spec = self.categorical_feature
                if isinstance(spec, (int, str)):
                    spec = [spec]      # scalar from bindings (e.g. R)
                cat = []
                for c in spec:
                    if isinstance(c, str):
                        # column-name spec (basic.py:224-291 pandas path
                        # semantics): resolve against explicit feature names
                        # or, with feature_name='auto', the generated
                        # Column_%d names — never silently drop the spec
                        if feature_names and c in feature_names:
                            cat.append(feature_names.index(c))
                        elif not feature_names and c.startswith("Column_") \
                                and c[len("Column_"):].isdigit():
                            cat.append(int(c[len("Column_"):]))
                        else:
                            raise LightGBMError(
                                "Unknown categorical column %r (known "
                                "feature names: %s)"
                                % (c, feature_names or "auto Column_<i>"))
                    else:
                        cat.append(int(c))
            ref_td = (self.reference._handle       # constructed above
                      if self.reference is not None else None)
            if sparse:
                self._handle = TrainingData.from_csc(
                    data, label=self.label, config=cfg,
                    weights=self.weight, group=self.group,
                    init_score=self.init_score,
                    categorical_feature=cat, feature_names=feature_names,
                    reference=ref_td)
            else:
                self._handle = TrainingData.from_matrix(
                    data, label=self.label, config=cfg,
                    weights=self.weight, group=self.group,
                    init_score=self.init_score,
                    categorical_feature=cat, feature_names=feature_names,
                    reference=ref_td, keep_raw=True)
        if self.label is not None and self._handle.metadata.label is None:
            self._handle.metadata.set_label(self.label)
        if not self.free_raw_data and isinstance(self.data, np.ndarray):
            self._handle.raw_data = self.data
        # continued-training predictor fills init scores
        # (engine.py:92-98 / dataset predict_fun_ path)
        if self._predictor is not None:
            from .io.sparse import SparseColumns, iter_dense_row_chunks
            if self._handle.raw_data is not None:
                raw = self._predictor.predict_raw_for_init(
                    self._handle.raw_data)
                self._handle.metadata.set_init_score(raw.T.reshape(-1))
            elif isinstance(self.data, SparseColumns):
                raw = np.concatenate(
                    [self._predictor.predict_raw_for_init(block)
                     for _, block in iter_dense_row_chunks(self.data)])
                self._handle.metadata.set_init_score(raw.T.reshape(-1))
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False, params=None) -> "Dataset":
        """Validation Dataset aligned to this one's bin mappers."""
        return Dataset(data, label=label, reference=self,
                       weight=weight, group=group, init_score=init_score,
                       silent=silent, params=params or self.params,
                       free_raw_data=self.free_raw_data)

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Align this dataset's bin mappers with a reference (train) dataset."""
        self.reference = reference
        return self

    def subset(self, used_indices, params=None) -> "Dataset":
        """New Dataset over a row subset, sharing this one's bin mappers."""
        self.construct()
        used_indices = np.asarray(used_indices)
        from .io.sparse import SparseColumns
        if isinstance(self.data, SparseColumns):
            sub = Dataset(self.data.take_rows(used_indices),
                          label=None if self.label is None
                          else np.asarray(self.label)[used_indices],
                          reference=self,
                          weight=None if self.weight is None
                          else np.asarray(self.weight)[used_indices],
                          params=params or self.params,
                          free_raw_data=self.free_raw_data)
            sub.used_indices = used_indices
            return sub
        if self._handle.raw_data is None:
            Log.fatal("Cannot subset a Dataset whose raw data was freed")
        sub = Dataset(self._handle.raw_data[used_indices],
                      label=None if self.label is None else np.asarray(self.label)[used_indices],
                      reference=self,
                      weight=None if self.weight is None else np.asarray(self.weight)[used_indices],
                      params=params or self.params,
                      free_raw_data=self.free_raw_data)
        sub.used_indices = used_indices
        return sub

    # ------------------------------------------------------------- metadata
    def set_label(self, label) -> "Dataset":
        """Set the target vector."""
        self.label = label
        if self._handle is not None:
            self._handle.metadata.set_label(label)
        return self

    def get_label(self):
        """The target vector, or None before it is set."""
        if self._handle is not None and self._handle.metadata.label is not None:
            return np.asarray(self._handle.metadata.label)
        return self.label

    def set_weight(self, weight) -> "Dataset":
        """Set per-row weights."""
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weights(weight)
        return self

    def get_weight(self):
        """Per-row weights, or None."""
        if self._handle is not None and self._handle.metadata.weights is not None:
            return np.asarray(self._handle.metadata.weights)
        return self.weight

    def set_group(self, group) -> "Dataset":
        """Set query/group sizes for ranking."""
        self.group = group
        if self._handle is not None:
            self._handle.metadata.set_query_counts(group)
        return self

    def get_group(self):
        """Query/group sizes, or None."""
        if self._handle is not None and self._handle.metadata.query_boundaries is not None:
            return np.diff(self._handle.metadata.query_boundaries)
        return self.group

    def set_init_score(self, init_score) -> "Dataset":
        """Set initial scores added to every prediction."""
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(init_score)
        return self

    def get_init_score(self):
        """Initial scores, or None."""
        if self._handle is not None:
            return self._handle.metadata.init_score
        return self.init_score

    def set_field(self, field_name: str, data) -> None:
        """Set a metadata field by name (label/weight/group/init_score)."""
        self.construct()
        self._handle.metadata.set_field(field_name, data)

    def get_field(self, field_name: str):
        """Get a metadata field by name."""
        self.construct()
        return self._handle.metadata.get_field(field_name)

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Set the categorical feature spec (indices or names)."""
        if self._handle is not None and categorical_feature != self.categorical_feature:
            Log.warning("categorical_feature in Dataset is overridden; "
                        "new categorical_feature is %s", str(categorical_feature))
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        """Set feature names (list of str)."""
        self.feature_name = feature_name
        if feature_name not in (None, "auto") and self._handle is not None:
            self._handle.feature_names = list(feature_name)
        return self

    def _update_params(self, params: Optional[dict]) -> "Dataset":
        if params:
            self.params.update(params)
        return self

    def _set_predictor(self, predictor) -> "Dataset":
        self._predictor = predictor
        return self

    # ------------------------------------------------------------------ info
    def num_data(self) -> int:
        """Row count; requires raw ndarray data or a constructed
        dataset (matches the reference's construct-first contract)."""
        if self._handle is not None:
            return self._handle.num_data
        if isinstance(self.data, np.ndarray):
            return self.data.shape[0]
        Log.fatal("Cannot get num_data before construct")

    def get_feature_name(self) -> List[str]:
        """Feature names after construction (auto names resolved)."""
        self.construct()
        return list(self._handle.feature_names)

    def num_feature(self) -> int:
        """Feature count; requires raw ndarray data or a constructed
        dataset (matches the reference's construct-first contract)."""
        if self._handle is not None:
            return self._handle.num_total_features
        if isinstance(self.data, np.ndarray):
            return self.data.shape[1]
        Log.fatal("Cannot get num_feature before construct")

    def save_binary(self, filename: str) -> None:
        """Save the constructed (binned) dataset for fast reload."""
        self.construct()
        self._handle.save_binary(filename)

    def save_binned(self, path: str) -> "Dataset":
        """Persist as the mmap-able pre-binned directory format: later
        runs open it with Dataset.from_binned (or just Dataset(path)) and
        skip host-side binning entirely."""
        self.construct()
        self._handle.save_binned(path)
        return self

    @classmethod
    def from_binned(cls, path: str, params=None, comm=None,
                    row_range=None) -> "Dataset":
        """Open a pre-binned dataset directory written by save_binned()
        or the streaming `ooc_binned_dir` ingest; shards stay mmap-backed
        and page to the device without a host-side bin matrix.  With a
        multi-process ``comm`` (or an explicit ``row_range``) the open is
        rank-sharded: this process maps only its own row range and the
        dataset trains over the global mesh (docs/Distributed.md)."""
        ds = cls(path, params=params)
        with timers.span("dataset_open"):
            ds._handle = TrainingData.from_binned(path, comm=comm,
                                                  row_range=row_range)
        return ds


class _InnerPredictor:
    """Continued-training score provider (basic.py:293-543 analog)."""

    def __init__(self, booster: Optional["Booster"] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        if booster is not None:
            self.gbdt = booster._gbdt
        elif model_file is not None:
            cfg = Config()
            self.gbdt = GBDT(cfg)
            with open(model_file) as f:
                self.gbdt.load_model_from_string(f.read())
        elif model_str is not None:
            # checkpoint resume (models/checkpoint.py): the model text
            # arrives in-memory, never via a file of its own
            cfg = Config()
            self.gbdt = GBDT(cfg)
            self.gbdt.load_model_from_string(model_str)
        else:
            raise LightGBMError("Need booster, model_file or model_str")

    def predict_raw_for_init(self, features: np.ndarray) -> np.ndarray:
        # exact f64 host path: continued-training init scores feed the
        # training parity contract (engine.py init_model), so the f32
        # device bulk path must not round them
        return self.gbdt.predict_raw(features, allow_device=False)


class Booster:
    """Training-capable model wrapper (python-package basic.py:1171)."""

    def __init__(self, params: Optional[dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set = train_set
        self._valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self._network = False
        self.pandas_categorical = None
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance, met %s"
                                % type(train_set).__name__)
            cfg = Config(self.params)
            train_set._update_params(self.params).construct()
            self.pandas_categorical = train_set.pandas_categorical
            objective = create_objective(cfg.objective, cfg)
            if objective is not None:
                objective.init(train_set._handle.metadata,
                               train_set._handle.num_data)
            # training metrics always exist; is_training_metric only gates
            # auto-printing (c_api.cpp CreateObjectiveAndMetrics semantics)
            training_metrics = []
            for mname in cfg.metrics():
                m = create_metric(mname, cfg)
                if m is not None:
                    m.init(train_set._handle.metadata,
                           train_set._handle.num_data)
                    training_metrics.append(m)
            with timers.span("booster_init"):
                self._gbdt = create_boosting(cfg.boosting_type, cfg,
                                             train_set._handle, objective,
                                             training_metrics)
            self._cfg = cfg
            # continuation: fold loaded models in
            if train_set._predictor is not None:
                base = train_set._predictor.gbdt
                base._materialize()
                self._gbdt.models = list(base.models) + self._gbdt.models
                self._gbdt._models_dev = [None] * len(base.models) + self._gbdt._models_dev
                self._gbdt._models_shrink = [1.0] * len(base.models) + self._gbdt._models_shrink
                self._gbdt.num_init_iteration = (
                    len(base.models) // max(base.num_tree_per_iteration, 1))
                self._gbdt.boost_from_average_used = base.boost_from_average_used
        elif model_file is not None:
            with open(model_file) as f:
                self._load_from_string(f.read())
        elif model_str is not None:
            self._load_from_string(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file to create booster instance")

    def _load_from_string(self, model_str: str) -> None:
        self._cfg = Config(self.params)
        self._gbdt = GBDT(self._cfg)
        self._gbdt.load_model_from_string(model_str)
        self.pandas_categorical = _parse_pandas_categorical(model_str)
        self._train_set = None

    # ------------------------------------------------------------- training
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        """Register a validation set for eval/early stopping."""
        if not isinstance(data, Dataset):
            raise TypeError("Validation data should be Dataset instance, met %s"
                            % type(data).__name__)
        data._update_params(self.params).construct()
        metrics = []
        for mname in self._cfg.metrics():
            m = create_metric(mname, self._cfg)
            if m is not None:
                m.init(data._handle.metadata, data._handle.num_data)
                metrics.append(m)
        self._gbdt.add_valid_dataset(data._handle, metrics)
        self._valid_sets.append(data)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; with fobj mirrors the __boost path
        (basic.py:1331-1412)."""
        if train_set is not None and train_set is not self._train_set:
            Log.fatal("Resetting train set inside update is not supported yet")
        if fobj is None:
            return self._gbdt.train_one_iter(None, None, False)
        if self._train_set is None:
            raise LightGBMError(
                "Custom objective needs the train Dataset, but it was "
                "released by free_dataset()")
        grad, hess = fobj(self.__inner_predict_raw(0), self._train_set)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, dtype=np.float32)
        hess = np.asarray(hess, dtype=np.float32)
        if len(grad) != len(hess):
            raise ValueError("Lengths of gradient(%d) and hessian(%d) don't match"
                             % (len(grad), len(hess)))
        return self._gbdt.train_one_iter(grad, hess, False)

    def __inner_predict_raw(self, data_idx: int) -> np.ndarray:
        if data_idx == 0:
            raw = self._gbdt.train_score
        else:
            raw = self._gbdt.valid_score_host(data_idx - 1)
        return raw[0] if raw.shape[0] == 1 else raw.reshape(-1)

    def telemetry(self) -> list:
        """The run observer's in-memory event timeline (lightgbm_tpu/obs)
        as a list of event dicts — empty unless an ``obs_*`` param enabled
        telemetry.  The list is a snapshot copy; docs/Observability.md
        describes the schema."""
        return list(self._gbdt._obs.timeline)

    def finalize_telemetry(self, status: str = "ok") -> None:
        """Emit the run_end summary event and flush/close the JSONL
        writer.  Called by engine.train()/cv() after the boosting loop —
        with ``status="aborted"`` on the exception path, so a crashed run
        still ends with a parseable timeline; idempotent, and safe when
        telemetry is disabled."""
        self._gbdt._obs.close(status=status)

    def reset_parameter(self, params: dict) -> "Booster":
        """LGBM_BoosterResetParameter semantics: rebuild the running config
        like GBDT::ResetConfig.  learning_rate alone takes a fast path (it
        is read every iteration anyway); any other key rebuilds the tree
        learner from the updated full parameter set so num_leaves,
        lambda_l1/l2, bagging, etc. actually take effect."""
        params = dict(params or {})
        self.params.update(params)
        if "learning_rate" in params:
            self._gbdt.shrinkage_rate = float(params["learning_rate"])
        rest = [k for k in params if k != "learning_rate"]
        if rest:
            if "objective" in rest:
                raise LightGBMError(
                    "Cannot change objective during training; "
                    "create a new Booster instead")
            cfg = Config(dict(self.params))
            gb = self._gbdt
            if gb.train_data is not None:
                gb.reset_config(cfg)     # in place: scores/dataset kept
            else:
                gb.config = cfg
        return self

    def set_train_data(self, train_set: "Dataset") -> "Booster":
        """LGBM_BoosterResetTrainingData: swap the training dataset while
        keeping the model (GBDT::ResetTrainingData, gbdt.cpp:64-208)."""
        cfg = Config(dict(self.params))
        train_set._update_params(self.params).construct()
        objective = create_objective(cfg.objective, cfg)
        if objective is not None:
            objective.init(train_set._handle.metadata,
                           train_set._handle.num_data)
        metrics = []
        for mname in cfg.metrics():
            m = create_metric(mname, cfg)
            if m is not None:
                m.init(train_set._handle.metadata, train_set._handle.num_data)
                metrics.append(m)
        self._gbdt.reset_training_data(cfg, train_set._handle, objective,
                                       metrics)
        self._train_set = train_set
        self._cfg = cfg          # later add_valid must see the new config
        return self

    # ------------------------------------------------------- attributes
    def attr(self, key: str):
        """Get a user attribute (basic.py:1769), or None when unset."""
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set STRING attributes; a None value deletes the key
        (basic.py:1785-1800 — non-strings raise like the reference)."""
        store = self.__dict__.setdefault("_attr", {})
        for key, value in kwargs.items():
            if value is None:
                store.pop(key, None)
            elif not isinstance(value, str):
                raise ValueError("Set attr only accepts strings")
            else:
                store[key] = value
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Display name of the training set in eval output."""
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """Release the Python-side train/valid Dataset references
        (basic.py:1281-1283; the reference engine calls this after
        training to let raw data be collected).  The engine retains its
        device-side data, so prediction, update(), and built-in-metric
        eval keep working; only custom fevals need the freed Dataset
        objects and will raise.  Valid slots become None PLACEHOLDERS so
        later add_valid keeps eval indices aligned."""
        self._train_set = None
        self._valid_sets = [None] * len(self._valid_sets)
        return self

    def rollback_one_iter(self) -> "Booster":
        """Undo the most recent boosting iteration."""
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        """Number of completed boosting iterations."""
        return self._gbdt.total_iterations()

    # ----------------------------------------------------------------- eval
    def eval(self, data: Dataset, name: str, feval=None) -> List[tuple]:
        """Evaluate on an arbitrary dataset."""
        if data is self._train_set:
            return self.eval_train(feval)
        for i, vs in enumerate(self._valid_sets):
            if data is vs:
                return self.__eval(i + 1, self.name_valid_sets[i], feval)
        raise LightGBMError("Data should be train set or a validation set")

    def eval_train(self, feval=None) -> List[tuple]:
        """Evaluate on the training data."""
        return self.__eval(0, getattr(self, "_train_data_name",
                                      "training"), feval)

    def eval_valid(self, feval=None) -> List[tuple]:
        """Evaluate on every registered validation set."""
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out.extend(self.__eval(i + 1, name, feval))
        return out

    def __eval(self, data_idx: int, name: str, feval=None) -> List[tuple]:
        out = []
        scores = self._gbdt.get_eval_at(data_idx)
        names = self._gbdt.eval_names(data_idx)
        higher_better = self._eval_higher_better(data_idx)
        for mname, s, hb in zip(names, scores, higher_better):
            out.append((name, mname, s, hb))
        if feval is not None:
            if data_idx == 0:
                ds = self._train_set
            else:
                ds = self._valid_sets[data_idx - 1]
            if ds is None:
                raise LightGBMError(
                    "Custom eval needs the Dataset, but it was released "
                    "by free_dataset()")
            ret = feval(self.__inner_predict_for_eval(data_idx), ds)
            if isinstance(ret, list):
                for fname, val, hb in ret:
                    out.append((name, fname, val, hb))
            else:
                fname, val, hb = ret
                out.append((name, fname, val, hb))
        return out

    def _eval_higher_better(self, data_idx: int) -> List[bool]:
        ms = (self._gbdt.training_metrics if data_idx == 0
              else self._gbdt.valid_metrics[data_idx - 1])
        out = []
        for m in ms:
            out.extend([m.factor_to_bigger_better > 0] * len(m.get_names()))
        return out

    def __inner_predict_for_eval(self, data_idx: int) -> np.ndarray:
        raw = (self._gbdt.train_score if data_idx == 0
               else self._gbdt.valid_score_host(data_idx - 1))
        return raw[0] if raw.shape[0] == 1 else raw.reshape(-1)

    # -------------------------------------------------------------- predict
    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                data_has_header: bool = False,
                is_reshape: bool = True, pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0):
        """Predict rows (numpy/pandas/CSR/CSC or a data file path).

        ``pred_contrib=True`` returns per-feature contributions
        (N, num_features + 1) — gain-weighted path attribution, last
        column = bias; rows sum to the raw score (GBDT.pred_contrib).

        The serving choke point: per-request latency and batch size land
        in the process metrics registry (lightgbm_tpu/obs/metrics.py) —
        the C API and file-path predicts all funnel through here.
        """
        if isinstance(data, Dataset):
            raise TypeError("Cannot use Dataset instance for prediction, "
                            "please use raw data instead")
        t0 = _time.perf_counter()
        out, rows = self._predict_data(data, num_iteration, raw_score,
                                       pred_leaf, pred_contrib,
                                       data_has_header, pred_early_stop,
                                       pred_early_stop_freq,
                                       pred_early_stop_margin)
        # rows counted from the INPUT blocks (1-D converted outputs and
        # (n, k) multiclass matrices both count n rows)
        observe_predict(rows, _time.perf_counter() - t0)
        return out

    def _predict_drift(self):
        """Lazy booster-level DriftMonitor (obs/drift.py) for the
        synchronous predict path; the ServingPredictor builds its own.
        Requires ``obs_drift_every`` > 0, an enabled observer and a
        fingerprinted model; ``False`` caches 'checked, unavailable'."""
        mon = self.__dict__.get("_drift_monitor")
        if mon is not None:
            return mon or None
        cfg = self._cfg
        obs = self._gbdt._obs
        mon = False
        if int(getattr(cfg, "obs_drift_every", 0) or 0) > 0 and obs.enabled:
            fp = self._gbdt.drift_fingerprint()
            if fp is not None:
                from .obs.drift import DriftMonitor
                m = DriftMonitor(
                    fp, observer=obs,
                    mode=(cfg.obs_health if cfg.obs_health != "off"
                          else "warn"),
                    every_rows=cfg.obs_drift_every,
                    window_rows=cfg.obs_drift_window,
                    psi_threshold=cfg.obs_drift_psi,
                    topk=cfg.obs_drift_topk,
                    min_labels=cfg.obs_drift_min_labels)
                if m.enabled:
                    mon = m
        self._drift_monitor = mon
        return mon or None

    def _predict_data(self, data, num_iteration, raw_score, pred_leaf,
                      pred_contrib, data_has_header,
                      pred_early_stop=False, pred_early_stop_freq=10,
                      pred_early_stop_margin=10.0):
        """-> (predictions, input row count)."""
        early_predictor = None
        if pred_early_stop and not (pred_leaf or pred_contrib):
            # margin-based prediction early stopping (predictor.hpp):
            # the tree-major loop drops rows whose margin cleared the
            # threshold — approximate by design, like the reference
            from .predictor import Predictor
            early_predictor = Predictor(
                self._gbdt, num_iteration=num_iteration,
                raw_score=raw_score, early_stop=True,
                early_stop_freq=pred_early_stop_freq,
                early_stop_margin=pred_early_stop_margin)
        drift = self._predict_drift()

        def run(block):
            if drift is not None:
                drift.observe_features(block)
            if early_predictor is not None:
                out = early_predictor._predict_impl(block)
            elif pred_contrib:
                return self._gbdt.pred_contrib(block,
                                               num_iteration=num_iteration)
            else:
                out = self._gbdt.predict(block,
                                         num_iteration=num_iteration,
                                         raw_score=raw_score,
                                         pred_leaf=pred_leaf)
            if drift is not None and not pred_leaf:
                drift.observe_scores(out, raw=raw_score)
            return out

        if isinstance(data, str):
            from .io import parser as _parser
            parsed = _parser.parse_file(data, has_header=data_has_header)
            mat = parsed.features
        else:
            if _is_pandas_frame(data):
                data, _, _, _ = _data_from_pandas(
                    data, None, None, self.pandas_categorical)
            mat, _ = _data_from_any(data)
            from .io.sparse import SparseColumns, iter_dense_row_chunks
            if isinstance(mat, SparseColumns):
                # bounded-memory sparse prediction: densify row chunks
                # (tree traversal wants raw values, O(chunk * F) at a time)
                rows = 0
                outs = []
                for _, block in iter_dense_row_chunks(mat):
                    rows += block.shape[0]
                    outs.append(run(block))
                return ((np.concatenate(outs) if outs
                         else np.zeros(0, dtype=np.float64)), rows)
            mat = np.asarray(mat, dtype=np.float64)
            if mat.ndim == 1:
                mat = mat.reshape(1, -1)
        return run(mat), mat.shape[0]

    def serve(self, num_iteration: int = -1, **overrides):
        """Build a ``ServingPredictor`` for this model — the production
        predict front end (docs/Serving.md).

        Concurrent callers ``submit()`` feature rows and get futures;
        requests coalesce into padded power-of-two batches that run
        through AOT-compiled per-bucket executables (zero steady-state
        recompiles), with ``pred_early_stop`` / ``pred_contrib`` served
        from the same queue.  Overload protection and SLO tracking ride
        the same parameters: ``serve_queue_limit`` /
        ``serve_request_deadline_ms`` shed doomed work at admission,
        and the ``serve_slo_*`` targets drive the rolling SLO engine
        whose burn-rate alerts route through the ``obs_health`` channel
        (docs/Observability.md, "Serving observability & SLOs").

        Configured from the booster's ``serve_*`` parameters
        (docs/Parameters.md); keyword ``overrides`` take precedence
        (``max_batch``, ``max_delay_ms``, ``bucket_min``, ``donate``,
        ``batch_event_every``, ``queue_limit``,
        ``request_deadline_ms``, ``request_event_every``,
        ``slo_p99_ms``, ``slo_qps``, ``slo_window_s``, ``slo_every_s``,
        ``slo_mode``, ``drift_every``, ``drift_window``, ``drift_psi``,
        ``drift_topk``, ``drift_min_labels``, ``num_features``,
        ``devices``).  With ``obs_drift_every`` > 0 and a fingerprinted
        model, a DriftMonitor watches the submitted traffic for
        distribution shift vs the training-time reference
        (docs/Observability.md, "Drift & online quality").  Close it
        (or use as a context manager) to flush the queue, stop the
        worker thread and leave the ``serve_summary`` lifetime record.
        """
        from .serve import ServingPredictor
        cfg = self._cfg
        kw = {"max_batch": cfg.serve_max_batch,
              "max_delay_ms": cfg.serve_max_delay_ms,
              "bucket_min": cfg.serve_bucket_min,
              "donate": cfg.serve_donate,
              "batch_event_every": cfg.serve_batch_event_every,
              "queue_limit": cfg.serve_queue_limit,
              "request_deadline_ms": cfg.serve_request_deadline_ms,
              "request_event_every": cfg.serve_request_event_every,
              "slo_p99_ms": cfg.serve_slo_p99_ms,
              "slo_qps": cfg.serve_slo_qps,
              "slo_window_s": cfg.serve_slo_window_s,
              "slo_every_s": cfg.serve_slo_every_s,
              # burn-rate alerts follow the training health channel's
              # consequence mode; obs_health=off still WARNS (an SLO
              # breach must never be silent once targets are set)
              "slo_mode": (cfg.obs_health if cfg.obs_health != "off"
                           else "warn"),
              "drift_every": cfg.obs_drift_every,
              "drift_window": cfg.obs_drift_window,
              "drift_psi": cfg.obs_drift_psi,
              "drift_topk": cfg.obs_drift_topk,
              "drift_min_labels": cfg.obs_drift_min_labels,
              "observer": self._gbdt._obs}
        kw.update(overrides)
        # live telemetry plane (obs/live.py): a serving process exposes
        # the same /metrics /healthz /statusz /events endpoints a
        # training run does — the SLO headline and queue depth ride in
        # through the observer's flight-provider registry
        obs = kw.get("observer")
        http_port = int(getattr(cfg, "obs_http_port", -1))
        if http_port >= 0 and obs is not None and obs.enabled:
            obs.ensure_live_server(
                http_port, str(getattr(cfg, "obs_http_addr", "127.0.0.1")
                               or "127.0.0.1"))
        return ServingPredictor(self._gbdt, num_iteration=num_iteration,
                                **kw)

    # ------------------------------------------------------------ model I/O
    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        """Write the model text file (loadable by the reference too)."""
        self._gbdt.save_model_to_file(filename, num_iteration)
        with open(filename, "a") as f:
            f.write("\npandas_categorical:%s\n"
                    % _dump_pandas_categorical(self.pandas_categorical))
        return self

    def model_to_string(self, num_iteration: int = -1) -> str:
        """Model in the reference-compatible text format."""
        return (self._gbdt.save_model_to_string(num_iteration)
                + "\npandas_categorical:%s\n"
                % _dump_pandas_categorical(self.pandas_categorical))

    def dump_model(self, num_iteration: int = -1) -> dict:
        """Model as a JSON-compatible dict."""
        import json
        return json.loads(self._gbdt.dump_model(num_iteration))

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Per-feature importance: 'split' counts or total 'gain'."""
        return self._gbdt.feature_importance(importance_type)

    def importance_history(self, importance_type: str = "split") -> list:
        """Importance trajectory from the telemetry timeline — the
        ``importance`` events written at the ``obs_importance_every``
        cadence, as ``[{"it", "importance": {feature_index: value}}]``.
        Empty when importance tracking was off for this run."""
        from .obs.model import importance_history as _history
        return _history(self.telemetry(), importance_type)

    def feature_name(self) -> List[str]:
        """Feature names of the training data."""
        return list(self._gbdt.feature_names)

    def num_feature(self) -> int:
        """Number of features the model was trained on."""
        return self._gbdt.max_feature_idx + 1

    def num_trees(self) -> int:
        """Total number of trees across all iterations."""
        return len(self._gbdt.models)

    # pickling support: serialize through the text model format
    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score,
                 "attr": dict(getattr(self, "_attr", {})),
                 "train_data_name": getattr(self, "_train_data_name",
                                            "training")}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._attr = dict(state.get("attr", {}))
        self._train_data_name = state.get("train_data_name", "training")
        self._train_set = None
        self._valid_sets = []
        self.name_valid_sets = []
        self._load_from_string(state["model_str"])

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(params=dict(self.params),
                       model_str=self.model_to_string())
