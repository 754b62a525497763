"""Training dataset: binned column store + metadata (host side).

Parity target: src/io/dataset.cpp + src/io/dataset_loader.cpp.  Differences
by design (TPU-first): the binned matrix is a dense row-major
``(num_data, num_used_features)`` uint8/uint16 array destined for device HBM
(row-sharded under data-parallel training) instead of per-group Bin objects —
the moral equivalent of the GPU learner's Feature4 packing
(gpu_tree_learner.cpp:234-353) without the dword gymnastics.  EFB bundling is
not needed for correctness (a bundle is a perf optimization) and is tracked as
a later optimization.

Reference flow mirrored here (dataset_loader.cpp:159-216,661-840):
sample rows -> per-feature BinMapper.find_bin -> drop trivial features ->
bin all rows -> metadata check.
"""
from __future__ import annotations

import json
import os
import time as _time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils.config import Config
from ..utils.log import Log
from ..utils.random import Random
from .binning import BinMapper, CATEGORICAL, NUMERICAL
from .bundle import (BundleLayout, bin_rows_grouped, build_layout,
                     find_feature_groups)
from .metadata import Metadata
from . import parser as _parser


class TrainingData:
    """The constructed dataset the tree learner consumes.

    Naming note: the Python-facing ``Dataset`` wrapper lives in basic.py; this
    class corresponds to the C++ ``Dataset`` (include/LightGBM/dataset.h:280).
    """

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        # per total-feature BinMapper (None for ignored)
        self.bin_mappers: List[Optional[BinMapper]] = []
        # inner (used) feature -> real feature index
        self.used_feature_idx: List[int] = []
        # real -> inner (-1 if unused), used_feature_map_ in the reference
        self.real_to_inner: Dict[int, int] = {}
        # mmap-backed shard reader (io/binned_format.py) when the dataset
        # came from / was streamed to the pre-binned on-disk format; the
        # `binned` property materializes from it only on demand so paged
        # device uploads never build the full host matrix
        self._binned_reader = None
        self._binned: Optional[np.ndarray] = None     # (N, F_used)
        self.metadata: Metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_bin: int = 255
        # learner-facing per-inner-feature arrays
        self.num_bin_arr: Optional[np.ndarray] = None
        self.default_bin_arr: Optional[np.ndarray] = None
        self.is_categorical_arr: Optional[np.ndarray] = None
        self.raw_data: Optional[np.ndarray] = None    # kept for valid alignment
        # EFB layout (io/bundle.py); None = binned is per-feature raw bins
        self.bundle: Optional[BundleLayout] = None
        # data-quality profile of the binning sample (obs/dataquality.py);
        # None when binning was copied/loaded rather than fitted here
        self._data_profile: Optional[dict] = None
        # per-feature drift fingerprint of the binning sample
        # (obs/drift.py feature_fingerprint) — the serving-time
        # reference; completed with score/eval snapshots by the GBDT
        self._drift_fingerprint: Optional[dict] = None
        # construction-phase accounting for the `dataset_construct` obs
        # event (rows, chunks, phase seconds, peak RSS, workers)
        self._construct_stats: Optional[dict] = None
        self._comm = None

    @property
    def binned(self) -> Optional[np.ndarray]:
        if self._binned is None and self._binned_reader is not None:
            r = self._binned_reader
            lo, hi = r.row_range
            if (lo, hi) == (0, r.num_data):
                self._binned = r.matrix()
            else:
                # rank-sharded open: materialize ONLY this rank's rows,
                # mapping only the shards that overlap them
                self._binned = np.ascontiguousarray(r.rows(lo, hi))
        return self._binned

    @binned.setter
    def binned(self, value) -> None:
        self._binned = value

    def _note_construct_stats(self, source: str, rows: int, chunks: int,
                              sketch_s: float, bin_s: float, write_s: float,
                              workers: int, rss_before: int,
                              **extra) -> None:
        from .streaming import _peak_rss_bytes
        peak = _peak_rss_bytes()
        self._construct_stats = {
            "source": source,
            "rows": int(rows),
            "chunks": int(chunks),
            "sketch_s": round(float(sketch_s), 6),
            "bin_s": round(float(bin_s), 6),
            "write_s": round(float(write_s), 6),
            "construct_s": round(float(sketch_s + bin_s + write_s), 6),
            "peak_rss_bytes": int(peak),
            "rss_growth_bytes": max(int(peak) - int(rss_before), 0),
            "workers": int(workers),
        }
        self._construct_stats.update(extra)

    # ------------------------------------------------------------- construct
    @classmethod
    def from_matrix(cls, data: np.ndarray, label=None, config: Optional[Config] = None,
                    weights=None, group=None, init_score=None,
                    categorical_feature: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["TrainingData"] = None,
                    keep_raw: bool = False, comm=None) -> "TrainingData":
        """comm: optional parallel.comm.HostComm for multi-host loading —
        `data` is then this rank's pre-partitioned row shard and bin
        mappers are constructed distributed (feature-sharded + allgather,
        dataset_loader.cpp:733-833)."""
        config = config or Config()
        data = np.ascontiguousarray(np.asarray(data, dtype=np.float64))
        if data.ndim != 2:
            Log.fatal("Data must be 2-dimensional")
        self = cls()
        self.num_data, self.num_total_features = data.shape
        self.max_bin = config.max_bin
        self.feature_names = list(feature_names) if feature_names else [
            "Column_%d" % i for i in range(self.num_total_features)]

        cats = set(int(c) for c in categorical_feature)
        # remember the comm: the Booster shards its observer's timeline
        # per rank (obs/events.py) off the training data's comm
        self._comm = comm if (comm is not None and comm.size > 1) else None
        from .streaming import _peak_rss_bytes
        rss0 = _peak_rss_bytes()
        t0 = _time.time()
        sketch_s = 0.0
        if reference is not None:
            self._align_with(reference, data)
        elif comm is not None and comm.size > 1:
            # ranks must agree on RNG-bearing params BEFORE any sampling
            # (GlobalSyncUpByMin, application.cpp:118-199) — automatic
            # here, like the reference's Application init
            from ..parallel.comm import sync_config_across_ranks
            sync_config_across_ranks(comm, config)
            self._construct_mappers_distributed(data, config, cats, comm)
            sketch_s = _time.time() - t0
            self._bin_data(data)
        else:
            self._construct_mappers(data, config, cats)
            sketch_s = _time.time() - t0
            self._bin_data(data)
        self._note_construct_stats("matrix", rows=self.num_data, chunks=1,
                                   sketch_s=sketch_s,
                                   bin_s=_time.time() - t0 - sketch_s,
                                   write_s=0.0, workers=1, rss_before=rss0)
        if keep_raw:
            self.raw_data = data
        if label is not None:
            self.metadata.set_label(label)
        else:
            self.metadata.num_data = self.num_data
        if weights is not None:
            self.metadata.set_weights(weights)
        if group is not None:
            self.metadata.set_query_counts(group)
        if init_score is not None:
            self.metadata.set_init_score(init_score)
        return self

    @classmethod
    def from_csc(cls, sp, label=None, config: Optional[Config] = None,
                 weights=None, group=None, init_score=None,
                 categorical_feature: Sequence[int] = (),
                 feature_names: Optional[List[str]] = None,
                 reference: Optional["TrainingData"] = None) -> "TrainingData":
        """Sparse ingestion without densification (SparseBin analog,
        sparse_bin.hpp:68 + dataset_loader.cpp:840-930).

        sp: io.sparse.SparseColumns.  Bin mappers are constructed from
        per-column NONZERO samples (zeros are implicit in find_bin's total
        count, exactly as the dense path drops them), and binned columns
        are written as a default-bin fill plus a nonzero scatter.  Peak
        host memory is O(nnz + N*F_used bin bytes) — the N x F float64
        matrix never exists.
        """
        config = config or Config()
        self = cls()
        n = sp.num_row
        self.num_data = n
        self.num_total_features = sp.num_col
        self.max_bin = config.max_bin
        self.feature_names = list(feature_names) if feature_names else [
            "Column_%d" % i for i in range(sp.num_col)]
        cats = set(int(c) for c in categorical_feature)
        from .streaming import _peak_rss_bytes
        rss0 = _peak_rss_bytes()
        t0 = _time.time()

        if reference is not None:
            if sp.num_col != reference.num_total_features:
                Log.fatal("Validation data has %d features, train data "
                          "has %d", sp.num_col,
                          reference.num_total_features)
            self._copy_binning_from(reference)
        else:
            sample_cnt = min(config.bin_construct_sample_cnt, n)
            rng = Random(config.data_random_seed)
            sample_idx = rng.sample(n, sample_cnt)
            if len(sample_idx) == 0:
                sample_idx = np.arange(n, dtype=np.int32)
            total_sample = len(sample_idx)
            # row -> sample position (or -1), so each column's sampled
            # nonzeros come from one O(col_nnz) lookup
            sample_pos = np.full(n, -1, dtype=np.int64)
            sample_pos[np.asarray(sample_idx, dtype=np.int64)] = \
                np.arange(total_sample)
            filter_cnt = int(config.min_data_in_leaf * total_sample
                             / max(n, 1))

            self.bin_mappers = []
            col_sample_cache = []
            for f in range(sp.num_col):
                rows, vals = sp.column(f)
                pos = sample_pos[rows]
                sel = pos >= 0
                sv, spos = vals[sel], pos[sel]
                # the cache keeps NaN entries: the dense EFB sample bins
                # them to the last bin via value_to_bin, and the sparse
                # sample must agree; only find_bin drops them (the dense
                # mapper-construction path does the same)
                col_sample_cache.append((spos, sv))
                fb = sv[~np.isnan(sv)]
                m = BinMapper()
                bin_type = CATEGORICAL if f in cats else NUMERICAL
                m.find_bin(fb[fb != 0.0], total_sample, config.max_bin,
                           config.min_data_in_bin, filter_cnt, bin_type)
                self.bin_mappers.append(m)
            # the row->sample map is O(N) int64 — drop it before the
            # (N, G) binned product allocates (RSS watermark audit)
            del sample_pos

            self.used_feature_idx = [
                i for i, m in enumerate(self.bin_mappers)
                if m is not None and not m.is_trivial]
            if not self.used_feature_idx:
                Log.warning("There are no meaningful features, as all "
                            "feature values are constant.")
            self.real_to_inner = {r: i for i, r in
                                  enumerate(self.used_feature_idx)}
            self._build_feature_arrays()

            def col_from_cache(f):
                # sampled column densified: implicit zeros + nonzero
                # scatter (NaN entries preserved by the cache)
                spos, sv = col_sample_cache[f]
                col = np.zeros(total_sample, dtype=np.float64)
                if len(spos):
                    col[spos] = sv
                return col
            self._profile_quality(col_from_cache, total_sample, cats,
                                  config)

            # EFB on the binning sample, rebuilt sparsely (dense path:
            # Dataset::Construct, dataset.cpp:229-235)
            if (config.enable_bundle and len(self.used_feature_idx) > 1
                    and config.tree_learner not in ("feature",
                                                    "feature_parallel")):
                # uint16 is enough for bin ids (max_bin caps below 65536)
                # and keeps the (S, F) sample ~8x smaller than int64 —
                # at Bosch shape (200k x 968) that is 0.39 GB vs 1.55 GB
                binned_sample = np.empty(
                    (total_sample, len(self.used_feature_idx)), np.uint16)
                for i, r in enumerate(self.used_feature_idx):
                    mapper = self.bin_mappers[r]
                    col = np.full(total_sample,
                                  self.default_bin_arr[i], np.uint16)
                    spos, sv = col_sample_cache[r]
                    if len(spos):
                        col[spos] = mapper.value_to_bin(sv)
                    binned_sample[:, i] = col
                self.bundle = find_feature_groups(
                    binned_sample, self.num_bin_arr, self.default_bin_arr,
                    config.max_conflict_rate, config.min_data_in_leaf,
                    self.num_data)
                del binned_sample   # before the (N, G) product allocates
                if self.bundle is not None:
                    Log.info("EFB bundled %d features into %d groups",
                             len(self.used_feature_idx),
                             self.bundle.num_groups)
            del col_sample_cache

        sketch_s = _time.time() - t0
        self._bin_sparse(sp)
        self._note_construct_stats("csc", rows=n, chunks=1,
                                   sketch_s=sketch_s,
                                   bin_s=_time.time() - t0 - sketch_s,
                                   write_s=0.0, workers=1, rss_before=rss0)
        if label is not None:
            self.metadata.set_label(label)
        else:
            self.metadata.num_data = n
        if weights is not None:
            self.metadata.set_weights(weights)
        if group is not None:
            self.metadata.set_query_counts(group)
        if init_score is not None:
            self.metadata.set_init_score(init_score)
        return self

    def _bin_sparse(self, sp) -> None:
        """Binned matrix from CSC columns: default-bin fill + nonzero
        scatter per column (never a dense float64 intermediate)."""
        n = sp.num_row
        f_used = len(self.used_feature_idx)

        def dense_binned_col(i):
            r = self.used_feature_idx[i]
            mapper = self.bin_mappers[r]
            rows, vals = sp.column(r)
            col = np.full(n, mapper.value_to_bin(0.0), dtype=np.int64)
            if len(rows):
                col[rows] = mapper.value_to_bin(vals)
            return col

        if self.bundle is not None:
            self.binned = bin_rows_grouped(dense_binned_col, self.bundle,
                                           self.default_bin_arr)
            return
        max_num_bin = int(self.num_bin_arr.max()) if f_used else 2
        dtype = np.uint8 if max_num_bin <= 256 else np.uint16
        out = np.empty((n, f_used), dtype=dtype)
        for i in range(f_used):
            out[:, i] = dense_binned_col(i).astype(dtype)
        self.binned = out

    @classmethod
    def from_file(cls, filename: str, config: Optional[Config] = None,
                  reference: Optional["TrainingData"] = None,
                  keep_raw: bool = False) -> "TrainingData":
        """CLI/file path (dataset_loader.cpp:159-216): parse, side files,
        label column handling."""
        config = config or Config()
        if cls.can_load_binned(filename):
            # pre-binned directory: construction cost was already paid
            return cls.from_binned(filename)
        label_idx = 0
        header_names: Optional[List[str]] = None
        if config.has_header:
            header_names = _parser.read_header(filename)
        if config.label_column:
            lc = config.label_column
            if lc.startswith("name:"):
                name = lc[5:]
                if not header_names or name not in header_names:
                    Log.fatal("Could not find label column %s in data file", name)
                label_idx = header_names.index(name)
            else:
                label_idx = int(lc)
        feature_names = None
        if header_names:
            feature_names = [n for i, n in enumerate(header_names) if i != label_idx]
        categorical = _resolve_columns(config.categorical_column, feature_names)
        ignore = _resolve_columns(config.ignore_column, feature_names)

        # streaming two-round loading (dataset_loader.cpp:554-660): pick it
        # when asked for, or automatically for big dense files — the
        # in-memory parser would otherwise materialize the whole text plus
        # an N x F float64 matrix
        from . import streaming as _streaming
        file_bytes = 0
        try:
            file_bytes = os.path.getsize(filename)
        except OSError:
            pass
        out_dir = (str(config.ooc_binned_dir)
                   if getattr(config, "ooc_binned_dir", "")
                   and reference is None else None)
        want_stream = (config.use_two_round_loading or bool(out_dir)
                       or file_bytes > (256 << 20)) and not keep_raw
        if want_stream and _streaming.stream_supported(filename,
                                                       config.has_header):
            self = cls()
            self.feature_names = feature_names or []
            keep = None
            if ignore:
                # column count from the first data lines only (O(1) memory
                # — the whole point of the streaming path)
                with open(filename, "r") as fh:
                    if config.has_header:
                        fh.readline()
                    head = [fh.readline() for _ in range(2)]
                probe = _parser.parse_text(
                    "".join(head), has_header=False, label_idx=label_idx)
                keep = [i for i in range(probe.features.shape[1])
                        if i not in ignore]
                if feature_names:
                    self.feature_names = [feature_names[i] for i in keep]
                categorical = {keep.index(c) for c in categorical
                               if c in keep}
            _streaming.stream_load(self, filename, config, label_idx,
                                   categorical, keep, reference=reference,
                                   out_dir=out_dir)
            if not self.feature_names:
                self.feature_names = ["Column_%d" % i
                                      for i in range(self.num_total_features)]
            self.metadata.init_from_file(filename)
            if out_dir:
                # side files (.weight/.query/.init) load after streaming,
                # so refresh the persisted metadata sidecars
                from . import binned_format as _bf
                _bf.update_metadata(out_dir, self.metadata)
            return self

        parsed = _parser.parse_file(filename, has_header=config.has_header,
                                    label_idx=label_idx)
        data = parsed.features
        if ignore:
            keep = [i for i in range(data.shape[1]) if i not in ignore]
            data = data[:, keep]
            if feature_names:
                feature_names = [feature_names[i] for i in keep]
            categorical = {keep.index(c) for c in categorical if c in keep}
        self = cls.from_matrix(data, label=parsed.label, config=config,
                               categorical_feature=sorted(categorical),
                               feature_names=feature_names,
                               reference=reference, keep_raw=keep_raw)
        self.metadata.init_from_file(filename)
        return self

    def _construct_mappers(self, data: np.ndarray, config: Config,
                           categorical: set) -> None:
        n = self.num_data
        sample_cnt = min(config.bin_construct_sample_cnt, n)
        rng = Random(config.data_random_seed)
        sample_idx = rng.sample(n, sample_cnt)
        if len(sample_idx) == 0:
            sample_idx = np.arange(n, dtype=np.int32)
        sample = data[sample_idx]
        self._fit_mappers_from_sample(sample, config, categorical)

    def _fit_mappers_from_sample(self, sample: np.ndarray, config: Config,
                                 categorical: set) -> None:
        """BinMapper construction from an already-drawn row sample (the
        shared tail of one-round and streaming two-round loading)."""
        n = self.num_data
        total_sample = len(sample)
        # filter_cnt formula from dataset_loader.cpp:491-492
        filter_cnt = int(config.min_data_in_leaf * total_sample / max(n, 1))

        self.bin_mappers = []
        for f in range(self.num_total_features):
            col = sample[:, f]
            col = col[~np.isnan(col)]
            nonzero = col[col != 0.0]
            m = BinMapper()
            bin_type = CATEGORICAL if f in categorical else NUMERICAL
            m.find_bin(nonzero, total_sample, config.max_bin,
                       config.min_data_in_bin, filter_cnt, bin_type)
            self.bin_mappers.append(m)

        self.used_feature_idx = [i for i, m in enumerate(self.bin_mappers)
                                 if m is not None and not m.is_trivial]
        if not self.used_feature_idx:
            Log.warning("There are no meaningful features, as all feature values are constant.")
        self.real_to_inner = {r: i for i, r in enumerate(self.used_feature_idx)}
        self._build_feature_arrays()
        self._profile_quality(lambda f: sample[:, f], total_sample,
                              categorical, config)

        # EFB on the binning sample (Dataset::Construct, dataset.cpp:229-235)
        if (config.enable_bundle and len(self.used_feature_idx) > 1
                and config.tree_learner not in ("feature",
                                                "feature_parallel")):
            binned_sample = np.stack(
                [self.bin_mappers[r].value_to_bin(sample[:, r])
                 .astype(np.uint16) for r in self.used_feature_idx], axis=1)
            self.bundle = find_feature_groups(
                binned_sample, self.num_bin_arr, self.default_bin_arr,
                config.max_conflict_rate, config.min_data_in_leaf,
                self.num_data)
            # drop the (S, F) sample bins before the (N, G) product
            # allocates (retained-intermediate RSS audit, BENCH_NOTES.md)
            del binned_sample
            if self.bundle is not None:
                Log.info("EFB bundled %d features into %d groups",
                         len(self.used_feature_idx), self.bundle.num_groups)

    def _construct_mappers_distributed(self, data: np.ndarray, config: Config,
                                       categorical: set, comm) -> None:
        """Distributed bin finding (dataset_loader.cpp:733-833): features
        partitioned evenly across ranks; each rank finds bins for its
        feature block from its LOCAL row shard's sample; serialized mappers
        are allgathered so every rank holds the identical full set.
        """
        F = self.num_total_features
        n_local = data.shape[0]
        local_counts = comm.allgather_obj(int(n_local))
        total_n = int(sum(local_counts))

        sample_cnt = min(config.bin_construct_sample_cnt, n_local)
        rng = Random(config.data_random_seed)
        sample_idx = rng.sample(n_local, sample_cnt)
        if len(sample_idx) == 0:
            sample_idx = np.arange(n_local, dtype=np.int32)
        sample = data[sample_idx]
        total_sample = len(sample_idx)
        # filter_cnt against the GLOBAL row count (dataset_loader.cpp:491)
        filter_cnt = int(config.min_data_in_leaf * total_sample
                         / max(total_n, 1))

        # even feature partition, same formula on every rank
        # (dataset_loader.cpp:741-767)
        bounds = np.linspace(0, F, comm.size + 1).astype(int)
        start, end = int(bounds[comm.rank]), int(bounds[comm.rank + 1])
        my_mappers = []
        for f in range(start, end):
            col = sample[:, f]
            col = col[~np.isnan(col)]
            nonzero = col[col != 0.0]
            m = BinMapper()
            bin_type = CATEGORICAL if f in categorical else NUMERICAL
            m.find_bin(nonzero, total_sample, config.max_bin,
                       config.min_data_in_bin, filter_cnt, bin_type)
            my_mappers.append(m.to_dict())

        gathered = comm.allgather_obj(my_mappers)
        self.bin_mappers = [BinMapper.from_dict(d)
                            for rank_list in gathered for d in rank_list]
        assert len(self.bin_mappers) == F
        self.used_feature_idx = [i for i, m in enumerate(self.bin_mappers)
                                 if m is not None and not m.is_trivial]
        if not self.used_feature_idx:
            Log.warning("There are no meaningful features, as all feature "
                        "values are constant.")
        self.real_to_inner = {r: i for i, r in enumerate(self.used_feature_idx)}
        self._build_feature_arrays()
        # rank-local sample: the profile reflects this rank's row shard
        self._profile_quality(lambda f: sample[:, f], total_sample,
                              categorical, config)

        # EFB under distribution: every rank MUST end with the identical
        # group structure (histogram psums assume one layout), so rank 0
        # decides from its sample and the groups are broadcast — the
        # allgather doubles as the broadcast.
        if (config.enable_bundle and len(self.used_feature_idx) > 1
                and config.tree_learner not in ("feature",
                                                "feature_parallel")):
            groups = None
            if comm.rank == 0:
                binned_sample = np.stack(
                    [self.bin_mappers[r].value_to_bin(sample[:, r])
                     .astype(np.uint16) for r in self.used_feature_idx],
                    axis=1)
                layout = find_feature_groups(
                    binned_sample, self.num_bin_arr, self.default_bin_arr,
                    config.max_conflict_rate, config.min_data_in_leaf,
                    total_n)
                del binned_sample
                if layout is not None:
                    groups = [list(map(int, g)) for g in layout.groups]
            groups = comm.allgather_obj(groups)[0]
            if groups is not None:
                self.bundle = build_layout(groups, self.num_bin_arr,
                                           self.default_bin_arr)
                if comm.rank == 0:
                    Log.info("EFB bundled %d features into %d groups",
                             len(self.used_feature_idx),
                             self.bundle.num_groups)

    def _copy_binning_from(self, reference: "TrainingData") -> None:
        """Share the train set's binning state (mappers, used features,
        per-feature arrays, EFB layout) — dataset_loader.cpp:220-261."""
        self.bin_mappers = reference.bin_mappers
        self.used_feature_idx = list(reference.used_feature_idx)
        self.real_to_inner = dict(reference.real_to_inner)
        self.num_bin_arr = reference.num_bin_arr
        self.default_bin_arr = reference.default_bin_arr
        self.is_categorical_arr = reference.is_categorical_arr
        self.max_bin = reference.max_bin
        self.bundle = reference.bundle

    def _align_with(self, reference: "TrainingData", data: np.ndarray) -> None:
        """Valid set shares the train set's mappers
        (dataset_loader.cpp:220-261 CreateValid path)."""
        if data.shape[1] != reference.num_total_features:
            Log.fatal("Validation data has %d features, train data has %d",
                      data.shape[1], reference.num_total_features)
        self._copy_binning_from(reference)
        self._bin_data(data)

    def _profile_quality(self, get_col, sample_size: int, categorical: set,
                         config: Config) -> None:
        """Post-binning quality pass: the single-bucket warning (always on
        — it costs one scan of the mappers) plus the data-quality profile
        the Booster emits as a ``data_profile`` obs event
        (``obs_data_profile``, default on)."""
        single = [i for i, m in enumerate(self.bin_mappers)
                  if m is not None and m.num_bin <= 1]
        if single:
            head = ",".join(str(i) for i in single[:20])
            Log.warning(
                "%d feature(s) binned into a single bucket (constant, "
                "never splittable): %s%s", len(single), head,
                ",..." if len(single) > 20 else "")
        if bool(getattr(config, "obs_drift_fingerprint", True)):
            from ..obs import drift
            self._drift_fingerprint = drift.feature_fingerprint(
                self.bin_mappers, get_col, self.num_total_features,
                sample_size, self.feature_names)
        if not bool(getattr(config, "obs_data_profile", True)):
            return
        from ..obs import dataquality
        self._data_profile = dataquality.profile_columns(
            self.bin_mappers, get_col, self.num_total_features,
            sample_size, categorical)

    def _build_feature_arrays(self) -> None:
        used = self.used_feature_idx
        self.num_bin_arr = np.asarray(
            [self.bin_mappers[r].num_bin for r in used], dtype=np.int32)
        self.default_bin_arr = np.asarray(
            [self.bin_mappers[r].default_bin for r in used], dtype=np.int32)
        self.is_categorical_arr = np.asarray(
            [self.bin_mappers[r].bin_type == CATEGORICAL for r in used], dtype=bool)

    def _bin_data(self, data: np.ndarray) -> None:
        n = data.shape[0]
        self.num_data = n
        f_used = len(self.used_feature_idx)
        if self.bundle is not None:
            getcol = lambda i: self.bin_mappers[
                self.used_feature_idx[i]].value_to_bin(
                    data[:, self.used_feature_idx[i]])
            self.binned = bin_rows_grouped(getcol, self.bundle,
                                           self.default_bin_arr)
            return
        max_num_bin = int(self.num_bin_arr.max()) if f_used else 2
        dtype = np.uint8 if max_num_bin <= 256 else np.uint16
        out = np.zeros((n, f_used), dtype=dtype)
        for i, r in enumerate(self.used_feature_idx):
            out[:, i] = self.bin_mappers[r].value_to_bin(data[:, r]).astype(dtype)
        self.binned = out

    # ------------------------------------------------------------- accessors
    @property
    def num_features(self) -> int:
        return len(self.used_feature_idx)

    def inner_feature_index(self, real_idx: int) -> int:
        return self.real_to_inner.get(real_idx, -1)

    def real_feature_index(self, inner_idx: int) -> int:
        return self.used_feature_idx[inner_idx]

    def real_threshold(self, inner_idx: int, threshold_bin: int) -> float:
        """bin threshold -> real-valued threshold (dataset.h:457-462)."""
        return self.bin_mappers[self.used_feature_idx[inner_idx]].bin_to_value(threshold_bin)

    def feature_bin_mapper(self, inner_idx: int) -> BinMapper:
        return self.bin_mappers[self.used_feature_idx[inner_idx]]

    def feature_infos(self) -> List[str]:
        """Per total-feature info string for the model file
        (dataset.h:514-526)."""
        out = []
        for i in range(self.num_total_features):
            if self.real_to_inner.get(i, -1) == -1:
                out.append("none")
            else:
                out.append(self.bin_mappers[i].bin_info())
        return out

    def subset(self, indices: np.ndarray) -> "TrainingData":
        """Bagging subset copy (dataset.cpp:399 CopySubset)."""
        out = TrainingData()
        out.num_data = len(indices)
        out.num_total_features = self.num_total_features
        out.bin_mappers = self.bin_mappers
        out.used_feature_idx = self.used_feature_idx
        out.real_to_inner = self.real_to_inner
        out.num_bin_arr = self.num_bin_arr
        out.default_bin_arr = self.default_bin_arr
        out.is_categorical_arr = self.is_categorical_arr
        out.max_bin = self.max_bin
        out.feature_names = self.feature_names
        out.bundle = self.bundle
        out.binned = self.binned[indices]
        out.metadata = self.metadata.subset(indices)
        return out

    # ------------------------------------------------------- binary file I/O
    _BINARY_MAGIC = "lightgbm_tpu.dataset.v1"

    def save_binary(self, filename: str) -> None:
        """Binary dataset file (dataset.cpp:489 SaveBinaryFile analog)."""
        meta = {
            "magic": self._BINARY_MAGIC,
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "used_feature_idx": self.used_feature_idx,
            "feature_names": self.feature_names,
            "max_bin": self.max_bin,
            "bin_mappers": [None if m is None else m.to_dict()
                            for m in self.bin_mappers],
            "bundle_groups": (None if self.bundle is None
                              else [list(map(int, g))
                                    for g in self.bundle.groups]),
        }
        arrays = {"binned": self.binned}
        if self.metadata.label is not None:
            arrays["label"] = self.metadata.label
        if self.metadata.weights is not None:
            arrays["weights"] = self.metadata.weights
        if self.metadata.query_boundaries is not None:
            arrays["query_boundaries"] = self.metadata.query_boundaries
        if self.metadata.init_score is not None:
            arrays["init_score"] = self.metadata.init_score
        # write through a handle: np.savez_compressed(<str>) appends
        # ".npz" to alien extensions, breaking the reference's
        # save-to-any-name contract (e.g. "train.bin")
        with open(filename, "wb") as f:
            np.savez_compressed(f, meta=json.dumps(meta), **arrays)

    @classmethod
    def can_load_binary(cls, filename: str) -> bool:
        try:
            with np.load(filename, allow_pickle=False) as z:
                meta = json.loads(str(z["meta"]))
            return meta.get("magic") == cls._BINARY_MAGIC
        except Exception:
            return False

    @classmethod
    def load_binary(cls, filename: str) -> "TrainingData":
        with np.load(filename, allow_pickle=False) as z:
            meta = json.loads(str(z["meta"]))
            if meta.get("magic") != cls._BINARY_MAGIC:
                Log.fatal("Not a lightgbm_tpu binary dataset file: %s", filename)
            self = cls()
            self.num_data = meta["num_data"]
            self.num_total_features = meta["num_total_features"]
            self.used_feature_idx = list(meta["used_feature_idx"])
            self.real_to_inner = {r: i for i, r in enumerate(self.used_feature_idx)}
            self.feature_names = meta["feature_names"]
            self.max_bin = meta["max_bin"]
            self.bin_mappers = [None if d is None else BinMapper.from_dict(d)
                                for d in meta["bin_mappers"]]
            self._build_feature_arrays()
            groups = meta.get("bundle_groups")
            if groups is not None:
                self.bundle = build_layout(groups, self.num_bin_arr,
                                           self.default_bin_arr)
            self.binned = z["binned"]
            self.metadata = Metadata(self.num_data)
            if "label" in z:
                self.metadata.label = z["label"]
            if "weights" in z:
                self.metadata.weights = z["weights"]
            if "query_boundaries" in z:
                self.metadata.query_boundaries = z["query_boundaries"]
            if "init_score" in z:
                self.metadata.init_score = z["init_score"]
        return self

    # --------------------------------------------- pre-binned mmap format
    @classmethod
    def from_streamed(cls, data, label=None, config: Optional[Config] = None,
                      weights=None, group=None, init_score=None,
                      categorical_feature: Sequence[int] = (),
                      feature_names: Optional[List[str]] = None,
                      reference: Optional["TrainingData"] = None,
                      out_dir: Optional[str] = None,
                      chunk_rows: Optional[int] = None) -> "TrainingData":
        """Out-of-core construction from an in-memory matrix, a ``.npy``
        path, or SparseColumns — the two-pass parallel pipeline of
        io/streaming.py (text files go through from_file, which streams
        automatically).  out_dir persists the result as a binned dataset
        directory and keeps td mmap-backed."""
        from . import streaming as _streaming
        config = config or Config()
        chunk = int(chunk_rows or config.ooc_chunk_rows
                    or _streaming.DEFAULT_CHUNK_ROWS)
        if hasattr(data, "colptr"):          # SparseColumns
            source = _streaming.SparseSource(data, label=label,
                                             chunk_rows=chunk)
        else:
            source = _streaming.MatrixSource(data, label=label,
                                             chunk_rows=chunk)
        self = cls()
        self.feature_names = list(feature_names) if feature_names else []
        cats = set(int(c) for c in categorical_feature)
        _streaming.stream_construct(self, source, config, categorical=cats,
                                    reference=reference, out_dir=out_dir)
        if not self.feature_names:
            self.feature_names = ["Column_%d" % i
                                  for i in range(self.num_total_features)]
        if weights is not None:
            self.metadata.set_weights(weights)
        if group is not None:
            self.metadata.set_query_counts(group)
        if init_score is not None:
            self.metadata.set_init_score(init_score)
        if out_dir and (weights is not None or group is not None
                        or init_score is not None):
            from . import binned_format as _bf
            _bf.update_metadata(out_dir, self.metadata)
        return self

    def save_binned(self, path: str) -> None:
        """Persist as the mmap-able pre-binned directory format
        (io/binned_format.py) so later runs skip construction entirely."""
        from . import binned_format as _bf
        _bf.save_training_data(self, path)

    @classmethod
    def can_load_binned(cls, path) -> bool:
        from . import binned_format as _bf
        return _bf.is_binned_dir(path)

    @classmethod
    def from_binned(cls, path: str, verify=None, comm=None,
                    row_range=None) -> "TrainingData":
        """Open a pre-binned dataset directory: shards stay mmap-backed
        (no bin matrix materialized until something asks for it; the
        learner pages shards straight to the device).

        ``comm``: optional parallel.comm.HostComm for multi-host sharded
        ingest — each rank opens only its balanced row-range of the
        shard table (``row_range`` overrides the balance), so peak
        per-host RSS stays O(rank rows).  Bin mappers come verbatim from
        the shared header, so every rank freezes bit-identical binning
        with zero collective rounds.

        ``verify``: ``None`` picks the right default — a full CRC scan
        for whole-dataset opens (the original ``verify=True`` contract),
        lazy per-mapped-shard CRCs for rank-sharded opens (a rank
        reading 1/64th of the rows must not stream the other 63/64ths).
        Pass ``True``/``"lazy"``/``False`` to force a mode."""
        from . import binned_format as _bf
        from .streaming import _peak_rss_bytes
        rss0 = _peak_rss_bytes()
        t0 = _time.time()
        sharded = (comm is not None and comm.size > 1) \
            or row_range is not None
        if verify is None:
            verify = "lazy" if sharded else True
        if comm is not None and comm.size > 1 and row_range is None:
            total = int(_bf._read_header(str(path))["num_data"])
            row_range = (comm.rank * total // comm.size,
                         (comm.rank + 1) * total // comm.size)
        reader = _bf.BinnedReader(path, verify=verify, row_range=row_range)
        h = reader.header
        self = cls()
        lo, hi = reader.row_range
        self.num_data = hi - lo
        self.num_total_features = int(h["num_total_features"])
        self.used_feature_idx = list(h["used_feature_idx"])
        self.real_to_inner = {r: i for i, r in
                              enumerate(self.used_feature_idx)}
        self.feature_names = list(h["feature_names"])
        self.max_bin = int(h["max_bin"])
        self.bin_mappers = [None if d is None else BinMapper.from_dict(d)
                            for d in h["bin_mappers"]]
        self._drift_fingerprint = h.get("drift_fingerprint")
        self._build_feature_arrays()
        groups = h.get("bundle_groups")
        if groups is not None:
            from ..obs import timers
            with timers.span("bundle_layout", groups=len(groups)):
                self.bundle = build_layout(groups, self.num_bin_arr,
                                           self.default_bin_arr)
        self._binned_reader = reader
        self._comm = comm if (comm is not None and comm.size > 1) else None
        self.metadata = Metadata(self.num_data)

        def _local(arr):
            """This rank's row slice of a per-row sidecar, copied out of
            the memmap so resident bytes stay O(rank rows)."""
            if arr is None or not sharded:
                return arr
            if arr.shape[0] == hi - lo:     # already rank-local
                return np.asarray(arr)
            return np.array(arr[lo:hi])

        label = reader.load_metadata_array("label", mmap=sharded)
        if label is not None:
            self.metadata.label = _local(label)
        self.metadata.weights = _local(
            reader.load_metadata_array("weights", mmap=sharded))
        qb = reader.load_metadata_array("query_boundaries")
        if qb is not None and sharded:
            # query groups straddle row-range cuts; pre-partition ranking
            # data per rank instead (the reference's pre_partition path)
            Log.fatal("rank-sharded from_binned does not support ranking "
                      "(query_boundaries) datasets — pre-partition them "
                      "per rank")
        self.metadata.query_boundaries = qb
        self.metadata.init_score = _local(
            reader.load_metadata_array("init_score", mmap=sharded))
        # sketch_s and bin_s stay 0: opening the format does ZERO
        # re-binning work (the CI ooc-smoke gate asserts exactly this)
        extra = {"load_s": round(_time.time() - t0, 6)}
        if sharded:
            extra["row_range"] = [int(lo), int(hi)]
            extra["world_size"] = int(comm.size) if comm is not None else 1
        self._note_construct_stats("binned", rows=self.num_data,
                                   chunks=reader.num_shards, sketch_s=0.0,
                                   bin_s=0.0, write_s=0.0, workers=1,
                                   rss_before=rss0, **extra)
        return self


def _resolve_columns(spec: str, feature_names: Optional[List[str]]) -> set:
    """Parse 'name:a,b,c' or '0,1,2' column specs (dataset_loader.cpp:22-120
    SetHeader column-role resolution)."""
    out: set = set()
    if not spec:
        return out
    if spec.startswith("name:"):
        names = spec[5:].split(",")
        if feature_names:
            for nm in names:
                if nm in feature_names:
                    out.add(feature_names.index(nm))
                else:
                    Log.warning("Could not find column %s in data file", nm)
    else:
        for tok in spec.split(","):
            tok = tok.strip()
            if tok:
                out.add(int(tok))
    return out
