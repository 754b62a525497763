"""AOT-compiled predict executables for the serving tier.

``Booster.predict`` goes through ``jax.jit``: every call pays Python
dispatch, signature hashing, and — on a novel batch shape — a full XLA
compile.  A serving process cannot afford any of that on the hot path.
``PredictExecutableCache`` therefore compiles each predict program ONCE,
ahead of time, and steady-state scoring calls the compiled executable
directly:

* programs are keyed by ``(batch bucket, num_used trees, k, convert)``;
  request rows round up to a power-of-two bucket between
  ``serve_bucket_min`` and ``serve_max_batch``, bounding the cache at
  ``log2(max_batch / bucket_min) + 1`` programs per route;
* the encoded inputs (the int32 rank matrix + zero-range mask from
  ops/predict.py) are DONATED to the executable on accelerator
  backends — the runtime reuses their buffers for outputs instead of
  allocating per request;
* the tree stack is replicated to every local device once via
  ``NamedSharding`` (the GSPMD replication pattern: data parallel in
  rows, model broadcast), so multi-chip hosts score one bucket
  cooperatively with zero collectives;
* objective conversion (sigmoid / softmax) is fused into the executable
  when the objective's ``convert_output`` is one of the closed forms, so
  a converted prediction is still a single program;
* every compile is announced through the observer as ``compile`` +
  ``compile_attr`` events with a per-bucket entry name
  (``serve_predict_b<bucket>[_conv]``) — each entry compiles exactly
  once, which is precisely what ``obs recompiles --check`` asserts.

Leaf routing is bit-equal to the host f64 predictor (rank encoding);
values accumulate in f32 with Kahan compensation — and because every
row's arithmetic is element-wise and independent of its neighbors, a row
scores bit-identically whatever bucket it lands in.  That invariant is
what lets the microbatcher coalesce freely (tests/test_serve.py pins
it).
"""
from __future__ import annotations

import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.compile import arg_signature, parse_compiled, render_signature
from ..obs.events import NULL_OBSERVER
from ..obs.metrics import REGISTRY
from ..obs.timers import fenced_get
from ..ops import predict as dev_predict
from ..utils.config import _TRUE_SET
from ..utils.log import Log


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _fused_conversion(objective):
    """('sigmoid', scale) | ('softmax', None) | None (identity) — or the
    string 'host' when the objective's convert_output has no fusable
    closed form and must run on the host after the raw program."""
    from ..objectives import (BinaryLogloss, MulticlassOVA,
                              MulticlassSoftmax, ObjectiveFunction)
    if objective is None:
        return None
    if isinstance(objective, (BinaryLogloss, MulticlassOVA)):
        return ("sigmoid", float(objective.sigmoid))
    if isinstance(objective, MulticlassSoftmax):
        return ("softmax", None)
    if type(objective).convert_output is ObjectiveFunction.convert_output:
        return None                      # identity: converted == raw
    return "host"


class PredictExecutableCache:
    """AOT predict programs over a frozen model snapshot.

    Construction packs the GBDT's first ``num_used`` trees into the
    stacked ranked representation (raises ValueError exactly when the
    host fallback must serve instead — mixed categorical/numerical
    feature use); compiles happen lazily per bucket (or eagerly via
    ``warmup``) and are counted, so a serving loop can assert the
    steady state compiles nothing (``steady_state_compiles``).
    """

    def __init__(self, gbdt, num_iteration: int = -1, num_features=None,
                 devices=None, donate: str = "auto", bucket_min: int = 64,
                 max_batch: int = 8192, observer=None):
        gbdt._materialize()
        self.k = int(gbdt.num_tree_per_iteration)
        self.num_used = int(gbdt._used_trees(num_iteration))
        self.objective = gbdt.objective
        self._conv = _fused_conversion(gbdt.objective)
        if num_features is None:
            mf = 0
            for t in gbdt.models[:self.num_used]:
                ni = t.num_leaves - 1
                if ni > 0:
                    mf = max(mf, int(t.split_feature[:ni].max()) + 1)
            num_features = max(mf, 1)
        self.num_features = int(num_features)
        self.rp = dev_predict.build_ranked_predictor(
            gbdt.models[:self.num_used], self.k, self.num_features)
        if self.num_features < self.rp.max_feature + 1:
            raise ValueError(
                "num_features=%d but the model splits on feature %d"
                % (self.num_features, self.rp.max_feature))
        self.devices = list(devices) if devices else jax.local_devices()
        self.backend = self.devices[0].platform
        self.donate = (bool(self.backend != "cpu")
                       if str(donate).strip().lower() == "auto"
                       else str(donate).strip().lower() in _TRUE_SET)
        self.bucket_min = max(1, int(bucket_min))
        self.max_batch = max(self.bucket_min, int(max_batch))
        self.observer = observer if observer is not None else NULL_OBSERVER
        self._exe = {}                   # (bucket, convert) -> Compiled
        self._lock = threading.Lock()
        # stage decomposition of the LAST predict_batch call (encode /
        # pad / execute / convert seconds) — read by the serve worker
        # right after the call to label request trace spans.  Worker-
        # thread state, like the batch itself: concurrent predict_batch
        # callers should not share one cache instance's spans.
        self.last_spans = {}
        self.compiles = 0
        self._warm_compiles = None       # set by mark_warm()
        self._mesh_ctx = None
        if len(self.devices) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel.mesh import DATA_AXIS, make_data_mesh
            mesh = make_data_mesh(self.devices)
            repl = NamedSharding(mesh, P())
            rows = NamedSharding(mesh, P(DATA_AXIS, None))
            self._mesh_ctx = (mesh, repl, rows)
            self._dev = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, repl), self.rp.dev)
        else:
            self._dev = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, self.devices[0]), self.rp.dev)

    # ------------------------------------------------------------ buckets
    def bucket_for(self, n: int) -> int:
        """Power-of-two bucket in [bucket_min, max_batch], rounded up to
        a device-mesh multiple so rows shard evenly."""
        b = min(max(next_pow2(max(n, 1)), self.bucket_min), self.max_batch)
        ndev = len(self.devices)
        return b + (-b) % ndev

    def mark_warm(self):
        """Declare warmup over: compiles from here on are steady-state
        violations (``steady_state_compiles`` counts them)."""
        self._warm_compiles = self.compiles

    @property
    def steady_state_compiles(self) -> int:
        if self._warm_compiles is None:
            return 0
        return self.compiles - self._warm_compiles

    # ----------------------------------------------------------- compile
    def _entry_name(self, bucket: int, convert: bool) -> str:
        return "serve_predict_b%d%s" % (bucket,
                                        "_conv" if convert else "")

    def _build(self, bucket: int, convert: bool):
        k, conv = self.k, (self._conv if convert else None)
        if conv == "host":               # fuse nothing; convert after
            conv = None

        def run(dev, V, D):
            score = dev_predict._ranked_predict_impl(dev, V, D, k)
            if conv is not None:
                kind, scale = conv
                if kind == "sigmoid":
                    score = 1.0 / (1.0 + jnp.exp(-scale * score))
                else:
                    score = jax.nn.softmax(score, axis=-1)
            return score

        donate = (1, 2) if self.donate else ()
        dev_avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._dev)
        if self._mesh_ctx is not None:
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import DATA_AXIS
            mesh, repl, rows_sh = self._mesh_ctx

            def local(dev, V, D):
                score = dev_predict._ranked_predict_impl(
                    dev, V, D, k, vary_axis=DATA_AXIS)
                if conv is not None:
                    kind, scale = conv
                    if kind == "sigmoid":
                        score = 1.0 / (1.0 + jnp.exp(-scale * score))
                    else:
                        score = jax.nn.softmax(score, axis=-1)
                return score

            fn = jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(DATA_AXIS, None), P(DATA_AXIS, None)),
                out_specs=P(DATA_AXIS, None)),
                donate_argnums=donate)
            dev_avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=repl), self._dev)
            V_aval = jax.ShapeDtypeStruct((bucket, self.num_features),
                                          jnp.int32, sharding=rows_sh)
            D_aval = jax.ShapeDtypeStruct((bucket, self.num_features),
                                          jnp.bool_, sharding=rows_sh)
        else:
            fn = jax.jit(run, donate_argnums=donate)
            V_aval = jax.ShapeDtypeStruct((bucket, self.num_features),
                                          jnp.int32)
            D_aval = jax.ShapeDtypeStruct((bucket, self.num_features),
                                          jnp.bool_)
        t0 = time.perf_counter()
        compiled = fn.lower(dev_avals, V_aval, D_aval).compile()
        dt = time.perf_counter() - t0
        self.compiles += 1
        entry = self._entry_name(bucket, convert)
        REGISTRY.counter(
            "lgbm_serve_compiles_total",
            "predict executables AOT-compiled by the serving tier").inc()
        REGISTRY.histogram(
            "lgbm_serve_compile_seconds",
            "AOT lower+compile time per serving executable").observe(dt)
        obs = self.observer
        if obs.enabled:
            sig = arg_signature((dev_avals, V_aval, D_aval),
                                names=("trees", "V", "D"),
                                donate=set(donate))
            fields = {"entry": entry, "n_compiles": 1,
                      "sig": render_signature(sig), "sig_compiles": 1,
                      "diff": []}
            fields.update(parse_compiled(compiled))
            obs.event("compile", entry=entry, first_call_s=dt, fenced=True)
            obs.event("compile_attr", **fields)
        Log.debug("serve: compiled %s in %.3fs (donate=%s, devices=%d)",
                  entry, dt, self.donate, len(self.devices))
        if self._warm_compiles is not None:
            Log.warning("serve: steady-state compile of %s — warm the "
                        "bucket ladder before taking traffic", entry)
        return compiled

    def get(self, bucket: int, convert: bool = True):
        """The compiled program for one bucket (compile on first use)."""
        key = (int(bucket), bool(convert))
        exe = self._exe.get(key)
        if exe is None:
            with self._lock:
                exe = self._exe.get(key)
                if exe is None:
                    exe = self._build(*key)
                    self._exe[key] = exe
        return exe

    def warmup(self, sizes=(), convert: bool = True):
        """Pre-compile the buckets covering ``sizes`` (row counts or
        bucket values); returns the sorted bucket list compiled."""
        buckets = sorted({self.bucket_for(int(s)) for s in sizes})
        for b in buckets:
            self.get(b, convert)
        return buckets

    # ------------------------------------------------------------ execute
    def normalize(self, features) -> np.ndarray:
        """(n, num_features) f64 view of a request: 1-D rows promote to
        one row; wider matrices slice down; narrower ones that still
        cover every used feature zero-pad (unread columns)."""
        X = np.asarray(features, np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        if X.shape[1] < self.rp.max_feature + 1:
            raise ValueError(
                "request has %d features; the model uses feature index %d"
                % (X.shape[1], self.rp.max_feature))
        if X.shape[1] > self.num_features:
            X = X[:, :self.num_features]
        elif X.shape[1] < self.num_features:
            X = np.concatenate(
                [X, np.zeros((X.shape[0],
                              self.num_features - X.shape[1]))], axis=1)
        return np.ascontiguousarray(X)

    def encode(self, features):
        """Host-side rank encoding of a normalized request block."""
        return dev_predict.rank_encode(self.rp, features)

    def run_encoded(self, V, D, n: int, convert: bool = True,
                    spans=None) -> np.ndarray:
        """Score ``n`` encoded rows through the bucket executable:
        pad to the bucket, execute, slice.  Returns (n, k) f64.
        ``spans`` (a dict) accumulates the stage decomposition —
        ``pad_s`` (bucket padding), ``execute_s`` (transfers + compiled
        program), ``convert_s`` (host-side objective conversion)."""
        t0 = time.perf_counter()
        bucket = self.bucket_for(n)
        exe = self.get(bucket, convert)
        pad = bucket - n
        if pad:
            V = np.concatenate(
                [V, np.zeros((pad, V.shape[1]), V.dtype)])
            D = np.concatenate(
                [D, np.zeros((pad, D.shape[1]), D.dtype)])
        t1 = time.perf_counter()
        if self._mesh_ctx is not None:
            rows_sh = self._mesh_ctx[2]
            Vd = jax.device_put(np.ascontiguousarray(V), rows_sh)
            Dd = jax.device_put(np.ascontiguousarray(D), rows_sh)
        else:
            Vd = jax.device_put(V, self.devices[0])
            Dd = jax.device_put(D, self.devices[0])
        out = np.asarray(fenced_get(exe(self._dev, Vd, Dd))[:n],
                         np.float64)
        t2 = time.perf_counter()
        if convert and self._conv == "host":
            out = np.asarray(self.objective.convert_output(
                out if self.k > 1 else out[:, 0]), np.float64)
            out = out.reshape(n, self.k) if self.k == 1 else out
        if spans is not None:
            t3 = time.perf_counter()
            spans["pad_s"] = spans.get("pad_s", 0.0) + (t1 - t0)
            spans["execute_s"] = spans.get("execute_s", 0.0) + (t2 - t1)
            if t3 - t2 > 0:
                spans["convert_s"] = spans.get("convert_s", 0.0) \
                    + (t3 - t2)
        return out

    def predict_batch(self, features, convert: bool = True) -> np.ndarray:
        """Normalize + encode + execute, chunking requests larger than
        ``max_batch`` through the top bucket.  Returns (n, k) f64.
        Refreshes ``last_spans`` with this call's stage decomposition."""
        spans = {}
        X = self.normalize(features)
        n = X.shape[0]
        out = np.empty((n, self.k), np.float64)
        for lo in range(0, max(n, 1), self.max_batch):
            part = X[lo:lo + self.max_batch]
            if part.shape[0] == 0:
                break
            te = time.perf_counter()
            V, D = self.encode(part)
            spans["encode_s"] = spans.get("encode_s", 0.0) \
                + (time.perf_counter() - te)
            out[lo:lo + part.shape[0]] = self.run_encoded(
                V, D, part.shape[0], convert, spans=spans)
        self.last_spans = spans
        return out

    def stats(self) -> dict:
        return {
            "compiles": self.compiles,
            "steady_state_compiles": self.steady_state_compiles,
            "buckets": sorted({b for b, _ in self._exe}),
            "devices": len(self.devices),
            "donate": self.donate,
            "num_used": self.num_used,
            "k": self.k,
        }
