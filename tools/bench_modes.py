"""A/B timing of wave-growth histogram modes on the current backend.

Usage: python tools/bench_modes.py [n_rows] [mode ...]
Modes are tpu_histogram_mode values ('onehot', 'pallas', ...).
Prints s/iter + AUC per mode at the 255-leaf, 63-bin recipe.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_data(n_rows, n_features=28):
    rng = np.random.default_rng(42)
    X = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    w = rng.normal(size=n_features) * (rng.random(n_features) > 0.3)
    logit = X @ w * 0.5 + 0.5 * rng.normal(size=n_rows)
    return X, (logit > 0).astype(np.float64)


def run(X, y, mode, wave_width=32, warmup=3, measured=10,
        extra=None, train_set=None, details=False):
    """Time one engine config; X/y are ignored when a prebuilt train_set
    (e.g. loaded from a .bin dataset cache) is passed instead.  The ONE
    copy of the measurement protocol (warmup -> block -> timed loop ->
    block) — bench_suite goes through it.  details=True
    additionally returns the trained GBDT for learner introspection."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.common import enable_compilation_cache
    enable_compilation_cache()
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 1, "verbose": -1,
              "metric": "auc", "tpu_growth": "wave",
              "tpu_wave_width": wave_width, "tpu_histogram_mode": mode}
    params.update(extra or {})
    # the per-iteration times come from the obs timeline (obs_timing=iter
    # fences once per iteration, so they sum to the fenced end-to-end
    # time) unless the caller routed the events elsewhere via extra
    params.setdefault("obs_events_path",
                      "/tmp/bench_modes_obs_%d.jsonl" % os.getpid())
    params.setdefault("obs_timing", "iter")
    if train_set is None:
        train_set = lgb.Dataset(X, label=y, params=params)
    else:
        train_set.params = dict(train_set.params or {}, **params)
    bst = lgb.Booster(params=params, train_set=train_set)
    gbdt = bst._gbdt
    for _ in range(warmup):
        gbdt.train_one_iter(None, None, False)
    jax.block_until_ready(gbdt._score_dev)
    t0 = time.time()
    for _ in range(measured):
        gbdt.train_one_iter(None, None, False)
    jax.block_until_ready(gbdt._score_dev)
    dt = (time.time() - t0) / measured
    # prefer the telemetry: same instrument as bench.py's headline number
    timeline = gbdt._obs.timeline
    iter_recs = [e for e in timeline
                 if e["ev"] == "iter" and e.get("fenced")]
    if len(iter_recs) >= warmup + measured:
        dt = sum(e["time_s"] for e in iter_recs[-measured:]) / measured
    gbdt._obs.close()
    metric = gbdt.get_eval_at(0)[0]
    if details:
        return dt, metric, gbdt
    return dt, metric


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    modes = sys.argv[2:] or ["onehot", "pallas"]
    X, y = make_data(n)
    for mode in modes:
        t0 = time.time()
        dt, auc = run(X, y, mode)
        total = time.time() - t0
        print("%s: %.3f s/iter (%.2f it/s)  auc=%.4f  [wall %.0fs]"
              % (mode, dt, 1.0 / dt, auc, total), flush=True)


if __name__ == "__main__":
    main()
