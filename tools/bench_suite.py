"""Headline-benchmark suite: the reference's GPU-Performance.md shapes,
synthetic stand-ins, timed on the chip.

Shapes (docs/GPU-Performance.md:75-82; sizes scaled to this host where
noted): Higgs 10.5M x 28 dense binary; Epsilon 400k x 2000 dense binary;
MS-LTR 2.27M x 137 lambdarank; Expo-style categorical (2M x 40, 10
high-cardinality categorical columns — the categorical-direct path the
reference claims ~8x over one-hot on, README.md:31).

Each shape's BINNED dataset is cached as /tmp/suite_<name>.bin (atomic
publish) so arms sharing a dataset skip the one-core host binning.  One
measurement per child process, in turn; the parent never initializes a
JAX backend, so each child holds the chip alone, and a child that finds
no TPU fails the run.  Results are appended to tools/BENCH_SUITE.md as
they land.

Usage:  python tools/bench_suite.py [shape ...]      # default: all
        python tools/bench_suite.py --ref [shape ..] # reference-CLI arms
        python tools/bench_suite.py --child <json>   # internal
REF_LGBM points at the reference binary (default /tmp/refbuild/lightgbm).
"""
import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "tools", "BENCH_SUITE.md")

NO_TPU_RC = 4       # a TPU-arm child that found another backend

SHAPES = {
    # name: (rows, features, task-params, warmup, measured, timeout_s)
    "higgs": dict(n=10_500_000, f=28, params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1},
        warmup=3, measured=10, timeout=2700),
    "epsilon": dict(n=400_000, f=2000, params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1},
        warmup=2, measured=5, timeout=2700),
    "msltr": dict(n=2_270_000, f=137, params={
        "objective": "lambdarank", "metric": "ndcg", "ndcg_eval_at": "10",
        "num_leaves": 255, "max_bin": 63, "learning_rate": 0.1,
        "min_data_in_leaf": 1}, warmup=2, measured=5, timeout=2700,
        query_size=120),
    # Yahoo-LTR stand-in (473,134 x 700 ranking, GPU-Performance.md:80):
    # the wide-feature ranking point of the reference's six-dataset table
    "yahoo": dict(n=473_134, f=700, params={
        "objective": "lambdarank", "metric": "ndcg", "ndcg_eval_at": "1,10",
        "num_leaves": 255, "max_bin": 63, "learning_rate": 0.1,
        "min_data_in_leaf": 1}, warmup=2, measured=5, timeout=2700,
        query_size=23),
    # width arm at the WIDE shape: epsilon's in-VMEM block at the auto
    # W=32 is 2000*64*3*32*4B ~= 49 MB — inside the 64 MB gate, so auto
    # runs pallas_t W=32; this arm measures W=16 against it (wide
    # shapes pay more VMEM per wave slot, so the width economics can
    # flip vs the 28-col flagship)
    "epsilon_p16": dict(n=400_000, f=2000, cache_as="epsilon", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_t", "tpu_wave_width": 16},
        warmup=2, measured=5, timeout=2700),
    "expo_cat": dict(n=2_000_000, f=40, params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "categorical_feature": ",".join(str(i) for i in range(10))},
        warmup=2, measured=5, timeout=2700, n_cat=10, cardinality=100),
    # width arm at the FLAGSHIP shape: at 1M the W=64 arm lost to W=32
    # (fixed per-wave cost dominates); at 10.5M each sweep is a full
    # pass over 10x the rows, so halving sweeps/tree may flip the
    # economics — measure, don't extrapolate
    "higgs_w64": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_t", "tpu_wave_width": 64},
        warmup=3, measured=10, timeout=2700),
    # v5 fused kernel at the flagship shape (one Xt read per wave, no
    # partition scan) — the candidate to beat pallas_t's auto default
    "higgs_ct": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32},
        warmup=3, measured=10, timeout=2700),
    # exact-commit-order waves at the flagship (tpu_wave_order=exact):
    # trees match tpu_wave_width=1 bit-for-bit, so its AUC delta vs the
    # reference equals the EXACT arm's (+7.7e-6 at 10.5M).  This is the
    # fallback headline config if the 10.5M batched-wave parity arm
    # lands >1e-4 (VERDICT r4 #5) — this arm prices that fallback.
    "higgs_xo": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32,
        "tpu_wave_order": "exact"},
        warmup=3, measured=10, timeout=2700),
    # single-bf16-product histograms (tpu_hist_precision=bf16, the
    # gpu_use_dp=false analog): the kernel is MXU-FLOP-bound (~71%
    # utilization at the flagship, 13:17 trace), so halving the dots
    # should land ~1.7-1.9x — quality delta vs the hi/lo arm decides
    # whether it can ever be a default
    "higgs_bf16": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32,
        "tpu_hist_precision": "bf16"},
        warmup=3, measured=10, timeout=2700),
    # compare-select score update at the flagship (the 86 ms/iter = 11%
    # gather term, 13:17 trace); and the everything-on arm stacking it
    # with bf16 single-product histograms
    "higgs_su": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_score_update": "pallas"},
        warmup=3, measured=10, timeout=2700),
    "higgs_fast": dict(n=10_500_000, f=28, cache_as="higgs", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_score_update": "pallas", "tpu_hist_precision": "bf16"},
        warmup=3, measured=10, timeout=2700),
    # pallas_ct at the WIDE shapes (promotion widening: ct auto is
    # currently gated to ncols*bin_pad <= 2048 — these arms supply the
    # wide-F datapoints; the W=16-epsilon / W=32-bosch pathology says
    # wide-F cells can surprise)
    # expo_cat sits just past the ct auto bound (40 cols x 64-pad =
    # 2560 > 2048) so it pays the pallas_t two-pass pipeline; this arm
    # prices ct there — with the small per-wave work of 2M x 40, the
    # saved partition pass is the biggest single lever the 3.9x shape
    # has (VERDICT r4 weak #7)
    "expo_ct": dict(n=2_000_000, f=40, cache_as="expo_cat", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "categorical_feature": ",".join(str(i) for i in range(10)),
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32},
        warmup=2, measured=5, timeout=2700, n_cat=10, cardinality=100),
    "epsilon_ct": dict(n=400_000, f=2000, cache_as="epsilon", params={
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32},
        warmup=2, measured=5, timeout=2700),
    "msltr_ct": dict(n=2_270_000, f=137, cache_as="msltr", params={
        "objective": "lambdarank", "metric": "ndcg", "ndcg_eval_at": "10",
        "num_leaves": 255, "max_bin": 63, "learning_rate": 0.1,
        "min_data_in_leaf": 1,
        "tpu_histogram_mode": "pallas_ct", "tpu_wave_width": 32},
        warmup=2, measured=5, timeout=2700, query_size=120),
    # width probe at the yahoo shape: if its 7.06 s/iter sits in the
    # same ~17-24 MB hist-block pathology band as epsilon-W16/bosch-W32,
    # W=64 (34 MB block) should be sharply faster
    "yahoo_w64": dict(n=473_134, f=700, cache_as="yahoo", params={
        "objective": "lambdarank", "metric": "ndcg",
        "ndcg_eval_at": "1,10", "num_leaves": 255, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 1,
        "tpu_wave_width": 64}, warmup=2, measured=5, timeout=2700,
        query_size=23),
}


def _check_aliases():
    """cache_as arms must agree with their target on every data-defining
    field — a mismatch would silently benchmark the wrong dataset."""
    for name, spec in SHAPES.items():
        tgt = spec.get("cache_as")
        if not tgt:
            continue
        for k in ("n", "f", "n_cat", "cardinality", "query_size"):
            assert spec.get(k) == SHAPES[tgt].get(k), (
                "%s.%s=%r != %s.%s=%r" % (name, k, spec.get(k),
                                          tgt, k, SHAPES[tgt].get(k)))


_check_aliases()


def make_shape(name):
    """Deterministic synthetic data for a shape; returns (X, y, query).
    Seeded by a STABLE hash — Python's hash() is salted per process,
    which would give the TPU and reference-CLI arms different data."""
    import zlib

    import numpy as np
    spec = SHAPES[name]
    n, f = spec["n"], spec["f"]
    seed_name = spec.get("cache_as", name)
    rng = np.random.default_rng(zlib.crc32(seed_name.encode()))
    chunks, ys = [], []
    w = rng.normal(size=f) * (rng.random(f) > 0.3)
    n_cat = spec.get("n_cat", 0)
    card = spec.get("cardinality", 0)
    cat_effect = (rng.normal(size=(n_cat, card)) * 0.6
                  if n_cat else None)
    for start in range(0, n, 500_000):
        m = min(500_000, n - start)
        X = rng.normal(size=(m, f)).astype(np.float32)
        logit = X @ w * 0.4
        if n_cat:
            codes = rng.integers(0, card, size=(m, n_cat))
            X[:, :n_cat] = codes
            logit = logit + cat_effect[np.arange(n_cat), codes].sum(axis=1)
        logit = logit + 0.6 * rng.normal(size=m)
        chunks.append(X)
        ys.append(logit)
    X = np.concatenate(chunks)
    raw = np.concatenate(ys)
    query = None
    if spec.get("query_size"):
        qs = spec["query_size"]
        nq = n // qs
        query = np.full(nq + (1 if n % qs else 0), qs, np.int32)
        if n % qs:
            query[-1] = n % qs
        # graded relevance 0-4 from the standardized raw score
        y = np.clip((raw - raw.mean()) / raw.std() * 1.2 + 2, 0,
                    4).round().astype(np.float64)
    else:
        y = (raw > 0).astype(np.float64)
    return X, y, query


def cache_path(name):
    return "/tmp/suite_%s.bin" % SHAPES.get(name, {}).get("cache_as", name)


def cached_dataset(name):
    import lightgbm_tpu as lgb
    spec = SHAPES[name]
    cache = cache_path(name)
    if os.path.exists(cache):
        return lgb.Dataset(cache)
    X, y, query = make_shape(name)
    ds = lgb.Dataset(X, label=y, params=dict(spec["params"], verbose=-1))
    if query is not None:
        ds.set_group(query)
    ds.construct()
    ds.save_binary(cache + ".tmp")
    os.replace(cache + ".tmp", cache)
    return lgb.Dataset(cache)


def child(name):
    """One timed measurement on the current backend; prints a JSON line.
    Timing protocol lives in bench_modes.run (one copy)."""
    from tools.bench_modes import run
    spec = SHAPES[name]
    ds = cached_dataset(name)
    t_load = time.time()
    # pin the timeline path (bench_modes.run only setdefaults it) so the
    # measurement can be ingested into the cross-run ledger afterwards
    obs_path = "/tmp/suite_obs_%s_%d.jsonl" % (name, os.getpid())
    try:
        os.unlink(obs_path)
    except OSError:
        pass
    # mode=auto + width -1: measure what a DEFAULT user gets at the shape
    dt, metric, g = run(None, None, "auto", wave_width=-1,
                        warmup=spec["warmup"], measured=spec["measured"],
                        extra=dict(spec["params"], tpu_growth="auto",
                                   verbose=-1, obs_events_path=obs_path),
                        train_set=ds, details=True)
    lrn = g.learner
    # ledger ingestion is explicit here (the observer belongs to
    # bench_modes): suite = the shape arm, shape = its nominal size —
    # best-effort, a ledger problem must not void the measurement
    try:
        from lightgbm_tpu.obs.ledger import Ledger, default_ledger_dir
        if default_ledger_dir():
            Ledger(default_ledger_dir()).ingest_timeline(
                obs_path, suite="suite_" + name,
                shape="%dx%d" % (spec["n"], spec["f"]))
    except Exception as e:
        print("suite: ledger ingest failed: %s" % e, file=sys.stderr)
    print(json.dumps({
        "dt": dt, "metric": float(metric),
        "mode": lrn.hist_mode, "growth": lrn.growth,
        "order": getattr(lrn, "wave_order", "-"),
        "W": int(getattr(lrn, "wave_width", 0)),
        "source": "obs_timeline",       # dt from the emitted telemetry
        "wall": time.time() - t_load}), flush=True)


def ref_arm(name, iters=3):
    """Time the reference CLI on the same data (s/iter from per-iteration
    wall lines); writes the shape as TSV once (cached)."""
    import numpy as np
    ref = os.environ.get("REF_LGBM", "/tmp/refbuild/lightgbm")
    if not os.path.exists(ref):
        raise RuntimeError("reference binary not found at %s" % ref)
    tsv = "/tmp/suite_%s.tsv" % name
    spec = SHAPES[name]
    if not os.path.exists(tsv):
        import pandas as pd
        X, y, query = make_shape(name)
        df = pd.DataFrame(X)
        df.insert(0, "label", y)
        df.to_csv(tsv + ".tmp", sep="\t", header=False, index=False,
                  float_format="%g")
        if query is not None:
            # the .query side-file must exist BEFORE the TSV publish —
            # the cache check tests only the TSV, so the reverse order
            # could publish a permanently query-less dataset
            np.savetxt(tsv + ".query", query, fmt="%d")
        os.replace(tsv + ".tmp", tsv)
    conf = dict(spec["params"])
    conf.update({"task": "train", "data": tsv, "num_trees": iters + 2,
                 "verbosity": 2, "output_model": "/tmp/suite_ref.model"})
    args = [ref] + ["%s=%s" % kv for kv in conf.items()]
    t0 = time.time()
    r = subprocess.run(args, capture_output=True, text=True,
                       timeout=3 * 3600)
    wall = time.time() - t0
    # per-iteration seconds from the CLI's timing lines
    import re
    if r.returncode != 0:
        raise RuntimeError("reference CLI rc=%d: %s"
                           % (r.returncode,
                              (r.stderr or r.stdout).strip()[-300:]))
    secs = [float(m.group(1)) for m in re.finditer(
        r"(\d+\.\d+) seconds elapsed", r.stdout + r.stderr)]
    if len(secs) < 2:
        raise RuntimeError("reference CLI produced no per-iteration "
                           "timing lines; cannot derive s/iter")
    dt = (secs[-1] - secs[0]) / (len(secs) - 1)
    print(json.dumps({"dt": dt, "wall": wall}), flush=True)


def append(line):
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main():
    names = [a for a in sys.argv[1:] if not a.startswith("--")] \
        or list(SHAPES)
    ref_mode = "--ref" in sys.argv
    if ref_mode:
        # cache_as arms differ only in TPU-side knobs — the CPU CLI
        # baseline would duplicate the target shape's number (and balk
        # at the tpu_* params), so they have no reference arm
        names = [n for n in names if "cache_as" not in SHAPES[n]]
    stamp = datetime.datetime.now(datetime.timezone.utc)
    if not os.path.exists(OUT):
        with open(OUT, "w") as f:
            f.write("# Headline-shape benchmark results "
                    "(tools/bench_suite.py)\n")
    append("\n## %s UTC — %s arms: %s"
           % (stamp.isoformat(timespec="seconds"),
              "reference-CLI" if ref_mode else "TPU", " ".join(names)))
    for name in list(names):
        if not ref_mode:
            break
        names.remove(name)
        t0 = time.time()
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child-ref", name], capture_output=True, text=True,
                timeout=3 * 3600, cwd=REPO)
            res = json.loads(out.stdout.strip().splitlines()[-1])
            append("    %-10s reference-CLI: %.3f s/iter (%.3f it/s) "
                   "[wall %.0fs]" % (name, res["dt"],
                                     1.0 / res["dt"],
                                     time.time() - t0))
        except Exception as e:
            append("    %-10s reference-CLI: FAILED (%s)" % (name, e))

    # TPU arms: one child per arm, in turn.  This parent never
    # initializes a JAX backend, so each child gets the chip to itself;
    # a child that does not see a TPU fails the run.
    for name in names:
        t0 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 name], capture_output=True, text=True,
                timeout=SHAPES[name]["timeout"], cwd=REPO)
        except subprocess.TimeoutExpired:
            append("    %-10s: TIMEOUT after %ds"
                   % (name, SHAPES[name]["timeout"]))
            continue
        if r.returncode == NO_TPU_RC:
            append("    %-10s: FAILED (%s)"
                   % (name, r.stderr.strip().splitlines()[-1]))
            sys.exit(NO_TPU_RC)
        if r.returncode != 0:
            append("    %-10s: FAILED (rc=%d: %s)"
                   % (name, r.returncode,
                      (r.stderr.strip().splitlines() or ["no stderr"])[-1]))
            continue
        res = json.loads(r.stdout.strip().splitlines()[-1])
        append("    %-10s: %.3f s/iter (%.2f it/s) metric=%.5f "
               "[%s/%s/%s W=%d, wall %.0fs]"
               % (name, res["dt"], 1.0 / res["dt"], res["metric"],
                  res["mode"], res["growth"], res["order"], res["W"],
                  time.time() - t0))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        import jax
        if jax.default_backend() != "tpu":
            print("suite: JAX backend is %r, not tpu"
                  % jax.default_backend(), file=sys.stderr)
            sys.exit(NO_TPU_RC)
        child(sys.argv[2])
    elif len(sys.argv) > 2 and sys.argv[1] == "--child-ref":
        ref_arm(sys.argv[2])
    else:
        main()
