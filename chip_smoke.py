"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls (``lgb.Dataset``, ``lgb.train``, ``Booster.predict``,
``Booster.serve``, the CLI) at the flagship width, checks what comes out
by the repo's own means, and prints as the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It exits non-zero, with no result line, when JAX finds no TPU, when any
phase fails (no phase failure is turned into a warning), or when it is
run without the rest of the repository.  Seconds and bytes printed on the
way are smoke output, not metrics.

Every phase is a function of its sizes and parameters, so
tests/test_chip_smoke.py drives the same code tiny on the CPU with
``tpu_pallas_interpret=true``; ``main()`` itself never runs off-TPU.

    python chip_smoke.py                  # the contract: 2,000,000 x 28
    python chip_smoke.py --rows 10500000  # builder's full-size run
    python chip_smoke.py --only multichip # builder's debugging
"""
import argparse
import importlib.metadata
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ROWS = 2_000_000            # Higgs-shaped: the flagship width, depth cut
FEATURES = 28
HOLDOUT_ROWS = 100_000
# the flagship recipe (BASELINE.md) and NO tpu_* key: what `auto` resolves
# to on the chip is part of what the smoke checks
FLAGSHIP = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
            "learning_rate": 0.1, "min_data_in_leaf": 1, "verbose": -1}
DEFAULT_ROUNDS = 5
STAGED_ROUNDS = 2
MULTICHIP_ROUNDS = 3
MULTICHIP_ROWS = 2_000_000  # of the phase's own binned table
MULTICHIP_SHARD_ROWS = 300_000  # no device's rows end where a file does
KERNEL_ROWS = 65_536
WAVE_WIDTH = 32
PREDICT_ROWS = 200_000      # above the 100,000-row device-path threshold
HOST_CHECK_ROWS = 10_000
SERVE_REQUESTS = 20
SERVE_MAX_ROWS = 4_096
MIN_AUC = 0.7               # the bar bench.py holds the flagship to
# Booster.predict below the device threshold is the f64 host predictor;
# the server scores and applies the sigmoid on the device in f32, where
# the chip's exp is an approximation (1.2e-6 seen on a v5e)
SERVE_TOL = 1e-5
# what `auto` must resolve to on one chip at this shape
EXPECT_SERIAL = {"learner": "SerialTreeLearner", "growth": "wave",
                 "hist_mode": "pallas_ct", "wave_width": WAVE_WIDTH,
                 "pallas_interpret": False}
EXPECT_DATA_PARALLEL = {"learner": "DataParallelTreeLearner",
                        "growth": "wave", "hist_mode": "pallas_t",
                        "wave_width": WAVE_WIDTH, "pallas_interpret": False}
# per-product error classes ops/pallas_wave.py documents, against the
# histogram of |weights|: hi/lo keeps a truncated bf16 head plus a rounded
# bf16 residual (worst case 2^-15, typically 2^-17); single bf16 rounds to
# nearest (worst case 2^-8, typically 2^-9)
KERNEL_TOL = {True: 2.0 ** -15, False: 2.0 ** -8}
# a cast that TRUNCATED would bias the summed hessian mass by about the
# product error itself; rounding to nearest leaves it a random walk far
# below that, so this bound tells the two apart
KERNEL_MASS_TOL = {True: 2.0 ** -19, False: 2.0 ** -12}

PHASES = ("train", "staged", "kernels", "predict", "serve", "cli",
          "multichip")


def check(cond, what, *args):
    """A failed check fails the smoke (``assert`` is stripped by -O)."""
    if not cond:
        raise AssertionError(what % args)


def make_data(rows, features, holdout):
    """bench.py's seeded Higgs-shaped recipe; the last `holdout` rows are
    held out of training."""
    import bench
    X, y = bench.make_data(rows + holdout, features)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def auc(label, score):
    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.metrics import AUCMetric
    meta = Metadata(len(label))
    meta.set_label(label)
    metric = AUCMetric()
    metric.init(meta, len(label))
    return metric.eval(score, None)[0]


def cache_census(cache_dir):
    """(entries, bytes) under the compile-cache directory."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0, 0
    sizes = [os.path.getsize(os.path.join(root, f))
             for root, _, files in os.walk(cache_dir) for f in files]
    return len(sizes), sum(sizes)


def device_bytes(counter="peak_bytes_in_use"):
    """One of the allocator's counters on every local device, in device
    order."""
    from lightgbm_tpu.obs.memory import device_memory_stats
    return [row.get(counter) for row in device_memory_stats()]


def split_trees(bst):
    """The booster's host trees that split at least once."""
    gbdt = bst._gbdt
    gbdt._materialize()
    return [t for t in gbdt.models if t.num_leaves > 1]


def check_learner(gbdt, expect, fused):
    lrn = gbdt.learner
    got = dict(learner=type(lrn).__name__, growth=lrn.growth,
               hist_mode=lrn.hist_mode, wave_width=lrn.wave_width,
               pallas_interpret=lrn.pallas_interpret)
    check(got == expect, "learner resolved to %s, expected %s", got, expect)
    check((gbdt._resolve_fused_iter() is not None) == fused,
          "fused iteration in use: expected %s", fused)


class IterationClock:
    """lgb.train callback: wall seconds at the end of every iteration,
    taken after the device has finished it."""

    def __init__(self, after_iteration=None):
        self.start = time.perf_counter()
        self.ends = []
        self._after = after_iteration

    def __call__(self, env):
        import jax
        jax.block_until_ready(env.model._gbdt._score_dev)
        self.ends.append(time.perf_counter())
        if self._after is not None:
            self._after(env)

    @property
    def seconds(self):
        """Per iteration; the first includes set-up and compilation."""
        return np.diff([self.start] + self.ends).round(3).tolist()


def train_and_check(name, params, train_set, rounds, holdout, expect,
                    fused, after_iteration=None):
    """lgb.train for `rounds`, then the checks every training phase
    shares: what the learner resolved to, every tree split, finite
    scores, holdout AUC.  Returns (booster, holdout AUC)."""
    import lightgbm_tpu as lgb
    clock = IterationClock(after_iteration)
    bst = lgb.train(params, train_set, num_boost_round=rounds,
                    callbacks=[clock], verbose_eval=False)
    gbdt = bst._gbdt
    check_learner(gbdt, expect, fused)
    trees = split_trees(bst)
    check(len(trees) == rounds, "%s: %d of %d trees split", name,
          len(trees), rounds)
    check(np.isfinite(gbdt.train_score).all(),
          "%s: non-finite training scores", name)
    Xh, yh = holdout
    got_auc = auc(yh, bst.predict(Xh))
    check(got_auc > MIN_AUC, "%s: holdout AUC %.4f <= %.2f", name, got_auc,
          MIN_AUC)
    print("%s: %d rounds, leaves %s, holdout AUC %.5f, seconds per "
          "iteration %s (the first includes set-up and compilation), "
          "peak_bytes_in_use %s"
          % (name, rounds, [t.num_leaves for t in trees], got_auc,
             clock.seconds, device_bytes()), flush=True)
    return bst, got_auc


def phase_train(train_set, holdout, params, rounds, expect):
    """The default path: no tpu_* key, the fused iteration."""
    return train_and_check("train", params, train_set, rounds, holdout,
                           expect, fused=True)[0]


def phase_staged(train_set, holdout, params, rounds, expect, fused_bst):
    """tpu_fused_iter=off: the staged chain every booster but plain GBDT,
    every multiclass run and every mesh learner takes, and the only one
    that runs the Pallas score update.  docs/FusedIteration.md promises
    the same trees as the fused run."""
    bst, _ = train_and_check(
        "staged", dict(params, tpu_fused_iter="off"), train_set, rounds,
        holdout, expect, fused=False)
    fused_tree, staged_tree = split_trees(fused_bst)[0], split_trees(bst)[0]
    for field in ("split_feature_inner", "threshold_in_bin"):
        ni = fused_tree.num_leaves - 1
        check(fused_tree.num_leaves == staged_tree.num_leaves
              and np.array_equal(getattr(fused_tree, field)[:ni],
                                 getattr(staged_tree, field)[:ni]),
              "staged: first tree's %s differs from the fused run's", field)
    print("staged: first tree identical to the fused run's (%d leaves)"
          % fused_tree.num_leaves, flush=True)
    return bst


def phase_kernels(rows, features, width, interpret):
    """The wave kernels against wave_histogram_reference, compiled: the
    kernels round to bf16 by hand because Mosaic's cast truncates, and
    only a compiled run can say that still holds."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas_wave import (wave_histogram_pallas_t,
                                              wave_histogram_reference,
                                              wave_partition_hist_pallas_ct)
    bins = FLAGSHIP["max_bin"]
    rng = np.random.default_rng(7)
    X = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    # one wave: parents 0..W-1 each split on their own column and bin;
    # rows above the threshold move to the right child W+w, whose
    # histogram the wave wants.  Leaves W..2W-1 are spectators.
    leaf = rng.integers(0, 2 * width, size=rows).astype(np.int32)
    w3 = np.stack([rng.normal(size=rows), rng.uniform(0.1, 1.0, size=rows),
                   np.ones(rows)], axis=1).astype(np.float32)
    slot = np.arange(width)
    col, thr = slot % features, 8 + slot % (bins - 16)
    right = 2 * width + slot
    cols = np.zeros((width, 10), np.float32)
    cols[:, 0], cols[:, 1], cols[:, 2] = 1.0, col, thr
    cols[:, 5], cols[:, 6], cols[:, 9] = 1.0, right, bins   # bin 0 -> left
    is_parent = leaf < width
    parent = np.where(is_parent, leaf, 0)
    goes_right = is_parent & (X[np.arange(rows), col[parent]] > thr[parent])
    want_leaf = np.where(goes_right, right[parent], leaf).astype(np.int32)

    Xd, Xt = jnp.asarray(X), jnp.asarray(X.T)
    w3d, cid = jnp.asarray(w3), jnp.asarray(right.astype(np.int32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(wave_histogram_reference(
            Xd, jnp.asarray(want_leaf), w3d, cid, bins), np.float64)
        mass = np.asarray(wave_histogram_reference(
            Xd, jnp.asarray(want_leaf), jnp.abs(w3d), cid, bins), np.float64)
    check(want[..., 2].sum() > rows * features / 8,
          "kernels: the oracle wave moved too few rows to test anything")
    for hilo in (True, False):
        got_leaf, ct = wave_partition_hist_pallas_ct(
            Xt, jnp.asarray(leaf), w3d, cid, jnp.asarray(cols),
            jnp.asarray(slot.astype(np.int32)), bins, hilo=hilo,
            interpret=interpret)
        check(np.array_equal(np.asarray(got_leaf), want_leaf),
              "kernels: pallas_ct routed rows differently from the oracle")
        t = wave_histogram_pallas_t(Xt, jnp.asarray(want_leaf), w3d, cid,
                                    bins, hilo=hilo, interpret=interpret)
        for kernel, got in (("pallas_ct", ct), ("pallas_t", t)):
            got = np.asarray(got, np.float64)
            check(got.shape == want.shape and np.isfinite(got).all(),
                  "kernels: %s output shape %s / non-finite", kernel,
                  got.shape)
            err = (np.abs(got - want) / np.maximum(mass, 1e-30)).max()
            bias = abs(got[..., 1].sum() - want[..., 1].sum()) \
                / want[..., 1].sum()
            print("kernels: %s %s: max cell error %.3g of |w| mass "
                  "(bound %.3g), hessian-mass bias %.3g (bound %.3g)"
                  % (kernel, "hi/lo" if hilo else "bf16", err,
                     KERNEL_TOL[hilo], bias, KERNEL_MASS_TOL[hilo]),
                  flush=True)
            check(err <= KERNEL_TOL[hilo],
                  "kernels: %s hilo=%s cell error %.3g > %.3g", kernel,
                  hilo, err, KERNEL_TOL[hilo])
            check(bias <= KERNEL_MASS_TOL[hilo],
                  "kernels: %s hilo=%s hessian mass off by %.3g > %.3g: "
                  "the bf16 cast is truncating", kernel, hilo, bias,
                  KERNEL_MASS_TOL[hilo])


def phase_predict(bst, X, host_rows):
    """Booster.predict on a batch large enough for the device path,
    against the host predictor on the same model."""
    import lightgbm_tpu as lgb
    gbdt = bst._gbdt
    gbdt._ranked_pred = gbdt._ranked_pred_key = None
    dev = bst.predict(X)
    check(getattr(gbdt, "_ranked_pred", None) is not None,
          "predict: %d rows did not take the device path", len(X))
    check(dev.shape == (len(X),) and np.isfinite(dev).all(),
          "predict: device output shape %s / non-finite", dev.shape)
    host_bst = lgb.Booster(params={"tpu_predict": "false"},
                           model_str=bst.model_to_string())
    host = host_bst.predict(X[:host_rows])
    check(getattr(host_bst._gbdt, "_ranked_pred", None) is None,
          "predict: tpu_predict=false took the device path")
    diff = np.abs(dev[:host_rows] - host).max()
    check(diff <= 1e-6, "predict: device vs host predictor differ by %.3g",
          diff)
    print("predict: %d rows on the device path, %d match the host "
          "predictor to %.3g" % (len(X), host_rows, diff), flush=True)


def phase_serve(bst, X, requests, max_rows):
    """Booster.serve: mixed-size requests answer what Booster.predict
    answers, and nothing compiles after warm-up."""
    rng = np.random.default_rng(11)
    sizes = [1, max_rows] + list(rng.integers(1, max_rows + 1,
                                              size=requests - 2))
    with bst.serve(max_batch=max_rows) as sp:
        check(sp.cache is not None,
              "serve: no device executable cache was built")
        # requests coalesce, so a batch can land in any bucket: warm all
        buckets = sp.warmup([1 << i for i in range(max_rows.bit_length())])
        futures = []
        for n in sizes:
            lo = int(rng.integers(0, len(X) - n + 1))
            futures.append((lo, int(n), sp.submit(X[lo:lo + n])))
        worst = 0.0
        for lo, n, fut in futures:
            got = fut.result(timeout=300)
            want = bst.predict(X[lo:lo + n])
            check(got.shape == want.shape, "serve: answer shape %s != %s",
                  got.shape, want.shape)
            worst = max(worst, float(np.abs(got - want).max()))
        check(worst <= SERVE_TOL, "serve: answers differ from "
              "Booster.predict by %.3g", worst)
        stats = sp.stats()["executables"]
        check(stats["steady_state_compiles"] == 0,
              "serve: %d compiles after warm-up",
              stats["steady_state_compiles"])
        print("serve: %d requests of 1..%d rows, buckets %s, donate=%s, "
              "max |answer - predict| %.3g, steady_state_compiles 0"
              % (len(sizes), max_rows, buckets, sp.cache.donate, worst),
              flush=True)


def phase_cli(workdir):
    """task=train then task=predict through lightgbm_tpu.cli.main, in
    this process (the chip belongs to it)."""
    from lightgbm_tpu import cli
    golden = os.path.join(HERE, "tests", "data", "golden")
    model = os.path.join(workdir, "cli_model.txt")
    result = os.path.join(workdir, "cli_pred.txt")
    check(cli.main(["task=train", "objective=binary", "num_trees=5",
                    "num_leaves=31", "verbose=-1",
                    "data=" + os.path.join(golden, "binary.train"),
                    "output_model=" + model]) == 0, "cli: task=train failed")
    with open(model) as f:
        check(f.read(4) == "tree", "cli: model file does not start 'tree'")
    check(cli.main(["task=predict", "verbose=-1",
                    "data=" + os.path.join(golden, "binary.test"),
                    "input_model=" + model,
                    "output_result=" + result]) == 0,
          "cli: task=predict failed")
    pred = np.loadtxt(result)
    check(pred.ndim == 1 and len(pred) > 0 and np.isfinite(pred).all()
          and ((pred >= 0) & (pred <= 1)).all(),
          "cli: predictions are not finite probabilities")
    print("cli: trained 5 trees on binary.train, predicted %d rows of "
          "binary.test" % len(pred), flush=True)


def phase_multichip(rows, columns, shard_rows, params, rounds, expect_dp,
                    workdir, seed=28):
    """tree_learner=data over every device, from a binned directory of the
    phase's own (benchmark/gen.py; its shard boundary falls inside a
    device's rows), held to the benchmark's plain reference: every leaf's
    count is the rows that walk to it, and the first step's leaf values,
    scores and split gains are the reference's own."""
    import jax
    import lightgbm_tpu as lgb
    from benchmark import gen
    from benchmark.files import load_module
    tree_dict = load_module("drivers", "train_loop").tree_dict
    gbdt_plain = load_module("references", "gbdt_plain")

    n_dev = jax.device_count()
    params = dict(params, tree_learner="data")
    num_bin = int(params["max_bin"])
    made = gen.generate({"rows": rows, "columns": columns,
                         "shard_rows": shard_rows, "params": params},
                        seed, workdir)
    ds = lgb.Dataset.from_binned(workdir, params=dict(params))
    ds.construct()
    bst = lgb.Booster(dict(params), ds)
    gbdt = bst._gbdt
    lrn = gbdt.learner
    jax.block_until_ready(lrn.X)
    print("multichip: %d x %d in %d shards; after Booster(...) "
          "bytes_in_use %s" % (rows, columns, made["shards"],
                               device_bytes("bytes_in_use")), flush=True)
    check_learner(gbdt, expect_dp, fused=False)
    check(ds._handle._binned is None,
          "multichip: the host built the whole bin matrix")
    check(lrn.mesh.devices.size == n_dev,
          "multichip: mesh has %d of %d devices", lrn.mesh.devices.size,
          n_dev)
    shards = lrn.X.addressable_shards
    check({s.device.id for s in shards} == {d.id for d in jax.devices()}
          and len(shards) == n_dev,
          "multichip: X has shards on devices %s",
          sorted(s.device.id for s in shards))
    print("multichip: X %s sharded %s, one shard of %s on each of %d "
          "devices" % (list(lrn.X.shape), lrn.X.sharding.spec,
                       list(shards[0].data.shape), n_dev), flush=True)
    scores = []
    for i in range(rounds):
        bst.update()
        scores.append(np.array(gbdt.train_score[0], np.float32))
        if i == 0:
            print("multichip: after the first step bytes_in_use %s; the "
                  "score is on %s with sharding %s"
                  % (device_bytes("bytes_in_use"),
                     sorted(d.id for d in gbdt._score_dev.devices()),
                     gbdt._score_dev.sharding), flush=True)
    trees = [tree_dict(t) for t in split_trees(bst)]
    check(len(trees) == rounds, "multichip: %d of %d trees split",
          len(trees), rounds)
    del bst, ds
    bins, label = gen.open_shards(workdir)
    gaps = gbdt_plain.follow(
        trees, scores, bins, label, params,
        gen.mapper_dicts(1, num_bin)[0]["default_bin"], num_bin, 8, seed)
    check(gaps["count_mismatch"] == 0,
          "multichip: %d rows sit in a leaf they do not walk to",
          gaps["count_mismatch"])
    for name, limit in (("leaf_value_gap_step0", 1e-4),
                        ("score_gap_step0", 1e-4),
                        ("split_gain_gap_step0", 0.01)):
        check(gaps[name] <= limit, "multichip: %s %.3g over %.3g", name,
              gaps[name], limit)
    print("multichip: %d devices, leaves %s, count mismatch 0, step-0 gaps "
          "%s, loss gap %.3g"
          % (n_dev, [t["num_leaves"] for t in trees],
             {k: float("%.3g" % gaps[k]) for k in (
                 "leaf_value_gap_step0", "score_gap_step0",
                 "split_gain_gap_step0")}, gaps["loss_gap"]), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="training rows (default %d)" % ROWS)
    ap.add_argument("--only", default=",".join(PHASES),
                    help="comma-separated phases of %s" % (PHASES,))
    args = ap.parse_args(argv)
    only = args.only.split(",")
    check(set(only) <= set(PHASES), "unknown phase in %s", only)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import native
    from lightgbm_tpu.utils.common import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit("chip_smoke: JAX backend is %r, not tpu; devices: %s"
                 % (backend, jax.devices()))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    entries0, bytes0 = cache_census(cache_dir)
    print("chip_smoke: device %s; jax %s, jaxlib %s, libtpu %s; compile "
          "cache %s (%d entries, %d bytes); native library %s"
          % (device, jax.__version__,
             importlib.metadata.version("jaxlib"),
             importlib.metadata.version("libtpu"), cache_dir, entries0,
             bytes0, native.available()), flush=True)
    check(native.available(), "the native library is missing: binning "
          "would fall back to Python in silence (build it: sh cpp/build.sh)")
    from lightgbm_tpu.obs.roofline import peaks_for
    print("chip_smoke: device_kind %r is roofline row %r"
          % (dev.device_kind, peaks_for(dev.device_kind)["kind"]),
          flush=True)

    t0 = time.perf_counter()
    X, y, Xh, yh = make_data(args.rows, FEATURES, HOLDOUT_ROWS)
    train_set = lgb.Dataset(X, label=y, params=FLAGSHIP)
    train_set.construct()
    print("data: %d x %d generated and binned in %.1f s"
          % (args.rows, FEATURES, time.perf_counter() - t0), flush=True)
    holdout = (Xh, yh)

    bst = None
    if {"train", "staged", "predict", "serve"} & set(only):
        bst = phase_train(train_set, holdout, FLAGSHIP, DEFAULT_ROUNDS,
                          EXPECT_SERIAL)
    if "staged" in only:
        phase_staged(train_set, holdout, FLAGSHIP, STAGED_ROUNDS,
                     EXPECT_SERIAL, bst)
    if "kernels" in only:
        phase_kernels(KERNEL_ROWS, FEATURES, WAVE_WIDTH, interpret=False)
    if "predict" in only:
        phase_predict(bst, X[:PREDICT_ROWS], HOST_CHECK_ROWS)
    if "serve" in only:
        phase_serve(bst, X[:PREDICT_ROWS], SERVE_REQUESTS, SERVE_MAX_ROWS)
    if "cli" in only:
        with tempfile.TemporaryDirectory() as workdir:
            phase_cli(workdir)
    if "multichip" in only:
        if jax.device_count() >= 2:
            with tempfile.TemporaryDirectory() as workdir:
                phase_multichip(MULTICHIP_ROWS, FEATURES,
                                MULTICHIP_SHARD_ROWS, FLAGSHIP,
                                MULTICHIP_ROUNDS, EXPECT_DATA_PARALLEL,
                                workdir)
        else:
            print("multichip: skipped (1 device)", flush=True)

    entries1, bytes1 = cache_census(cache_dir)
    print("chip_smoke: compile cache %s now %d entries, %d bytes (%+d "
          "entries); peak_bytes_in_use %s; %.0f s in all"
          % (cache_dir, entries1, bytes1, entries1 - entries0, device_bytes(),
             time.perf_counter() - t0), flush=True)
    print("chip_smoke: passed %s at %d rows" % (only, args.rows),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
