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


def peak_bytes():
    """peak_bytes_in_use of every local device, in device order."""
    from lightgbm_tpu.obs.memory import device_memory_stats
    return [row.get("peak_bytes_in_use") for row in device_memory_stats()]


def split_trees(bst):
    """The booster's host trees that split at least once."""
    gbdt = bst._gbdt
    gbdt._materialize()
    return [t for t in gbdt.models if t.num_leaves > 1]


def root_split(tree):
    return int(tree.split_feature_inner[0]), int(tree.threshold_in_bin[0])


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
             clock.seconds, peak_bytes()), flush=True)
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


def phase_multichip(train_set, holdout, params, rounds, expect_dp,
                    expect_serial, serial_fused):
    """tree_learner=data over every device, against a serial run forced
    to the kernel and precision the mesh learner resolves to."""
    import jax
    n_dev = jax.device_count()

    def placements(env):
        it = env.iteration + 1
        if it not in (1, rounds):
            return
        gbdt = env.model._gbdt
        grad, _ = gbdt.objective.get_gradients(gbdt._score_for_objective())
        for what, arr in (("score", gbdt._score_dev), ("gradient", grad)):
            print("multichip: after round %d the %s array is %s%s on %s "
                  "with sharding %s"
                  % (it, what, arr.dtype, list(arr.shape),
                     sorted(d.id for d in arr.devices()), arr.sharding),
                  flush=True)

    dp, dp_auc = train_and_check(
        "multichip data-parallel", dict(params, tree_learner="data"),
        train_set, rounds, holdout, expect_dp, fused=False,
        after_iteration=placements)
    lrn = dp._gbdt.learner
    check(lrn.mesh.devices.size == n_dev,
          "multichip: mesh has %d of %d devices", lrn.mesh.devices.size,
          n_dev)
    shard_devices = {s.device.id for s in lrn.X.addressable_shards}
    check(shard_devices == {d.id for d in jax.devices()}
          and len(lrn.X.addressable_shards) == n_dev,
          "multichip: X has shards on devices %s", sorted(shard_devices))
    print("multichip: X %s sharded %s, one shard of %s on each of %d "
          "devices" % (list(lrn.X.shape), lrn.X.sharding.spec,
                       list(lrn.X.addressable_shards[0].data.shape), n_dev),
          flush=True)
    serial, serial_auc = train_and_check(
        "multichip serial reference",
        dict(params, tpu_histogram_mode="pallas_t",
             tpu_hist_precision="hilo"),
        train_set, rounds, holdout, expect_serial, fused=serial_fused)
    check(abs(dp_auc - serial_auc) <= 1e-3,
          "multichip: data-parallel AUC %.5f vs serial %.5f", dp_auc,
          serial_auc)
    dp_root = root_split(split_trees(dp)[0])
    serial_root = root_split(split_trees(serial)[0])
    check(dp_root == serial_root,
          "multichip: root split %s vs serial %s", dp_root, serial_root)
    print("multichip: %d devices, AUC %.5f vs serial %.5f, same root "
          "split %s" % (n_dev, dp_auc, serial_auc, dp_root), flush=True)


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
            phase_multichip(train_set, holdout, FLAGSHIP, MULTICHIP_ROUNDS,
                            EXPECT_DATA_PARALLEL,
                            dict(EXPECT_SERIAL, hist_mode="pallas_t"),
                            serial_fused=True)
        else:
            print("multichip: skipped (1 device)", flush=True)

    entries1, bytes1 = cache_census(cache_dir)
    print("chip_smoke: compile cache %s now %d entries, %d bytes (%+d "
          "entries); peak_bytes_in_use %s; %.0f s in all"
          % (cache_dir, entries1, bytes1, entries1 - entries0, peak_bytes(),
             time.perf_counter() - t0), flush=True)
    print("chip_smoke: passed %s at %d rows" % (only, args.rows),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
