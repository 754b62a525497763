"""Flagship-scale AUC parity: ours (TPU) vs the reference CLI, identical bytes.

The quality half of the north star — a multi-hundred-
iteration head-to-head at >=1M rows (the prior parity pins stop at 50k
rows / 13 iters).  Mirrors the discipline of the reference's published
speed/accuracy table (/root/reference/docs/GPU-Performance.md:127-145):
same bytes, same recipe, compare the final validation AUC.

Protocol:
  * One deterministic synthetic Higgs-like set (PARITY_N train rows x 28,
    250k valid rows), written ONCE as TSV (%.7g) — both frameworks read
    the SAME text file, so binning sees identical input bytes.
  * Reference arm: the unmodified CLI (REF_LGBM) with valid= + metric=auc,
    final "Iteration:<last> ... auc : <v>" line parsed from its log.
  * Our arms (one child process each, in turn; the parent never
    initializes a JAX backend, so the child gets the chip):
      exact — tpu_growth=exact, the reference's split order: the parity
              claim (target |delta| <= 1e-4);
      wave  — the TPU speed default (auto -> wave/pallas_t/compact):
              the headline config's quality envelope (expect <= ~1e-3).
  * Results append to PARITY_TRAINING.md and print as one JSON line.

Usage: python tools/parity_flagship.py            # 1M x 28, 150 iters
       PARITY_N=10500000 python tools/parity_flagship.py
"""
import datetime
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_TRAIN = int(os.environ.get("PARITY_N", 1_000_000))
N_VALID = int(os.environ.get("PARITY_NVALID", 250_000))
N_FEAT = 28
ITERS = int(os.environ.get("PARITY_ITERS", 150))
CHILD_TIMEOUT = float(os.environ.get("PARITY_CHILD_S", 2400))
REF = os.environ.get("REF_LGBM", "/tmp/refbuild/lightgbm")

TRAIN_TSV = "/tmp/parity_fs_%d.train.tsv" % N_TRAIN
VALID_TSV = "/tmp/parity_fs_%d.valid.tsv" % N_TRAIN

PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 255,
          "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1}


def write_tsvs():
    if os.path.exists(TRAIN_TSV) and os.path.exists(VALID_TSV):
        return
    import numpy as np
    rng = np.random.default_rng(4242)
    w = None

    def emit(path, rows):
        nonlocal w
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            done = 0
            while done < rows:
                n = min(500_000, rows - done)
                X = rng.normal(size=(n, N_FEAT)).astype(np.float32)
                if w is None:
                    w = rng.normal(size=N_FEAT) * (rng.random(N_FEAT) > 0.3)
                logit = X @ w * 0.5 + 0.5 * rng.normal(size=n)
                y = (logit > 0).astype(np.int32)
                block = np.column_stack([y.astype(np.float32), X])
                np.savetxt(f, block, fmt="%.7g", delimiter="\t")
                done += n
        os.replace(tmp, path)

    emit(TRAIN_TSV, N_TRAIN)
    emit(VALID_TSV, N_VALID)


def ref_arm():
    """Train the reference CLI; return (final_valid_auc, s_per_iter)."""
    conf = dict(PARAMS)
    conf.update({"task": "train", "data": TRAIN_TSV, "valid": VALID_TSV,
                 "num_trees": ITERS, "verbosity": 2, "metric_freq": ITERS,
                 "output_model": "/tmp/parity_fs_ref.model",
                 "num_threads": 1})
    args = [REF] + ["%s=%s" % kv for kv in conf.items()]
    t0 = time.time()
    r = subprocess.run(args, capture_output=True, text=True,
                       timeout=6 * 3600)
    wall = time.time() - t0
    text = r.stdout + r.stderr
    if r.returncode != 0:
        raise RuntimeError("reference CLI rc=%d:\n%s"
                           % (r.returncode, text[-1000:]))
    aucs = re.findall(r"Iteration:(\d+).*?auc\s*:\s*([0-9.]+)", text)
    if not aucs:
        raise RuntimeError("no auc lines in reference log:\n" + text[-1000:])
    last_iter, auc = max(((int(i), float(a)) for i, a in aucs))
    iters_timed = re.findall(r"([0-9.]+) seconds elapsed, finished iteration"
                             r"\s*(\d+)", text)
    spi = wall / ITERS
    if len(iters_timed) >= 2:
        (t_a, i_a), (t_b, i_b) = iters_timed[0], iters_timed[-1]
        if int(i_b) > int(i_a):
            spi = (float(t_b) - float(t_a)) / (int(i_b) - int(i_a))
    return auc, spi


def child(growth):
    """Our arm, on the chip; prints one JSON line."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.common import enable_compilation_cache
    enable_compilation_cache()
    if jax.default_backend() != "tpu":
        sys.exit("parity: JAX backend is %r, not tpu"
                 % jax.default_backend())
    params = dict(PARAMS, verbose=-1, tpu_growth=growth)
    cache = "/tmp/parity_fs_%d_%s.bin" % (N_TRAIN, "ds")
    if os.path.exists(cache):
        dtrain = lgb.Dataset(cache)
        dtrain.construct()
        dtrain.params = dict(dtrain.params or {}, **params)
    else:
        dtrain = lgb.Dataset(TRAIN_TSV, params=params)
        dtrain.construct()
        try:
            tmp = "%s.tmp.%d" % (cache, os.getpid())
            dtrain.save_binary(tmp)
            os.replace(tmp, cache)
        except Exception as e:
            print("cache write failed: %s" % e, file=sys.stderr)
    dvalid = lgb.Dataset(VALID_TSV, reference=dtrain, params=params)
    evals = {}
    t0 = time.time()
    lgb.train(params, dtrain, num_boost_round=ITERS, valid_sets=[dvalid],
              evals_result=evals)
    wall = time.time() - t0
    auc = float(evals["valid_0"]["auc"][-1])
    print(json.dumps({"auc": auc, "spi": wall / ITERS,
                      "backend": jax.default_backend()}), flush=True)


def our_arm(growth):
    """One arm in its own child process.  This parent never initializes
    a JAX backend, so each child in turn gets the chip to itself; a child
    that fails — one that finds no TPU included — fails the run."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", growth],
        capture_output=True, text=True, timeout=CHILD_TIMEOUT, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError("our[%s]: rc=%d\n%s"
                           % (growth, r.returncode, r.stderr[-800:]))
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    print("writing TSVs (cached: %s)" % os.path.exists(TRAIN_TSV),
          flush=True)
    write_tsvs()
    # the reference arm is deterministic for (N, ITERS) — cache it so a
    # repeat invocation spends its time on OUR arms only
    ref_cache = "/tmp/parity_fs_ref_%d_%d.json" % (N_TRAIN, ITERS)
    if os.path.exists(ref_cache) and not os.environ.get("PARITY_REF_FRESH"):
        rec = json.load(open(ref_cache))
        ref_auc, ref_spi = rec["auc"], rec["spi"]
        print("reference arm: cached", flush=True)
    else:
        print("reference arm...", flush=True)
        ref_auc, ref_spi = ref_arm()
        tmp = "%s.tmp.%d" % (ref_cache, os.getpid())
        json.dump({"auc": ref_auc, "spi": ref_spi}, open(tmp, "w"))
        os.replace(tmp, ref_cache)
    print("reference: auc=%.6f  %.3f s/iter" % (ref_auc, ref_spi),
          flush=True)
    if "--ref-only" in sys.argv:     # precompute without a chip
        print(json.dumps({"ref_auc": ref_auc, "ref_spi": ref_spi}),
              flush=True)
        return
    # --wave-only / --exact-only: re-run a single arm (the ref arm and
    # the other arm's committed row stay valid)
    arms = ("exact", "wave")
    if "--wave-only" in sys.argv:
        arms = ("wave",)
    elif "--exact-only" in sys.argv:
        arms = ("exact",)
    rows = []
    for growth in arms:
        res = our_arm(growth)
        rows.append((growth, res["auc"], res["auc"] - ref_auc,
                     res["spi"]))
        print("ours[%s]: auc=%.6f delta=%+.2e  %.3f s/iter"
              % (growth, res["auc"], res["auc"] - ref_auc, res["spi"]),
              flush=True)
    stamp = datetime.datetime.now(datetime.timezone.utc)
    with open(os.path.join(REPO, "PARITY_TRAINING.md"), "a") as f:
        f.write("\n## Flagship-scale AUC parity — %s UTC\n\n"
                % stamp.isoformat(timespec="seconds"))
        f.write("%d train rows x %d, %d valid rows, %d iterations, "
                "identical TSV bytes both sides (tools/parity_flagship.py).\n\n"
                % (N_TRAIN, N_FEAT, N_VALID, ITERS))
        f.write("| arm | valid AUC | delta vs ref | s/iter |\n")
        f.write("|---|---|---|---|\n")
        f.write("| reference CLI | %.6f | — | %.3f |\n" % (ref_auc, ref_spi))
        for growth, auc, delta, spi in rows:
            f.write("| ours (%s) | %.6f | %+.2e | %.3f |\n"
                    % (growth, auc, delta, spi))
    print(json.dumps({
        "ref_auc": ref_auc,
        "arms": {g: {"auc": a, "delta": d, "spi": s}
                 for g, a, d, s in rows}}), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        child(sys.argv[2])
    else:
        main()
