#!/usr/bin/env python
"""CI smoke for the measured kernel autotuner (ops/autotune.py) — on CPU.

Exercises the full decide -> probe -> persist -> reuse path without a
TPU by installing the injectable bench hook (deterministic synthetic
timings, no kernels executed) and forcing the wave growth schedule:

  run 1 (cold cache): measure mode probes >0 cells, emits one
         autotune_decision with source "measured", writes the cache;
  run 2 (warm cache): zero probe waves, source "cache", same winning
         cell — the contract bench_compare's autotune_overhead_s
         metric gates in production.

Also asserts `obs explain` renders the decision section.  Exits
nonzero on any violation.  See docs/Autotuning.md.

``--fused`` runs the fused-iteration smoke instead (ops/fused_iter.py,
docs/FusedIteration.md): trains with ``tpu_fused_iter=on`` on CPU,
asserts the model is bit-identical to the staged chain, that the
``fused_iter`` entry compiled, and that the fused run passes the same
same-signature-recompile check as ``obs recompiles --check`` (the
single-compile contract — a fused program that recompiles per
iteration would silently give back everything fusion buys).
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fake_bench(cell, bucket):
    """Synthetic s_per_wave: wider is faster, bf16 beats hilo, ct pays a
    startup tax at this scale.  Deterministic, so
    the winner is stable across runs and platforms."""
    s = 1.0 / max(1, cell.wave_width)
    if cell.hist_hilo:
        s += 0.1
    if cell.hist_mode == "pallas_ct":
        s += 0.5
    return s


def events_of(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def train_once(lgb, X, y, cache_dir, events_path):
    params = {
        "objective": "binary", "num_leaves": 15, "max_bin": 255,
        "min_data_in_leaf": 5, "verbose": -1,
        "tpu_growth": "wave", "tpu_histogram_mode": "pallas_t",
        "tpu_autotune": "measure", "tpu_autotune_cache":
            os.path.join(cache_dir, "autotune_cache.json"),
        "obs_events_path": events_path,
    }
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=2)
    return events_of(events_path)


def fused_main():
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import query

    rng = np.random.default_rng(1)
    X = rng.standard_normal((1500, 10)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)

    fails = []

    def check(cond, msg):
        if not cond:
            fails.append(msg)
            print("FAIL: %s" % msg)

    with tempfile.TemporaryDirectory() as tmp:
        ev_path = os.path.join(tmp, "fused.jsonl")
        fused_params = {
            "objective": "binary", "num_leaves": 15,
            "min_data_in_leaf": 5, "verbose": -1,
            "tpu_fused_iter": "on", "obs_events_path": ev_path,
            "obs_compile": True,
            # the fused program hides the staged g/h the health/audit
            # instruments read between stages
            "obs_health": "off", "obs_split_audit": False,
            "obs_importance_every": 0, "obs_ledger_dir": "",
        }
        staged_params = dict(fused_params, tpu_fused_iter="off",
                             obs_events_path="")
        bst_f = lgb.train(fused_params,
                          lgb.Dataset(X, label=y, params=fused_params),
                          num_boost_round=6)
        bst_s = lgb.train(staged_params,
                          lgb.Dataset(X, label=y, params=staged_params),
                          num_boost_round=6)

        check(bst_f._gbdt._fused_state[0] is not None,
              "tpu_fused_iter=on did not resolve to the fused program")
        check(bst_f.model_to_string() == bst_s.model_to_string(),
              "fused model differs from the staged chain")
        check((bst_f.predict(X) == bst_s.predict(X)).all(),
              "fused predictions differ from the staged chain")

        evs = events_of(ev_path)
        check(any(e.get("ev") == "compile"
                  and e.get("entry") == "fused_iter" for e in evs),
              "fused run never compiled the fused_iter entry")
        iters = [e for e in evs if e.get("ev") == "iter"]
        check(bool(iters) and all(
            e.get("host_orchestration_s", -1.0) >= 0.0 for e in iters),
            "fused timeline missing host_orchestration_s")

        # the `obs recompiles --check` gate on the fused timeline: no
        # entry may recompile a signature it already compiled
        import io
        buf = io.StringIO()
        thrash = query.render_recompiles(evs, out=buf)
        check(thrash is False,
              "fused run thrashed the jit cache:\n%s" % buf.getvalue())

    if fails:
        print("fused smoke: %d failure(s)" % len(fails))
        return 1
    print("fused smoke: OK (fused == staged over 6 rounds, "
          "single fused_iter compile)")
    return 0


def main():
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import autotune

    rng = np.random.default_rng(0)
    X = rng.standard_normal((2000, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)

    fails = []

    def check(cond, msg):
        if not cond:
            fails.append(msg)
            print("FAIL: %s" % msg)

    with tempfile.TemporaryDirectory() as tmp:
        autotune.install_probe_hooks(bench=fake_bench)
        try:
            ev1 = train_once(lgb, X, y, tmp,
                             os.path.join(tmp, "run1.jsonl"))
            ev2 = train_once(lgb, X, y, tmp,
                             os.path.join(tmp, "run2.jsonl"))
        finally:
            autotune.clear_probe_hooks()

        d1 = [e for e in ev1 if e.get("ev") == "autotune_decision"]
        p1 = [e for e in ev1 if e.get("ev") == "autotune_probe"]
        d2 = [e for e in ev2 if e.get("ev") == "autotune_decision"]
        p2 = [e for e in ev2 if e.get("ev") == "autotune_probe"]

        check(len(d1) == 1, "run1: expected 1 decision, got %d" % len(d1))
        check(len(p1) > 0, "run1: expected >0 probes (cold cache)")
        check(d1 and d1[0].get("source") == "measured",
              "run1: source %r != 'measured'" % (d1 and d1[0].get("source")))
        check(len(d2) == 1, "run2: expected 1 decision, got %d" % len(d2))
        check(len(p2) == 0,
              "run2: expected 0 probes on warm cache, got %d" % len(p2))
        check(d2 and d2[0].get("source") == "cache",
              "run2: source %r != 'cache'" % (d2 and d2[0].get("source")))
        check(d2 and d2[0].get("cache_hit") is True, "run2: cache_hit false")
        if d1 and d2:
            check(d1[0].get("cell") == d2[0].get("cell"),
                  "cached cell differs from measured winner: %r vs %r"
                  % (d1[0].get("cell"), d2[0].get("cell")))
        cache = os.path.join(tmp, "autotune_cache.json")
        check(os.path.exists(cache), "cache file not written")
        if os.path.exists(cache):
            with open(cache) as f:
                blob = json.load(f)
            check(blob.get("entries"), "cache file has no entries")

        import io

        from lightgbm_tpu.obs import query
        buf = io.StringIO()
        query.render_explain(
            query.load_timeline(os.path.join(tmp, "run1.jsonl")), out=buf)
        check("autotune" in buf.getvalue(),
              "obs explain does not mention autotune")

    if fails:
        print("autotune smoke: %d failure(s)" % len(fails))
        return 1
    print("autotune smoke: OK (run1 probed %d cells -> %s; "
          "run2 cache hit, 0 probes)"
          % (len(p1), d1[0]["cell"] if d1 else "?"))
    return 0


if __name__ == "__main__":
    sys.exit(fused_main() if "--fused" in sys.argv[1:] else main())
