"""The `train_bundled_loop` driver: `train_loop` on a bundled store.

    lgb.Dataset.from_binned(dir) -> lgb.Booster(params, ds) -> Booster.update() ...

The same loop, clock, end-to-end numbers and checks as
``drivers/train_loop.py`` (whose helpers it imports); it differs in two
places only.  The data come from ``gen_onehot`` (one-hot source columns
bundled by the program's own EFB into a few byte columns), and the plain
reference is handed the directory's header, from which it decodes the
bundles itself.  ``run["columns"]`` is the store's GROUP count: EFB is
the reference algorithm's own store, so the algorithm's own work
(``benchmark/shapes.py``) is one byte a group a row, not one a feature.
"""
import gc
import json
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark.files import load_module

_base = load_module("drivers", "train_loop")
CompileCounter, tree_dict, resolved_auto = (
    _base.CompileCounter, _base.tree_dict, _base.resolved_auto)
sync, step, peak_bytes, digest = (
    _base.sync, _base.step, _base.peak_bytes, _base.digest)


def compare(ctx, trees, scores, data_dir, log=print):
    """The numbers that decide `correct`, each beside its limit."""
    from benchmark import gen

    config, traffic = ctx["config"], ctx["traffic"]
    reference = load_module("references", config["reference"])
    shards, label = gen.open_shards(data_dir)
    with open(os.path.join(data_dir, "header.json")) as f:
        header = json.load(f)
    gaps = reference.follow(trees, scores, shards, label, config["params"],
                            header, int(traffic["check_nodes"]), ctx["seed"])
    del shards
    log("first trees %08x; per step: %s" % (digest(trees), [
        {k: (v if k == "leaves" else float("%.3g" % v)) for k, v in s.items()}
        for s in gaps.get("per_step", [])]))
    return {name: {"value": float(gaps[name]), "limit": float(limit)}
            for name, limit in config["limits"].items()}


def run(ctx, log=print):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.common import enable_compilation_cache
    from benchmark import gen_onehot, trace as trace_mod

    config, traffic = ctx["config"], ctx["traffic"]
    devices = ctx["devices"]
    params = dict(config["params"])
    check_steps = int(traffic["check_steps"])
    counter = CompileCounter()
    cache_dir = enable_compilation_cache()
    spans = {}
    # control.py hands in a directory it made, to read several faults on
    # one seed's data; a benchmark run always makes its own
    data_dir = ctx.get("data_dir")
    own_data = data_dir is None
    if own_data:
        data_dir = tempfile.mkdtemp(prefix="binned_")
    trace_dir = tempfile.mkdtemp(prefix="trace_") if ctx["trace"] else None
    try:
        t = time.time()
        if own_data:
            log("generated: %s" % gen_onehot.generate(
                config, ctx["seed"], data_dir, log))
        spans["generate_s"] = time.time() - t
        t = time.time()
        ds = lgb.Dataset.from_binned(data_dir, params=dict(params))
        ds.construct()
        spans["construct_s"] = time.time() - t
        t = time.time()
        bst = lgb.Booster(dict(params), ds)
        jax.block_until_ready([a for a in (bst._gbdt.learner.X,
                                           getattr(bst._gbdt.learner, "_Xt",
                                                   None))
                               if a is not None])
        spans["booster_init_s"] = time.time() - t
        scores = []
        for i in range(check_steps):
            t = time.time()
            bst.update()
            scores.append(np.array(bst._gbdt.train_score[0], np.float32))
            if i == 0:
                spans["first_iter_s"] = time.time() - t
        for _ in range(int(traffic["warmup_iterations"])):
            step(bst)
        log("setup: %s" % {k: round(v, 3) for k, v in spans.items()})
        # the store's byte columns: the groups, not the model's features
        with open(os.path.join(data_dir, "header.json")) as f:
            groups = int(json.load(f)["num_columns"])
        info = {"rows": int(ds._handle.num_data), "columns": groups,
                "features": int(ds._handle.num_total_features)}
        log("data: %s; auto resolved to %s" % (info, resolved_auto(bst)))
        log("compile cache %s: %d programs lowered, %d compiled, %d cache "
            "hits" % (cache_dir, counter.lowered, counter.compiled,
                      counter.cache_hits))

        # ---- the window
        lowered_before = counter.lowered
        iter_seconds = []
        attempted = failed = 0
        setup_s = time.time() - ctx["t0"]
        if ctx["trace"]:
            jax.profiler.start_trace(trace_dir)
        start = last = time.time()
        limit = int(traffic["trace_iterations"]) if ctx["trace"] else None
        while (attempted < limit if ctx["trace"]
               else last - start < ctx["seconds"]):
            attempted += 1
            try:
                if ctx["trace"]:
                    with jax.profiler.TraceAnnotation("bench_update"):
                        bst.update()
                    with jax.profiler.TraceAnnotation("bench_sync"):
                        sync(bst)
                else:
                    step(bst)
            except Exception as exc:   # counted, reported, never hidden
                failed += 1
                log("iteration %d raised %r" % (attempted, exc))
            now = time.time()
            iter_seconds.append(now - last)
            last = now
        window_s = last - start
        if ctx["trace"]:
            jax.profiler.stop_trace()
        compiles_in_window = counter.lowered - lowered_before
        peak = peak_bytes(devices)

        # ---- after the window: the answers, then free the program
        t = time.time()
        bst._gbdt._materialize()
        spans["materialize_s"] = time.time() - t
        models = list(bst._gbdt.models)
        trees = [tree_dict(m) for m in models[:check_steps]]
        first = check_steps + int(traffic["warmup_iterations"])
        window_trees = ([tree_dict(m) for m in models[first:first + attempted]]
                        if ctx["trace"] else [])
        final = np.asarray(bst._gbdt.train_score[0])
        if not np.all(np.isfinite(final)):
            failed = attempted      # a non-finite score taints every step
        completed = attempted - failed
        del bst, ds, models, final
        gc.collect()

        t = time.time()
        checks = compare(ctx, trees, scores, data_dir, log)
        checks["compiles_in_window"] = {"value": float(compiles_in_window),
                                        "limit": 0.0}
        checks["failed_iterations"] = {"value": float(failed), "limit": 0.0}
        spans["compare_s"] = time.time() - t
        correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                      for c in checks.values())
        log("window: %d iterations in %.3f s; peak %d B; compare %.1f s"
            % (completed, window_s, peak, spans["compare_s"]))

        dev0 = devices[0]
        device = {"platform": dev0.platform, "kind": dev0.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        out = {"correct": correct, "attempted": attempted, "failed": failed,
               "checks": checks, "device": device,
               "end_to_end": {
                   "iters_per_s": completed / window_s if window_s else 0.0,
                   "peak_hbm_gib": peak / 2.0 ** 30,
                   "setup_s": setup_s}}
        if ctx["trace"]:
            t = time.time()
            reduced = trace_mod.reduce_dir(trace_dir, len(devices))
            log("trace read in %.1f s" % (time.time() - t))
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                "idle_gaps": reduced["idle_gaps"][:10]}
            out["run"] = {"spans": spans, "iter_seconds": iter_seconds,
                          "window_s": window_s, "trace": reduced,
                          "trees": window_trees,
                          "rows": info["rows"], "columns": info["columns"],
                          "device_kind": dev0.device_kind}
        return out
    finally:
        if own_data:
            shutil.rmtree(data_dir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
