"""Serving benchmark: latency distribution + sustained QPS of the serve tier.

Drives ``Booster.serve()`` (lightgbm_tpu/serve) with a closed-loop load
generator — N submitter threads, each firing mixed-size requests and
waiting for its future — and reports p50/p99 request latency and
sustained queries/sec.  The numbers land in an obs JSONL timeline as a
``serve_bench`` event (next to the ``compile_attr`` and sampled
``serve_batch`` events the serve tier emits), so ``tools/bench_compare.py``
can gate ``serve_qps`` / ``serve_p99_s`` between runs and ``obs
recompiles --check`` can assert the steady state compiled nothing.

Prints ONE JSON line:
    {"metric", "value", "unit", "serve_qps", "serve_p50_s", "serve_p99_s",
     "requests", "path"}

``--dry`` is the CI smoke (JAX_PLATFORMS=cpu): a tiny model, a short
mixed-size burst, then hard asserts — schema-valid timeline, zero
steady-state compiles, every ``compile_attr`` entry compiled exactly
once, serve output matching ``Booster.predict``, zero sheds, and a
full serving-telemetry trail (serve_request / serve_slo /
serve_summary) that ``obs serve --check`` accepts.

``--overload`` replaces the closed loop with open-loop bursts against
a deliberately small queue (tight ``queue_limit`` + per-request
deadline + a fault-hook execution floor), then asserts the overload
protection actually worked: nonzero shed rate, p99 of the ADMITTED
requests still bounded, and a ``slo_burn_rate`` health warning on the
timeline.  The JSON line gains ``serve_shed_rate`` for
``tools/bench_compare.py``.
"""
import argparse
import json
import os
import sys
import threading
import time

import numpy as np


def build_model(rows, features, leaves, rounds):
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    w = rng.normal(size=features)
    y = (X @ w > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "verbose": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    return bst, np.asarray(X, np.float64), w


def run_load(sp, X, requests, threads, sizes, seed=5):
    """Closed-loop load: each thread submits ``requests // threads``
    mixed-size blocks and waits for each future.  Returns (latencies,
    wall_s, rows_scored)."""
    lat = [[] for _ in range(threads)]
    rows = [0] * threads
    per = max(requests // threads, 1)

    def worker(i):
        rng = np.random.default_rng(seed + i)
        for _ in range(per):
            n = int(rng.choice(sizes))
            lo = int(rng.integers(0, max(X.shape[0] - n, 1)))
            t0 = time.perf_counter()
            sp.submit(X[lo:lo + n]).result()
            lat[i].append(time.perf_counter() - t0)
            rows[i] += n

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    return np.concatenate([np.asarray(x) for x in lat]), wall, sum(rows)


def run_overload(sp, X, requests, threads, burst, sizes, seed=7):
    """Open-loop burst load for ``--overload``: each worker fires
    ``burst`` futures back-to-back (no waiting between submits), then
    drains them, counting requests the scheduler shed at admission.
    Returns (admitted_latencies, wall_s, offered, shed, rows_scored)."""
    from lightgbm_tpu.serve import ServeOverloadError
    lat = [[] for _ in range(threads)]
    shed = [0] * threads
    rows = [0] * threads
    per = max(requests // threads, 1)

    def worker(i):
        rng = np.random.default_rng(seed + i)
        done = 0
        while done < per:
            b = min(burst, per - done)
            done += b
            pend = []
            for _ in range(b):
                n = int(rng.choice(sizes))
                lo = int(rng.integers(0, max(X.shape[0] - n, 1)))
                pend.append((time.perf_counter(), n,
                             sp.submit(X[lo:lo + n])))
            for t0, n, f in pend:
                try:
                    f.result()
                    lat[i].append(time.perf_counter() - t0)
                    rows[i] += n
                except ServeOverloadError:
                    shed[i] += 1

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    return (np.concatenate([np.asarray(x) for x in lat]), wall,
            per * threads, sum(shed), sum(rows))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serving-tier load benchmark (p50/p99 latency, QPS)")
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke: tiny shape + hard telemetry asserts")
    ap.add_argument("--overload", action="store_true",
                    help="open-loop burst load against a small queue + "
                         "per-request deadline; asserts shed rate > 0, "
                         "bounded p99 of admitted, burn-rate alert")
    ap.add_argument("--drift", action="store_true",
                    help="drift drill: train on one distribution, serve "
                         "a mean-shifted stream (drift alert MUST fire, "
                         "`obs drift --check` exits 1) and an unshifted "
                         "control (MUST stay clean, exits 0); control "
                         "timeline lands at <obs-path>.control")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="scheduler queue limit in requests "
                         "(overload default 48)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (overload default 50)")
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (default 4000 dry / 200000 full)")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--leaves", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default 400 dry / 5000 full)")
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--obs-path", default=None,
                    help="serve timeline path (default /tmp/bench_serve_"
                         "obs_<pid>.jsonl)")
    ap.add_argument("--ledger", default=None,
                    help="cross-run ledger directory (default "
                         "LGBM_TPU_LEDGER or /tmp/lgbm_tpu_ledger; "
                         "empty string disables ingestion)")
    args = ap.parse_args(argv)


    from lightgbm_tpu.utils.common import enable_compilation_cache
    enable_compilation_cache()

    rows = args.rows or (4000 if args.dry else 200_000)
    leaves = args.leaves or (15 if args.dry else 255)
    rounds = args.rounds or (10 if args.dry else 100)
    requests = args.requests or (1600 if args.overload
                                 else 400 if args.dry else 5000)
    obs_path = args.obs_path or ("/tmp/bench_serve_obs_%d.jsonl"
                                 % os.getpid())
    try:
        os.unlink(obs_path)
    except OSError:
        pass

    bst, X, w = build_model(rows, args.features, leaves, rounds)

    if args.drift:
        return _drift_drill(bst, X, w, obs_path, args)

    # the serve run gets its OWN timeline (training closes its observer
    # when lgb.train returns): compile attribution lands here so `obs
    # recompiles --check` sees the per-bucket serve entries, plus a
    # sampled serve_batch trail for postmortems
    import jax
    from lightgbm_tpu.obs import RunObserver
    from lightgbm_tpu.obs.ledger import default_ledger_dir
    ledger_dir = (default_ledger_dir() if args.ledger is None
                  else args.ledger)
    # --dry also stands up the live telemetry plane (obs/live.py,
    # port 0 = ephemeral): the scrape-under-load assert below proves the
    # serving process exposes /statusz with the queue depth + SLO
    # headline, and that being scraped sheds nothing and compiles
    # nothing in steady state
    obs = RunObserver(events_path=obs_path, compile_attr=True,
                      ledger_dir=ledger_dir,
                      ledger_suite="serve_overload" if args.overload
                      else "serve",
                      http_port=(0 if args.dry else None),
                      # incident engine armed on the gated drills: the
                      # overload shed storm must OPEN one, the clean dry
                      # run must open ZERO (asserted below)
                      incident=(args.dry or args.overload),
                      incident_window_s=10.0,
                      incident_dir=obs_path + ".incidents",
                      # continuous host profiler: the serve worker's
                      # queue/encode/execute split shows up as folded
                      # stacks under the lgbm-*-microbatch role
                      prof_hz=29, prof_window_s=5.0)
    obs.run_header(backend=jax.default_backend(),
                   devices=[str(d) for d in jax.local_devices()],
                   params={"requests": requests, "threads": args.threads,
                           "max_delay_ms": args.max_delay_ms,
                           "max_batch": args.max_batch},
                   context={"tool": "bench_serve"})
    obs.prof_arm()                      # obs.close() disarms + flushes

    # request-size mix: singletons up to full buckets, so the deadline
    # flush, padding, and every bucket rung all see traffic
    sizes = [1, 3, 16, 50, 120, 400] if args.dry else \
            [1, 8, 32, 100, 256, 512, 1024]
    serve_kw = {"max_delay_ms": args.max_delay_ms,
                "max_batch": args.max_batch, "observer": obs,
                "batch_event_every": 8}
    deadline_ms = 0.0
    if args.overload:
        # small queue, tight deadline, a fault-hook execution floor so
        # even a fast CPU model saturates, and an SLO target every
        # request will blow through — the burn-rate alert MUST fire
        deadline_ms = args.deadline_ms or 50.0
        sizes = [1, 3, 8]
        serve_kw.update(
            max_batch=32, max_delay_ms=1.0,
            queue_limit=args.queue_limit or 48,
            request_deadline_ms=deadline_ms,
            request_event_every=8, batch_event_every=4,
            slo_p99_ms=5.0, slo_window_s=3.0, slo_every_s=0.25,
            slo_mode="warn",
            fault_hook=lambda route, batch: time.sleep(0.004))
    elif args.dry:
        # generous targets: the point is the telemetry trail
        # (serve_request / serve_slo / serve_summary), not breaching
        serve_kw.update(request_event_every=4, slo_p99_ms=60_000.0,
                        slo_window_s=5.0, slo_every_s=0.5)
    with bst.serve(**serve_kw) as sp:
        # warm the FULL rung ladder (coalesced batches can land on any
        # bucket up to max_batch), then mark warm: any later compile is
        # a steady-state violation
        buckets = []
        if sp.cache is not None:
            rungs, b = [], sp.cache.bucket_min
            while b < sp.cache.max_batch:
                rungs.append(b)
                b <<= 1
            rungs.append(sp.cache.max_batch)
            buckets = sp.cache.warmup(rungs)
            sp.cache.mark_warm()
        if args.overload:
            lat, wall, offered, shed, nrows = run_overload(
                sp, X, requests, args.threads, burst=24, sizes=sizes)
        elif args.dry:
            # scrape /statusz CONCURRENTLY with the load: the live plane
            # reads host-side state only, so the data plane must not
            # notice (the zero-shed / zero-steady-state-compile asserts
            # in _dry_asserts run against exactly this scraped window)
            import threading as _threading
            import urllib.request as _urlreq
            assert obs.live_url.startswith("http://127.0.0.1:"), \
                "serve --dry: live plane did not bind"
            scraped = {"n": 0}
            stop_scrape = _threading.Event()

            def _scraper():
                while not stop_scrape.is_set():
                    with _urlreq.urlopen(obs.live_url + "/statusz",
                                         timeout=5) as r:
                        scraped["last"] = json.loads(r.read().decode())
                    scraped["n"] += 1
                    time.sleep(0.02)

            scr = _threading.Thread(target=_scraper, daemon=True)
            scr.start()
            try:
                lat, wall, nrows = run_load(sp, X, requests,
                                            args.threads, sizes)
            finally:
                stop_scrape.set()
                scr.join(timeout=10)
            offered, shed = len(lat), 0
            assert scraped["n"] > 0, "statusz scraper never completed"
            flight = (scraped.get("last") or {}).get("flight") or {}
            assert "serve" in flight and \
                "queue_depth" in flight["serve"], \
                "/statusz under load missing serve queue state: %r" \
                % flight
            assert "slo" in flight and "targets" in flight["slo"], \
                "/statusz under load missing the SLO headline: %r" \
                % flight
        else:
            lat, wall, nrows = run_load(sp, X, requests, args.threads,
                                        sizes)
            offered, shed = len(lat), 0
        stats = sp.stats()
    qps = len(lat) / wall if wall else 0.0
    p50 = float(np.percentile(lat, 50)) if len(lat) else 0.0
    p99 = float(np.percentile(lat, 99)) if len(lat) else 0.0
    shed_rate = shed / float(offered) if offered else 0.0
    ssc = (stats.get("executables") or {}).get("steady_state_compiles")

    obs.event("serve_bench", qps=round(qps, 3),
              p50_s=round(p50, 6), p99_s=round(p99, 6),
              requests=len(lat), rows=int(nrows),
              rows_per_s=round(nrows / wall, 1) if wall else 0.0,
              threads=args.threads, wall_s=round(wall, 3),
              batches=stats["batches"], pad_rows=stats["pad_rows"],
              buckets=buckets, offered=int(offered), shed=int(shed),
              shed_rate=round(shed_rate, 4),
              deadline_ms=deadline_ms,
              steady_state_compiles=ssc)
    obs.close()

    if args.overload:
        _overload_asserts(obs_path, offered, shed, p99, deadline_ms,
                          stats)
    elif args.dry:
        _dry_asserts(bst, X, obs_path, ssc, stats)

    print(json.dumps({
        "metric": "serve_qps_mixed%dthreads" % args.threads,
        "value": round(qps, 3), "unit": "req/s",
        "serve_qps": round(qps, 3),
        "serve_p50_s": round(p50, 6), "serve_p99_s": round(p99, 6),
        "requests": len(lat), "rows": int(nrows),
        "offered": int(offered), "serve_shed": int(shed),
        "serve_shed_rate": round(shed_rate, 4),
        "steady_state_compiles": ssc,
        "path": obs_path,
    }))


def _drift_drill(bst, X, w, obs_path, args):
    """The drift drill (``--dry --drift``): the model trained on
    N(0,1)^d serves two streams through a drift-monitored
    ServingPredictor — a mean-shifted one (the drift alert MUST fire;
    ``obs drift --check`` exits 1 on its timeline) and an unshifted
    i.i.d. control (zero alerts over the whole run; exits 0).  The
    control also joins delayed labels so the ``online_quality`` channel
    is exercised end-to-end.  Both sessions keep the PR-6/7 serve
    guarantees: warmed rung ladder, zero steady-state compiles."""
    import jax
    from lightgbm_tpu.obs import RunObserver, read_events
    from lightgbm_tpu.obs.drift import drift_metrics
    from lightgbm_tpu.obs.ledger import default_ledger_dir
    ledger_dir = (default_ledger_dir() if args.ledger is None
                  else args.ledger)
    control_path = obs_path + ".control"
    rng = np.random.default_rng(23)
    block, blocks = 256, 8
    out = {}

    for name, path in (("shifted", obs_path), ("control", control_path)):
        try:
            os.unlink(path)
        except OSError:
            pass
        obs = RunObserver(events_path=path, compile_attr=True,
                          ledger_dir=ledger_dir,
                          ledger_suite="serve_drift_%s" % name)
        obs.run_header(backend=jax.default_backend(),
                       devices=[str(d) for d in jax.local_devices()],
                       params={"stream": name, "block": block,
                               "blocks": blocks},
                       context={"tool": "bench_serve", "mode": "drift"})
        with bst.serve(observer=obs, max_batch=block, max_delay_ms=1.0,
                       drift_every=2 * block, drift_window=8 * block,
                       drift_min_labels=64) as sp:
            assert sp.drift is not None and sp.drift.enabled, \
                "drift monitor did not come up (fingerprint missing?)"
            if sp.cache is not None:
                rungs, b = [], sp.cache.bucket_min
                while b < sp.cache.max_batch:
                    rungs.append(b)
                    b <<= 1
                rungs.append(sp.cache.max_batch)
                sp.cache.warmup(rungs)
                sp.cache.mark_warm()
            futs = []
            for i in range(blocks):
                Xb = rng.normal(loc=2.0 if name == "shifted" else 0.0,
                                size=(block, X.shape[1]))
                ids = list(range(i * block, (i + 1) * block))
                futs.append((Xb, ids, sp.submit(Xb, ids=ids)))
            for _, _, f in futs:
                f.result()
            time.sleep(0.2)       # let score-capture callbacks land
            if name == "control":
                for Xb, ids, _ in futs[:2]:
                    sp.record_outcome(
                        ids, (Xb @ w > 0).astype(np.float64))
            stats = sp.stats()
        obs.close()

        evs = read_events(path)   # validates every record (schema 14)
        m = drift_metrics(evs)
        assert m.get("present"), "%s timeline has no drift events" % name
        ssc = (stats.get("executables") or {}).get(
            "steady_state_compiles")
        assert ssc == 0, \
            "%s stream: steady state compiled %r executables" % (name,
                                                                 ssc)
        out[name] = {"psi_max": m.get("psi_max"),
                     "alerts_fired": m["alerts"]["fired"]}
        if name == "shifted":
            assert m["alerts"]["fired"] > 0, \
                "shifted stream fired no drift alert: %r" % m
            warns = [e for e in evs if e["ev"] == "health"
                     and e.get("check") == "drift"
                     and e.get("status") == "warn"]
            assert warns, "drift alert missing from the health channel"
        else:
            assert m["alerts"]["fired"] == 0, \
                "control stream false-positived: %r" % m
            oq = [e for e in evs if e["ev"] == "online_quality"]
            assert oq, "control stream joined labels but emitted no " \
                "online_quality event"
            out[name]["online_auc"] = oq[-1].get("auc")

    print(json.dumps({"status": "serve_drift_ok", "path": obs_path,
                      "control_path": control_path, **out}))


def _dry_asserts(bst, X, obs_path, steady_state_compiles, stats):
    """The CI gates: parseable timeline, the serve event trail present
    (batch traces, sampled request traces, SLO snapshots, the lifetime
    summary), zero steady-state compiles, zero sheds, and correct
    predictions."""
    from lightgbm_tpu.obs import read_events
    evs = read_events(obs_path)          # validates every record
    kinds = {e["ev"] for e in evs}
    for need in ("run_header", "compile", "compile_attr", "serve_batch",
                 "serve_request", "serve_slo", "serve_summary",
                 "serve_bench", "run_end"):
        assert need in kinds, "serve timeline missing %r events" % need
    assert stats.get("shed_total", 0) == 0, \
        "non-overload dry run shed requests: %r" % stats.get("shed")
    assert not [e for e in evs if e["ev"].startswith("incident_")], \
        "clean serve dry run opened an incident — the control side of " \
        "the CI incident gate must stay silent"
    reqs = [e for e in evs if e["ev"] == "serve_request"]
    assert all("queue_s" in e.get("spans", {}) for e in reqs), \
        "serve_request trace missing queue_s span"
    serve_attr = [e for e in evs if e["ev"] == "compile_attr"
                  and str(e.get("entry", "")).startswith("serve_predict")]
    assert serve_attr, "no serve compile_attr entries recorded"
    thrash = [e for e in serve_attr if e.get("sig_compiles", 1) > 1
              or e.get("n_compiles", 1) > 1]
    assert not thrash, "serve entry recompiled: %r" % thrash
    assert steady_state_compiles == 0, \
        "steady state compiled %r executables" % steady_state_compiles
    sb = [e for e in evs if e["ev"] == "serve_bench"][-1]
    assert sb["qps"] > 0 and sb["p99_s"] >= sb["p50_s"] > 0
    # correctness probe: the serve path must match Booster.predict
    with bst.serve(max_delay_ms=0.5) as sp:
        got = sp.predict(X[:100])
    want = bst.predict(X[:100])
    assert np.allclose(got, want, rtol=2e-6, atol=1e-7), \
        "serve prediction diverged from Booster.predict"
    print(json.dumps({"status": "serve_dry_ok", "events": len(evs),
                      "serve_compiles": len(serve_attr)}),
          file=sys.stderr)


def _overload_asserts(obs_path, offered, shed, p99_admitted,
                      deadline_ms, stats):
    """The overload gates: the protection sheds (rate > 0, matching the
    scheduler's own count), the ADMITTED requests stay bounded (the
    admission projection is an EWMA estimate, so allow 3x deadline for
    CPU scheduling jitter), and the burn-rate alert reached the
    timeline as a ``slo_burn_rate`` health warning."""
    from lightgbm_tpu.obs import read_events
    evs = read_events(obs_path)
    kinds = {e["ev"] for e in evs}
    for need in ("serve_request", "serve_slo", "serve_summary",
                 "serve_bench"):
        assert need in kinds, "overload timeline missing %r" % need
    assert shed > 0, ("overload run shed nothing (offered %d) — "
                      "queue_limit/deadline not engaging" % offered)
    assert stats.get("shed_total") == shed, \
        "scheduler shed count %r != caller-observed %d" % (
            stats.get("shed_total"), shed)
    bound_s = 3.0 * deadline_ms / 1e3
    assert p99_admitted <= bound_s, \
        "p99 of ADMITTED requests %.1fms exceeds %.0fms (3x deadline)" \
        % (p99_admitted * 1e3, bound_s * 1e3)
    alerts = [e for e in evs if e["ev"] == "health"
              and e.get("check") == "slo_burn_rate"
              and e.get("status") != "ok"]
    assert alerts, "no slo_burn_rate health warning under overload"
    summ = [e for e in evs if e["ev"] == "serve_summary"][-1]
    assert summ["shed_total"] == shed
    # incident engine (obs/incident.py): the shed storm fires
    # incident_signal from the scheduler, and the burn-rate warning
    # joins the same debounce window — ONE grouped incident, with its
    # evidence bundle captured entirely host-side
    opens = [e for e in evs if e["ev"] == "incident_open"]
    closes = [e for e in evs if e["ev"] == "incident_close"]
    assert len(opens) == 1, \
        "overload must open exactly ONE grouped incident, got %d" \
        % len(opens)
    assert closes and "shed_storm" in closes[0]["signals"], \
        "shed storm never reached the incident: %r" % closes
    arts = [e["artifact"] for e in evs if e["ev"] == "incident_evidence"
            and not e.get("error")]
    assert len(arts) >= 3, \
        "overload incident bundle thin (%r) — want ring, metrics, " \
        "statusz at least" % arts
    print(json.dumps({
        "status": "serve_overload_ok", "offered": offered,
        "shed": shed, "shed_rate": round(shed / float(offered), 4),
        "p99_admitted_ms": round(p99_admitted * 1e3, 2),
        "incident_signals": sorted(closes[0]["signals"]),
        "burn_alerts": len(alerts)}), file=sys.stderr)


if __name__ == "__main__":
    main()
