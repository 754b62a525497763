"""Compare two bench/timeline artifacts and gate on perf regressions.

CI needs a yes/no answer to "did this PR make the bench slower", not a
human squinting at BENCH_*.json — the discipline 1809.04559 frames as
the hard part of GBDT perf work.  This tool loads two artifacts, lines
up the comparable metrics, applies per-metric tolerances, and exits
nonzero on regression so a workflow can gate on it.

Accepted artifact kinds (auto-detected per file):

* an obs JSONL timeline (``obs_events_path`` / ``bench.py --dry``) —
  iters/sec over the LAST run's fenced iter records, compile seconds
  from the run_end entry summaries (or compile events), recompile
  count from ``compile_attr`` events (``obs_compile=true``), peak
  device memory from memory snapshots (absent on CPU);
* a ``BENCH_r*.json`` lineage record — ``parsed.value`` with
  ``parsed.unit`` of iters/sec;
* a bare bench JSON line — ``{"metric": ..., "value": ...}`` as printed
  by ``bench.py --child``.

Direction is per metric: iters/sec regresses when the candidate drops
below baseline x (1 - tol); compile time and peak memory regress when
the candidate exceeds baseline x (1 + tol).  A ZERO baseline breaks the
relative form, so those cells gate on the absolute delta instead: any
bad-direction move past the (default 0) zero-baseline epsilon regresses.
Metrics present in only one artifact are reported and skipped; no
overlap at all is a usage error.

``--baseline rolling`` swaps the single parent for the cross-run ledger
(lightgbm_tpu/obs/ledger.py): each candidate metric is z-scored against
the median/MAD of the last N comparable clean runs (same suite/shape
filters) and regresses when it sits beyond ``--z`` noise-floored sigmas
in the bad direction.  Metrics with fewer than ``--min-history``
comparable runs fall back to the positional parent compare with a
stderr notice — thin history must not silently pass.

Usage:
    python tools/bench_compare.py BASELINE CANDIDATE \
        [--baseline rolling --ledger DIR --suite NAME --shape NxF \
         --window 8 --min-history 3 --z 3.0] \
        [--tol-ips 0.08] [--tol-compile 0.25] [--tol-mem 0.10] \
        [--tol-recompile 0] [--tol-eval 0.02] \
        [--tol-serve-qps 0.15] [--tol-serve-p99 0.30] \
        [--tol-serve-shed 0.25] \
        [--tol-construct 0.30] [--tol-host-orch 0.50] [--json]

Exit codes: 0 pass, 1 regression beyond tolerance, 2 load/usage error.
"""
import argparse
import json
import os
import sys

EXIT_CODES = """\
exit codes:
  0  pass — every comparable metric within tolerance
  1  regression — at least one metric beyond its tolerance
  2  load/usage error — unreadable artifact or no comparable metrics\
"""

# metric -> (direction, default tolerance); direction +1 = higher is
# better, -1 = lower is better
METRICS = {
    "iters_per_sec": (+1, 0.08),
    "compile_s": (-1, 0.25),
    "peak_mem_bytes": (-1, 0.10),
    # compiles beyond the first per entry (compile_attr events);
    # tolerance 0: ANY new recompile vs a clean baseline is a failure
    "recompile_count": (-1, 0.0),
    # worst first-vs-last barrier arrival gap across ranks (merged
    # multi-rank timelines only — `obs merge` output); a growing skew
    # means a rank got slower relative to its peers
    "barrier_skew_max_s": (-1, 0.50),
    # model quality next to the perf numbers: the last `eval` event's
    # metric (bench --child records it as final_eval_metric).  Assumes a
    # higher-is-better metric (auc — the bench protocol's); a perf win
    # that costs more than 2% quality is a regression, not a win
    "final_eval_metric": (+1, 0.02),
    # serving-tier load numbers (bench_serve.py: the `serve_bench`
    # timeline event / JSON line).  Throughput and tail latency gate
    # separately — a QPS win that blows up p99 is not a win
    "serve_qps": (+1, 0.15),
    "serve_p99_s": (-1, 0.30),
    # fraction of offered requests shed at admission (overload
    # protection).  Zero-baseline rule applies: a non-overload baseline
    # sheds nothing, so ANY shedding in the candidate is a regression;
    # overload-vs-overload runs tolerate 25% load-generator noise
    "serve_shed_rate": (-1, 0.25),
    # dataset construction wall seconds (summed over dataset_construct
    # events, io/streaming.py two-pass ingest).  A pre-binned reload
    # reports sketch_s == bin_s == 0, so candidate-vs-baseline catches
    # both slow binning AND accidental re-binning of a binned artifact
    "construct_s": (-1, 0.30),
    # mean host seconds between device program submissions per iteration
    # (schema v11 iter field, obs/timers.py open_self_seconds) — the
    # number the fused iteration (ops/fused_iter.py) drives to ~0.  A
    # fused baseline sits near zero where scheduler jitter is a large
    # relative move, so the tolerance is wide (50%) — the gate is for
    # real orchestration creep (a new host sync, a regrown glue path),
    # which shows up as multiples, not percentages
    "host_orchestration_s": (-1, 0.50),
    # roofline utilization rollups (schema v13, obs/roofline.py): the
    # last `utilization` event's exec-weighted achieved/peak fractions.
    # Higher is better — a drop means a kernel moved away from its
    # hardware roof even if wall time hid it behind compile or host
    # noise.  Utilization is a ratio of two timed quantities, so the
    # tolerance is wider than it/s (timer noise enters twice)
    "flop_util": (+1, 0.20),
    "hbm_util": (+1, 0.20),
}


def _from_timeline(events):
    """Metrics of the LAST run in an obs timeline."""
    run = events[-1].get("run")
    events = [e for e in events if e.get("run") == run]
    out = {}
    iters = [e for e in events if e.get("ev") == "iter"]
    total = sum(e["time_s"] for e in iters)
    if iters and total > 0:
        out["iters_per_sec"] = len(iters) / total
    run_end = next((e for e in events if e.get("ev") == "run_end"), None)
    entries = (run_end or {}).get("entries") or {}
    if entries:
        out["compile_s"] = sum(st.get("first_s", 0.0)
                               for st in entries.values())
    else:
        compiles = [e for e in events if e.get("ev") == "compile"]
        if compiles:
            out["compile_s"] = sum(e["first_call_s"] for e in compiles)
    peak = 0
    for e in events:
        if e.get("ev") != "memory":
            continue
        for d in e.get("devices", ()):
            peak = max(peak, d.get("peak_bytes_in_use",
                                   d.get("bytes_in_use", 0)))
    if peak:
        out["peak_mem_bytes"] = peak
    # compiles beyond the first, per entry (obs_compile=true runs only —
    # a timeline without compile_attr events just skips the metric)
    attr = [e for e in events if e.get("ev") == "compile_attr"]
    if attr:
        worst = {}
        for e in attr:
            worst[e.get("entry")] = max(worst.get(e.get("entry"), 0),
                                        int(e.get("n_compiles", 1)))
        out["recompile_count"] = sum(n - 1 for n in worst.values())
    # merged multi-rank timelines (`obs merge`) stamp per-collective
    # barrier skew; absent on single-rank shards
    skews = [float(e["skew_s"]) for e in events
             if e.get("ev") == "host_collective" and "skew_s" in e]
    if skews:
        out["barrier_skew_max_s"] = max(skews)
    # final model quality: the LAST eval event's first result (schema v5;
    # runs without metrics simply skip the gate)
    evals = [e for e in events if e.get("ev") == "eval"
             and e.get("results")]
    if evals:
        out["final_eval_metric"] = float(evals[-1]["results"][-1]["value"])
    # serving-tier load results (bench_serve.py timelines)
    serve = [e for e in events if e.get("ev") == "serve_bench"]
    if serve:
        out["serve_qps"] = float(serve[-1]["qps"])
        out["serve_p99_s"] = float(serve[-1]["p99_s"])
        if serve[-1].get("shed_rate") is not None:
            out["serve_shed_rate"] = float(serve[-1]["shed_rate"])
    # host-orchestration glue (schema v11): mean over the run's iter
    # records; older timelines without the field simply skip the metric
    orch = [float(e["host_orchestration_s"]) for e in iters
            if "host_orchestration_s" in e]
    if orch:
        out["host_orchestration_s"] = sum(orch) / len(orch)
    # dataset-construction cost (schema v9): sum over dataset_construct
    # events of the run (train + valid sets all count toward the gate)
    cons = [e for e in events if e.get("ev") == "dataset_construct"]
    if cons:
        out["construct_s"] = sum(
            float(e.get("construct_s",
                        e.get("sketch_s", 0.0) + e.get("bin_s", 0.0)
                        + e.get("write_s", 0.0)))
            for e in cons)
    # pod scale-out summary (schema v12, bench.py --mp) — kept in
    # lockstep with obs/ledger.py metrics_from_events
    sc = [e for e in events if e.get("ev") == "scaling"]
    if sc:
        out["rows_per_sec_per_chip"] = float(
            sc[-1]["rows_per_sec_per_chip"])
        out["weak_scaling_eff"] = float(sc[-1]["efficiency"])
    # roofline rollup (schema v13): the LAST utilization event is the
    # steady-state one — also in lockstep with metrics_from_events
    utils = [e for e in events if e.get("ev") == "utilization"]
    if utils and utils[-1].get("flop_util") is not None:
        out["flop_util"] = float(utils[-1]["flop_util"])
        out["hbm_util"] = float(utils[-1].get("hbm_util", 0.0))
    return out


def _from_parsed(parsed):
    out = {}
    unit = str(parsed.get("unit", ""))
    value = parsed.get("value")
    if value is None:
        return out
    if "iters/sec" in unit or "iters_per_sec" in str(parsed.get("metric",
                                                                "")):
        out["iters_per_sec"] = float(value)
    if parsed.get("final_eval_metric") is not None:
        out["final_eval_metric"] = float(parsed["final_eval_metric"])
    if parsed.get("serve_qps") is not None:
        out["serve_qps"] = float(parsed["serve_qps"])
    if parsed.get("serve_p99_s") is not None:
        out["serve_p99_s"] = float(parsed["serve_p99_s"])
    if parsed.get("serve_shed_rate") is not None:
        out["serve_shed_rate"] = float(parsed["serve_shed_rate"])
    if parsed.get("construct_s") is not None:
        out["construct_s"] = float(parsed["construct_s"])
    if parsed.get("flop_util") is not None:
        out["flop_util"] = float(parsed["flop_util"])
    if parsed.get("hbm_util") is not None:
        out["hbm_util"] = float(parsed["hbm_util"])
    return out


def load_metrics(path):
    """{metric: value} from any accepted artifact kind."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise SystemExit2("cannot read %s: %s" % (path, e))
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            break
    else:
        if records and all(isinstance(r, dict) for r in records):
            if any(r.get("ev") for r in records):        # obs timeline
                return _from_timeline(records)
            for r in reversed(records):   # bench --child / lineage line
                got = _from_parsed(r["parsed"]
                                   if isinstance(r.get("parsed"), dict)
                                   else r)
                if got:
                    return got
            return {}
    # whole-file JSON (BENCH_r*.json lineage, or an indented export)
    try:
        doc = json.loads(text)
    except ValueError as e:
        raise SystemExit2("%s is neither JSONL nor JSON: %s" % (path, e))
    if isinstance(doc, dict):
        if isinstance(doc.get("parsed"), dict):
            return _from_parsed(doc["parsed"])
        return _from_parsed(doc)
    return {}


class SystemExit2(Exception):
    """Load/usage failure -> exit 2 (distinct from regression -> 1)."""


def compare(base, cand, tols, zero_eps=None):
    """[(metric, base, cand, delta, regressed, tol)] over the metrics
    present in both artifacts.  ``delta`` is the relative change except
    against a zero baseline, where it is the finite ABSOLUTE delta
    (`c - b`) and gating switches to the per-metric ``zero_eps``
    epsilon (default 0: any bad-direction move regresses)."""
    zero_eps = zero_eps or {}
    rows = []
    for name, (direction, _) in METRICS.items():
        if name not in base or name not in cand:
            continue
        b, c = float(base[name]), float(cand[name])
        tol = tols.get(name, METRICS[name][1])
        if b == 0:
            # a zero baseline breaks the relative form (and the old
            # inf delta broke --json); gate on the absolute delta in
            # BOTH directions: recompile_count 0 -> 2 regresses, and so
            # does a higher-is-better metric going 0 -> negative
            eps = float(zero_eps.get(name, 0.0))
            delta = c - b
            regressed = (direction < 0 and c > eps) or \
                        (direction > 0 and c < -eps)
        else:
            delta = (c - b) / b
            regressed = (direction > 0 and c < b * (1.0 - tol)) or \
                        (direction < 0 and c > b * (1.0 + tol))
        rows.append((name, b, c, delta, regressed, tol))
    return rows


# ------------------------------------------------- rolling-ledger gating

def _ledger_mod():
    """Import lightgbm_tpu.obs.ledger from the repo this script lives
    in — lazy, so parent-compare runs never touch the package."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from lightgbm_tpu.obs import ledger
    return ledger


def _candidate_cell(path, led):
    """Ledger identity of the candidate timeline: {run, suite, shape,
    device_kind}, or None for non-timeline artifacts.  Derived the same
    way ingestion derives it (header params / context / shape bucket),
    so an un-flagged rolling compare gates against the candidate's OWN
    cell instead of pooling every suite in the ledger."""
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return None
    events, run = [], None
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            return None
        if not isinstance(rec, dict) or not rec.get("ev"):
            return None
        events.append(rec)
        run = rec.get("run", run)
    if not events:
        return None
    events = [e for e in events if e.get("run", run) == run]
    header = next((e for e in events if e.get("ev") == "run_header"), {})
    params = header.get("params") or {}
    ctx = header.get("context") or {}
    suite = str(params.get("obs_ledger_suite") or ctx.get("tool")
                or ctx.get("suite") or "")
    return {"run": run, "suite": suite,
            "shape": led._shape_bucket(events, header),
            "device_kind": led._device_kind(header),
            "world_size": int(header.get("world_size", 1) or 1)}


def rolling_rows(args, tols, base, cand):
    """Rows gated against the ledger's rolling baseline.  Returns
    (rows, modes): rows shaped like compare()'s, modes[name] one of
    'rolling' (z-gate, base column = rolling median) or 'parent'
    (thin history -> positional-parent fallback, noticed on stderr)."""
    led = _ledger_mod()
    ledger = led.Ledger(args.ledger or led.default_ledger_dir())
    entries = ledger.entries()
    cell = _candidate_cell(args.candidate, led) or {}
    exclude = {cell["run"]} if cell.get("run") else set()
    suite = args.suite or cell.get("suite") or None
    shape = args.shape or cell.get("shape") or None
    device_kind = cell.get("device_kind") or None
    # world_size is part of the candidate's shape identity (schema 12):
    # a pod run only gates against same-world-size history
    world_size = cell.get("world_size")
    rows, modes = [], {}
    for name, (direction, _) in METRICS.items():
        if name not in cand:
            continue
        c = float(cand[name])
        comp = led.comparable_entries(
            entries, suite=suite, shape=shape, device_kind=device_kind,
            metric=name, exclude_runs=exclude, world_size=world_size)
        vals = [float(r["metrics"][name]) for r in comp]
        if len(vals) >= args.min_history:
            st = led.rolling_stats(vals, args.window)
            z = (c - st["median"]) / st["sigma"]
            regressed = direction * z < -args.z
            delta = (c - st["median"]) / st["median"] \
                if st["median"] else c - st["median"]
            rows.append((name, st["median"], c, delta, regressed, z))
            modes[name] = "rolling"
        elif name in base:
            print("notice: %s has %d comparable ledger run(s) "
                  "(< %d): falling back to parent compare"
                  % (name, len(vals), args.min_history), file=sys.stderr)
            rows.extend(compare({name: base[name]}, {name: c}, tols))
            modes[name] = "parent"
        else:
            print("notice: %s has %d comparable ledger run(s) "
                  "(< %d) and no parent value: skipped"
                  % (name, len(vals), args.min_history), file=sys.stderr)
    return rows, modes


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="compare two bench/timeline artifacts; nonzero exit "
                    "on perf regression beyond tolerance",
        epilog=EXIT_CODES,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("baseline")
    ap.add_argument("candidate")
    ap.add_argument("--baseline", dest="baseline_mode",
                    choices=("parent", "rolling"), default="parent",
                    help="gate source: 'parent' compares against the "
                         "positional baseline artifact; 'rolling' "
                         "z-scores against the run ledger's rolling "
                         "median/MAD (thin history falls back to "
                         "parent per metric)")
    ap.add_argument("--ledger", default="",
                    help="ledger directory for --baseline rolling "
                         "(default: LGBM_TPU_LEDGER or "
                         "/tmp/lgbm_tpu_ledger)")
    ap.add_argument("--suite", default="",
                    help="restrict rolling history to this ledger suite")
    ap.add_argument("--shape", default="",
                    help="restrict rolling history to this shape bucket")
    ap.add_argument("--window", type=int, default=8,
                    help="rolling-baseline window (last N runs)")
    ap.add_argument("--min-history", type=int, default=3,
                    help="comparable runs required before the rolling "
                         "gate engages (below: parent fallback)")
    ap.add_argument("--z", type=float, default=3.0,
                    help="rolling-gate z-score threshold (MAD-based, "
                         "noise-floored sigma)")
    ap.add_argument("--tol-ips", type=float, default=METRICS[
        "iters_per_sec"][1], help="iters/sec relative tolerance")
    ap.add_argument("--tol-compile", type=float, default=METRICS[
        "compile_s"][1], help="compile-time relative tolerance")
    ap.add_argument("--tol-mem", type=float, default=METRICS[
        "peak_mem_bytes"][1], help="peak-memory relative tolerance")
    ap.add_argument("--tol-recompile", type=float, default=METRICS[
        "recompile_count"][1],
        help="recompile-count relative tolerance (0 = any new "
             "recompile vs a clean baseline fails)")
    ap.add_argument("--tol-eval", type=float, default=METRICS[
        "final_eval_metric"][1],
        help="final eval-metric relative tolerance (higher-is-better)")
    ap.add_argument("--tol-serve-qps", type=float, default=METRICS[
        "serve_qps"][1], help="serving QPS relative tolerance")
    ap.add_argument("--tol-serve-p99", type=float, default=METRICS[
        "serve_p99_s"][1],
        help="serving p99-latency relative tolerance")
    ap.add_argument("--tol-serve-shed", type=float, default=METRICS[
        "serve_shed_rate"][1],
        help="serving shed-rate relative tolerance (a zero-shed "
             "baseline fails on ANY candidate shedding)")
    ap.add_argument("--tol-construct", type=float, default=METRICS[
        "construct_s"][1],
        help="dataset-construction time relative tolerance (a "
             "pre-binned zero-rebin baseline fails on ANY candidate "
             "re-binning)")
    ap.add_argument("--tol-host-orch", type=float, default=METRICS[
        "host_orchestration_s"][1],
        help="per-iteration host-orchestration seconds relative "
             "tolerance (schema v11; the fused-iteration gate)")
    ap.add_argument("--tol-flop-util", type=float, default=METRICS[
        "flop_util"][1],
        help="achieved/peak FLOP-utilization relative tolerance "
             "(schema v13 roofline rollups; higher is better)")
    ap.add_argument("--tol-hbm-util", type=float, default=METRICS[
        "hbm_util"][1],
        help="achieved/peak HBM-bandwidth-utilization relative "
             "tolerance (schema v13 roofline rollups)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable verdict on stdout")
    args = ap.parse_args(argv)
    tols = {"iters_per_sec": args.tol_ips, "compile_s": args.tol_compile,
            "peak_mem_bytes": args.tol_mem,
            "recompile_count": args.tol_recompile,
            "final_eval_metric": args.tol_eval,
            "serve_qps": args.tol_serve_qps,
            "serve_p99_s": args.tol_serve_p99,
            "serve_shed_rate": args.tol_serve_shed,
            "construct_s": args.tol_construct,
            "host_orchestration_s": args.tol_host_orch,
            "flop_util": args.tol_flop_util,
            "hbm_util": args.tol_hbm_util}
    try:
        base = load_metrics(args.baseline)
        cand = load_metrics(args.candidate)
    except SystemExit2 as e:
        print("error: %s" % e, file=sys.stderr)
        return 2
    modes = {}
    if args.baseline_mode == "rolling":
        try:
            rows, modes = rolling_rows(args, tols, base, cand)
        except Exception as e:
            print("error: rolling baseline unavailable: %s" % e,
                  file=sys.stderr)
            return 2
    else:
        rows = compare(base, cand, tols)
    if not rows:
        print("error: no comparable metrics between %s (%s) and %s (%s)"
              % (args.baseline, sorted(base) or "none",
                 args.candidate, sorted(cand) or "none"), file=sys.stderr)
        return 2
    regressed = [r for r in rows if r[4]]
    if args.json:
        print(json.dumps({
            "status": "regression" if regressed else "ok",
            "mode": args.baseline_mode,
            "metrics": [dict(
                {"metric": n, "baseline": b, "candidate": c,
                 "regressed": r},
                **({"z": round(t, 3), "delta_frac": round(d, 6),
                    "gate": "rolling"}
                   if modes.get(n) == "rolling" else
                   {"delta_frac": round(d, 6), "tolerance": t,
                    "gate": modes.get(n, "parent"),
                    "delta_kind": "abs" if b == 0 else "frac"}))
                        for n, b, c, d, r, t in rows]}))
    else:
        print("%-16s %14s %14s %9s %7s  verdict"
              % ("metric", "baseline", "candidate", "delta", "gate"))
        for n, b, c, d, r, t in rows:
            if modes.get(n) == "rolling":
                gate = "z%+.1f" % t
            else:
                gate = "%.0f%%" % (100 * t) if b != 0 else "abs"
            delta = "%+8.2f%%" % (100 * d) if b != 0 else "%+9.4g" % d
            print("%-16s %14.6g %14.6g %s %7s  %s"
                  % (n, b, c, delta, gate,
                     "REGRESSED" if r else "ok"))
        skipped = (set(base) | set(cand)) - {r[0] for r in rows}
        if skipped:
            print("skipped (present in only one artifact): %s"
                  % ", ".join(sorted(skipped)))
    if regressed:
        print("FAIL: %d metric(s) regressed beyond %s"
              % (len(regressed),
                 "the rolling noise band" if args.baseline_mode ==
                 "rolling" else "tolerance"), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
