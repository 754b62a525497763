"""What the program recorded from inside, joined with the run.

``lightgbm_tpu.obs.timers`` keeps a process-wide ring of the program's own
spans (set-up, one ``iteration`` and its ``dispatch`` an update) and of one
counter record a tree, and the step program's table from HLO instruction
name to ``jax.named_scope``.  The readers under ``metrics/`` take their
numbers from here: device seconds by scope (the trace's self times by
name, folded by the program's own ``device_time_by_scope``), the window's
iterations and trees (the ring's last ``len(run["trees"])``), and the
set-up spans.  A program without the ring (the parent of the PR that
brought it), an empty ring, no table or no trace gives ``None``, never 0.
"""
import statistics

UPLOAD_HOST_SPANS = ("host_copy", "h2d", "concat", "transpose_xt")
COMPILE_SPANS = ("trace", "lower", "compile", "cache_load")


def timers():
    """The program's span module, or None where it has no ring."""
    try:
        from lightgbm_tpu.obs import timers as module
    except ImportError:
        return None
    return module if hasattr(module, "device_time_by_scope") else None


def records():
    module = timers()
    return module.snapshot() if module else []


def device_seconds(run):
    """``{scope: seconds}`` of the traced window by the step program's
    table: of the tables registered, the one that names most of the
    window's device time (other programs' few microseconds stay
    unscoped)."""
    module = timers()
    ops = (run.get("trace") or {}).get("device_ops")
    if not module or not ops:
        return None
    best = None
    for table in module.device_scopes().values():
        folded = module.device_time_by_scope(ops, table)
        if best is None or folded.get(module.UNSCOPED, 0.0) \
                < best.get(module.UNSCOPED, 0.0):
            best = folded
    return best


def scope_pct(run, *scopes):
    """100 x device seconds under `scopes` over device busy time."""
    seconds = device_seconds(run)
    busy = (run.get("trace") or {}).get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * sum(seconds.get(s, 0.0) for s in scopes) / busy


def window_counters(run):
    """The summed counter records of the window's trees: the ring's last
    ``len(run["trees"])`` ``tree`` records."""
    n = len(run.get("trees") or [])
    trees = [r["fields"] for r in records()
             if r["kind"] == "count" and r["name"] == "tree"][-n:]
    if not n or len(trees) < n:
        return None
    return {key: sum(int(t[key]) for t in trees) for key in trees[0]
            if key not in ("it", "tree")}


def counter_pct(run, useful, spent):
    total = window_counters(run)
    if not total or not total.get(spent):
        return None
    return 100.0 * total[useful] / total[spent]


def window_dispatch_ms(run):
    """Median ``dispatch`` span of the window's iterations."""
    n = len(run.get("trees") or [])
    recs = records()
    its = [r["seq"] for r in recs
           if r["kind"] == "span" and r["name"] == "iteration"][-n:]
    times = [(r["t1"] - r["t0"]) * 1e-6 for r in recs
             if r["kind"] == "span" and r["name"] == "dispatch"
             and r["cause"] in its]
    if not n or len(its) < n or not times:
        return None
    return statistics.median(times)


def self_seconds_inside(outer, names):
    """Summed self time of the spans called `names` that lie inside the
    last span `outer` matches (a predicate on a record)."""
    module = timers()
    recs = [r for r in records() if r["kind"] == "span"]
    frames = [r for r in recs if outer(r)]
    if not module or not frames:
        return None
    frame = frames[-1]
    own = module.self_seconds(recs)
    inside = [own[r["seq"]] for r in recs if r["name"] in names
              and r["t0"] >= frame["t0"] and r["t1"] <= frame["t1"]]
    return sum(inside) if inside else None


def upload_host_s():
    return self_seconds_inside(lambda r: r["name"] == "booster_init",
                               UPLOAD_HOST_SPANS)


def first_iter_host_s():
    return self_seconds_inside(
        lambda r: r["name"] == "iteration" and r["ids"].get("it") == 0,
        COMPILE_SPANS)
