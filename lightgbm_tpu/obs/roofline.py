"""Roofline attribution: achieved vs peak for every jitted entry.

The timeline has carried the two halves of a roofline model since PR 1
without ever joining them: ``compile_attr`` events record XLA's
``cost_analysis`` FLOPs / bytes-accessed estimates per compiled entry
(obs/compile.py), and ``run_end.entries`` records the measured
compile-vs-execute wall-time split (obs/timers.py).  This module closes
the loop against a device-peak registry:

    achieved FLOP/s   = flops / exec_mean_s
    achieved B/s      = bytes_accessed / exec_mean_s
    arithmetic intensity (AI) = flops / bytes_accessed
    roof_s   = max(flops / peak_flops, bytes / peak_hbm [, ici terms])
    headroom = (exec_mean_s - roof_s) * exec_n     # seconds recoverable

and classifies each entry as **compute**-, **memory**-,
**collective**- or **host-orchestration**-bound — the instrument the
GPU-GBDT literature (arxiv 1706.08359 frames histogram building as a
memory-bandwidth roofline problem) and the accelerator-design paper
(arxiv 2011.02022, per-stage utilization) both assume exists.

Two consumers:

* ``python -m lightgbm_tpu obs roofline RUN.jsonl [--check]``
  (obs/query.py) renders the headroom-ranked table; ``--check`` fails
  when the timeline is structurally unusable (no finished run, or no
  cost estimates at all — run with ``obs_compile=true``);
* ``RunObserver.iter_end`` emits a per-iteration ``utilization``
  rollup event (schema 13, ``obs_utilization_every``) whose
  ``flop_util`` / ``hbm_util`` feed the cross-run ledger and the
  ``bench_compare`` gate exactly like it/s.

Peaks are **dataplane ceilings, not promises**: the table below holds
published per-chip figures for the TPU generations the wave engine
targets plus a deliberately modest ``cpu`` row so the whole layer is
testable off-TPU.  A ``device_kind`` that is not in the table is an
error (``peaks_for`` raises): a utilization against another device's
roof is not a number.  Override or extend via ``obs_roofline_peaks`` (a
JSON file mapping device kinds to profiles, merged over the defaults; a
kind the table does not have must give every field).
"""
from __future__ import annotations

import json
import sys

from ..utils.log import Log

# -- device-peak registry ------------------------------------------------
# Per-chip dataplane peaks keyed by normalized device_kind.  Fields:
#   flops_f32 / flops_bf16  peak FLOP/s by compute dtype (MXU)
#   hbm_bytes_per_s         main-memory bandwidth
#   ici_bytes_per_s         aggregate interconnect bandwidth per chip
#   vmem_bytes              on-chip vector memory
# Figures are the published per-chip numbers (bf16 MXU peak; f32 taken
# as half the bf16 rate where the generation has no native f32 MXU
# path).  They bound attribution, they do not certify hardware.
DEFAULT_PEAKS = {
    "tpu_v4": {
        "flops_f32": 137.5e12, "flops_bf16": 275e12,
        "hbm_bytes_per_s": 1228e9, "ici_bytes_per_s": 300e9,
        "vmem_bytes": 128 * 2**20,
    },
    "tpu_v5_lite": {
        "flops_f32": 98.5e12, "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 400e9,
        "vmem_bytes": 128 * 2**20,
    },
    "tpu_v5p": {
        "flops_f32": 229.5e12, "flops_bf16": 459e12,
        "hbm_bytes_per_s": 2765e9, "ici_bytes_per_s": 600e9,
        "vmem_bytes": 128 * 2**20,
    },
    "tpu_v6_lite": {
        "flops_f32": 459e12, "flops_bf16": 918e12,
        "hbm_bytes_per_s": 1640e9, "ici_bytes_per_s": 448e9,
        "vmem_bytes": 128 * 2**20,
    },
    # the CPU backend (CI, tests): a deliberately modest single-socket
    # profile so CPU timelines produce finite, clearly-labelled
    # utilization numbers
    "cpu": {
        "flops_f32": 100e9, "flops_bf16": 100e9,
        "hbm_bytes_per_s": 25e9, "ici_bytes_per_s": 10e9,
        "vmem_bytes": 32 * 2**20,
    },
}

# aliases seen in the wild for jax's device_kind strings
_KIND_ALIASES = {
    "tpu_v5e": "tpu_v5_lite",
    "tpu_v5litepod": "tpu_v5_lite",
    "tpu_v6e": "tpu_v6_lite",
    "trillium": "tpu_v6_lite",
    "cpu_device": "cpu",
}

# below this fraction of EVERY roof the entry is dominated by dispatch /
# host glue, not the dataplane — the launch-overhead regime both GPU
# boosting papers single out (arxiv 1806.11248 §4, 1809.04559 §5)
ORCH_FLOOR = 0.02

BOUNDS = ("compute", "memory", "collective", "host-orchestration")


def normalize_kind(kind):
    """Canonical registry key for a raw ``device_kind`` string."""
    k = str(kind or "").strip().lower().replace(" ", "_").replace("-", "_")
    return _KIND_ALIASES.get(k, k)


def device_kind():
    """This process's device kind (the peaks table's key convention):
    ``jax.devices()[0].device_kind``, else the backend name."""
    try:
        import jax
        return str(jax.devices()[0].device_kind).strip().replace(" ", "_")
    except Exception:
        try:
            import jax
            return str(jax.default_backend())
        except Exception:
            return "cpu"


def load_peak_overrides(path):
    """Parse an ``obs_roofline_peaks`` JSON file: {kind: profile}."""
    if not path:
        return {}
    try:
        with open(path) as f:
            raw = json.load(f)
        return {normalize_kind(k): dict(v) for k, v in raw.items()
                if isinstance(v, dict)}
    except Exception as e:
        Log.warning("obs: roofline peak overrides %s unreadable: %s",
                    path, e)
        return {}


_PEAK_FIELDS = frozenset(DEFAULT_PEAKS["cpu"])


def peaks_for(kind, overrides=None):
    """The peak profile of ``kind`` with provenance attached.

    Resolution: exact normalized match in ``overrides``, then in the
    default table, then a prefix match against the defaults (a
    ``tpu_v5p_pod`` kind still finds ``tpu_v5p``).  A kind that matches
    no row raises ValueError, as does an override for a new kind that
    leaves a field out: there is no default device.
    """
    nk = normalize_kind(kind)
    table = dict(DEFAULT_PEAKS)
    for k, v in (overrides or {}).items():
        base = dict(table.get(normalize_kind(k), {}))
        base.update(v)
        table[normalize_kind(k)] = base
    match = nk if nk in table else next(
        (k for k in table if nk and k != "cpu"
         and (nk.startswith(k) or k.startswith(nk))), None)
    if match is None:
        raise ValueError(
            "no roofline peaks for device_kind %r (normalized %r): add a "
            "row to obs/roofline.py DEFAULT_PEAKS or an obs_roofline_peaks "
            "override; known kinds: %s" % (kind, nk, sorted(table)))
    missing = _PEAK_FIELDS - set(table[match])
    if missing:
        raise ValueError("roofline peaks for %r lack %s"
                         % (match, sorted(missing)))
    return dict(table[match], kind=match,
                source="override" if nk in (overrides or {}) else "table")


# -- the per-entry join --------------------------------------------------

def entry_roofline(cost, exec_mean_s, exec_n, peaks, dtype="f32",
                   ici_bytes=0.0, world_size=1):
    """Join one entry's cost estimate with its measured execute time.

    ``cost`` is the ``compile_attr`` cost dict ({flops, bytes_accessed},
    either may be missing); an entry with no estimate at all classifies
    as host-orchestration with zero utilization — XLA saw nothing worth
    modelling, so dispatch is what its wall time buys.
    """
    cost = cost or {}
    flops = float(cost.get("flops") or 0.0)
    nbytes = float(cost.get("bytes_accessed") or 0.0)
    ici = float(ici_bytes or 0.0) if int(world_size or 1) > 1 else 0.0
    mean = max(float(exec_mean_s or 0.0), 0.0)
    peak_flops = float(peaks.get("flops_%s" % dtype)
                       or peaks.get("flops_f32") or 1.0)
    peak_hbm = float(peaks.get("hbm_bytes_per_s") or 1.0)
    peak_ici = float(peaks.get("ici_bytes_per_s") or 1.0)
    t_compute = flops / peak_flops
    t_memory = nbytes / peak_hbm
    t_ici = ici / peak_ici
    roof_s = max(t_compute, t_memory, t_ici)
    out = {
        "flops": flops, "bytes_accessed": nbytes,
        "achieved_flops_per_s": (flops / mean) if mean > 0 else 0.0,
        "achieved_bytes_per_s": (nbytes / mean) if mean > 0 else 0.0,
        "ai": (flops / nbytes) if nbytes > 0 else None,
        "flop_util": min(1.0, t_compute / mean) if mean > 0 else 0.0,
        "hbm_util": min(1.0, t_memory / mean) if mean > 0 else 0.0,
        "roof_s": roof_s,
        "headroom_s": max(0.0, mean - roof_s) * max(int(exec_n or 0), 0),
        "exec_mean_s": mean, "exec_n": int(exec_n or 0),
    }
    if ici > 0:
        out["ici_util"] = min(1.0, t_ici / mean) if mean > 0 else 0.0
    # bound: the tallest roof wins; under the floor on every roof the
    # entry is pinned by host orchestration, not the dataplane
    fracs = {"compute": out["flop_util"], "memory": out["hbm_util"]}
    if ici > 0:
        fracs["collective"] = out["ici_util"]
    bound = max(fracs, key=lambda k: fracs[k])
    if fracs[bound] < ORCH_FLOOR:
        bound = "host-orchestration"
    out["bound"] = bound
    return out


def _entry_costs(events):
    """{entry: cost dict} — the LAST compile_attr per entry wins (the
    steady-state program; early shape-warmup compiles are superseded)."""
    costs = {}
    for e in events:
        if e.get("ev") == "compile_attr" and e.get("cost"):
            costs[e.get("entry")] = e.get("cost")
    return costs


def _collective_bytes(events, entry):
    """Static per-call ICI byte estimate for ``entry`` from the
    ``collectives`` event, when the learner published one that names
    it; else 0 (the host cannot time collectives inside a program)."""
    for e in events:
        if e.get("ev") != "collectives":
            continue
        est = e.get("estimates") or {}
        if isinstance(est, dict):
            v = est.get(entry)
            if isinstance(v, (int, float)):
                return float(v)
        for key in ("psum", "allgather"):
            v = e.get(key)
            if isinstance(v, dict) and entry in str(v.get("entry", "")):
                b = v.get("bytes")
                if isinstance(b, (int, float)):
                    return float(b)
    return 0.0


def timeline_roofline(events, overrides=None, peaks_path=""):
    """The roofline join of ONE run's events (use query.last_run first).

    Returns ``{device_kind, peaks, rows, problems}`` where ``rows`` is
    headroom-ranked (most recoverable seconds first) and ``problems``
    lists the structural defects ``--check`` fails on.
    """
    if overrides is None:
        overrides = load_peak_overrides(peaks_path)
    problems = []
    header = next((e for e in events if e.get("ev") == "run_header"), {})
    kind = ""
    for d in header.get("devices") or ():
        if isinstance(d, dict) and d.get("kind"):
            kind = str(d["kind"])
            break
    kind = kind or str(header.get("backend", "") or "")
    world_size = int(header.get("world_size") or 1)
    peaks = peaks_for(kind, overrides)
    run_end = next((e for e in events if e.get("ev") == "run_end"), None)
    entries = (run_end or {}).get("entries") or {}
    if not entries:
        problems.append("no run_end entry stats on the timeline "
                        "(run did not finalize, or never timed an entry)")
    costs = _entry_costs(events)
    if entries and not costs:
        problems.append("no cost estimates on the timeline — run with "
                        "obs_compile=true so compile_attr events carry "
                        "cost_analysis")
    rows = []
    for name, st in entries.items():
        r = entry_roofline(
            costs.get(name), st.get("exec_mean_s", 0.0),
            st.get("exec_n", 0), peaks,
            ici_bytes=_collective_bytes(events, name),
            world_size=world_size)
        r["entry"] = name
        r["has_cost"] = name in costs
        r["exec_total_s"] = float(st.get("exec_total_s", 0.0))
        rows.append(r)
    rows.sort(key=lambda r: -r["headroom_s"])
    return {"device_kind": kind or "unknown", "peaks": peaks,
            "world_size": world_size, "rows": rows, "problems": problems}


# -- per-iteration rollup (the `utilization` event, schema 13) ----------

def utilization_rollup(entry_summary, costs, peaks, world_size=1):
    """Aggregate roofline position across entries for ONE iteration's
    ``utilization`` event: exec-time-weighted mean utilization plus the
    bound of the entry with the most absolute headroom.

    ``entry_summary`` is EntryTimers.summary() (mid-run snapshots work);
    ``costs`` is CompileTracker.costs().  Returns None when nothing can
    be said yet (no timed entries, or no cost estimate on any of them).
    """
    rows = []
    for name, st in (entry_summary or {}).items():
        if name not in costs:
            continue
        r = entry_roofline(costs.get(name), st.get("exec_mean_s", 0.0),
                           st.get("exec_n", 0), peaks,
                           world_size=world_size)
        r["entry"] = name
        r["weight"] = float(st.get("exec_total_s", 0.0))
        rows.append(r)
    if not rows:
        return None
    wsum = sum(r["weight"] for r in rows) or 1.0
    worst = max(rows, key=lambda r: r["headroom_s"])
    return {
        "flop_util": sum(r["flop_util"] * r["weight"] for r in rows) / wsum,
        "hbm_util": sum(r["hbm_util"] * r["weight"] for r in rows) / wsum,
        "headroom_s": sum(r["headroom_s"] for r in rows),
        "bound": worst["bound"],
        "device_kind": peaks["kind"],
        "roof_source": peaks["source"],
        "entries": {r["entry"]: {"flop_util": round(r["flop_util"], 6),
                                 "hbm_util": round(r["hbm_util"], 6),
                                 "bound": r["bound"]}
                    for r in rows},
    }


# -- rendering -----------------------------------------------------------

def fmt_quantity(v, unit=""):
    """Humanize a count into K/M/G/T units (1e9 -> '1.00 G')."""
    v = float(v or 0.0)
    for thresh, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                           (1e3, "K")):
        if abs(v) >= thresh:
            return "%.2f %s%s" % (v / thresh, suffix, unit)
    return "%.3g %s" % (v, unit) if unit else "%.3g" % v


def fmt_bytes(v):
    v = float(v or 0.0)
    for thresh, suffix in ((2**40, "TiB"), (2**30, "GiB"),
                           (2**20, "MiB"), (2**10, "KiB")):
        if abs(v) >= thresh:
            return "%.2f %s" % (v / thresh, suffix)
    return "%d B" % int(v)


def render_roofline(events, out=None, check=False, peaks_path=""):
    """Print the headroom-ranked roofline table of the last run; return
    the problems list (``--check`` exits nonzero when non-empty)."""
    out = out or sys.stdout
    w = lambda s="": print(s, file=out)  # noqa: E731
    res = timeline_roofline(events, peaks_path=peaks_path)
    peaks = res["peaks"]
    w("== roofline: %s (%s peaks%s) ==" % (
        res["device_kind"], peaks.get("source", "?"),
        ", world_size=%d" % res["world_size"]
        if res["world_size"] > 1 else ""))
    w("  peak %sFLOP/s f32, %s/s HBM, %s/s ICI, %s VMEM" % (
        fmt_quantity(peaks.get("flops_f32")),
        fmt_bytes(peaks.get("hbm_bytes_per_s")),
        fmt_bytes(peaks.get("ici_bytes_per_s")),
        fmt_bytes(peaks.get("vmem_bytes"))))
    rows = res["rows"]
    if rows:
        w()
        w("  %-34s %5s %10s %6s %6s %8s %-18s %10s" % (
            "entry", "calls", "mean", "MXU%", "HBM%", "AI",
            "bound", "headroom"))
        for r in rows:
            w("  %-34s %5d %9.2fms %5.1f%% %5.1f%% %8s %-18s %9.3fs%s" % (
                r["entry"][:34], r["exec_n"], r["exec_mean_s"] * 1e3,
                100 * r["flop_util"], 100 * r["hbm_util"],
                ("%.2f" % r["ai"]) if r["ai"] is not None else "-",
                r["bound"], r["headroom_s"],
                "" if r["has_cost"] else "  (no cost estimate)"))
        total = sum(r["headroom_s"] for r in rows)
        w()
        w("  total headroom %.3fs across %d entries — seconds recoverable"
          " if every entry hit its roof" % (total, len(rows)))
        counts = {}
        for r in rows:
            counts[r["bound"]] = counts.get(r["bound"], 0) + 1
        w("  bound mix: " + ", ".join(
            "%s x%d" % (b, counts[b]) for b in BOUNDS if b in counts))
    for p in res["problems"]:
        w("  PROBLEM: %s" % p)
    return res["problems"] if check else []
